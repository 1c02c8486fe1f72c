"""Over the joined steps that carried no prompt token: the least time the
chip's memory could take to read the latent rows the step's queries
SELECTED (``dsa_bytes.selected_latent_bytes`` of the published shapes,
``sel_tokens`` from ``llm.pack``) over the device time under the scope
``latent_attn``.  By scope, so that it reads the same work whether the
rows are gathered or walked.  Memory bounds it: a decode query does the
heads' arithmetic on each selected byte once."""
from benchmarks.harness import dsa_bytes, dsa_spans, xing_spans


def read(run):
    return dsa_spans.decode_roofline_share(
        run, xing_spans.LATENT, "sel_tokens",
        dsa_bytes.selected_latent_bytes)
