"""Over the joined steps that carried no prompt token: the least time the
chip's memory could take to read the rows' cached index keys and the
indexer's weights once (``dsa_bytes.index_read_bytes`` of the published
shapes, ``ctx_tokens`` from ``llm.pack``) over the device time under the
scope ``dsa_index``.  By scope, so that it reads the same work whatever
computes the scores.  Memory bounds it: a decode query does 32 light
heads' arithmetic on each cached key byte once."""
from benchmarks.harness import dsa_bytes, dsa_spans


def read(run):
    return dsa_spans.decode_roofline_share(
        run, dsa_spans.INDEX, "ctx_tokens", dsa_bytes.index_read_bytes)
