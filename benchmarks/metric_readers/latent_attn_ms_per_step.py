"""Device time under the scope ``latent_attn`` (the kernel
``ragged_latent_attention`` and the operations that feed it) per whole
execution of the serving step, mean over the traced window."""
from benchmarks.harness import xing_spans


def read(run):
    return xing_spans.ms_per_step(run, xing_spans.LATENT)
