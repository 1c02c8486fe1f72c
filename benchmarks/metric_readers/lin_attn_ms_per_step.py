"""Device time under the scope ``lin_attn`` and its kernels
(``lightning_decode``, ``lightning_chunk``) per whole execution of the
serving step, mean over the traced window: the lightning layers'
recurrence, without their projections."""
from benchmarks.harness import sala_spans


def read(run):
    return sala_spans.ms_per_step(run, sala_spans.LIN_ATTN)
