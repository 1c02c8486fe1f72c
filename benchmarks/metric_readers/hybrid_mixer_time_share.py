"""Self time under the six scopes of the hybrid's mixers (``lin_proj``,
``lin_attn``, ``bsa_compress``, ``bsa_score``, ``bsa_select``,
``sparse_attn``, their kernels included) over the busy time of the
serving step's executions in the traced window, %: how much of a step
is the two mechanisms, beside the dense matmuls every model has."""
from benchmarks.harness import sala_spans


def read(run):
    return sala_spans.time_share(run, sala_spans.MIXERS)
