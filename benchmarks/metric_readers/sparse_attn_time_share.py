"""Self time under ``dsa_index``, ``dsa_select`` and ``latent_attn`` (the
kernel ``ragged_latent_attention`` included) over the busy time of the
serving step's executions in the traced window, %: how much of a step the
sparse attention is, indexer and selection included."""
from benchmarks.harness import dsa_spans


def read(run):
    return dsa_spans.time_share(run, dsa_spans.SPARSE_ATTN)
