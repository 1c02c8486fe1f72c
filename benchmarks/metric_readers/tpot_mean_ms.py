"""Mean over requests of (last token - first token) / (tokens - 1)."""
from benchmarks.harness import request_metrics as rq
from benchmarks.harness import stats


def read(run):
    return stats.mean(rq.tpots_ms(rq.measured(run)))
