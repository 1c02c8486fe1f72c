"""Median over the requests due in the window of the time per output
token: (last token - first token) / (tokens - 1), as the client saw it."""
from benchmarks.harness import request_metrics as rq
from benchmarks.harness import stats


def read(run):
    return stats.percentile(rq.tpots_ms(rq.measured(run)), 50)
