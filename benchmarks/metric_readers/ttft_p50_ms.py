"""Median first-token time over the requests due in the window, from the
instant each was due, as the client saw it."""
from benchmarks.harness import request_metrics as rq
from benchmarks.harness import stats


def read(run):
    return stats.percentile(rq.ttfts_ms(rq.measured(run)), 50)
