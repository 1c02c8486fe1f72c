"""90th percentile of the same first-token times as ttft_p50_ms."""
from benchmarks.harness import request_metrics as rq
from benchmarks.harness import stats


def read(run):
    return stats.percentile(rq.ttfts_ms(rq.measured(run)), 90)
