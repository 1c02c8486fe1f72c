"""90th percentile over requests of the time per output token."""
from benchmarks.harness import request_metrics as rq
from benchmarks.harness import stats


def read(run):
    return stats.percentile(rq.tpots_ms(rq.measured(run)), 90)
