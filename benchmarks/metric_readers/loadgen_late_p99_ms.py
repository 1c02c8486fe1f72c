"""How late the load generator sent: send time - due time, 99th percentile."""
from benchmarks.harness import request_metrics as rq
from benchmarks.harness import stats


def read(run):
    late = [(r["sent"] - r["due"]) * 1e3 for r in rq.measured(run)
            if r["due"] is not None and r["sent"] is not None]
    return stats.percentile(late, 99)
