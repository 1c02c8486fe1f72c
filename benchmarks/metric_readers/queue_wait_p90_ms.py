"""Engine ring, QUEUED to PREFILLING, 90th percentile."""
from benchmarks.harness import request_metrics as rq
from benchmarks.harness import stats


def read(run):
    waits = []
    for r in rq.completed(run):
        q, p = rq.ring_ts(run, r, "QUEUED"), rq.ring_ts(run, r, "PREFILLING")
        if q is not None and p is not None:
            waits.append((p - q) * 1e3)
    return stats.percentile(waits, 90)
