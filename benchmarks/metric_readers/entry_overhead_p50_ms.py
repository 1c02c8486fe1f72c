"""Client TTFT from the send, less the engine's own (QUEUED to DECODING in
its request ring), median: handle, router, replica and the way back."""
from benchmarks.harness import request_metrics as rq
from benchmarks.harness import stats


def read(run):
    over = []
    for r in rq.completed(run):
        q, d = rq.ring_ts(run, r, "QUEUED"), rq.ring_ts(run, r, "DECODING")
        if q is not None and d is not None:
            over.append((r["first"] - r["sent"] - (d - q)) * 1e3)
    return stats.percentile(over, 50)
