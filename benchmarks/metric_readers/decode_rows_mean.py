"""Decode rows per engine step over the window (engine counters)."""
from benchmarks.harness import request_metrics as rq


def read(run):
    steps = rq.counter_delta(run, "steps")
    rows = rq.counter_delta(run, "step_tokens", "decode")
    return rows / steps if steps and rows is not None else None
