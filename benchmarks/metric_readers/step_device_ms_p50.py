"""Median device time of the step program (the module with most time in
the trace's XLA Modules line)."""
from benchmarks.harness import stats


def read(run):
    if run.trace is None or not run.trace["step_ms"]:
        return None
    return stats.percentile(run.trace["step_ms"], 50)
