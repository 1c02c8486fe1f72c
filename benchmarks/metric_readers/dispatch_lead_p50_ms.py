"""How far the host runs ahead of the device: from the return of a step's
``llm.dispatch`` to the start of its execution, median over the traced
window's joined steps (``step_timeline``).  The time a dispatched step
waited in the device's queue; near 0, or under it, the device waits for
the host."""
from benchmarks.harness import stats, step_timeline


def read(run):
    got = step_timeline.lags_ms(run, "exec_start", "dispatch_end")
    return None if got is None else stats.percentile(got, 50)
