"""1 - union of device operation intervals over the traced window (the
harness's own span from the trace's start to its stop)."""


def read(run):
    if run.trace is None or not run.trace["window_s"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
