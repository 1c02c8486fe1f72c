"""From the end of a step's execution to the end of the ``llm.fetch`` that
brought its tokens to the host, median over the traced window's joined
steps (``step_timeline``)."""
from benchmarks.harness import stats, step_timeline


def read(run):
    got = step_timeline.lags_ms(run, "fetch_end", "exec_end")
    return None if got is None else stats.percentile(got, 50)
