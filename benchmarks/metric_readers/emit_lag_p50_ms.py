"""A fetched step waiting for the loop thread: from the end of its
``llm.fetch`` to the start of the ``llm.emit`` that hands its tokens to the
requests, median over the traced window's joined steps whose emit lies in
the window (``step_timeline``)."""
from benchmarks.harness import stats, step_timeline


def read(run):
    got = step_timeline.lags_ms(run, "emit_start", "fetch_end")
    return None if got is None else stats.percentile(got, 50)
