"""From the device finishing a token to the transport having it: the end
of a ``serve.stream_item`` span (the replica's thread of the request has
encoded the item and sealed it) less the end of the execution of the step
its ``seq`` names, median over the traced window's items whose step is
joined (``step_timeline``).  ``fetch_lag_p50_ms`` and ``emit_lag_p50_ms``
are its first two hops."""
from benchmarks.harness import stats, step_timeline


def read(run):
    got = step_timeline.token_out_lags_ms(run)
    return None if got is None else stats.percentile(got, 50)
