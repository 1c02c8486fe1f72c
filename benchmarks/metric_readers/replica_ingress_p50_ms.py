"""The replica's way in: from the start of a request's ``serve.replica``
span to the end of the ``llm.submit`` nested in it on the same thread,
median over the requests that arrived AND ended in the traced window (a
capture keeps a span that ended inside it: a cell whose requests outlive
the capture has none, which is why only chat_short lists this metric).  Under
``step_timeline.MIN_ARRIVALS`` of them it reads None, and the run's notes
say so."""
from benchmarks.harness import stats, step_timeline


def read(run):
    got = step_timeline.ingress_ms(run)
    if not got:
        return None
    if len(got) < step_timeline.MIN_ARRIVALS:
        run.notes["replica_ingress_p50_ms"] = (
            f"{len(got)} requests arrived and ended in the traced window, "
            f"fewer than {step_timeline.MIN_ARRIVALS}: not reported")
        return None
    return stats.percentile(got, 50)
