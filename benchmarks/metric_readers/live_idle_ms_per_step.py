"""The device standing still while the engine held a request, per step:
every stretch of the traced window of at least ``trace_reduce.MIN_GAP_NS``
with no device operation whose middle no ``llm.idle`` span covers
(``llm.idle`` is the loop with no request anywhere in the engine), summed,
over the steps ``program_spans.join_steps`` joins.  A mean.  The books
(``step_timeline.idle_books``: ``live``, of it ``unnamed`` at the capture's
edges, ``empty`` under ``llm.idle``, ``short`` between a step's operations,
the longest live stretch and the span that names it) go into the run's
notes."""
from benchmarks.harness import program_spans, step_timeline


def read(run):
    trace = program_spans.trace_of(run)
    if trace is None:
        return None
    steps, books = step_timeline.build(trace), step_timeline.idle_books(trace)
    if steps is None or books is None:
        return None
    run.notes["live_idle_ms_per_step"] = dict(books, joined_steps=len(steps))
    return books["live_ms"] / len(steps)
