"""Median over the steps in the traced window of the time the engine
loop's thread worked on a step: the self time of ``llm.control``,
``llm.admit``, ``llm.pack``, ``llm.dispatch``, ``llm.commit`` and
``llm.emit`` in the iteration (``llm.loop``) that dispatched it.  Waiting
(``llm.idle``) is left out."""
from benchmarks.harness import program_spans, stats


def read(run):
    return stats.percentile(
        program_spans.host_ms_per_step(program_spans.lines_of(run)), 50)
