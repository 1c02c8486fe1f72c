"""Median device time of the serving step's executions whose step
carried prompt tokens (``llm.pack``'s ``n_prefill`` above 0), joined to
the step by ``program_spans.join_steps``."""
from benchmarks.harness import program_spans


def read(run):
    return program_spans.step_device_ms_p50(run, prefill=True)
