"""Device time under the scope ``retention`` (the kernels
``retention_decode`` and ``retention_chunk`` and the operations that
feed them) per whole execution of the serving step, mean over the traced
window."""
from benchmarks.harness import program_spans, retention_spans


def read(run):
    trace = retention_spans.trace_of(run)
    if trace is None:
        return None
    return program_spans.label_ms_per_step(
        trace, program_spans.SERVE_MODULE, retention_spans.RETENTION)
