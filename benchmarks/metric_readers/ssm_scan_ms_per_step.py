"""Device time under the scope ``ssm_scan`` (the selective-scan kernel of
that name and the operations that feed it) per whole execution of the
serving step, mean over the traced window."""
from benchmarks.harness import program_spans, ssm_spans


def read(run):
    trace = ssm_spans.trace_of(run)
    if trace is None:
        return None
    return program_spans.label_ms_per_step(
        trace, program_spans.SERVE_MODULE, [ssm_spans.SCAN])
