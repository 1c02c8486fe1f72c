"""Device time under the retention mixers' scopes (``ret_proj``,
``retention`` and its two kernels) over the busy time of the serving
step's whole executions in the traced window."""
from benchmarks.harness import retention_spans


def read(run):
    trace = retention_spans.trace_of(run)
    return (None if trace is None
            else retention_spans.mixer_time_share(trace))
