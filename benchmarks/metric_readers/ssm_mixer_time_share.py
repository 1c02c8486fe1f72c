"""Device time under the Mamba mixers' scopes (``ssm_proj``,
``ssm_conv``, ``ssm_scan``) over the busy time of the serving step's
whole executions in the traced window."""
from benchmarks.harness import ssm_spans


def read(run):
    trace = ssm_spans.trace_of(run)
    return None if trace is None else ssm_spans.mixer_time_share(trace)
