"""Device time under the routed layers' scopes (``moe_route``,
``moe_experts`` with the kernel ``moe_grouped_ffn``, ``moe_shared``) over
the busy time of the serving step's whole executions in the traced
window."""
from benchmarks.harness import xing_spans


def read(run):
    return xing_spans.time_share(run, xing_spans.MOE)
