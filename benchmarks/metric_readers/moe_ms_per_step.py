"""Device time under the routed layers' scopes (``moe_route``,
``moe_experts`` with the kernel ``moe_grouped_ffn``, ``moe_shared``) per
whole execution of the serving step, mean over the traced window."""
from benchmarks.harness import xing_spans


def read(run):
    return xing_spans.ms_per_step(run, xing_spans.MOE)
