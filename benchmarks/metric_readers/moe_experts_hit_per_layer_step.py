"""Distinct experts a routed layer reads in a step: the growth of the
device counter ``moe_distinct`` between the traced window's two ends over
the engine's steps between them and the routed layers."""
from benchmarks.harness import xing_spans


def read(run):
    return xing_spans.experts_hit_per_layer_step(run)
