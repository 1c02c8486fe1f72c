"""The least time the chip's memory could take to read the experts a
step's tokens chose (distinct experts hit a layer and step from the
device counter ``moe_distinct``, times the routed layers, times an
expert's published bytes, at the chip's published bytes a second) over
the device time under the scope ``moe_experts`` (the kernel
``moe_grouped_ffn`` included) per step.  By scope, so that it reads the
same work whichever form does the grouped products.  Memory bounds it in
a decode step; a step that carries a prompt chunk does several tokens'
arithmetic per expert byte and pulls it down."""
from benchmarks.harness import peaks, xing_bytes, xing_spans


def read(run):
    hit = xing_spans.experts_hit_per_layer_step(run)
    took_ms = xing_spans.ms_per_step(run, xing_spans.EXPERTS)
    if hit is None or not took_ms:
        return None
    rate = peaks.peaks(run.device["kind"])["hbm_bytes_per_s"]
    least = (hit * xing_bytes.routed_layers(run.config)
             * xing_bytes.expert_bytes(run.config)) / rate
    return 100.0 * least / (took_ms / 1e3)
