"""Over the joined steps that carried prompt tokens: the least time the
chip's matrix units could take for the chunk form's operations
(``retention_bytes.chunk_ops`` of the published shapes, ``n_prefill``
and the step's longest row ``scan_len`` from ``llm.pack``, at the chip's
published bf16 peak) over the device time of the kernel
``retention_chunk``.  Compute bounds it."""
from benchmarks.harness import peaks, retention_bytes, retention_spans


def read(run):
    trace = retention_spans.trace_of(run)
    if trace is None:
        return None
    steps = retention_spans.joined_steps(trace, prefill=True)
    took = sum(booked.get(retention_spans.CHUNK_KERNEL, 0)
               for _p, booked in steps or ()) / 1e12
    if not took:
        return None
    rate = peaks.peaks(run.device["kind"])["bf16_flops"]
    least = sum(retention_bytes.chunk_ops(
        run.config, int(pack["n_prefill"]), int(pack["scan_len"]))
        for pack, _b in steps) / rate
    return 100.0 * least / took
