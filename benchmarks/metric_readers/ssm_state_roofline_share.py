"""Over the joined steps that carried no prompt token: the least time
the chip's memory could take to move the bytes the state update has to
move (``ssm_bytes.state_update_bytes`` of the configuration's published
shapes, ``rows`` from ``llm.pack``, at the chip's published bytes a
second) over the device time under ``ssm_scan``.  Memory bounds it: the
update does a handful of operations per byte of state."""
from benchmarks.harness import peaks, ssm_bytes, ssm_spans


def read(run):
    trace = ssm_spans.trace_of(run)
    if trace is None:
        return None
    steps = ssm_spans.decode_scan_steps(trace)
    if not steps:
        return None
    rate = peaks.peaks(run.device["kind"])["hbm_bytes_per_s"]
    least = sum(ssm_bytes.state_update_bytes(run.config, rows)
                for rows, _s in steps) / rate
    return 100.0 * least / sum(s for _rows, s in steps)
