"""Over the joined steps that carried no prompt token: the least time
the chip's memory could take to move the bytes the state update has to
move (``retention_bytes.state_update_bytes`` of the configuration's
published shapes, ``rows`` from ``llm.pack``, at the chip's published
bytes a second) over the device time under ``retention``.  Memory bounds
it: a decode token does two operations per byte of state."""
from benchmarks.harness import peaks, retention_bytes, retention_spans


def read(run):
    trace = retention_spans.trace_of(run)
    if trace is None:
        return None
    steps = retention_spans.joined_steps(trace, prefill=False)
    took = sum(booked.get(lb, 0) for _p, booked in steps or ()
               for lb in retention_spans.RETENTION) / 1e12
    if not took:
        return None
    rate = peaks.peaks(run.device["kind"])["hbm_bytes_per_s"]
    least = sum(retention_bytes.state_update_bytes(run.config,
                                                   int(pack["rows"]))
                for pack, _b in steps) / rate
    return 100.0 * least / took
