"""Over the joined steps that carried no prompt token: the least time
the chip's memory could take to read the rows' pooled latent
(``xing_bytes.latent_read_bytes`` of the configuration's published
shapes, ``ctx_tokens`` from ``llm.pack``, at the chip's published bytes a
second) over the device time under ``latent_attn``.  Memory bounds it: a
decode token does the heads' arithmetic on each cached byte once."""
from benchmarks.harness import peaks, xing_bytes, xing_spans


def read(run):
    steps = xing_spans.decode_steps(run)
    took = sum(booked.get(lb, 0) for _p, booked in steps or ()
               for lb in xing_spans.LATENT) / 1e12
    if not took or not all("ctx_tokens" in pack for pack, _b in steps):
        return None
    rate = peaks.peaks(run.device["kind"])["hbm_bytes_per_s"]
    least = sum(xing_bytes.latent_read_bytes(run.config,
                                             int(pack["ctx_tokens"]))
                for pack, _b in steps) / rate
    return 100.0 * least / took
