"""Model FLOP/s utilisation: required operations per token (6 per matmul
parameter plus causal attention; recomputation not counted) times
tokens/s/chip over the chip's published bf16 peak."""
from benchmarks.harness import flops, peaks, run_record


def read(run):
    tps = run_record.train_tokens_per_s(run)
    if tps is None:
        return None
    per_token = flops.train_flops_per_token(run.config,
                                            run.traffic["seq_len"])
    return 100.0 * tps * per_token / peaks.peaks(
        run.device["kind"])["bf16_flops"]
