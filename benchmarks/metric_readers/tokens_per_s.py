"""Training tokens per second per chip: the tokens of the whole optimizer
steps that ended in the window over the time those steps took."""
from benchmarks.harness import run_record


def read(run):
    return run_record.train_tokens_per_s(run)
