"""Rows a step carried that start a sequence (``llm.pack``'s
``n_state_reset``: each resets its slot's recurrent state on the
device), mean over the steps packed in the traced window."""
from benchmarks.harness import program_spans, stats


def read(run):
    packs = program_spans.packs_by_seq(program_spans.lines_of(run))
    got = [p["n_state_reset"] for p in packs.values()
           if "n_state_reset" in p]
    return stats.mean(got) if got else None
