"""Mean over the traced window's steps of the tokens a step carried
(decode rows, prompt tokens, speculative candidates) over its token
budget: the counts ``llm.pack`` records at the step's boundary."""
from benchmarks.harness import program_spans, stats


def read(run):
    packs = program_spans.packs_by_seq(program_spans.lines_of(run))
    return stats.mean(
        100.0 * (p["n_decode"] + p["n_prefill"] + p["n_spec"]) / p["budget"]
        for p in packs.values())
