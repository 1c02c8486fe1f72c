"""Mean over the traced window's steps of the tokens a step carried
(decode rows, prompt tokens, speculative candidates) over the positions
its program was compiled for: ``llm.pack``'s ``shape``, and its
``budget`` where the program records no ``shape`` (it then runs every
step at the budget, and this reads what ``token_budget_fill_share``
reads)."""
from benchmarks.harness import program_spans, stats


def read(run):
    packs = program_spans.packs_by_seq(program_spans.lines_of(run))
    return stats.mean(
        100.0 * (p["n_decode"] + p["n_prefill"] + p["n_spec"])
        / p.get("shape", p["budget"])
        for p in packs.values())
