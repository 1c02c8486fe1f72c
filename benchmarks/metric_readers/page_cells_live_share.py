"""Page cells that hold a packed row's tokens over the page cells the
fused layer kernel's grid walks, summed over the traced window's steps
(``live_cells`` and ``grid_cells`` of ``llm.pack``)."""
from benchmarks.harness import program_spans


def read(run):
    packs = program_spans.packs_by_seq(program_spans.lines_of(run))
    walked = sum(p["grid_cells"] for p in packs.values())
    if not walked:
        return None
    return 100.0 * sum(p["live_cells"] for p in packs.values()) / walked
