"""The readers PR 39 added for the sparse-attention cell: bytes against
hand counts, shares on a hand-built step, and that every reader says
nothing on a trace without the program's spans (the parent of that PR
opens none of the scopes and its ``llm.pack`` counts no ``sel_tokens``)."""

import json
import pathlib
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import dsa_bytes, dsa_spans, xing_spans

REPO = pathlib.Path(__file__).resolve().parent.parent.parent
CONFIG = json.loads(
    (REPO / "benchmarks" / "configs" / "glm5_ep16.json").read_text())
CELL = "glm5_ep16-doc_32k"
LISTED = ("dsa_index_ms_per_step", "dsa_select_ms_per_step",
          "sparse_attn_time_share")
# over joined steps WITHOUT prompt tokens: the cell's traced window (4 s
# from a third of the way in) lies inside one 28k-token prompt's prefill
# in the replayed schedule and holds no such step, so the registry does
# not list them for it (PERF.md section 7); the readers stand for a cell
# whose window does
DECODE_ONLY = ("dsa_index_roofline_share", "sparse_latent_roofline_share")
NEW = LISTED + DECODE_ONLY
RUN = dict(cell="hand-built", config=CONFIG, trace={},
           device={"kind": "TPU v5 lite"})


def test_bytes_against_hand_counts():
    # W_iq 2048 x 32 x 128, W_ik 6144 x 128, W_iw 6144 x 32, the norm's 256
    assert dsa_bytes.indexer_weight_bytes(CONFIG) == 5 * 2 * 9_371_904
    # 8 rows of 10,000 cached tokens, 5 layers of 128 bf16 values
    assert dsa_bytes.index_read_bytes(CONFIG, 80_000) == (
        80_000 * 5 * 256 + 93_719_040)
    # 8 queries of 2048 selected positions, 5 layers of 576 bf16 values
    assert dsa_bytes.selected_latent_bytes(CONFIG, 16_384) == (
        16_384 * 5 * 1152)


def test_the_benchmark_lists_the_readers_for_the_one_cell():
    bench = bench_run.benchmark_file()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in LISTED:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"
    for name in NEW:
        assert callable(bench_run.reader(name))
    # a traced run has to report every metric that lists its cell, and
    # this cell's window holds no step without prompt tokens
    for name in DECODE_ONLY:
        assert name not in by_name
    assert CELL not in by_name["step_device_ms_p50_decode"]["workloads"]
    # a share that counts every pooled token as read does not list a
    # cell whose attention reads 2048 a query
    assert CELL not in by_name["latent_cache_roofline_share"]["workloads"]
    for name in ("page_cells_live_share", "hc_mix_time_share"):
        assert CELL not in by_name[name]["workloads"]
    listed = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())]
    for name in ("moe_ms_per_step", "moe_time_share",
                 "moe_experts_hit_per_layer_step",
                 "moe_expert_roofline_share", "latent_attn_ms_per_step"):
        assert name in listed


@pytest.mark.parametrize("name", NEW)
def test_reader_says_nothing_without_a_trace(name):
    run = types.SimpleNamespace(**dict(RUN, cell="no-such-cell", trace=None))
    assert bench_run.reader(name)(run) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_says_nothing_on_a_trace_without_the_scopes(name, monkeypatch):
    """A step that opens none of the scopes, joined steps whose
    ``llm.pack`` has no ``sel_tokens``: the parent's program (which still
    has a ``latent_attn`` scope in its Xing step)."""
    ops = [("unscoped", 1_000, 400), ("latent_attn", 1_500, 300)]
    trace = {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [("jit_serve_ragged", 900, 1_200, 7)]}},
        "host": [[("bench.trace_window", -100, 10_000, {})]]}
    monkeypatch.setattr(dsa_spans, "trace_of", lambda run: trace)
    monkeypatch.setattr(
        dsa_spans, "decode_steps",
        lambda run: [({"ctx_tokens": 9_000, "n_prefill": 0},
                      {"latent_attn": 300, "unscoped": 400})])
    assert bench_run.reader(name)(types.SimpleNamespace(**RUN)) is None


def test_shares_by_label_on_a_hand_built_step(monkeypatch):
    """One execution of 1,000 ps: 250 under the indexer, 150 under the
    selection, 200 under the attention (120 of them the kernel's), 400
    elsewhere."""
    ops = [("dsa_index", 0, 250), ("dsa_select", 250, 150),
           ("latent_attn", 400, 80), ("ragged_latent_attention", 480, 120),
           ("moe_experts", 600, 100), ("unscoped", 700, 300)]
    trace = {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [("jit_serve_ragged", 0, 1_000, 7)]}},
        "host": [[("bench.trace_window", -100, 10_000, {})]]}
    monkeypatch.setattr(dsa_spans, "trace_of", lambda run: trace)
    run = types.SimpleNamespace(**RUN)
    read = bench_run.reader
    assert read("dsa_index_ms_per_step")(run) == pytest.approx(250 / 1e9)
    assert read("dsa_select_ms_per_step")(run) == pytest.approx(150 / 1e9)
    assert read("sparse_attn_time_share")(run) == pytest.approx(60.0)


def test_roofline_shares_on_hand_built_decode_steps(monkeypatch):
    """Two decode-only steps.  The indexer: 60,000 and 100,000 cached
    tokens, 0.5 ms under ``dsa_index`` each; the attention: 16,384
    selected positions a step, 0.25 ms under ``latent_attn``."""
    steps = [({"ctx_tokens": 60_000, "sel_tokens": 16_384, "n_prefill": 0},
              {"dsa_index": 500_000_000, "latent_attn": 200_000_000,
               "ragged_latent_attention": 50_000_000}),
             ({"ctx_tokens": 100_000, "sel_tokens": 16_384, "n_prefill": 0},
              {"dsa_index": 500_000_000, "latent_attn": 250_000_000})]
    monkeypatch.setattr(dsa_spans, "decode_steps", lambda run: steps)
    run = types.SimpleNamespace(**RUN)
    index_bytes = 160_000 * 5 * 256 + 2 * 93_719_040
    assert bench_run.reader("dsa_index_roofline_share")(run) == \
        pytest.approx(100 * index_bytes / 819e9 / 1e-3)
    latent_bytes = 2 * 16_384 * 5 * 1152
    assert bench_run.reader("sparse_latent_roofline_share")(run) == \
        pytest.approx(100 * latent_bytes / 819e9 / 0.5e-3)
    assert xing_spans.LATENT == ("latent_attn", "ragged_latent_attention")
