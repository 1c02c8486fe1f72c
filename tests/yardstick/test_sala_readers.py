"""The readers PR 41 added for the lightning / block-sparse cell: bytes
and operations against hand counts, shares on hand-built steps, and that
every reader says nothing on a trace without the program's spans (the
parent of that PR opens none of the scopes and runs none of the
kernels)."""

import json
import pathlib
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import sala_bytes, sala_spans

REPO = pathlib.Path(__file__).resolve().parent.parent.parent
CONFIG = json.loads((REPO / "benchmarks" / "configs"
                     / "minicpm_sala_pp2.json").read_text())
CELL = "minicpm_sala_pp2-doc_64k"
BY_SCOPE = ("lin_attn_ms_per_step", "block_select_ms_per_step",
            "hybrid_mixer_time_share")
DECODE_ONLY = ("lin_state_roofline_share", "sparse_walk_roofline_share",
               "sparse_read_share")
PREFILL = ("lin_chunk_roofline_share",)
NEW = BY_SCOPE + DECODE_ONLY + PREFILL
RUN = dict(cell="hand-built", config=CONFIG, trace={},
           device={"kind": "TPU v5 lite"})


def test_bytes_and_operations_against_hand_counts():
    assert sala_bytes.layers(CONFIG, "lightning-attn") == 12
    assert sala_bytes.layers(CONFIG, "minicpm4") == 4
    # 32 heads of a 128 x 128 float32 matrix
    assert sala_bytes.lin_state_bytes_per_row_layer(CONFIG) == 2_097_152
    # 8 rows, 12 layers, read and written once
    assert sala_bytes.lin_state_update_bytes(CONFIG, 8) == (
        8 * 12 * 2 * 2_097_152)
    # 512 tokens in a row of 512: q S and k^T v (2 x 128 x 128 each) and
    # the causal half of 512 keys twice, 32 heads, 12 layers
    assert sala_bytes.lin_chunk_ops(CONFIG, 512, 512) == (
        12 * 32 * 512 * (4 * 128 * 128 + 2 * 512 * 128))
    # one state in and out, q, k, v in bfloat16 and a float32 output
    assert sala_bytes.lin_chunk_bytes(CONFIG, 512, 1) == 12 * (
        2 * 2_097_152 + 512 * 32 * 128 * (3 * 2 + 4))
    # a page of one KV head: 64 tokens of k and of v, 128 bfloat16
    assert sala_bytes.page_bytes(CONFIG) == 32_768
    # 8 rows of 64 pages a KV head; 160,000 cached tokens' compressed keys
    assert sala_bytes.walk_read_bytes(CONFIG, 8 * 2 * 64, 160_000) == 4 * (
        1024 * 32_768 + 10_000 * 2 * 128 * 2)


def test_the_benchmark_lists_the_readers_for_the_one_cell():
    bench = bench_run.benchmark_file()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert callable(bench_run.reader(name))
        if name in by_name:
            m = by_name[name]
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"
    for name in BY_SCOPE:
        assert name in by_name
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1
    assert cell[0]["config"] == "minicpm_sala_pp2"
    assert cell[0]["traffic"] == "doc_64k"
    # mechanisms of other models do not list the cell
    for m in bench["per_layer"]:
        if m["name"].startswith(("dsa_", "moe_", "latent_", "hc_",
                                 "retention_", "ssm_", "fused_layer_",
                                 "weight_slice_", "kv_append_")):
            assert CELL not in m.get("workloads", ())


@pytest.mark.parametrize("name", NEW)
def test_reader_says_nothing_without_a_trace(name):
    run = types.SimpleNamespace(**dict(RUN, cell="no-such-cell", trace=None))
    assert bench_run.reader(name)(run) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_says_nothing_on_a_trace_without_the_scopes(name, monkeypatch):
    """A step that opens none of the scopes and runs none of the kernels,
    joined steps whose ``llm.pack`` counts no ``sel_tokens``: the
    parent's program."""
    ops = [("unscoped", 1_000, 400), ("attention", 1_500, 300)]
    trace = {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [("jit_serve_ragged", 900, 1_200, 7)]}},
        "host": [[("bench.trace_window", -100, 10_000, {})]]}
    monkeypatch.setattr(sala_spans, "trace_of", lambda run: trace)
    pack = {"ctx_tokens": 9_000, "rows": 2, "n_decode": 2, "n_prefill": 0,
            "grid_cells": 300, "scan_len": 1}
    booked = {"attention": 300, "unscoped": 400}
    monkeypatch.setattr(
        sala_spans, "steps",
        lambda run, prefill: [(dict(pack, n_prefill=512 * prefill), booked)])
    assert bench_run.reader(name)(types.SimpleNamespace(**RUN)) is None


def test_shares_by_label_on_a_hand_built_step(monkeypatch):
    """One execution of 1,000 ps: 100 under the lightning projections,
    50 + 150 under the recurrence (150 of them a kernel's), 40 + 30 + 30
    under the three scopes of the selection, 20 + 80 under the walk, 500
    elsewhere."""
    ops = [("lin_proj", 0, 100), ("lin_attn", 100, 50),
           ("lightning_decode", 150, 150), ("bsa_compress", 300, 40),
           ("bsa_score", 340, 30), ("bsa_select", 370, 30),
           ("sparse_attn", 400, 20), ("block_sparse_walk", 420, 80),
           ("mlp", 500, 400), ("unscoped", 900, 100)]
    trace = {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [("jit_serve_ragged", 0, 1_000, 7)]}},
        "host": [[("bench.trace_window", -100, 10_000, {})]]}
    monkeypatch.setattr(sala_spans, "trace_of", lambda run: trace)
    run = types.SimpleNamespace(**RUN)
    read = bench_run.reader
    assert read("lin_attn_ms_per_step")(run) == pytest.approx(200 / 1e9)
    assert read("block_select_ms_per_step")(run) == pytest.approx(100 / 1e9)
    assert read("hybrid_mixer_time_share")(run) == pytest.approx(50.0)


def test_roofline_shares_on_hand_built_steps(monkeypatch):
    """Two decode-only steps of 8 rows past ``dense_len`` (64 pages a KV
    head and a self cell each, 20,000 cached tokens a row) and one step
    with a chunk of 512."""
    decode = {"rows": 8, "n_decode": 8, "n_prefill": 0, "scan_len": 1,
              "ctx_tokens": 160_000, "sel_tokens": 8 * 4_064,
              "grid_cells": 8 * 2 * 64 + 2 * 8}
    steps = {False: [(decode, {"lightning_decode": 1_000_000_000,
                               "sparse_attn": 100_000_000,
                               "block_sparse_walk": 900_000_000})] * 2,
             True: [({"rows": 3, "n_decode": 2, "n_prefill": 512,
                      "scan_len": 512, "ctx_tokens": 50_000},
                     {"lightning_chunk": 2_000_000_000})]}
    monkeypatch.setattr(sala_spans, "steps",
                        lambda run, prefill: steps[prefill])
    run = types.SimpleNamespace(**RUN)
    read = bench_run.reader
    state = 2 * 8 * 12 * 2 * 2_097_152
    assert read("lin_state_roofline_share")(run) == pytest.approx(
        100 * state / 819e9 / 2e-3)
    walk = 2 * 4 * (1024 * 32_768 + 10_000 * 512)
    assert read("sparse_walk_roofline_share")(run) == pytest.approx(
        100 * walk / 819e9 / 2e-3)
    assert read("sparse_read_share")(run) == pytest.approx(
        100 * 8 * 4_064 / (160_000 + 8))
    # the chunk form: its bytes bound it, not its operations
    ops = sala_bytes.lin_chunk_ops(CONFIG, 512, 512) / 197e12
    moved = sala_bytes.lin_chunk_bytes(CONFIG, 512, 1) / 819e9
    assert moved > ops
    assert read("lin_chunk_roofline_share")(run) == pytest.approx(
        100 * moved / 2e-3)
