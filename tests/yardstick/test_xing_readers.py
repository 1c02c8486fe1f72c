"""The readers PR 34 added for the Xing cell: bytes against hand counts,
and that every reader says nothing on a trace without the program's
spans (the parent of that PR opens none of the scopes)."""

import json
import pathlib
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import xing_bytes, xing_spans

REPO = pathlib.Path(__file__).resolve().parent.parent.parent
CONFIG = json.loads(
    (REPO / "benchmarks" / "configs" / "xing4_29b_pp8.json").read_text())
NEW = ("moe_ms_per_step", "moe_time_share", "moe_experts_hit_per_layer_step",
       "moe_expert_roofline_share", "latent_attn_ms_per_step",
       "latent_cache_roofline_share", "hc_mix_time_share")


def test_bytes_against_hand_counts():
    assert xing_bytes.expert_bytes(CONFIG) == 22_020_096
    assert xing_bytes.routed_layers(CONFIG) == 5
    assert xing_bytes.latent_bytes_per_token_layer(CONFIG) == 1152
    # 24 rows of 1,500 pooled tokens, 7 layers
    assert xing_bytes.latent_read_bytes(CONFIG, 36_000) == 36_000 * 8_064


def test_the_benchmark_lists_the_readers_last_for_the_one_cell():
    bench = bench_run.benchmark_file()
    tail = bench["per_layer"][-len(NEW):]
    assert tuple(m["name"] for m in tail) == NEW
    for m in tail:
        assert m["workloads"] == ["xing4_29b_pp8-reason"]
        assert m["moves"] == "tpot_p50_ms"
        assert callable(bench_run.reader(m["name"]))


@pytest.mark.parametrize("name", NEW)
def test_reader_says_nothing_without_a_trace(name):
    run = types.SimpleNamespace(
        cell="no-such-cell", config=CONFIG, trace=None,
        device={"kind": "TPU v5 lite"})
    assert bench_run.reader(name)(run) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_says_nothing_on_a_trace_without_the_scopes(name, monkeypatch):
    """A step that opens none of the scopes and a reduced trace without
    the counters: the parent's program."""
    ops = [("unscoped", 1_000, 400), ("fused_ragged_layer", 1_500, 300)]
    trace = {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [("jit_serve_ragged", 900, 1_200, 7)]}},
        "host": [[("bench.trace_window", -100, 10_000, {})]]}
    monkeypatch.setattr(xing_spans, "trace_of", lambda run: trace)
    run = types.SimpleNamespace(
        cell="hand-built", config=CONFIG, trace={"busy_s": 1.0},
        device={"kind": "TPU v5 lite"})
    assert bench_run.reader(name)(run) is None


def test_experts_hit_reads_the_counters_two_ends():
    ends = [{"steps": 100, "moe_distinct": [1000, 1100, 900, 1000, 1000]},
            {"steps": 110, "moe_distinct": [1500, 1600, 1400, 1500, 1500]}]
    run = types.SimpleNamespace(trace={"model_counters": ends})
    assert xing_spans.experts_hit_per_layer_step(run) == 50.0
    run.trace = {"model_counters": ends[:1]}
    assert xing_spans.experts_hit_per_layer_step(run) is None


def test_shares_by_label_on_a_hand_built_step(monkeypatch):
    """One execution of 1,000 ps: 500 under the experts (300 of them the
    kernel's), 100 under the router, 150 under the latent attention, 50
    under the residual mix, 200 elsewhere."""
    ops = [("moe_experts", 0, 200), ("moe_grouped_ffn", 200, 300),
           ("moe_route", 500, 100), ("ragged_latent_attention", 600, 150),
           ("hc_mix", 750, 50), ("unscoped", 800, 200)]
    trace = {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [("jit_serve_ragged", 0, 1_000, 7)]}},
        "host": [[("bench.trace_window", -100, 10_000, {})]]}
    monkeypatch.setattr(xing_spans, "trace_of", lambda run: trace)
    run = types.SimpleNamespace(cell="hand-built", config=CONFIG, trace={},
                                device={"kind": "TPU v5 lite"})
    assert xing_spans.time_share(run, xing_spans.MOE) == pytest.approx(60.0)
    assert xing_spans.time_share(run, xing_spans.HC) == pytest.approx(5.0)
    assert xing_spans.ms_per_step(run, xing_spans.LATENT) == \
        pytest.approx(150 / 1e9)
    assert xing_spans.ms_per_step(run, xing_spans.EXPERTS) == \
        pytest.approx(500 / 1e9)
