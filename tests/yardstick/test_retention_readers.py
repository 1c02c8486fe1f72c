"""The readers of the power-retention layers' metrics on a small
hand-built event list (retention_span_events.json, beside this file),
the byte and operation counts behind the two shares from the published
shapes, and what the readers give for a program that has no such layer.
Times in the list are picoseconds."""

import json
import os
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import program_spans as ps
from benchmarks.harness import retention_bytes, retention_spans

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = bench_run.load_json(os.path.join(
    bench_run.ROOT, "benchmarks", "configs", "brumby14b_pp4.json"))
METRICS = ["retention_ms_per_step", "retention_time_share",
           "retention_state_roofline_share", "retention_chunk_mxu_share"]


def _load(name):
    raw = json.load(open(os.path.join(HERE, name)))
    return {"host": [[(n, s, d, dict(stats)) for n, s, d, stats in line]
                     for line in raw["host"]],
            "devices": {k: {"ops": [tuple(o) for o in d["ops"]],
                            "modules": [tuple(m) for m in d["modules"]]}
                        for k, d in raw["devices"].items()}}


def _run(monkeypatch, events):
    trace = _load(events)
    monkeypatch.setattr(ps, "trace_of", lambda _run: trace)
    monkeypatch.setattr(retention_spans, "trace_of", lambda _run: trace)
    return types.SimpleNamespace(cell="hand-built", notes={}, config=CONFIG,
                                 device={"kind": "TPU v5 lite"})


def test_bytes_and_operations_come_from_the_published_shapes():
    # 8 KV heads x (8256 x 128 + 8256) float32: the deduplicated features
    assert retention_bytes.state_bytes_per_row_layer(CONFIG) == 34_080_768
    assert retention_bytes.state_update_bytes(CONFIG, 1) \
        == 10 * 2 * 34_080_768
    assert retention_bytes.state_update_bytes(CONFIG, 12) \
        == 12 * 681_615_360
    # a token: 48 heads' features against or into (S, z), and the causal
    # half of a 512-token row's scores and products with v, ten layers
    per_token = 2 * 48 * 8256 * 129 + 2 * 40 * 512 * 128
    assert retention_bytes.chunk_ops(CONFIG, 1, 512) == 10 * per_token
    assert retention_bytes.chunk_ops(CONFIG, 512, 512) \
        == 512 * 10 * per_token
    # the program's layout pads the features, so it moves more than is
    # counted: a share computed from the count cannot pass 100% by it
    from benchmarks.runners.common import model_config

    cfg = model_config(CONFIG)
    assert cfg.state_bytes_per_slot() == 10 * 8 * (9216 * 128 + 9216) * 4
    assert cfg.state_bytes_per_slot() \
        > 10 * retention_bytes.state_bytes_per_row_layer(CONFIG)


@pytest.mark.parametrize("metric,want", [
    ("retention_ms_per_step", (10.0 + 25.0 + 12.0) / 3),
    ("retention_time_share", 100 * (12.0 + 30.0 + 14.0) / 94),
    # steps 31 and 33 carry no prompt token: 10 and 12 rows in 10 and 12 ms
    ("retention_state_roofline_share",
     100 * (22 * 681_615_360 / 819e9) / 22e-3),
    # step 32: 500 prompt tokens in a row of 500, the chunk kernel 16 ms
    ("retention_chunk_mxu_share",
     100 * (500 * 10 * (2 * 48 * 8256 * 129 + 2 * 40 * 500 * 128)
            / 197e12) / 16e-3),
])
def test_readers_on_the_hand_built_trace(monkeypatch, metric, want):
    run = _run(monkeypatch, "retention_span_events.json")
    got = bench_run.reader(metric)(run)
    assert got == pytest.approx(want)
    assert "share" not in metric or 0 < got < 100


def test_pack_counts_of_a_model_without_pages_read_zero(monkeypatch):
    run = _run(monkeypatch, "retention_span_events.json")
    packs = ps.packs_by_seq(ps.lines_of(run))
    assert {(p["live_cells"], p["grid_cells"], p["append_cells"])
            for p in packs.values()} == {(0, 0, 0)}
    assert bench_run.reader("state_reset_rows_per_step")(run) \
        == pytest.approx(1 / 3)


@pytest.mark.parametrize("events", ["program_span_events.json",
                                    "ssm_span_events.json"])
@pytest.mark.parametrize("metric", METRICS)
def test_readers_give_nothing_for_a_program_without_such_layers(
        monkeypatch, metric, events):
    """The llama trace and the Jamba trace: no retention scope, no
    retention kernel.  None, and no exception."""
    run = _run(monkeypatch, events)
    assert bench_run.reader(metric)(run) is None
    monkeypatch.setattr(ps, "trace_of", lambda _run: None)
    monkeypatch.setattr(retention_spans, "trace_of", lambda _run: None)
    assert bench_run.reader(metric)(run) is None


def test_the_scopes_are_known_only_while_a_trace_is_read():
    path = "jit(serve_ragged)/while/body/closed_call/ret_proj/dot_general:"
    inner = "jit(serve_ragged)/while/body/closed_call/retention/div:"
    assert ps.op_label("fusion.7", "fusion(...)", path) == ps.UNSCOPED
    before = ps.SCOPES
    with retention_spans.scopes_added():
        assert ps.op_label("fusion.7", "fusion(...)", path) == "ret_proj"
        assert ps.op_label("fusion.9", "fusion(...)", inner) == "retention"
        assert ps.op_label("fusion.8", "fusion(...)",
                           "jit(serve_ragged)/mlp/dot_general:") == "mlp"
    assert ps.SCOPES == before
    assert ps.op_label("fusion.7", "fusion(...)", path) == ps.UNSCOPED
    # the kernels are booked under their own names with or without them
    for kernel in ("retention_decode", "retention_chunk"):
        assert ps.op_label(f"%{kernel}.3", ps.MOSAIC_CALL, inner) == kernel
