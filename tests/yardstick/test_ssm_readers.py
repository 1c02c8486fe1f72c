"""The readers of the state-space layers' metrics on a small hand-built
event list (ssm_span_events.json, beside this file), the byte count
behind the roofline share from the published shapes, and what the
readers give for a program that has no such layer.  Times in the list
are picoseconds."""

import json
import os
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import program_spans as ps
from benchmarks.harness import ssm_bytes, ssm_spans

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = bench_run.load_json(os.path.join(
    bench_run.ROOT, "benchmarks", "configs", "jamba2_3b.json"))


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
    monkeypatch.setattr(ssm_spans, "trace_of", lambda _run: trace)
    return types.SimpleNamespace(cell="hand-built", notes={}, config=CONFIG,
                                 device={"kind": "TPU v5 lite"})


def test_state_bytes_come_from_the_published_shapes():
    assert ssm_bytes.mamba_layers(CONFIG) == 26
    # [5120, 16] float32 and three bf16 inputs of 5120
    assert ssm_bytes.state_bytes_per_row_layer(CONFIG) == 327680 + 30720
    assert ssm_bytes.state_update_bytes(CONFIG, 1) == 2 * 9_318_400
    assert ssm_bytes.state_update_bytes(CONFIG, 40) == 40 * 18_636_800
    # the program's own count of what a slot holds agrees
    from benchmarks.runners import serve_jamba

    assert serve_jamba.model_config(CONFIG).state_bytes_per_slot() \
        == 9_318_400


@pytest.mark.parametrize("metric,want", [
    ("ssm_scan_ms_per_step", (2.0 + 3.0 + 2.5) / 3),
    ("ssm_mixer_time_share", 100 * 19.5 / 28),
    # steps 21 and 23 carry no prompt token: 40 and 50 rows in 2 and 2.5 ms
    ("ssm_state_roofline_share",
     100 * (90 * 18_636_800 / 819e9) / 4.5e-3),
    ("state_reset_rows_per_step", 2 / 3),
])
def test_readers_on_the_hand_built_trace(monkeypatch, metric, want):
    run = _run(monkeypatch, "ssm_span_events.json")
    assert bench_run.reader(metric)(run) == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "ssm_scan_ms_per_step", "ssm_mixer_time_share",
    "ssm_state_roofline_share", "state_reset_rows_per_step"])
def test_readers_give_nothing_for_a_program_without_such_layers(
        monkeypatch, metric):
    """The llama trace of test_program_spans: no ssm scope, no
    ``n_state_reset`` on ``llm.pack``.  None, and no exception."""
    run = _run(monkeypatch, "program_span_events.json")
    assert bench_run.reader(metric)(run) is None
    monkeypatch.setattr(ps, "trace_of", lambda _run: None)
    monkeypatch.setattr(ssm_spans, "trace_of", lambda _run: None)
    assert bench_run.reader(metric)(run) is None


def test_the_three_scopes_are_known_only_while_a_trace_is_read():
    path = "jit(serve_ragged)/while/body/closed_call/ssm_proj/dot_general:"
    assert ps.op_label("fusion.7", "fusion(...)", path) == ps.UNSCOPED
    with ssm_spans.scopes_added():
        assert ps.op_label("fusion.7", "fusion(...)", path) == "ssm_proj"
        assert ps.op_label("fusion.8", "fusion(...)",
                           "jit(serve_ragged)/mlp/dot_general:") == "mlp"
    assert ps.op_label("fusion.7", "fusion(...)", path) == ps.UNSCOPED
    # the kernel is booked under its own name with or without them
    assert ps.op_label("%ssm_scan.3", ps.MOSAIC_CALL, path) == "ssm_scan"
