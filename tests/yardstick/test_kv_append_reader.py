"""``kv_append_ms_per_step`` on the hand-built event list
(program_span_events.json, beside this file): the append kernel's device
time per whole execution of the serving step where the trace names the
kernel, and nothing where it does not.  Times in the list are
picoseconds."""

import json
import os
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import program_spans as ps

HERE = os.path.dirname(os.path.abspath(__file__))


def _load():
    raw = json.load(open(os.path.join(HERE, "program_span_events.json")))
    return {"host": [[(n, s, d, dict(stats)) for n, s, d, stats in line]
                     for line in raw["host"]],
            "devices": {k: {"ops": [tuple(o) for o in d["ops"]],
                            "modules": [tuple(m) for m in d["modules"]]}
                        for k, d in raw["devices"].items()}}


def _without_the_kernel(trace):
    """A program whose append is not a named kernel (the two-program
    path's, or a parent's of the PR that named it)."""
    for dev in trace["devices"].values():
        dev["ops"] = [(ps.UNSCOPED if lb == "ragged_kv_append" else lb, s, d)
                      for lb, s, d in dev["ops"]]
    return trace


def _a_shorter_append(trace):
    """The third whole execution's append takes half a millisecond."""
    for dev in trace["devices"].values():
        dev["ops"] = [(lb, s, d // 2 if (lb, s) == (
            "ragged_kv_append", 35_500_000_000) else d)
            for lb, s, d in dev["ops"]]
    return trace


@pytest.mark.parametrize("edit, want", [
    # 1 ms in each of the five whole executions; the clipped one is out
    (lambda t: t, 1.0),
    (_a_shorter_append, (4 * 1.0 + 0.5) / 5),
    (_without_the_kernel, None),
    (lambda t: None, None),                   # the run left no trace
], ids=["recorded", "one_shorter", "no_such_kernel", "no_trace"])
def test_kv_append_ms_per_step(monkeypatch, edit, want):
    trace = edit(_load())
    monkeypatch.setattr(ps, "trace_of", lambda _run: trace)
    run = types.SimpleNamespace(cell="hand-built", notes={})
    got = bench_run.reader("kv_append_ms_per_step")(run)
    assert got == (want if want is None else pytest.approx(want))
    assert run.notes == {}


def test_the_benchmark_lists_it_for_both_serving_cells():
    (entry,) = [m for m in bench_run.benchmark_file()["per_layer"]
                if m["name"] == "kv_append_ms_per_step"]
    assert entry == {
        "name": "kv_append_ms_per_step", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels (ops/)",
        "moves": "tpot_p50_ms",
        "workloads": ["mistral7b_w8-chat", "jamba2_3b-chat_short"]}
