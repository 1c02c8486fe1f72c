"""The reduction from a profiler trace to busy and idle share, Pallas
share, exposed collective time, top operations and idle gaps, on a small
hand-built event list (trace_events.json, beside this file); and the
peaks table's refusal of a device it does not know."""

import json
import os

import pytest

from benchmarks.harness import flops, peaks, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace():
    raw = json.load(open(os.path.join(HERE, "trace_events.json")))
    return {"devices": {k: {kk: [tuple(e) for e in vv]
                            for kk, vv in d.items()}
                        for k, d in raw["devices"].items()},
            "host": [tuple(e) for e in raw["host"]]}


@pytest.mark.parametrize("key,want", [
    ("window_s", 2000e-6),
    ("busy_s", 1500e-6),               # [0,1000) and [1500,2000)
    ("pallas_s", 400e-6),
    ("collective_s", 300e-6),          # all-gather 200 + all-reduce-done 100
    ("collective_exposed_s", 300e-6),  # nothing else ran beside either
])
def test_reduce_totals(trace, key, want):
    assert trace_reduce.reduce(trace)[key] == pytest.approx(want)


def test_self_time_counts_every_nanosecond_once(trace):
    out = trace_reduce.reduce(trace)
    names = dict(out["device_ops"])
    assert names["fusion.7"] == pytest.approx(550e-6)
    # the while's own share is what its children do not cover
    assert names["while.1"] == pytest.approx(100e-6)
    assert sum(names.values()) == pytest.approx(out["busy_s"])
    assert out["device_ops"][0][0] == "fusion.7"


def test_idle_gap_is_named_by_the_innermost_host_span(trace):
    out = trace_reduce.reduce(trace)
    assert out["idle_gaps"] == [["bench.data_next", pytest.approx(500e-6)]]


def test_step_program_is_the_module_with_most_time(trace):
    out = trace_reduce.reduce(trace)
    assert out["step_program"] == "jit_step(1)"
    assert sorted(out["step_ms"]) == pytest.approx([0.5, 1.0])


def test_overlapped_collective_is_not_exposed():
    ops = [("all-reduce.1", 0, 100), ("fusion.2", 50, 100)]
    dev = trace_reduce.reduce_device(ops)
    assert dev["collective_ns"] == 100
    assert dev["collective_exposed_ns"] == 50


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 4), (6, 20)], [(0, 2), (4, 6)]),
    ([(0, 10), (20, 30)], [], [(0, 10), (20, 30)]),
    ([(0, 10)], [(0, 10)], []),
])
def test_interval_subtract(a, b, want):
    assert trace_reduce.subtract(a, b) == want


def test_union_merges_touching_and_nested():
    assert trace_reduce.union([(5, 7), (0, 3), (3, 4), (1, 2)]) == \
        [(0, 4), (5, 7)]


def test_empty_trace_is_refused():
    with pytest.raises(ValueError, match="no device operation"):
        trace_reduce.reduce({"devices": {}, "host": [
            (trace_reduce.WINDOW_SPAN, 0, 100)]})


def _edge_trace(ops, modules=()):
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": [(trace_reduce.WINDOW_SPAN, 1000, 4000),
                     ("fit", 0, 9000)]}


@pytest.mark.parametrize("ops,busy", [
    ([("fusion.1", 2000, 1000)], 1000),     # idle at both edges counts
    ([("fusion.1", 500, 1000)], 500),       # cut at the window's start
    ([("fusion.1", 4500, 1000), ("fusion.2", 6000, 10)], 500),  # and end
])
def test_window_is_the_harness_span_not_the_ops_extent(ops, busy):
    out = trace_reduce.reduce(_edge_trace(ops))
    assert out["window_s"] == pytest.approx(4000e-9)
    assert out["busy_s"] == pytest.approx(busy * 1e-9)
    assert sum(v for _k, v in out["device_ops"]) == \
        pytest.approx(out["busy_s"])


def test_step_times_are_of_whole_steps_inside_the_window():
    out = trace_reduce.reduce(_edge_trace(
        [("fusion.1", 500, 4000)],
        modules=[("jit_step(1)", 500, 1500), ("jit_step(1)", 2000, 1000),
                 ("jit_step(1)", 4000, 2000)]))
    assert out["step_ms"] == pytest.approx([1000e-6])


def test_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="bench.trace_window"):
        trace_reduce.reduce({"devices": {"/device:TPU:0": {
            "ops": [("fusion.1", 0, 10)]}}, "host": [("fit", 0, 10)]})


def test_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")


def test_train_flops_from_published_shapes():
    c = {"hidden_size": 2048, "intermediate_size": 8192,
         "num_hidden_layers": 24, "num_attention_heads": 16,
         "num_key_value_heads": 8, "vocab_size": 92544}
    n = flops.matmul_params(c)
    per_layer = 2048 * 2048 * 2 + 2 * 2048 * 1024 + 3 * 2048 * 8192
    assert n == 24 * per_layer + 2048 * 92544
    assert flops.train_flops_per_token(c, 4096) == \
        6.0 * n + 6.0 * 4096 * 2048 * 24


def test_hlo_text_names_are_shortened_and_pallas_marked(trace):
    names = dict(trace_reduce.reduce(trace)["device_ops"])
    assert names["closed_call.3__pallas"] == pytest.approx(400e-6)
    assert trace_reduce.short_name("%all-gather.2 = bf16[8]") == \
        "all-gather.2"
    assert not trace_reduce.is_pallas("%fusion.7 = bf16[8] fusion(...)")


def test_async_collective_is_exposed_only_where_nothing_else_runs():
    ops = [("fusion.1", 0, 100), ("fusion.2", 150, 50)]
    async_ops = [("all-gather-start.3", 50, 100), ("copy-start.4", 0, 500)]
    dev = trace_reduce.reduce_device(ops, (), async_ops)
    assert dev["collective_ns"] == 100          # [50, 150); the copy is none
    assert dev["collective_exposed_ns"] == 50   # [100, 150)
    assert dev["busy_ns"] == 150                # async transfers are not busy
