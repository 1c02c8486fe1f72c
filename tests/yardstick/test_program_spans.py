"""The reduction from the program's own spans and the device's labelled
operations to the per-layer metrics that read them, on a small
hand-built event list (program_span_events.json, beside this file): self
time by thread, the counts by step, device time by kernel and scope per
module execution, the join of module execution to step with its checks,
and each new reader's arithmetic.  Times in the list are picoseconds."""

import copy
import json
import os
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import program_spans as ps

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000_000


def _load():
    raw = json.load(open(os.path.join(HERE, "program_span_events.json")))
    return {"host": [[(n, s, d, dict(stats)) for n, s, d, stats in line]
                     for line in raw["host"]],
            "devices": {k: {"ops": [tuple(o) for o in d["ops"]],
                            "modules": [tuple(m) for m in d["modules"]]}
                        for k, d in raw["devices"].items()}}


@pytest.fixture()
def trace():
    return _load()


@pytest.fixture()
def run(trace, monkeypatch):
    """A run whose trace is the hand-built list."""
    monkeypatch.setattr(ps, "trace_of", lambda _run: trace)
    return types.SimpleNamespace(cell="hand-built", notes={})


def test_window_and_whole_spans(trace):
    assert ps.window(trace) == (0, 100 * MS)
    lines = ps.program_lines(trace)
    assert len(lines) == 5
    # the window's own span is no program span; the worker's enqueues
    # (no prefix) are kept for the join only
    assert lines[4] == [] and lines[2] == []
    assert len(ps.named(lines, "llm.loop")) == 7


def test_self_time_is_duration_minus_nested_spans_on_the_thread(trace):
    own = ps.self_time_by_name(ps.program_lines(trace))
    # five stepping iterations of 6, 6, 7, 6, 6 ms whose phases cover
    # 4, 4, 5.6, 4, 4; two waiting ones of 3 and 1.2 covering 2.6 and 0.9
    assert own["llm.loop"] == pytest.approx(
        (2.0 * 4 + 1.4 + 0.4 + 0.3) * MS)
    assert own["llm.pack"] == pytest.approx(6 * MS)
    # the harness's iterator span sits inside the trainer's data wait
    assert own["train.data_wait"] == pytest.approx(1 * MS)
    assert own["bench.data_next"] == pytest.approx(1 * MS)
    assert own["train.step"] == pytest.approx(0.5 * MS)
    # another thread's span is nobody's child
    assert own["llm.fetch"] == pytest.approx((16 + 30.5 + 9.5) * MS)


def test_counts_by_step(trace):
    packs = ps.packs_by_seq(ps.program_lines(trace))
    assert sorted(packs) == [11, 12, 13, 14, 15]
    assert packs[13]["n_prefill"] == 29 and packs[12]["n_prefill"] == 0
    fetches = ps.named(ps.program_lines(trace), "llm.fetch")
    assert [ps.seqs_of(f) for f in fetches] == [[11], [12, 13, 14], [15]]


def test_host_time_per_step_counts_the_dispatching_iterations(trace):
    per_step = ps.host_ms_per_step(ps.program_lines(trace))
    # waiting iterations are no steps; the third also emitted
    assert sorted(per_step) == pytest.approx([4.0, 4.0, 4.0, 4.0, 5.6])


def test_device_time_by_label_per_execution(trace):
    per = ps.label_ps_per_execution(trace, ps.SERVE_MODULE)
    assert len(per) == 5        # the clipped execution is not whole
    assert [p["weight_slice"] for p in per] == [
        3 * MS, 3 * MS, 4 * MS, 3 * MS, 3 * MS]
    # the loop over the layers keeps what its children do not cover
    assert all(p["unscoped"] == MS // 2 for p in per)
    assert sum(per[0].values()) == 10 * MS
    assert ps.label_ms_per_step(trace, ps.SERVE_MODULE,
                                ["fused_ragged_layer"]) == pytest.approx(5.0)
    assert ps.label_ms_per_step(trace, ps.SERVE_MODULE,
                                ["weight_slice"]) == pytest.approx(3.2)
    assert ps.label_ms_per_step(
        trace, ps.TRAIN_MODULE,
        ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]) == pytest.approx(12.0)
    # a program without these names: nothing to read, not zero
    assert ps.label_ms_per_step(trace, ps.SERVE_MODULE,
                                ["closed_call"]) is None
    assert ps.label_ms_per_step(trace, "jit_ragged_step_fn",
                                ["weight_slice"]) is None
    table = ps.label_table(trace, ps.TRAIN_MODULE)
    assert table[0] == ("mlp", pytest.approx(8.0),
                        pytest.approx(100 * 8 / 23))
    assert sum(row[2] for row in table) == pytest.approx(100.0)


@pytest.mark.parametrize("instruction, hlo, path, want", [
    ("fused_ragged_layer.9",
     '%fused_ragged_layer.9 = (bf16[80,4096]) custom-call(%x), '
     'custom_call_target="tpu_custom_call"',
     "jit(serve_ragged)/while/body/closed_call/fused_layer/"
     "fused_ragged_layer/pallas_call:", "fused_ragged_layer"),
    ("dynamic-slice_bitcast_fusion.16", "%f = s8[1] fusion(%p)",
     "jit(serve_ragged)/while/body/closed_call/fused_layer/reshape;"
     "weight_slice/squeeze:", "weight_slice"),
    ("fusion.877", "%fusion.877 = bf16[4] fusion(%pallas_call.52)",
     "jit(train_step)/loss/transpose(jvp(attention))/mul:", "attention"),
    ("fusion.3", "%fusion.3 = f32[] fusion()",
     "jit(train_step)/optimizer/add:", "optimizer"),
    ("while.4", "%while.4 = () while()", "jit(serve_ragged)/while:",
     ps.UNSCOPED),
])
def test_op_label(instruction, hlo, path, want):
    assert ps.op_label(instruction, hlo, path) == want


def test_join_by_run_id_and_its_checks(trace):
    joined = ps.join_steps(trace)
    assert joined == {11: (6 * MS, 16 * MS), 12: (16 * MS, 26 * MS),
                      13: (26 * MS, 38 * MS), 14: (38 * MS, 48 * MS),
                      15: (48 * MS, 58 * MS)}
    assert ps.step_device_ms(trace, prefill=False) == pytest.approx(
        [10.0, 10.0, 10.0, 10.0])
    assert ps.step_device_ms(trace, prefill=True) == pytest.approx([12.0])


def test_join_by_anchoring_where_the_trace_has_no_run_id(trace):
    dev = trace["devices"]["/device:TPU:0"]
    dev["modules"] = [(n, s, d, None) for n, s, d, _rid in dev["modules"]]
    assert ps.join_steps(trace) == ps.join_steps(_load())


def test_join_refuses_an_out_of_order_pair(trace):
    """Two executions with their ids exchanged: the one matched to step
    13 now starts before step 13's dispatch began."""
    dev = trace["devices"]["/device:TPU:0"]
    swap = {102: 103, 103: 102}
    dev["modules"] = [(n, s, d, swap.get(rid, rid))
                      for n, s, d, rid in dev["modules"]]
    assert ps.join_steps(trace) is None
    assert ps.step_device_ms(trace, prefill=False) is None


def test_join_refuses_an_execution_that_outlasts_its_fetch(trace):
    line = trace["host"][1]
    name, start, _dur, stats = line[0]
    line[0] = (name, start, 10 * MS, stats)   # ends at 15, the step at 16
    assert ps.join_steps(trace) is None


def test_idle_gaps_are_named_by_the_programs_spans(trace):
    gaps = dict(ps.gap_table(trace))
    # [0, 6): its middle lies in the first step's llm.pack; [58, 66.5):
    # in the second waiting iteration's llm.control; [87.5, 88.5), before
    # the optimizer: the trainer is in train.report; [90.5, 100): nothing
    assert gaps == {"llm.pack": pytest.approx(6.0),
                    "llm.control": pytest.approx(8.5),
                    "train.report": pytest.approx(1.0),
                    "no_program_span": pytest.approx(9.5)}


# -- the readers -----------------------------------------------------------

@pytest.mark.parametrize("metric, want", [
    ("sched_host_ms_per_step", 4.0),
    ("token_budget_fill_share", (4 * 3.75 + 40.0) / 5),
    ("page_cells_live_share", 100.0 * 68 / 3280),
    ("step_device_ms_p50_decode", None),      # four steps: under five
    ("step_device_ms_p50_prefill", None),     # one step
    ("fused_layer_ms_per_step", 5.0),
    ("weight_slice_ms_per_step", 3.2),
    ("flash_attn_ms_per_step", 12.0),
    ("optimizer_ms_per_step", 2.0),
    ("data_wait_ms_per_step", 2.0),
])
def test_reader(run, metric, want):
    got = bench_run.reader(metric)(run)
    if want is None:
        assert got is None
        assert "fewer than 5" in run.notes[metric]
    else:
        assert got == pytest.approx(want)


def test_step_classes_report_with_five_steps(run, trace, monkeypatch):
    monkeypatch.setattr(ps, "MIN_CLASS_STEPS", 4)
    assert bench_run.reader("step_device_ms_p50_decode")(run) == \
        pytest.approx(10.0)
    assert bench_run.reader("step_device_ms_p50_prefill")(run) is None


def test_readers_return_none_without_the_programs_spans(run, trace):
    """The parent of the PR that added them opens no span, names no
    kernel and no scope: every reader finds nothing and says nothing."""
    trace["host"] = [[s for s in line if s[0] == ps.WINDOW_SPAN
                      or not s[0].startswith(ps.SPAN_PREFIXES)]
                     for line in trace["host"]]
    dev = trace["devices"]["/device:TPU:0"]
    dev["ops"] = [("closed_call" if lb == "fused_ragged_layer" else
                   ps.UNSCOPED, s, d) for lb, s, d in dev["ops"]]
    dev["modules"] = [("jit_ragged_step_fn", s, d, rid)
                      for _n, s, d, rid in dev["modules"]]
    bench = bench_run.benchmark_file()
    new = [m["name"] for m in bench["per_layer"]][-10:]
    assert new[0] == "sched_host_ms_per_step"
    for metric in new:
        assert bench_run.reader(metric)(run) is None, metric
    empty = copy.deepcopy(trace)
    empty["devices"] = {}
    assert ps.gap_table(empty) == []


def test_trace_of_finds_nothing_without_a_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(ps, "ROOT", str(tmp_path))
    run = types.SimpleNamespace(cell="no-such-cell", notes={})
    assert ps.trace_of(run) is None
    for metric in ("sched_host_ms_per_step", "flash_attn_ms_per_step",
                   "step_device_ms_p50_decode"):
        assert bench_run.reader(metric)(run) is None
