"""The eight readers of a step's way to the device and a token's way to
the transport, on a small hand-built event list
(step_timeline_events.json, beside this file), and what they give for a
program without the spans they read and for a window whose join fails.
Times in the list are picoseconds; the values below are worked out by
hand from it, in milliseconds:

    step            21     22     23     24     25
    dispatch_end     4.0    7.0   10.0   40.6   44.0
    exec_start       5     15     25     41     51
    exec_end        15     25     35     51     61
    fetch_end       15.5   25.3   38.9   51.2   61.5
    emit_start      16.0   26.0   39.0   52.0   62.2
"""

import copy
import json
import os
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import program_spans as ps
from benchmarks.harness import step_timeline

HERE = os.path.dirname(os.path.abspath(__file__))
EVENTS = "step_timeline_events.json"
WANT = {
    # 1.0, 8.0, 15.0, 0.4, 7.0
    "dispatch_lead_p50_ms": 7.0,
    # 0.5, 0.3, 3.9 (the late one), 0.2, 0.5
    "fetch_lag_p50_ms": 0.5,
    # 0.5, 0.7, 0.1, 0.8, 0.7
    "emit_lag_p50_ms": 0.7,
    # cpu_us of the five iterations that dispatched: 1200 1500 900 1400 1100;
    # their mean (a clock that ticks every 10 ms leaves no median to take)
    "sched_cpu_ms_per_step": 1.22,
    # nine items of three requests: 1.9 2.0 2.1 2.1 2.2 2.3 2.4 5.1 5.4
    "token_out_lag_p50_ms": 2.2,
    # 2.3 + 1.9 + 0.7 ms of three requests' threads and 0.5 of a target
    # that is no engine, over five steps
    "stream_send_ms_per_step": 5.4 / 5,
    # three requests: 1.3, 1.5, 2.0 (the fourth enters no engine)
    "replica_ingress_p50_ms": 1.5,
    # 5 ms before the loop's first recorded iteration and 6 ms under
    # llm.wait, over five steps; 39 ms under llm.idle are nobody's loss
    "live_idle_ms_per_step": 11.0 / 5,
}
# what only this PR's spans can answer: the program before it has none
NEEDS_NEW_SPANS = ("sched_cpu_ms_per_step", "token_out_lag_p50_ms",
                   "stream_send_ms_per_step", "replica_ingress_p50_ms")


def _load(name):
    raw = json.load(open(os.path.join(HERE, name)))
    return {"host": [[(n, s, d, dict(stats)) for n, s, d, stats in line]
                     for line in raw["host"]],
            "devices": {k: {"ops": [tuple(o) for o in d["ops"]],
                            "modules": [tuple(m) for m in d["modules"]]}
                        for k, d in raw["devices"].items()}}


@pytest.fixture()
def trace():
    return _load(EVENTS)


@pytest.fixture()
def run(monkeypatch, trace):
    monkeypatch.setattr(ps, "trace_of", lambda _run: trace)
    return types.SimpleNamespace(cell="hand-built", notes={})


def _renamed(trace, old, new):
    trace["host"] = [[(new if n == old else n, s, d, st)
                      for n, s, d, st in line] for line in trace["host"]]


def test_the_instants_of_each_step(trace):
    steps = step_timeline.build(trace)
    assert sorted(steps) == [21, 22, 23, 24, 25]
    ms = step_timeline.MS
    assert [(s.dispatch_end / ms, s.exec_start / ms, s.exec_end / ms,
             s.fetch_end / ms, s.emit_start / ms)
            for _seq, s in sorted(steps.items())] == [
        (4.0, 5.0, 15.0, 15.5, 16.0), (7.0, 15.0, 25.0, 25.3, 26.0),
        (10.0, 25.0, 35.0, 38.9, 39.0), (40.6, 41.0, 51.0, 51.2, 52.0),
        (44.0, 51.0, 61.0, 61.5, 62.2)]
    assert [len(s.item_ends) for _seq, s in sorted(steps.items())] \
        == [1, 1, 2, 2, 3]
    # asked again, the same object: eight readers, one pass
    assert step_timeline.build(trace) is steps


@pytest.mark.parametrize("metric", sorted(WANT))
def test_readers_on_the_hand_built_trace(run, metric):
    assert bench_run.reader(metric)(run) == pytest.approx(WANT[metric])


def test_the_hops_lie_inside_the_tokens_way_out(run):
    got = {m: bench_run.reader(m)(run) for m in WANT}
    assert got["fetch_lag_p50_ms"] + got["emit_lag_p50_ms"] \
        <= got["token_out_lag_p50_ms"]


def test_idle_books_balance_and_name_the_longest_stretch(run, trace):
    assert bench_run.reader("live_idle_ms_per_step")(run) \
        == pytest.approx(2.2)
    books = run.notes["live_idle_ms_per_step"]
    assert books["joined_steps"] == 5
    assert books["live_ms"] == pytest.approx(11.0)
    assert books["unnamed_ms"] == pytest.approx(5.0)
    assert books["empty_ms"] == pytest.approx(39.0)
    # 10 us between two operations of step 21: under MIN_GAP
    assert books["short_ms"] == pytest.approx(0.01)
    assert (books["longest_ms"], books["longest_under"]) \
        == (pytest.approx(6.0), "llm.wait")
    # the parts are the window less the device's busy time: 100 - 49.99
    busy_ms = sum(d for _n, _s, d in
                  trace["devices"]["/device:TPU:0"]["ops"]) / 1e9
    assert books["live_ms"] + books["empty_ms"] + books["short_ms"] \
        == pytest.approx(100.0 - busy_ms)
    # the harness's own table names the two waits apart
    gaps = dict(ps.gap_table(trace))
    assert gaps["llm.wait"] == pytest.approx(6.0)
    assert gaps["llm.idle"] == pytest.approx(39.0)


def test_a_gap_under_idle_is_nobodys_loss_and_under_wait_it_counts(
        run, trace):
    """The same 39 ms with no device operation: the engine empty
    (``llm.idle``) it is left out, steps in flight (``llm.wait``) it is
    the device standing still while a request waits."""
    read = bench_run.reader("live_idle_ms_per_step")
    assert read(run) == pytest.approx(11.0 / 5)
    _renamed(trace, "llm.idle", "llm.wait")
    assert read(run) == pytest.approx((11.0 + 39.0) / 5)
    assert run.notes["live_idle_ms_per_step"]["empty_ms"] == 0
    assert run.notes["live_idle_ms_per_step"]["longest_ms"] \
        == pytest.approx(39.0)


def test_ingress_under_three_arrivals_is_not_reported(run, trace):
    # the third request's thread is gone: two arrivals are no median
    trace["host"] = [line for line in trace["host"]
                     if not any(s[0] == "llm.submit" and s[1] == 42200000000
                                for s in line)]
    assert bench_run.reader("replica_ingress_p50_ms")(run) is None
    assert "fewer than 3" in run.notes["replica_ingress_p50_ms"]
    # the other readers still read: the request's one item is gone
    assert bench_run.reader("token_out_lag_p50_ms")(run) \
        == pytest.approx(2.15)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_readers_give_nothing_without_the_programs_spans(run, trace,
                                                         metric):
    """A program that opens no span at all, and a run that left no
    trace: None, and no exception."""
    trace["host"] = [[s for s in line if s[0] == ps.WINDOW_SPAN
                      or not s[0].startswith(ps.SPAN_PREFIXES)]
                     for line in trace["host"]]
    assert bench_run.reader(metric)(run) is None
    assert run.notes == {}


@pytest.mark.parametrize("metric", sorted(WANT))
def test_readers_give_nothing_without_a_trace(run, monkeypatch, metric):
    monkeypatch.setattr(ps, "trace_of", lambda _run: None)
    assert bench_run.reader(metric)(run) is None
    assert run.notes == {}


@pytest.mark.parametrize("metric", sorted(WANT))
def test_readers_on_the_program_before_these_spans(run, trace, metric):
    """The parent of the PR that added them: ``llm.loop`` carries no
    ``cpu_us``, both waits are ``llm.idle``, nothing is open on a
    request's thread but ``serve.replica``.  What needs the new spans
    reads None; the rest reads what the old spans give; nothing
    raises."""
    _renamed(trace, "llm.wait", "llm.idle")
    trace["host"] = [[(n, s, d, {k: v for k, v in st.items()
                                 if k not in ("cpu_us", "in_flight")})
                      for n, s, d, st in line
                      if n not in ("llm.submit", "serve.stream_item")]
                     for line in trace["host"]]
    got = bench_run.reader(metric)(run)
    if metric in NEEDS_NEW_SPANS:
        assert got is None
    elif metric == "live_idle_ms_per_step":
        # it cannot tell the waits apart there: the 6 ms read as empty
        assert got == pytest.approx(5.0 / 5)
    else:
        assert got == pytest.approx(WANT[metric])


@pytest.mark.parametrize("metric", sorted(WANT))
def test_readers_give_nothing_where_the_join_fails(run, trace, metric):
    """An execution that began before its step's dispatch did: the join
    is wrong somewhere, and no reader builds on it."""
    dev = trace["devices"]["/device:TPU:0"]
    dev["modules"] = [(n, s - (3_000_000_000 if rid == 204 else 0), d, rid)
                      for n, s, d, rid in dev["modules"]]
    assert ps.join_steps(copy.deepcopy(trace)) is None
    assert bench_run.reader(metric)(run) is None
    assert run.notes == {}


def test_the_registry_lists_the_eight_for_the_serving_cells():
    """Found by name: what a later PR appends does not move them."""
    bench = bench_run.benchmark_file()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    serving = ["mistral7b_w8-chat", "brumby14b_pp4-doc_long",
               "xing4_29b_pp8-reason", "jamba2_3b-chat_short"]
    for metric in WANT:
        entry = by_name[metric]
        assert entry["moves"] == "tpot_p50_ms" and entry["unit"] == "ms"
        assert os.path.exists(os.path.join(
            bench_run.HERE, "metric_readers", metric + ".py"))
        if metric == "replica_ingress_p50_ms":
            # a request has to arrive AND end inside the 4 s capture
            assert entry["workloads"] == ["jamba2_3b-chat_short"]
        else:
            assert sorted(entry["workloads"]) == sorted(serving)
