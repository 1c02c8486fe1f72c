"""The seven ``setup_*`` readers over a recorded start-up record
(``startup_events.json``: a driver and a replica's worker, imports that
nest, a traced function inside another's trace, a lowering done twice,
one cache hit, one miss before the window and one after it)."""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import startup
from benchmarks.harness.run_record import Run

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "startup_events.json")) as f:
    FIXTURE = json.load(f)
NAMES = ("setup_import_s", "setup_runtime_s", "setup_weights_s",
         "setup_trace_lower_s", "setup_compile_s",
         "setup_cache_miss_programs", "setup_unnamed_s")
BENCH = bench_run.benchmark_file()


def _run():
    # a closed loop's ramp is the traffic's; an open loop's is the lead
    # the generator took (test_the_ramp_is_the_lead_the_run_took)
    return Run(cell="mistral7b_w8-chat", config={},
               traffic={"ramp_s": FIXTURE["ramp_s"], "loop": "closed"},
               chips=1, seconds=45.0,
               setup_s=FIXTURE["setup_s"],
               device={"platform": "tpu", "memory_peak_bytes": 13 * 2**30})


@pytest.fixture
def recorded(monkeypatch):
    monkeypatch.setattr(startup, "record", lambda: FIXTURE["record"])
    monkeypatch.setattr(startup, "process_start",
                        lambda: FIXTURE["t_start"])


@pytest.mark.parametrize("name", NAMES)
def test_reader_value(recorded, name):
    assert bench_run.reader(name)(_run()) == pytest.approx(
        FIXTURE["expect"][name], abs=1e-6)


@pytest.mark.parametrize("name", NAMES)
def test_benchmark_entry_by_name(name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["moves"] == "setup_s" and entry["better"] == "lower"
    assert entry["workloads"] == [w["name"] for w in BENCH["workloads"]]
    assert entry["source"] == ("program_counter" if name.endswith("programs")
                               else "program_span")
    assert entry["unit"] == ("programs" if name.endswith("programs")
                             else "s")
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"]
                              if not m["name"].startswith("setup_")}


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_none_without_a_record(monkeypatch, name):
    from ray_tpu.util import flight_recorder

    run = _run()
    monkeypatch.setattr(flight_recorder, "startup",
                        lambda proc=None: {"driver": []})
    assert bench_run.reader(name)(run) is None
    # the parent's recorder has no such function at all
    monkeypatch.delattr(flight_recorder, "startup")
    assert bench_run.reader(name)(run) is None
    assert "setup" not in run.notes


def test_books_balance_and_an_instant_is_counted_once(recorded):
    run = _run()
    books = startup.books(run)
    assert run.notes["setup"] is books and startup.books(run) is books
    want = FIXTURE["expect"]
    assert books["unnamed_by_span"] == pytest.approx(want["unnamed_by_span"])
    assert sum(books["unnamed_by_span"].values()) == pytest.approx(
        books["unnamed_s"])
    assert sum(books["classes"].values()) + books["unnamed_s"] == \
        pytest.approx(FIXTURE["setup_s"] - FIXTURE["ramp_s"])
    assert books["books"]["ok"] and books["books"]["named_s"] == \
        pytest.approx(18.7)
    # the trace of ``wrapped`` lies inside ``serve_ragged``'s: the stage
    # table has both, the class counts the second it covers once
    stages = books["by_program"]["serve.ragged@8"]
    assert stages["trace"] == {"n": 2, "seconds": pytest.approx(1.4)}
    assert stages["lower"] == {"n": 2, "seconds": pytest.approx(2.0)}
    assert stages["cache"] == ["miss"]
    assert books["by_program"]["check"]["cache"] == ["hit"]
    assert books["cache_missed"] == ["serve.ragged@8"]   # not ``late``
    # nested imports: the outer one's self time leaves the inner's out
    outer = books["by_span"]["import{ray_tpu.serve.llm_engine}"]
    assert outer["seconds"] == pytest.approx(3.0)
    assert outer["self_s"] == pytest.approx(2.0)
    assert books["by_span"]["import{ray_tpu}"]["n"] == 2
    assert books["replica_init_self_s"] == pytest.approx(2.5)
    assert books["short_stages"] == {"compile": {"n": 40, "seconds": 1.2}}
    assert books["processes"] == {"driver": 1, "w1": 2}
    assert books["hbm_peak"]["first_shown_by"] == \
        "llm.first_step{serve.ragged@8}"
    assert books["hbm_peak"]["bytes"] == 12 * 2**30
    assert books["hbm_peak"]["at_s"] == pytest.approx(25.0)
    # a compile inside the ramp is a hole in the warm-up: named, no class
    assert books["stages_in_ramp"] == ["multiply:compile"]


def test_a_misread_process_start_fails_the_books(recorded, monkeypatch):
    assert startup.books(_run())["books"]["first_event_s"] == \
        pytest.approx(0.5)
    # read 3 s late, the record's first events precede "the start"
    monkeypatch.setattr(startup, "process_start",
                        lambda: FIXTURE["t_start"] + 3.0)
    late = startup.books(_run())["books"]
    assert late["first_event_s"] == pytest.approx(-2.5) and not late["ok"]


def test_the_ramp_is_the_lead_the_run_took():
    run = _run()
    assert startup.ramp_of(run) == 8.0
    run.traffic["loop"] = "open"
    run.requests = [{"due": -6.5}, {"due": -1.0}, {"due": 3.0}]
    assert startup.ramp_of(run) == pytest.approx(6.55)
    run.requests = [{"due": 0.4}]        # a rate too low for a ramp request
    assert startup.ramp_of(run) == pytest.approx(0.05)
    train = Run(cell="internlm2_1b8-pretrain_4k", config={},
                traffic={"loop": "train"}, chips=1, seconds=45.0,
                setup_s=40.0, device={})
    assert startup.ramp_of(train) == 0.0
