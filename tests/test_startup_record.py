"""The start-up record (ISSUE 51): start-up spans that leave a flight-
recorder event whether or not tracing is on, the compile watch that
names every jitted function's trace, lowering and compile with what the
persistent cache did, and the recorder's list of start-up kinds, which
ring traffic never evicts, the window never filters and
``ray_tpu.shutdown()`` does not clear.  CPU only; no Pallas anywhere."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.models import llama
from ray_tpu.parallel import MeshSpec
from ray_tpu.serve.llm_engine import (
    EngineConfig,
    LLMEngine,
    llama_paged_adapter,
)
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
from ray_tpu.util import flight_recorder, metrics, tracing, xprof

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WATCHED = """
import json, sys
import jax, jax.numpy as jnp
from ray_tpu.util import flight_recorder, metrics, xprof
xprof.STAGE_EVENT_S = 0.0
assert xprof.watch_compiles()
def watched_fn(x):
    return jnp.tanh(x @ x).sum()
jax.jit(watched_fn)(jnp.ones((32, 32))).block_until_ready()
print(json.dumps({
    "events": [e for e in flight_recorder.startup("driver")
               if e["kind"] == "compile" and e["program"] == "watched_fn"],
    "prom": metrics.export_prometheus()}))
"""


def _watched_run(cache_dir):
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    out = subprocess.run([sys.executable, "-c", WATCHED], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.splitlines()[-1])


def _samples(text, prefix):
    return {line.rsplit(" ", 1)[0]: float(line.rsplit(" ", 1)[1])
            for line in text.splitlines() if line.startswith(prefix)}


def test_compile_watch_names_three_stages_and_reads_miss_then_hit(tmp_path):
    first, second = _watched_run(tmp_path), _watched_run(tmp_path)
    for run, cache in ((first, "miss"), (second, "hit")):
        stages = {e["stage"]: e for e in run["events"]}
        assert set(stages) == {"trace", "lower", "compile"}
        assert all(e["end"] >= e["start"] > 0 for e in stages.values())
        assert (stages["trace"]["start"] <= stages["lower"]["start"]
                <= stages["compile"]["start"])
        assert stages["compile"]["cache"] == cache
        assert "cache" not in stages["trace"]
        # the counters the watch feeds: the family that was there, with
        # one label more, and the cache's results
        seconds = _samples(run["prom"], "raytpu_xla_compile_seconds_total{")
        for stage in ("trace", "lower", "compile"):
            assert seconds[
                'raytpu_xla_compile_seconds_total{program="watched_fn",'
                f'stage="{stage}"}}'] > 0
        results = _samples(run["prom"], "raytpu_xla_compile_cache_total{")
        assert results[
            f'raytpu_xla_compile_cache_total{{result="{cache}"}}'] >= 1
    assert second["events"][-1]["retrieval_s"] >= 0
    assert "retrieval_s" not in first["events"][-1]


def test_short_stages_are_tallied_not_listed():
    flight_recorder.clear()
    xprof.watch_compiles()
    before = xprof.startup_table()["short"].get("trace", {"n": 0})["n"]
    jax.jit(lambda x: x + 1)(jnp.ones(3)).block_until_ready()
    table = xprof.startup_table()
    assert table["short"]["trace"]["n"] > before
    assert not [e for e in flight_recorder.startup("driver")
                if e["kind"] == "compile" and e["end"] - e["start"] < 0.05
                and e.get("cache") not in ("hit", "miss")
                and not e.get("tally")]


def test_startup_span_leaves_its_event_with_tracing_off():
    tracing.disable_tracing()
    tracing.clear()
    flight_recorder.clear()
    with tracing.span("outer.phase", startup=True,
                      attributes={"deployment": "d"}) as outer:
        with tracing.span("inner.phase", startup=True) as inner:
            inner.set(rows=3)
        with tracing.span("plain.phase"):
            pass
    assert tracing.finished_spans() == []
    events = flight_recorder.startup("driver")
    assert [e["name"] for e in events] == ["inner.phase", "outer.phase"]
    inner_ev, outer_ev = events
    assert inner_ev["parent"] == "outer.phase" and outer_ev["parent"] is None
    assert inner_ev["rows"] == 3 and outer_ev["deployment"] == "d"
    assert all(e["kind"] == "startup" and e["pid"] == os.getpid()
               for e in events)
    assert outer_ev["start"] <= inner_ev["start"] <= inner_ev["end"] \
        <= outer_ev["end"] == outer.end
    assert tracing.current_startup() is None
    # a CPU keeps no memory peak: the stamp is absent, never zero
    assert "hbm_peak_bytes" not in outer_ev
    born = tracing.process_start()
    assert born is not None and 0 < time.time() - born < 7200


def test_startup_kinds_outlive_the_ring_and_the_window(tmp_path,
                                                       monkeypatch):
    flight_recorder.clear()
    with tracing.span("boot.phase", startup=True):
        pass
    flight_recorder.record("compile", program="f", stage="compile",
                           start=1.0, end=2.0, cache="miss")
    for kind in ("serve_cache_parts", "serve_model_parts",
                 "ragged_weight_routes"):
        flight_recorder.record(kind, engine="engine-0")
    kinds = ["startup", "compile", "serve_cache_parts",
             "serve_model_parts", "ragged_weight_routes"]
    for i in range(5000):
        flight_recorder.record("ring", request_id=f"r{i}")
    now = time.time()
    monkeypatch.setattr(flight_recorder.time, "time", lambda: now + 61.0)
    snap = flight_recorder.snapshot()["driver"]
    assert not [e for e in snap if e["kind"] in kinds]
    assert [e["kind"] for e in flight_recorder.startup("driver")] == kinds
    assert [e["kind"] for e in flight_recorder.startup()["driver"]] == kinds
    bundle = flight_recorder.dump(reason="manual", dump_dir=str(tmp_path))
    with open(os.path.join(bundle, "events.json")) as f:
        dumped = json.load(f)
    assert [e["kind"] for e in dumped["startup"]["driver"]] == kinds
    # ring kinds are never pinned, and the list has a bound of its own
    for i in range(2 * flight_recorder.STARTUP_CAP):
        flight_recorder.record("startup", name=f"s{i}", start=0.0, end=0.0)
    assert len(flight_recorder.startup("driver")) == \
        flight_recorder.STARTUP_CAP


def test_ship_carries_a_startup_event_the_ring_already_lost():
    flight_recorder.clear()
    flight_recorder.record("startup", name="early", start=0.0, end=1.0)
    for i in range(5000):
        flight_recorder.record("ring", request_id=f"r{i}")
    shipped = flight_recorder.ship()
    assert shipped[0]["name"] == "early"
    assert [e["seq"] for e in shipped] == sorted(e["seq"] for e in shipped)
    assert flight_recorder.ship() == []
    flight_recorder.ingest("w1", shipped)
    assert [e["name"] for e in flight_recorder.startup("w1")] == ["early"]
    flight_recorder.clear()


def test_replica_record_reaches_the_driver_and_survives_shutdown():
    flight_recorder.clear()
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    try:
        @serve.deployment
        class Echo:
            def __call__(self, x):
                return x + 1

        handle = serve.run(Echo.bind(), name="echo51", route_prefix=None)
        assert handle.remote(41).result() == 42   # one reply

        def replica_events():
            return [(p, e) for p, evs in flight_recorder.startup().items()
                    if p != "driver" for e in evs
                    if e.get("name") == "serve.replica_init"]

        deadline = time.time() + 30
        while not replica_events() and time.time() < deadline:
            handle.remote(1).result()
        (proc, init), = replica_events()
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    assert init["deployment"] == "Echo" and init["replica"]
    assert init["pid"] != os.getpid()
    after = flight_recorder.startup()
    names = {e.get("name") for e in after[proc]}
    assert {"serve.replica_init", "worker.boot", "import"} <= names
    boot = next(e for e in after[proc] if e["name"] == "worker.boot")
    assert boot["start"] < init["start"] and boot["end"] <= init["end"]
    driver = {e["name"]: e for e in after["driver"]
              if e["kind"] == "startup"}
    assert {"runtime.init", "serve.run", "serve.deploy",
            "serve.wait_ready"} <= set(driver)
    assert driver["serve.deploy"]["parent"] == "serve.run"
    assert driver["serve.wait_ready"]["parent"] == "serve.run"
    assert driver["serve.run"]["start"] <= init["start"] \
        and init["end"] <= driver["serve.run"]["end"]


def test_hot_loop_spans_still_leave_nothing():
    flight_recorder.clear()
    tracing.clear()
    tracing.enable_tracing()
    try:
        with tracing.span("llm.loop", record=False):
            pass
    finally:
        tracing.disable_tracing()
    assert tracing.finished_spans() == []
    assert flight_recorder.snapshot()["driver"] == []
    assert flight_recorder.startup("driver") == []


CFG = llama.LlamaConfig(
    vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, max_seq_len=128, remat=False, dtype=jnp.float32,
    param_dtype=jnp.float32,
)


def test_engine_says_how_it_started():
    flight_recorder.clear()
    xprof.clear()
    params = llama.init_params(jax.random.key(0), CFG)
    eng = LLMEngine(params, llama_paged_adapter(CFG), EngineConfig(
        max_slots=4, max_seq_len=128, page_size=16, ragged_batching=True,
        token_budget=36, prefill_chunk=16))
    try:
        eng.generate([1, 2, 3], max_new_tokens=4)
        eng.generate(list(range(1, 21)), max_new_tokens=4)
        table = eng.stats()["startup"]
    finally:
        eng.shutdown()
    assert {"llm.engine_init", "llm.init_cache", "llm.first_step",
            "llm.cost_analysis"} <= set(table["seconds"])
    assert table["ready_s"] > 0
    assert table["seconds"]["llm.engine_init"] >= \
        table["seconds"]["llm.init_cache"]
    events = flight_recorder.startup("driver")
    first = [e for e in events if e.get("name") == "llm.first_step"]
    assert [(e["program"], e["shape"]) for e in first] == [
        ("serve.ragged@8", 8), ("serve.ragged", 36)]
    cache = next(e for e in events if e.get("name") == "llm.init_cache")
    assert cache["parent"] == "llm.engine_init"
    # the stages of a program's first call carry its registered name,
    # and the device plane's compile window is their extent
    for name in ("serve.ragged@8", "serve.ragged"):
        stages = [e for e in events if e["kind"] == "compile"
                  and e.get("registered") == name]
        assert {e["stage"] for e in stages} == {"trace", "lower", "compile"}
        rec = xprof.programs()[name]
        assert rec.compiled_at == max(e["end"] for e in stages)
        assert rec.compile_time_s == pytest.approx(
            rec.compiled_at - min(e["start"] for e in stages))
        assert table["programs"][name]["compile"]["n"] == 1


def test_trainer_says_how_it_started_and_feeds_the_watchs_counters():
    flight_recorder.clear()
    xprof.clear()

    def batches():
        rng = np.random.default_rng(0)
        while True:
            yield {"x": rng.normal(size=(16, 8)).astype(np.float32),
                   "y": rng.normal(size=(16, 4)).astype(np.float32)}

    trainer = JaxTrainer(
        init_params=lambda r: {"w": jax.random.normal(r, (8, 4))},
        loss_fn=lambda p, b: (jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2), {}),
        params_axes={"w": (None, None)},
        batch_axes={"x": ("batch", None), "y": ("batch", None)},
        scaling_config=ScalingConfig(mesh_spec=MeshSpec()),
        run_config=RunConfig(report_every=1),
    )
    result = trainer.fit(batches(), num_steps=2)
    assert result.error is None
    table = result.startup
    assert {"train.build", "train.shardings", "train.init_state",
            "train.first_step", "train.cost_analysis"} <= set(
                table["seconds"])
    assert table["ready_s"] > 0 and table["cache_misses"] >= 0
    by_name = {e["name"]: e for e in flight_recorder.startup("driver")
               if e["kind"] == "startup"}
    assert by_name["train.shardings"]["parent"] == "train.build"
    assert by_name["train.init_state"]["parent"] == "train.build"
    assert by_name["train.first_step"]["program"] == "train.step"
    rec = xprof.programs()["train.step"]
    assert rec.compile_time_s > 0 and rec.compiled_at is not None
    # ported from tests/test_telemetry_plane.py: the device plane's
    # compile counter is the watch's, by stage, and the trainer's own
    # counter is gone with its clock
    text = metrics.export_prometheus()
    seconds = _samples(
        text, 'raytpu_xla_compile_seconds_total{program="train.step",')
    assert {k.split('stage="')[1].rstrip('"}') for k in seconds} >= {
        "trace", "lower", "compile"}
    assert all(v > 0 for v in seconds.values())
    assert "raytpu_train_compile_seconds_total" not in text
    assert _samples(text, "raytpu_xla_compile_cache_total{")
    assert not [s for s in tracing.finished_spans()
                if s["name"] == "train.compile"]
