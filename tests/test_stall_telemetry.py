"""Stall telemetry: the loop clock's pace (the interval between
consecutive fetched steps while the pipeline holds work, its high-water
mark and its N-x-median stall count: serve/loop_clock.py), and the
admission-queue age, in serve/llm_engine.py (the instrumentation
BENCH_r05's 1.14B collapse was missing — p95 TTFT 200x p50 with no
engine-side record of where the time went)."""

import queue
import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.serve import loop_clock
from ray_tpu.serve.llm_engine import LLMEngine, _telemetry


class _Gauge:
    def __init__(self):
        self.value = None

    def set(self, v):
        self.value = v


class _FakeTime:
    """perf_counter, and the thread's CPU clock, the test moves by
    hand."""

    def __init__(self):
        self.t = 1000.0
        self.cpu = 50.0

    def perf_counter(self):
        return self.t

    def thread_time(self):
        return self.cpu


def _shim():
    """A bare object carrying just the state _admission_queue_age
    touches, so the helper is unit-testable without building an
    engine."""
    ns = types.SimpleNamespace()
    ns._slot_req = {}
    ns._waiting = queue.Queue()
    ns._backlog = []
    return ns


def _paced_clock(monkeypatch):
    """A loop clock on a fake perf_counter, its high-water mark mirrored
    to a gauge and its stall reports collected."""
    fake = _FakeTime()
    monkeypatch.setattr(loop_clock, "time", fake)
    gauge, reports = _Gauge(), []
    clock = loop_clock.LoopClock(
        on_stall=lambda **kw: reports.append(kw),
        on_high_water=gauge.set)

    def step(interval_s, n_steps=1, in_flight=1):
        """One fetch ``interval_s`` x ``n_steps`` after the last."""
        clock.step_dispatched()
        fake.t += interval_s * n_steps
        return clock.steps_fetched(n_steps, in_flight)

    return clock, gauge, reports, step, fake


def test_step_interval_watermark_and_stall(monkeypatch):
    clock, gauge, reports, step, _fake = _paced_clock(monkeypatch)
    # 20 normal fetches of 8-step chunks at ~1 ms/step: no stall, the
    # watermark tracks the largest interval per step.
    for i in range(20):
        assert not step(0.001 + 0.0000125 * i, n_steps=8)
    assert gauge.value == pytest.approx(0.001 + 0.0000125 * 19)
    assert clock.stall_events == 0
    # One 10x interval: counted, and the watermark jumps to it.  It is
    # under the 250 ms floor, so it is no flight-recorder event.
    assert step(0.010, n_steps=8)
    assert gauge.value == pytest.approx(0.010)
    assert clock.stall_events == 1 and reports == []
    # A stall past the floor is reported with the phase that held the
    # interval: nothing ran in the loop, so the largest share is the
    # first phase's zero and the report still names a phase.
    assert step(0.4)
    assert clock.stall_events == 2
    assert [r["phase"] for r in reports] == [loop_clock.PHASES[0]]
    assert reports[0]["wall_ms"] == pytest.approx(400.0)


def test_step_interval_needs_history(monkeypatch):
    """The first few intervals establish the median — no stall before
    there is a baseline to deviate from, and idle time between an empty
    pipeline and the next dispatch is no interval at all."""
    clock, _gauge, _reports, step, fake = _paced_clock(monkeypatch)
    for _ in range(7):
        assert not step(0.001)
    # 8th sample has 7 of history — still below the 8-sample floor.
    assert not step(1.0)
    # With >=8 samples of history the same interval now counts.
    assert step(1.0)
    assert clock.stall_events == 1
    # The pipeline drains; ten idle seconds pass; the next step's
    # interval starts at its own dispatch.
    assert not step(0.001, in_flight=0)
    fake.t += 10.0
    assert not step(0.001)
    assert clock.stall_events == 1


def test_stall_with_steps_in_flight_is_held_in_wait(monkeypatch):
    """The loop blocked for the fetch thread with steps in flight: that
    is phase ``wait`` (``idle`` until PR 37, which now means the engine
    is empty), and a stalled interval held there is reported so."""
    clock, _gauge, reports, step, fake = _paced_clock(monkeypatch)
    for _ in range(10):
        assert not step(0.01)
    # (the engine's loop takes the wait 20 ms at a time, an iteration
    # each: no single iteration is long, the step's interval is)
    clock.step_dispatched()
    for _ in range(25):
        clock.begin()
        with clock.phase("control"):
            fake.t += 0.0001
        with clock.phase("wait", {"in_flight": 6}):
            fake.t += 0.02
        clock.end()
    assert clock.steps_fetched(1, 0, seq=11)
    assert [(r["phase"], r["seq"]) for r in reports] == [("wait", 11)]
    assert reports[0]["wall_ms"] == pytest.approx(502.5)
    snap = clock.snapshot()
    assert snap["seconds"]["wait"] == pytest.approx(0.5)
    assert snap["seconds"]["idle"] == 0.0
    assert snap["longest"]["phase"] == "wait"
    # the same half second with the engine empty is no step's interval
    # and nobody's loss: booked under ``idle``, and no report
    for _ in range(10):
        clock.begin()
        with clock.phase("idle"):
            fake.t += 0.05
        clock.end()
    assert not step(0.01)
    assert len(reports) == 1
    assert clock.snapshot()["seconds"]["idle"] == pytest.approx(0.5)


def test_loop_cpu_beside_its_wall(monkeypatch):
    """``cpu_s`` sums the loop thread's own CPU clock over its
    iterations; against ``wall_s`` less the two waits it says how much
    of the loop's working time the thread was not running."""
    clock, _gauge, _reports, _step, fake = _paced_clock(monkeypatch)
    for wall, cpu in ((0.010, 0.004), (0.020, 0.020)):
        clock.begin()
        with clock.phase("pack"):
            fake.t += wall
            fake.cpu += cpu
        assert clock.cpu_spent() == pytest.approx(cpu)
        fake.cpu += 7.0     # another iteration's business: not counted
        clock.end()
    snap = clock.snapshot()
    assert snap["cpu_s"] == pytest.approx(0.024)
    assert snap["wall_s"] == pytest.approx(0.030)
    assert snap["cpu_s"] <= snap["wall_s"]


def test_admission_queue_age():
    ns = _shim()
    assert LLMEngine._admission_queue_age(ns) == 0.0
    now = time.monotonic()
    ns._waiting.put(types.SimpleNamespace(submitted_at=now - 2.0))
    ns._backlog.append(types.SimpleNamespace(submitted_at=now - 5.0))
    age = LLMEngine._admission_queue_age(ns)
    assert 4.9 < age < 6.0  # the backlog request is the oldest
    # An empty backlog leaves the waiting queue's oldest.
    ns2 = _shim()
    ns2._waiting.put(types.SimpleNamespace(submitted_at=now - 1.0))
    assert 0.9 < LLMEngine._admission_queue_age(ns2) < 2.0


def test_engine_run_populates_gauges_with_clean_grammar():
    """End-to-end: a tiny paged-engine run sets both new gauges, and
    the resulting exposition passes the repo metric-name contract."""
    import importlib.util
    import pathlib

    from ray_tpu.serve.llm_engine import EngineConfig, llama_paged_adapter
    from ray_tpu.util import metrics

    cfg = llama.LlamaConfig(
        vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        mlp_dim=64, max_seq_len=128, remat=False, dtype=jnp.float32,
        param_dtype=jnp.float32)
    params = llama.init_params(__import__("jax").random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    eng = LLMEngine(
        params, llama_paged_adapter(cfg),
        EngineConfig(max_slots=2, max_seq_len=128, decode_chunk=4,
                     max_new_tokens_default=6, min_prefill_bucket=64,
                     page_size=64))
    eng.generate(rng.integers(0, cfg.vocab_size, 20).tolist())
    eng.shutdown()

    text = metrics.export_prometheus()
    assert "raytpu_serve_step_wall_seconds" in text
    assert "raytpu_serve_admission_queue_age_seconds" in text
    # The loop clock's per-phase seconds, read at scrape time.
    for phase in loop_clock.PHASES:
        assert f'raytpu_serve_loop_seconds_total{{phase="{phase}"}}' in text
    assert {"wait", "idle"} <= set(loop_clock.PHASES)
    loop = eng.stats()["loop"]
    assert loop["iterations"] > 0 and loop["seconds"]["dispatch"] > 0
    assert 0 < loop["cpu_s"] <= loop["wall_s"]
    # The decode path ran, so the watermark must be a real positive.
    samples = _telemetry()["step_wall"]._samples()
    assert samples and samples[0][2] > 0

    path = (pathlib.Path(__file__).resolve().parent.parent
            / "scripts" / "check_metrics.py")
    spec = importlib.util.spec_from_file_location("check_metrics", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.check_exposition(text) == []
