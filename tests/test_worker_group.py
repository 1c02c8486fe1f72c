"""WorkerGroup / BackendExecutor / DataParallelTrainer / session
(parity: train/_internal/worker_group.py:101, backend_executor.py:46,
session.py:132 report/get_context, air FailureConfig)."""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import train as rtrain
from ray_tpu.util import collective as col

pytestmark = pytest.mark.long_file(95)


@pytest.fixture
def rt():
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def test_worker_group_execute(rt):
    wg = rtrain.WorkerGroup(4, resources_per_worker={"CPU": 1})
    try:
        outs = wg.execute(lambda: "pong")
        assert outs == ["pong"] * 4
        assert wg.execute_single(2, lambda: 42) == 42
    finally:
        wg.shutdown()
    # Resources return after shutdown (asynchronously: the actor death
    # path releases them once each shell drains; under the whole suite's
    # load that has taken over 10 s: the deadline only bounds a failure).
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if ray_tpu.available_resources().get("CPU") == 8.0:
            break
        time.sleep(0.05)
    assert ray_tpu.available_resources()["CPU"] == 8.0


def test_session_context_and_report(rt):
    def loop():
        ctx = rtrain.get_context()
        for step in range(3):
            rtrain.report({"step": step, "rank": ctx.get_world_rank()})
        return ctx.get_world_rank(), ctx.get_world_size()

    trainer = rtrain.DataParallelTrainer(loop, num_workers=3,
                                         resources_per_worker={"CPU": 1})
    out = trainer.fit()
    assert out.error is None
    assert sorted(out.worker_returns) == [(0, 3), (1, 3), (2, 3)]
    # 3 workers x 3 reports, all delivered.
    assert len(out.metrics_history) == 9
    per_rank = [r["metrics"]["step"] for r in out.metrics_history
                if r["rank"] == 1]
    assert per_rank == [0, 1, 2]  # per-worker report order preserved


def test_rendezvous_env_set(rt):
    wg = rtrain.WorkerGroup(2)
    try:
        envs = ray_tpu.get([w.get_env.remote() for w in wg.workers])
        assert envs[0]["RAYTPU_PROCESS_ID"] == "0"
        assert envs[1]["RAYTPU_PROCESS_ID"] == "1"
        assert all(e["RAYTPU_NUM_PROCESSES"] == "2" for e in envs)
        assert all("RAYTPU_COORDINATOR_ADDRESS" in e for e in envs)
    finally:
        wg.shutdown()


def test_data_parallel_loop_with_collectives(rt):
    """A real data-parallel SGD loop: per-worker gradients averaged via
    the host-plane collective group (the actor-group DP path; on a pod
    this is XLA collectives inside pjit instead)."""

    def loop():
        ctx = rtrain.get_context()
        col.init_collective_group(ctx.get_world_size(),
                                  ctx.get_world_rank(),
                                  group_name="dp")
        rng = np.random.default_rng(ctx.get_world_rank())
        # Fit y = 3x with per-worker data shards.
        w = 0.0
        for step in range(12):
            x = rng.normal(size=16)
            y = 3.0 * x
            grad = np.mean(2 * (w * x - y) * x)
            grad = float(col.allreduce(np.array([grad]),
                                       group_name="dp")[0]) \
                / ctx.get_world_size()
            w -= 0.3 * grad
            rtrain.report({"w": w, "step": step})
        return w

    trainer = rtrain.DataParallelTrainer(loop, num_workers=2,
                                         resources_per_worker={"CPU": 1})
    out = trainer.fit()
    assert out.error is None
    # All workers converge to the SAME w (synchronized updates).
    assert all(abs(w - 3.0) < 0.2 for w in out.worker_returns)
    assert abs(out.worker_returns[0] - out.worker_returns[1]) < 1e-9


def test_failure_config_retries_from_checkpoint(rt):
    import os
    import tempfile

    marker = os.path.join(tempfile.mkdtemp(), "failed_once")

    def loop():
        start = rtrain.get_checkpoint() or 0
        for step in range(start, 4):
            if step == 2 and not os.path.exists(marker):
                open(marker, "w").close()
                raise RuntimeError("worker crash at step 2")
            rtrain.report({"step": step}, checkpoint=step + 1)
        return "done"

    trainer = rtrain.DataParallelTrainer(
        loop, num_workers=1,
        failure_config=rtrain.FailureConfig(max_failures=1),
    )
    out = trainer.fit()
    assert out.error is None
    assert out.worker_returns == ["done"]
    # Second attempt resumed from checkpoint 2, not step 0.
    steps = [r["metrics"]["step"] for r in out.metrics_history]
    assert steps.count(0) == 1 and steps.count(2) == 1


def test_failure_config_survives_real_worker_death(rt):
    """FailureConfig under REAL worker death — the worker actor is
    hard-killed mid-step (SIGKILL semantics), not an in-loop raise: the
    whole-run retry restarts from the latest rank-0 checkpoint and the
    failed attempt's reports stay in the accumulated history."""
    import os
    import tempfile
    import threading

    from ray_tpu.core import api
    from ray_tpu.utils.test_utils import kill_actor_hard

    marker = os.path.join(tempfile.mkdtemp(), "wedged")

    def loop():
        start = rtrain.get_checkpoint() or 0
        for step in range(start, 5):
            if step == 3 and start == 0:
                open(marker, "w").close()
                while True:  # wedged: only actor death frees this step
                    time.sleep(0.01)
            rtrain.report({"step": step}, checkpoint=step + 1)
        return "done"

    def killer():
        deadline = time.monotonic() + 120
        while not os.path.exists(marker):
            if time.monotonic() > deadline:
                return
            time.sleep(0.01)
        runtime = api.runtime()
        with runtime._lock:
            victims = [a for a, s in runtime._actors.items()
                       if not s.dead and s.cls.__name__ == "_TrainWorker"]
        for actor_id in victims:
            kill_actor_hard(runtime, actor_id)

    t = threading.Thread(target=killer, daemon=True)
    t.start()
    trainer = rtrain.DataParallelTrainer(
        loop, num_workers=1,
        failure_config=rtrain.FailureConfig(max_failures=1),
    )
    out = trainer.fit()
    t.join(timeout=120)
    assert out.error is None
    assert out.worker_returns == ["done"]
    # Attempt 1 reported 0,1,2 then died wedged at 3; attempt 2 resumed
    # from checkpoint 3 — every step exactly once, none lost or redone.
    steps = [r["metrics"]["step"] for r in out.metrics_history]
    assert steps == [0, 1, 2, 3, 4]


def test_failure_budget_exhausted(rt):
    def loop():
        raise ValueError("always broken")

    trainer = rtrain.DataParallelTrainer(
        loop, num_workers=1,
        failure_config=rtrain.FailureConfig(max_failures=1),
    )
    out = trainer.fit()
    assert out.error is not None
    assert "always broken" in str(out.error)
