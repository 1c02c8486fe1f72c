"""Tune tests (models the reference's tune test approach: tiny
trainables, deterministic schedulers — python/ray/tune/tests/)."""

import time

import pytest

import ray_tpu
from ray_tpu import tune


@pytest.fixture(scope="module", autouse=True)
def rt():
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def test_grid_and_random_sampling():
    gen = tune.BasicVariantGenerator(
        {"a": tune.grid_search([1, 2, 3]), "b": tune.uniform(0, 1),
         "c": "fixed"},
        num_samples=2, seed=0)
    cfgs = list(gen)
    assert len(cfgs) == 6
    assert sorted({c["a"] for c in cfgs}) == [1, 2, 3]
    assert all(0 <= c["b"] <= 1 and c["c"] == "fixed" for c in cfgs)


def test_function_trainable_and_best_result():
    def trainable(config):
        for step in range(5):
            tune.report({"score": config["x"] * (step + 1)})

    grid = tune.Tuner(
        trainable,
        param_space={"x": tune.grid_search([1.0, 3.0, 2.0])},
        tune_config=tune.TuneConfig(metric="score", mode="max"),
    ).fit()
    best = grid.get_best_result()
    assert best.config["x"] == 3.0
    assert best.metrics["score"] == 15.0
    assert len(grid) == 3


def test_trial_errors_are_captured():
    def bad(config):
        if config["x"] == 2:
            raise RuntimeError("boom")
        tune.report({"score": 1})

    grid = tune.run(bad, param_space={"x": tune.grid_search([1, 2])},
                    metric="score")
    errors = [r for r in [grid[i] for i in range(len(grid))] if r.error]
    assert len(errors) == 1
    assert "boom" in errors[0].error


def test_stop_criteria():
    def forever(config):
        step = 0
        while True:
            step += 1
            tune.report({"training_iteration": step, "score": step})

    grid = tune.run(forever, param_space={}, metric="score",
                    stop={"training_iteration": 7})
    assert grid[0].metrics["training_iteration"] == 7


def test_asha_stops_bad_trials_early():
    class Step(tune.Trainable):
        def setup(self, config):
            self.lr = config["lr"]
            self.step_n = 0

        def step(self):
            self.step_n += 1
            return {"training_iteration": self.step_n,
                    "acc": self.lr * self.step_n}

    sched = tune.AsyncHyperBandScheduler(
        metric="acc", mode="max", max_t=32, grace_period=2,
        reduction_factor=2)
    # Strong configs first: ASHA is asynchronous, so rung cutoffs only
    # bite once a strong trial has already recorded at the rung.
    grid = tune.run(Step,
                    param_space={"lr": tune.grid_search(
                        [1.0, 0.5, 0.2, 0.1])},
                    metric="acc", scheduler=sched,
                    max_concurrent_trials=4)
    iters = {grid[i].config["lr"]: grid[i].metrics["training_iteration"]
             for i in range(len(grid))}
    # The best lr runs longest; the worst is cut early.
    assert iters[1.0] == 32
    assert iters[0.1] < 32


def test_class_trainable_api():
    class MyTrainable(tune.Trainable):
        def setup(self, config):
            self.x = config["x"]
            self.total = 0

        def step(self):
            self.total += self.x
            return {"total": self.total}

        def save_checkpoint(self):
            return {"total": self.total}

        def load_checkpoint(self, ckpt):
            self.total = ckpt["total"]

    grid = tune.run(MyTrainable, param_space={"x": tune.grid_search([1, 5])},
                    metric="total", stop={"training_iteration": 4})
    best = grid.get_best_result()
    assert best.config["x"] == 5
    assert best.metrics["total"] == 20


def test_pbt_exploits_checkpoints(tmp_path):
    """The two trials are actors that start when their processes do: the
    weak one, started first, used to run all nine steps alone on a
    loaded machine, with nobody to exploit (0.1 nine times over is
    0.8999999999999999, the number the assertion then showed).  It now
    takes no step before the driver has seen a result of the strong
    one, whose SECOND step says so."""
    strong_reported = tmp_path / "strong_reported"  # seen across processes

    class PBTTrainable(tune.Trainable):
        def setup(self, config):
            self.lr = config["lr"]
            self.score = 0.0

        def step(self):
            if self.lr == 0.1:
                deadline = time.monotonic() + 120
                while (not strong_reported.exists()
                       and time.monotonic() < deadline):
                    time.sleep(0.005)
            elif self.score > 0:
                # a second step is handed out on the first one's result
                strong_reported.touch()
            self.score += self.lr
            return {"score": self.score}

        def save_checkpoint(self):
            return {"score": self.score}

        def load_checkpoint(self, ckpt):
            self.score = ckpt["score"]

    sched = tune.PopulationBasedTraining(
        metric="score", mode="max", perturbation_interval=3,
        hyperparam_mutations={"lr": [0.5, 1.0, 2.0]}, seed=0)
    grid = tune.run(PBTTrainable,
                    param_space={"lr": tune.grid_search([0.1, 1.0])},
                    metric="score", scheduler=sched,
                    stop={"training_iteration": 9})
    # The weak trial must have been lifted by exploiting the strong one.
    scores = sorted(grid[i].metrics["score"] for i in range(len(grid)))
    assert scores[0] > 0.1 * 9  # better than it could do alone


def test_resume_checkpoint_in_function_trainable(tmp_path):
    seen = tmp_path / "start"  # visible across worker processes

    def trainable(config):
        ckpt = tune.get_checkpoint()
        start = ckpt["step"] + 1 if ckpt else 1
        seen.write_text(str(start))
        for step in range(start, 4):
            tune.report({"training_iteration": step},
                        checkpoint={"step": step})

    grid = tune.run(trainable, param_space={}, metric="training_iteration")
    assert seen.read_text() == "1"
    assert grid[0].checkpoint == {"step": 3}


def test_tuner_survives_driver_crash(tmp_path):
    """kill -9 of the DRIVER mid-sweep → Tuner.restore resumes from the
    periodic experiment snapshot: finished trials keep results,
    interrupted ones restart from their last checkpoint (parity:
    tune/execution/experiment_state.py + Tuner.restore)."""
    import os
    import subprocess
    import sys
    import textwrap
    import time

    from ray_tpu.tune import RunConfig, TuneConfig, Tuner

    storage = str(tmp_path / "exp")
    runs_dir = tmp_path / "runs"
    runs_dir.mkdir()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = textwrap.dedent(f"""
        import os, sys, time
        sys.path.insert(0, {repo!r})
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("RAYTPU_WORKERS", "thread")
        import jax; jax.config.update("jax_platforms", "cpu")
        import ray_tpu
        from ray_tpu import tune
        from ray_tpu.tune import RunConfig, TuneConfig, Tuner

        def slow_trial(config):
            ckpt = tune.get_checkpoint()
            start = 0 if ckpt is None else ckpt["step"] + 1
            for step in range(start, 4):
                with open(os.path.join({str(runs_dir)!r},
                          f"t{{config['x']}}_s{{step}}"), "w") as f:
                    f.write("1")
                time.sleep(0.6)
                tune.report({{"training_iteration": step,
                             "score": config["x"]}},
                            checkpoint={{"step": step}})

        ray_tpu.init(num_cpus=2)
        Tuner(slow_trial,
              param_space={{"x": tune.grid_search([1, 2, 3, 4])}},
              tune_config=TuneConfig(max_concurrent_trials=2),
              run_config=RunConfig(storage_path={storage!r},
                                   name="crashme",
                                   snapshot_period_s=0.2)).fit()
    """)
    proc = subprocess.Popen([sys.executable, "-c", script])
    # Let it make progress (snapshots every 0.2 s), then hard-kill.
    deadline = time.time() + 60
    state = os.path.join(storage, "crashme", "experiment_state.pkl")
    while time.time() < deadline:
        if os.path.exists(state) and len(list(runs_dir.iterdir())) >= 3:
            break
        time.sleep(0.1)
    proc.kill()
    proc.wait()
    assert os.path.exists(state), "no snapshot written before the crash"

    def slow_trial(config):
        ckpt = tune.get_checkpoint()
        start = 0 if ckpt is None else ckpt["step"] + 1
        for step in range(start, 4):
            (runs_dir / f"t{config['x']}_s{step}").write_text("1")
            tune.report({"training_iteration": step, "score": config["x"]},
                        checkpoint={"step": step})

    grid = Tuner.restore(os.path.join(storage, "crashme"),
                         slow_trial).fit()
    assert len(grid) == 4
    scores = sorted(r.metrics["score"] for r in grid)
    assert scores == [1, 2, 3, 4]
    for r in grid:
        assert r.error is None
        assert r.checkpoint == {"step": 3}
