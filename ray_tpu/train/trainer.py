"""JaxTrainer — the Train-equivalent entry point.

API parity with the reference's DataParallelTrainer/TorchTrainer
(ray: python/ray/train/data_parallel_trainer.py:59,
train/torch/torch_trainer.py:14, base_trainer.py:608 fit()), redesigned
for SPMD: instead of N worker processes each running a copy of a
training loop synchronized by NCCL, one logical program is jitted over a
device mesh; scaling config is a ``MeshSpec`` rather than
``num_workers``.  Multi-host operation reuses the same code — the actor
layer (ray_tpu.core) pins one controller process per host and jax's
distributed runtime makes ``jax.devices()`` span hosts.

``fit()`` is usable standalone (the reference inverts this by routing
fit() through Tune; see SURVEY.md §7 phase 6 note).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax
import numpy as np

from ray_tpu.parallel.mesh import MeshSpec, create_mesh
from ray_tpu.parallel.sharding import Rules
from ray_tpu.train.checkpoint import CheckpointManager
from ray_tpu.train.state import TrainState, create_train_state, default_optimizer
from ray_tpu.train.step import compile_train_step
from ray_tpu.util import tracing, xprof

_TELEMETRY = None


def _telemetry():
    """Trainer metric singletons (re-registered on refetch — see
    serve/llm_engine._telemetry for the registry-clear rationale)."""
    global _TELEMETRY
    from ray_tpu.util import metrics

    if _TELEMETRY is None:
        _TELEMETRY = {
            "step_s": metrics.Histogram(
                "raytpu_train_step_seconds",
                "Host-side duration of one training step (dispatch, plus "
                "device sync on report steps).",
                boundaries=[0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0,
                            5.0, 30.0, 120.0],
            ),
            "data_wait_s": metrics.Histogram(
                "raytpu_train_data_wait_seconds",
                "Seconds each step waited on the input iterator + batch "
                "sharding.",
                boundaries=[0.0001, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5,
                            1.0, 5.0],
            ),
            "steps": metrics.Counter(
                "raytpu_train_steps_total",
                "Training steps completed.",
            ),
            "checkpoints": metrics.Counter(
                "raytpu_train_checkpoints_total",
                "Checkpoints written by the trainer.",
            ),
            "opt_bytes": metrics.Gauge(
                "raytpu_train_opt_state_bytes",
                "Optimizer-state footprint from the arrays' shardings: "
                "scope=global across the mesh, scope=per_device resident "
                "on one device (~global/dp under ZeRO sharding).",
                tag_keys=("scope",),
            ),
            "hbm_headroom": metrics.Gauge(
                "raytpu_train_hbm_headroom_bytes",
                "Per-device HBM left above the peak watermark "
                "(bytes_limit - peak_bytes_in_use), sampled on report "
                "steps; absent on backends without memory_stats (CPU).",
                tag_keys=("device",),
            ),
        }
    else:
        reg = metrics.registry()
        for m in _TELEMETRY.values():
            reg.register(m)
    return _TELEMETRY


@dataclasses.dataclass
class ScalingConfig:
    """Parity: air.ScalingConfig(num_workers, use_gpu) → mesh layout."""

    mesh_spec: MeshSpec = dataclasses.field(default_factory=MeshSpec)
    devices: Optional[list] = None  # default: all


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    """Step-program options.

    ``zero_sharding`` shards the optimizer state (and the weight
    update) across the data axes, ZeRO-style — grads reduce-scatter,
    each replica updates 1/dp of the blocks, params all-gather back
    (train/zero.py).  ``grad_accum`` scans each batch as that many
    microbatches before the single update (train/step.py)."""

    zero_sharding: bool = False
    grad_accum: int = 1


@dataclasses.dataclass
class RunConfig:
    """Parity: air.RunConfig (name, storage_path, checkpoint/failure cfg)."""

    name: str = "run"
    storage_path: Optional[str] = None
    checkpoint_every: int = 0  # steps; 0 = only final
    checkpoints_to_keep: int = 3
    report_every: int = 10


@dataclasses.dataclass
class Result:
    """Parity: air.Result (metrics, checkpoint path, error)."""

    metrics: Dict[str, float]
    metrics_history: List[Dict[str, float]]
    checkpoint_path: Optional[str]
    error: Optional[BaseException] = None
    # How this process started (``xprof.startup_table``): seconds by
    # start-up span, ``ready_s``, programs compiled, cache misses.
    startup: Optional[Dict[str, Any]] = None


class JaxTrainer:
    def __init__(
        self,
        *,
        init_params: Callable[[jax.Array], Any],
        loss_fn: Callable[[Any, Dict[str, jax.Array]], Tuple[jax.Array, Dict]],
        params_axes: Any,
        batch_axes: Dict[str, Tuple[Optional[str], ...]],
        optimizer=None,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        trainer_config: Optional[TrainerConfig] = None,
        rules: Optional[Rules] = None,
        seed: int = 0,
    ):
        self.init_params_fn = init_params
        self.loss_fn = loss_fn
        self.params_axes = params_axes
        self.batch_axes = batch_axes
        self.tx = optimizer or default_optimizer()
        self.scaling = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.trainer_config = trainer_config or TrainerConfig()
        self.rules = rules
        self.seed = seed

        self.mesh = create_mesh(self.scaling.mesh_spec,
                                devices=self.scaling.devices)
        self._state: Optional[TrainState] = None
        self._step_fn = None
        self._state_sh = None
        self._batch_sh = None

    # -- setup -------------------------------------------------------------

    def _build(self):
        """Start-up spans: ``train.build`` round ``train.shardings``
        (the abstract pass) and ``train.init_state`` (the sharded init,
        run to completion)."""
        rng = jax.random.key(self.seed)
        with tracing.span("train.build", startup=True), self.mesh:
            with tracing.span("train.shardings", startup=True):
                abstract = jax.eval_shape(
                    lambda r: create_train_state(
                        self.init_params_fn(r), self.tx), rng)
                # Compile the step against abstract state to get
                # shardings first.
                self._step_fn, self._state_sh, self._batch_sh = \
                    compile_train_step(
                        self.mesh, self.loss_fn, self.tx, abstract,
                        self.params_axes, self.batch_axes, self.rules,
                        zero_sharding=self.trainer_config.zero_sharding,
                        grad_accum=self.trainer_config.grad_accum,
                    )
            # Init params *directly sharded* — no host-memory full copy, so
            # 70B-scale states can initialize on the mesh.
            with tracing.span("train.init_state", startup=True):
                init = jax.jit(
                    lambda r: create_train_state(
                        self.init_params_fn(r), self.tx),
                    out_shardings=self._state_sh,
                )
                self._state = jax.block_until_ready(init(rng))
        self._emit_memory_gauges()

    def _emit_memory_gauges(self):
        """Opt-state footprint from the live arrays' shardings, plus
        per-device HBM headroom (absent-not-zero on CPU backends)."""
        from ray_tpu.train import zero as zero_mod

        tm = _telemetry()
        b = zero_mod.opt_state_bytes(self._state.opt_state)
        tm["opt_bytes"].set(b["global"], tags={"scope": "global"})
        tm["opt_bytes"].set(b["per_device"], tags={"scope": "per_device"})
        for d in jax.local_devices():
            try:
                stats = d.memory_stats()
            except Exception:
                return
            if not stats or "bytes_limit" not in stats:
                continue
            peak = stats.get("peak_bytes_in_use",
                             stats.get("bytes_in_use", 0))
            tm["hbm_headroom"].set(
                stats["bytes_limit"] - peak,
                tags={"device": f"{d.platform}:{d.id}"})

    @property
    def state(self) -> TrainState:
        if self._state is None:
            self._build()
        return self._state

    def restore(self, path: str) -> int:
        """Resume from latest checkpoint under ``path``; returns step."""
        if self._state is None:
            self._build()
        mngr = CheckpointManager(path)
        self._state = mngr.restore(self._state)
        mngr.close()
        return int(jax.device_get(self._state.step))

    # -- training ----------------------------------------------------------

    def shard_batch(self, batch: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
        return jax.device_put(batch, self._batch_sh)

    def fit(
        self,
        data: Iterable[Dict[str, np.ndarray]],
        *,
        num_steps: int,
        report: Optional[Callable[[Dict[str, float]], None]] = None,
    ) -> Result:
        if self._state is None:
            self._build()
        rc = self.run_config
        ckpt = None
        if rc.storage_path:
            ckpt = CheckpointManager(
                f"{rc.storage_path}/{rc.name}", max_to_keep=rc.checkpoints_to_keep
            )

        history: List[Dict[str, float]] = []
        last_metrics: Dict[str, float] = {}
        tm = _telemetry()
        it = iter(data)
        t0 = time.perf_counter()
        error: Optional[BaseException] = None
        try:
            with self.mesh:
                for i in range(num_steps):
                    step = i + 1
                    with tracing.span("train.step",
                                      attributes={"step": step}):
                        w0 = time.perf_counter()
                        with tracing.span("train.data_wait"):
                            batch = self.shard_batch(next(it))
                        c0 = time.perf_counter()
                        tm["data_wait_s"].observe(c0 - w0)
                        # ``train.compute`` times the asynchronous
                        # call, not the step: jax dispatch returns at
                        # once, so this is dispatch cost (plus whatever
                        # the call had to wait for).  The step's device
                        # time ends in ``train.report``, where the
                        # metrics are read back.  The name stays because
                        # xprof.record_compiled joins on it.
                        with tracing.span("train.compute"):
                            self._state, metrics = self._step_fn(
                                self._state, batch)
                        tm["step_s"].observe(time.perf_counter() - c0)
                        tm["steps"].inc()
                        if step % rc.report_every == 0 or step == num_steps:
                            with tracing.span("train.report"):
                                m = {k: float(jax.device_get(v))
                                     for k, v in metrics.items()}
                                m["steps_per_sec"] = step / (
                                    time.perf_counter() - t0)
                                history.append(m)
                                last_metrics = m
                                # Shared device-plane sampler (TPU/GPU
                                # HBM watermarks; absent on CPU
                                # backends).
                                xprof.sample_device_memory()
                                self._emit_memory_gauges()
                                if report:
                                    report(m)
                        if ckpt and rc.checkpoint_every \
                                and step % rc.checkpoint_every == 0:
                            # sharded arrays go straight to orbax — each
                            # host writes its own shards, no host gather
                            with tracing.span("train.checkpoint",
                                              attributes={"step": step}):
                                ckpt.save(step, self._state)
                            tm["checkpoints"].inc()
        except BaseException as e:  # report partial progress + the failure
            error = e
            if not isinstance(e, Exception):
                raise
        finally:
            path = None
            if ckpt:
                final_step = int(jax.device_get(self._state.step))
                if error is None and ckpt.latest_step() != final_step:
                    ckpt.save(final_step, self._state, wait=True)
                else:
                    ckpt._mngr.wait_until_finished()
                path = f"{rc.storage_path}/{rc.name}"
                ckpt.close()
        return Result(
            metrics=last_metrics,
            metrics_history=history,
            checkpoint_path=path,
            error=error,
            startup=xprof.startup_table(),
        )
