"""Runner for configurations trained through ``JaxTrainer.fit``.

One process: it owns the chips, builds the trainer, and calls ``fit``
with the input iterator running (seeded synthetic token batches made on
the host every step).  ``fit`` is called twice: once for two steps,
which compiles and is set-up, and once with an iterator that ends when
the window closes.  ``RunConfig(report_every=1)`` makes every step end
in a transfer of its loss, so each report stamp is a true step end.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Dict

from benchmarks.harness.run_record import Run
from benchmarks.runners.common import (
    CompileCounter,
    TraceWindow,
    memory_peak_bytes,
    model_config,
)

# Two layers at published widths, one sequence of 512 tokens: the
# program's loss and global gradient norm (bf16 parameters and
# activations, flash kernels, chunked head) against the float32 reference
# on the same parameters.  Measured on the chip at InternLM2-1.8B's widths
# (my chip runs, PR 23, five seeds): the loss differs by 3e-5 to 5e-5
# relative, the gradient norm by 1.9e-3 to 2.1e-3 (bf16's own bias).  The
# bounds are ten and five times that: an 8-bit matmul in place of bf16, or
# a gradient with a term left out, moves either by more.
LOSS_TOL = 5e-4
GRAD_NORM_TOL = 1e-2
# Step-0 loss of random weights against ln(vocab): logits of about unit
# variance (fan-in scaled weights) put it near ln(V) + 0.5.
LN_V_TOL = 1.0


def reference_check(cfg, config: Dict[str, Any], seed: int, *,
                    n_layers: int = 2, seq: int = 512) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import reference
    from ray_tpu.models import llama

    cfg2 = dataclasses.replace(cfg, n_layers=n_layers, max_seq_len=seq)
    params = jax.jit(lambda k: llama.init_params(k, cfg2))(
        jax.random.key(seed % (2**31 - 1)))
    tokens = jnp.asarray(np.random.default_rng(seed % (2**32)).integers(
        0, cfg.vocab_size, (1, seq)), jnp.int32)

    # tokens are an argument of both programs: closed over, they would be
    # a constant of the HLO and every seed would compile anew
    def program(p, toks):
        (loss, _aux), grads = jax.value_and_grad(
            lambda q: llama.loss_fn(q, {"tokens": toks}, cfg2),
            has_aux=True)(p)
        sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                 for g in jax.tree.leaves(grads))
        return loss, jnp.sqrt(sq)

    loss, gnorm = jax.jit(program)(params, tokens)
    hf = dict(config, num_hidden_layers=n_layers)
    with jax.default_matmul_precision("highest"):
        ref_params = reference.from_program_tree(params, hf)
        want_loss, want_gnorm = jax.jit(
            lambda p, toks: reference.loss_and_grad_norm(p, toks, hf))(
                ref_params, tokens)
    loss, gnorm, want_loss, want_gnorm = (
        float(loss), float(gnorm), float(want_loss), float(want_gnorm))
    loss_err = abs(loss - want_loss) / abs(want_loss)
    gnorm_err = abs(gnorm - want_gnorm) / abs(want_gnorm)
    return {"loss": loss, "ref_loss": want_loss, "loss_rel_err": loss_err,
            "grad_norm": gnorm, "ref_grad_norm": want_gnorm,
            "grad_norm_rel_err": gnorm_err, "layers": n_layers,
            "ok": bool(math.isfinite(loss) and loss_err <= LOSS_TOL
                       and gnorm_err <= GRAD_NORM_TOL)}


def _optimizer(spec: Dict[str, Any]):
    from ray_tpu import train

    spec = dict(spec)
    kind = spec.pop("kind")
    if kind == "adamw8bit":
        return train.adamw8bit(**spec)
    if kind == "adamw":
        return train.default_optimizer(**spec)
    raise ValueError(f"unknown optimizer kind {kind!r}")


class _Batches:
    """The trainer's input: an endless seeded stream of token batches,
    made on the host as they are asked for.  With ``stop_at`` set the
    stream ends once that instant has passed, which ends ``fit``.  The
    time spent in here is the data wait."""

    def __init__(self, rng, vocab: int, batch: int, seq: int):
        self.rng, self.vocab, self.batch, self.seq = rng, vocab, batch, seq
        self.stop_at = None
        self.waits = []

    def __iter__(self):
        return self

    def __next__(self):
        import jax
        import numpy as np

        t = time.perf_counter()
        if self.stop_at is not None and t >= self.stop_at:
            raise StopIteration
        with jax.profiler.TraceAnnotation("bench.data_next"):
            out = {"tokens": self.rng.integers(
                0, self.vocab, (self.batch, self.seq),
                dtype=np.int64).astype(np.int32)}
        self.waits.append((t, time.perf_counter() - t))
        return out


def run(ctx) -> Run:
    import jax
    import numpy as np

    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import (
        JaxTrainer,
        RunConfig,
        ScalingConfig,
        TrainerConfig,
    )
    from ray_tpu.utils import accelerator

    config, tr = ctx.config, ctx.config["train"]
    if not ctx.rehearse:
        accelerator.claim_tpu()   # raises where there is no chip to own
    else:
        accelerator.enable_compile_cache()
    devices = jax.devices()
    if not ctx.rehearse and devices[0].platform != "tpu":
        raise SystemExit(f"benchmark: JAX computes on "
                         f"{devices[0].platform!r}, not a TPU; no result")
    if len(devices) < ctx.chips:
        raise SystemExit(f"benchmark: cell {ctx.cell} needs {ctx.chips} "
                         f"chip(s) and JAX shows {len(devices)}; no result")
    devices = devices[:ctx.chips]
    compiled = CompileCounter()

    cfg = model_config(config)
    check = reference_check(cfg, config, ctx.seed,
                            seq=tr.get("reference_seq", 512))
    batch = int(ctx.traffic["sequences_per_step"])
    seq = int(ctx.traffic["seq_len"])
    trainer = JaxTrainer(
        init_params=lambda r: llama.init_params(r, cfg),
        loss_fn=lambda p, b: llama.loss_fn(p, b, cfg),
        params_axes=llama.logical_axes(cfg),
        batch_axes={"tokens": ("batch", None)},
        optimizer=_optimizer(tr["optimizer"]),
        scaling_config=ScalingConfig(mesh_spec=MeshSpec(**tr["mesh"]),
                                     devices=devices),
        run_config=RunConfig(report_every=1),
        trainer_config=TrainerConfig(
            zero_sharding=bool(tr.get("zero_sharding", False)),
            grad_accum=int(tr.get("grad_accum", 1))),
        seed=ctx.seed % (2**31 - 1),
    )
    data = _Batches(np.random.default_rng(ctx.seed % (2**32)),
                    cfg.vocab_size, batch, seq)
    warm = trainer.fit(data, num_steps=2)
    if warm.error is not None:
        raise warm.error
    loss0 = warm.metrics_history[0]["loss"]
    compiles_before = compiled.count

    trace_s = float(ctx.traffic.get("trace_s", 8.0))
    tracer = TraceWindow(ctx.trace_dir, allow_empty=ctx.rehearse)
    tracing = {"on": False, "done": False}
    stamps = []

    def report(m):
        now = time.perf_counter()
        stamps.append((now, m["loss"]))
        if ctx.trace and not tracing["done"]:
            # trace whole steps: start after the window's second step,
            # stop at the first step end past ``trace_s``
            if not tracing["on"] and len(stamps) == 2:
                tracer.start()
                tracing.update(on=True, t=now)
            elif tracing["on"] and now - tracing["t"] >= trace_s:
                tracer.stop()
                tracing.update(on=False, done=True)

    data.waits.clear()
    t_start = time.perf_counter()
    data.stop_at = t_start + ctx.seconds
    res = trainer.fit(data, num_steps=10**9, report=report)
    if tracing["on"]:
        tracer.stop()
        tracing.update(on=False, done=True)
    if res.error is not None and not isinstance(res.error, StopIteration):
        raise res.error
    steps, prev = [], t_start
    for t, loss in stamps:
        if t <= t_start + ctx.seconds:
            steps.append({"start": prev - t_start, "end": t - t_start,
                          "loss": loss, "tokens": batch * seq})
        prev = t
    waits = [w for t, w in data.waits if t < t_start + ctx.seconds]
    finite = all(math.isfinite(s["loss"]) for s in steps)
    ln_v = math.log(cfg.vocab_size)
    in_window = compiled.count - compiles_before
    return Run(
        cell=ctx.cell, config=config, traffic=ctx.traffic, chips=ctx.chips,
        seconds=ctx.seconds, setup_s=t_start - ctx.t_process_start,
        device={"platform": devices[0].platform,
                "kind": devices[0].device_kind, "count": len(devices),
                "memory_peak_bytes": memory_peak_bytes(devices)},
        attempted=len(stamps), failed=sum(
            1 for _t, loss in stamps if not math.isfinite(loss)),
        correct=bool(steps and finite and abs(loss0 - ln_v) <= LN_V_TOL
                     and check["ok"] and in_window == 0),
        notes={"compiles_in_window": in_window, "reference_check": check,
               "loss0": loss0, "ln_vocab": ln_v,
               "data_wait_s": sum(waits)},
        steps=steps, trace=tracer.reduce() if tracing["done"] else None)
