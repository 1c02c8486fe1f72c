"""The sharded training step.

One jitted SPMD program spans the whole mesh: forward, backward,
optimizer update.  Gradient reduction over dp/fsdp, parameter
all-gathers under fsdp, and tp collectives are all inserted by the GSPMD
partitioner from the sharding annotations — the step function contains
no explicit communication (contrast the reference, where NCCL allreduce
hides inside torch DDP; ray: python/ray/train/torch/config.py:63).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from ray_tpu.parallel.sharding import Rules, tree_shardings
from ray_tpu.train.state import TrainState, state_shardings

def _instrument_first_call(jitted):
    """The first invocation of a jitted step traces + compiles the XLA
    program: it goes through ``xprof.first_call``, which registers the
    step's cost in the device plane as ``train.step`` and leaves the
    start-up span ``train.first_step``.  Subsequent calls pass straight
    through."""
    compiled = []

    def wrapped(state, batch):
        if compiled:
            return jitted(state, batch)
        from ray_tpu.util import xprof

        out = xprof.first_call("train.step", jitted, (state, batch),
                               span_name="train.compute")
        compiled.append(True)
        return out

    wrapped.__wrapped__ = jitted
    return wrapped

LossFn = Callable[[Any, Dict[str, jax.Array]], Tuple[jax.Array, Dict[str, jax.Array]]]


def apply_gradients(tx, grads, opt_state, params):
    """``tx.update`` then ``optax.apply_updates`` -> (params, opt_state).
    A transformation that adds its update to the parameter in the pass
    that computes it (``train/optim8.InPlaceTransformation``) brings its
    own ``apply`` with that contract, which is used instead."""
    apply = getattr(tx, "apply", None)
    if apply is not None:
        return apply(grads, opt_state, params)
    updates, opt_state = tx.update(grads, opt_state, params)
    return optax.apply_updates(params, updates), opt_state


def make_train_step(
    loss_fn: LossFn,
    tx: optax.GradientTransformation,
    *,
    grad_accum: int = 1,
) -> Callable[[TrainState, Dict[str, jax.Array]], Tuple[TrainState, Dict[str, jax.Array]]]:
    """Returns step(state, batch) -> (state, metrics). Pure; jit outside.

    ``grad_accum > 1`` scans the batch as that many microbatches along
    the leading dim, accumulating grads before the single optimizer
    update — same math (mean-of-means for equal microbatches), 1/k the
    activation memory, which is what lets a full-8B step fit.
    """

    def _grads(state, batch):
        if grad_accum <= 1:
            return jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, batch)

        def split(x):
            if x.shape[0] % grad_accum:
                raise ValueError(
                    f"batch dim {x.shape[0]} not divisible by "
                    f"grad_accum={grad_accum}")
            return x.reshape(grad_accum, x.shape[0] // grad_accum,
                             *x.shape[1:])

        micro = jax.tree.map(split, batch)

        def body(carry, mb):
            loss_sum, gsum = carry
            (l, a), g = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, mb)
            return (loss_sum + l.astype(jnp.float32),
                    jax.tree.map(jnp.add, gsum, g)), a

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype),
                             state.params)
        (loss_sum, gsum), auxs = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zeros), micro)
        loss = loss_sum / grad_accum
        grads = jax.tree.map(lambda g: g / grad_accum, gsum)
        aux = jax.tree.map(lambda x: x[-1], auxs)
        return (loss, aux), grads

    # The function's name is the registered program name ("train.step")
    # with the dot as an underscore: a profiler trace shows the module
    # as ``jit_train_step``.  The scopes change HLO metadata only.
    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        with jax.named_scope("loss"):
            (loss, aux), grads = _grads(state, batch)
        with jax.named_scope("optimizer"):
            new_params, new_opt_state = apply_gradients(
                tx, grads, state.opt_state, state.params)
        with jax.named_scope("grad_norm"):
            gnorm = optax.global_norm(grads)
        # Canonical keys win over aux duplicates: under grad_accum the
        # aux rides from the last microbatch only, while ``loss`` is
        # the mean over all of them.
        metrics = {**aux, "loss": loss, "grad_norm": gnorm,
                   "step": state.step}
        return (
            TrainState(state.step + 1, new_params, new_opt_state),
            metrics,
        )

    return train_step


def compile_train_step(
    mesh,
    loss_fn: LossFn,
    tx: optax.GradientTransformation,
    state: TrainState,
    params_axes: Any,
    batch_axes: Dict[str, Tuple[Optional[str], ...]],
    rules: Optional[Rules] = None,
    *,
    zero_sharding: bool = False,
    grad_accum: int = 1,
):
    """Jit the step with explicit in/out shardings over ``mesh``.

    ``zero_sharding=True`` pins the optimizer state to the ZeRO layout
    (train/zero.py) in BOTH in_ and out_shardings — the state stays
    donation-safe (matched layouts), and forcing the update's outputs
    sharded is what makes GSPMD reduce-scatter the grads instead of
    all-reducing them.

    Returns (jitted_step, state_shardings_tree, batch_shardings_tree).
    """
    step = make_train_step(loss_fn, tx, grad_accum=grad_accum)
    st_sh = state_shardings(mesh, state, params_axes, rules,
                            zero=zero_sharding)
    batch_sh = {k: tree_shardings(mesh, v, rules) for k, v in batch_axes.items()}
    jitted = jax.jit(
        step,
        in_shardings=(st_sh, batch_sh),
        out_shardings=(st_sh, None),
        donate_argnums=(0,),
    )
    return _instrument_first_call(jitted), st_sh, batch_sh
