"""Train state: params + optimizer state + step, sharding-aware.

Replaces the reference's framework-wrapper approach (ray:
python/ray/train/torch/train_loop_utils.py prepare_model/DDP/FSDP) with
a GSPMD-native one: optimizer state inherits the params' logical axes,
so FSDP-style (ZeRO) sharding of Adam moments falls out of the same rule
table that shards the params (cf. PAPERS.md "Automatic Cross-Replica
Sharding of Weight Update in Data-Parallel Training").
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from ray_tpu.parallel.sharding import Rules, tree_shardings


class TrainState(NamedTuple):
    step: jax.Array
    params: Any
    opt_state: Any


def create_train_state(params: Any, tx: optax.GradientTransformation) -> TrainState:
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=tx.init(params),
    )


def _is_axes_leaf(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)


def state_logical_axes(state: TrainState, params_axes: Any) -> TrainState:
    """Logical axes for a whole TrainState, derived from the params' axes.

    Optimizer-state leaves that mirror a param (same shape) inherit its
    axes; scalars/others replicate.
    """
    flat_axes = jax.tree.leaves(params_axes, is_leaf=_is_axes_leaf)
    params_struct = jax.tree.structure(state.params)

    def annotate_like(opt_tree):
        """Map each optimizer-state subtree: if it has the same structure
        as params, zip with params_axes; else replicate leaves."""

        def rec(node):
            if jax.tree.structure(node) == params_struct:
                return jax.tree.unflatten(params_struct, flat_axes)
            if isinstance(node, (dict,)):
                return {k: rec(v) for k, v in node.items()}
            if isinstance(node, tuple) and hasattr(node, "_fields"):
                return type(node)(*[rec(v) for v in node])
            if isinstance(node, (list, tuple)):
                return type(node)(rec(v) for v in node)
            # leaf: replicate (scalars like counts, schedules)
            ndim = getattr(node, "ndim", 0)
            return tuple([None] * ndim)

        return rec(opt_tree)

    return TrainState(
        step=(),
        params=jax.tree.unflatten(params_struct, flat_axes),
        opt_state=annotate_like(state.opt_state),
    )


def state_shardings(
    mesh,
    state: TrainState,
    params_axes: Any,
    rules: Optional[Rules] = None,
    *,
    zero: bool = False,
) -> TrainState:
    """Shardings for a whole TrainState.  ``zero=True`` switches to the
    ZeRO layout (train/zero.py): optimizer state — including optim8's
    int8 (q, scale) blockwise leaves, which the mirror-structure check
    below can only replicate — shards over the data axes (the codes and
    scales of a leaf by its rows)."""
    if zero:
        from ray_tpu.train.zero import zero_state_shardings

        return zero_state_shardings(mesh, state, params_axes, rules)
    axes = state_logical_axes(state, params_axes)
    return jax.tree.map(
        lambda a: tree_shardings(mesh, a, rules),
        axes,
        is_leaf=_is_axes_leaf,
    )


def default_optimizer(
    learning_rate: float | Callable = 3e-4,
    *,
    weight_decay: float = 0.1,
    b1: float = 0.9,
    b2: float = 0.95,
    grad_clip: float = 1.0,
    warmup_steps: int = 100,
    total_steps: Optional[int] = None,
    mu_dtype: Any = None,
) -> optax.GradientTransformation:
    """AdamW with cosine schedule + global-norm clipping (LLM defaults).
    ``mu_dtype=jnp.bfloat16`` halves the first-moment buffer (HBM
    headroom for bigger batches; the variance stays float32)."""
    if callable(learning_rate):
        schedule = learning_rate
    elif total_steps:
        schedule = optax.warmup_cosine_decay_schedule(
            0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1)
        )
    else:
        schedule = optax.linear_schedule(0.0, learning_rate, max(warmup_steps, 1))
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay,
                    mu_dtype=mu_dtype),
    )
