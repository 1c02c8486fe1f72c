"""Block-wise 8-bit Adam optimizer states (TPU-native bitsandbytes
analogue).

The reference ecosystem fits big models with 8-bit optimizers
(bitsandbytes' CUDA kernels).  Here Adam's m/v tensors live as int8 with
one float32 absmax scale per block of at most 256 consecutive elements,
laid out AS THE LEAF IS (``ops/adam8bit.py`` says how a leaf's shape
decides its blocks), and one Mosaic kernel a leaf dequantises, updates
and requantises them where they lie — 2 bytes/param of optimizer state
instead of 8, which is what lets a ~2.4B-param AdamW config train on one
16 GB chip, at 14 bytes of memory traffic a parameter and step.
Quantization error behaves like rounding noise on m/v; each block keeps
full dynamic range via its own scale.

Under a mesh of several devices the same update runs as plain
``jax.numpy`` (``adam8bit.adam8_update_reference``), which the
partitioner can shard: blocks run along a leaf's last axis, so the
state shards by rows as the parameters' mirrors do (train/zero.py) and
no shard cuts a block.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from ray_tpu.ops import adam8bit
from ray_tpu.ops.adam8bit import BLOCK, Adam8  # noqa: F401  (re-export)
from ray_tpu.parallel.sharding import current_mesh


class InPlaceTransformation(NamedTuple):
    """An ``optax.GradientTransformation`` (``init``, ``update``) that
    can also ``apply(grads, state, params) -> (params, state)``: the
    update added to the parameter where it is computed, which saves
    ``optax.apply_updates``' read and write of every parameter
    (``train/step.apply_gradients`` asks for it)."""
    init: Callable
    update: Callable
    apply: Callable


class ScaleByAdam8State(NamedTuple):
    count: Any
    mu: Any   # pytree with (q, scale) tuples at param leaf positions
    nu: Any


def _init(params) -> ScaleByAdam8State:
    def zero(p):
        rows, cols = adam8bit.view_shape(p.shape)
        return (jnp.zeros((rows, cols), jnp.int8),
                jnp.full(adam8bit.scale_shape(rows, cols), 1e-12,
                         jnp.float32))

    return ScaleByAdam8State(jnp.zeros([], jnp.int32),
                             jax.tree.map(zero, params),
                             jax.tree.map(zero, params))


def _sharded() -> bool:
    """Is the trace partitioned over several devices?  Then the plain
    ``jax.numpy`` update serves: a kernel is opaque to the partitioner."""
    mesh = current_mesh()
    return mesh is not None and mesh.size > 1


def _update(hp: Adam8, grads, state: ScaleByAdam8State, params, *,
            gnorm=None, step_size=None):
    """Every leaf through ``adam8_update`` in its own layout."""
    count = state.count + 1
    step = (adam8bit.adam8_update_reference if _sharded()
            else adam8bit.adam8_update)

    scal = {}   # the step's scalars, once a dtype

    def leaf(g, p, mu, nu):
        view = functools.partial(adam8bit.to_view, rows=mu[0].shape[0],
                                 cols=mu[0].shape[1])
        if g.dtype not in scal:
            scal[g.dtype] = adam8bit.scalars(hp, count, g.dtype, gnorm,
                                             step_size)
        out, mq, ms, nq, ns = step(
            scal[g.dtype], view(g), view(p) if hp.fused else None,
            *mu, *nu, hp=hp)
        return adam8bit.from_view(out, g.shape), (mq, ms), (nq, ns)

    flat_g, treedef = jax.tree.flatten(grads)
    flat_p = treedef.flatten_up_to(params) if hp.fused else flat_g
    outs = [leaf(*a) for a in zip(flat_g, flat_p,
                                  treedef.flatten_up_to(state.mu),
                                  treedef.flatten_up_to(state.nu))]
    return (treedef.unflatten([o[0] for o in outs]),
            ScaleByAdam8State(count,
                              treedef.unflatten([o[1] for o in outs]),
                              treedef.unflatten([o[2] for o in outs])))


def scale_by_adam8bit(b1: float = 0.9, b2: float = 0.95,
                      eps: float = 1e-8) -> optax.GradientTransformation:
    """Adam moment tracking with int8 block-quantized mu/nu."""
    hp = Adam8(b1=b1, b2=b2, eps=eps)

    def update(grads, state, params=None):
        return _update(hp, grads, state, params)

    return optax.GradientTransformation(_init, update)


def adamw8bit(
    learning_rate: float = 3e-4,
    *,
    weight_decay: float = 0.1,
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1e-8,
    grad_clip: float = 1.0,
    warmup_steps: int = 100,
    total_steps: Optional[int] = None,
) -> InPlaceTransformation:
    """AdamW with 8-bit states + the same schedule/clipping wrapping as
    train.default_optimizer: ``optax.chain(clip_by_global_norm,
    scale_by_adam8bit, add_decayed_weights, scale_by_learning_rate)``
    as ONE transformation, because the clip's scale, the decay and the
    step size ride in the moments' one pass over the leaf (rounded to
    the leaf's dtype where the compiled chain rounds:
    ``adam8bit._stepped``)."""
    if total_steps:
        schedule = optax.warmup_cosine_decay_schedule(
            0.0, learning_rate, warmup_steps,
            max(total_steps, warmup_steps + 1))
    else:
        schedule = optax.linear_schedule(
            0.0, learning_rate, max(1, warmup_steps))
    hp = Adam8(b1=b1, b2=b2, eps=eps, fused=True,
               clip=float(grad_clip or 0.0),
               weight_decay=float(weight_decay or 0.0))

    def run(hp, grads, state, params=None):
        if params is None:
            raise ValueError("adamw8bit reads the parameters (weight "
                             "decay): call update(grads, state, params)")
        gnorm = optax.global_norm(grads) if hp.clip else 0.0
        # the chain's schedule counts the updates BEFORE this one
        return _update(hp, grads, state, params, gnorm=gnorm,
                       step_size=-schedule(state.count))

    return InPlaceTransformation(
        _init, functools.partial(run, hp),
        functools.partial(run, hp._replace(apply=True)))
