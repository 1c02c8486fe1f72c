"""Cross-replica sharding of the weight update (ZeRO-style).

Implements the layout side of "Automatic Cross-Replica Sharding of
Weight Update in Data-Parallel Training" (PAPERS.md, arXiv 2004.13336):
Adam's mu/nu (and the fp32 mirror of the fused update) live sharded
over the data axes instead of replicated per dp member.  With the
optimizer state's out_shardings pinned here, the GSPMD partitioner
converts the gradient all-reduce into reduce-scatter → local update on
1/dp of the blocks → all-gather of the updated params — no explicit
collectives in the step function.

Memory math this buys: int8 Adam states cost ~2 B/param replicated
(train/optim8.py); sharded they cost ~2/dp B/param per device, which is
what lets full-8B AdamW train on a slice where the replicated states
alone would blow HBM.

Layout rules, per optimizer-state subtree of a ``TrainState``:

* param-mirror subtrees (fp32/bf16 mu/nu with the params' structure)
  keep their param logical axes and additionally shard their largest
  still-replicated dim over the free data axes when sizes divide;
* int8 blockwise subtrees (optim8's ``(q [rows, cols], scale [blocks a
  row, rows])`` leaves, the leaf's own 2-D view) shard their ROWS, the
  codes' first dim and the scales' last: blocks run along a row, so no
  shard cuts one (where a block is two rows, shards of an even number);
* scalars (counts, schedule state) replicate.

Sharding never pads: a dim is sharded over the longest prefix of the
data axes whose size product divides it (XLA rejects uneven
in/out shardings), so tiny leaves (norms, biases) stay replicated and
all the bytes that matter — the big matmul weights — shard fully.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.ops import adam8bit
from ray_tpu.parallel.sharding import Rules, spec_for
from ray_tpu.train.state import TrainState, _is_axes_leaf

# Logical axis name the rule table maps to the weight-update shard axes
# (DEFAULT_RULES: ("dp", "fsdp"), DCN-expanded on hybrid meshes).
ZERO_AXIS = "zero"


def zero_axes(mesh, rules: Optional[Rules] = None) -> Tuple[str, ...]:
    """Mesh axes the weight update shards over: the "zero" rule resolved
    against ``mesh``, keeping only axes actually present with size > 1."""
    spec = spec_for((ZERO_AXIS,), rules,
                    mesh_axes=frozenset(mesh.axis_names))
    entry = spec[0] if len(spec) else None
    if entry is None:
        return ()
    axes = (entry,) if isinstance(entry, str) else tuple(entry)
    return tuple(a for a in axes if mesh.shape.get(a, 1) > 1)


def dp_shards(mesh, rules: Optional[Rules] = None) -> int:
    """How many ways the optimizer state shards (1 = replicated layout)."""
    return max(1, math.prod(mesh.shape[a] for a in zero_axes(mesh, rules)))


def shardable_prefix(size: int, axes: Tuple[str, ...], mesh
                     ) -> Tuple[str, ...]:
    """Longest prefix of ``axes`` whose size product divides ``size``."""
    for k in range(len(axes), 0, -1):
        prefix = axes[:k]
        if size % math.prod(mesh.shape.get(a, 1) for a in prefix) == 0:
            return prefix
    return ()


def _axis_tuple(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    return (entry,) if isinstance(entry, str) else tuple(entry)


def _is_blockpair(node) -> bool:
    """optim8's (q int8 [rows, cols], f32 scale [blocks a row, rows])
    leaf pair (ops/adam8bit.py)."""
    if not (isinstance(node, tuple) and not hasattr(node, "_fields")
            and len(node) == 2):
        return False
    q, s = node
    return (getattr(q, "ndim", 0) == 2 and getattr(s, "ndim", 0) == 2
            and str(getattr(q, "dtype", "")) == "int8"
            and tuple(s.shape) == adam8bit.scale_shape(*q.shape))


def block_shardings(mesh, q_shape: Tuple[int, int],
                    rules: Optional[Rules] = None
                    ) -> Tuple[NamedSharding, NamedSharding]:
    """Shardings of a blockwise pair: the rows over the data axes,
    every shard whole blocks, divisibility permitting; replicated
    otherwise."""
    rows, cols = q_shape
    units = rows // 2 if cols == adam8bit.HALF else rows
    ax = shardable_prefix(units, zero_axes(mesh, rules), mesh)
    if not ax:
        return NamedSharding(mesh, P()), NamedSharding(mesh, P())
    entry = ax[0] if len(ax) == 1 else ax
    return (NamedSharding(mesh, P(entry, None)),
            NamedSharding(mesh, P(None, entry)))


def _extend_spec(entries, shape, free: Tuple[str, ...], mesh):
    """Assign the free data axes to the largest effectively-replicated
    dim they divide.  A dim already annotated with size-1 axes counts as
    replicated — the free axes compose onto it (sub-axis sharding), so
    e.g. a ("vocab", "embed") mirror still ZeRO-shards on a pure-dp
    mesh where vocab→tp and embed→fsdp are both trivial."""
    for d in sorted(range(len(shape)), key=lambda d: -shape[d]):
        cur = _axis_tuple(entries[d])
        if math.prod(mesh.shape.get(a, 1) for a in cur) != 1:
            continue
        usable = shardable_prefix(shape[d], free, mesh)
        if not usable:
            continue
        new = cur + usable
        entries[d] = new[0] if len(new) == 1 else new
        return entries
    return entries


def zero_state_shardings(mesh, state: TrainState, params_axes: Any,
                         rules: Optional[Rules] = None) -> TrainState:
    """ZeRO layout for a whole ``TrainState``: params keep their logical
    axes; optimizer state additionally shards over the data axes."""
    mesh_axes = frozenset(mesh.axis_names)
    flat_axes = jax.tree.leaves(params_axes, is_leaf=_is_axes_leaf)
    params_struct = jax.tree.structure(state.params)
    param_sh = jax.tree.unflatten(
        params_struct,
        [NamedSharding(mesh, spec_for(a, rules, mesh_axes=mesh_axes))
         for a in flat_axes])
    zaxes = zero_axes(mesh, rules)

    def mirror(axes, leaf) -> NamedSharding:
        spec = spec_for(axes, rules, mesh_axes=mesh_axes)
        entries = list(spec) + [None] * (leaf.ndim - len(spec))
        used = {a for e in entries for a in _axis_tuple(e)}
        free = tuple(a for a in zaxes if a not in used)
        if free:
            entries = _extend_spec(entries, leaf.shape, free, mesh)
        return NamedSharding(mesh, P(*entries))

    def rec(node):
        if jax.tree.structure(node) == params_struct:
            leaves = params_struct.flatten_up_to(node)
            return jax.tree.unflatten(
                params_struct,
                [mirror(a, l) for a, l in zip(flat_axes, leaves)])
        try:
            sub = params_struct.flatten_up_to(node)
        except Exception:
            sub = None
        if sub is not None and all(_is_blockpair(x) for x in sub):
            return jax.tree.unflatten(
                params_struct,
                [block_shardings(mesh, tuple(q.shape), rules)
                 for q, _s in sub])
        if isinstance(node, dict):
            return {k: rec(v) for k, v in node.items()}
        if isinstance(node, tuple) and hasattr(node, "_fields"):
            return type(node)(*[rec(v) for v in node])
        if isinstance(node, (list, tuple)):
            return type(node)(rec(v) for v in node)
        return NamedSharding(mesh, P())

    return TrainState(
        step=NamedSharding(mesh, P()),
        params=param_sh,
        opt_state=rec(state.opt_state),
    )


def opt_state_bytes(opt_state: Any) -> dict:
    """Optimizer-state footprint from the arrays' actual shardings:
    ``global`` bytes across the mesh and ``per_device`` bytes resident
    on one device (~global/dp under ZeRO, == global replicated)."""
    g = per = 0
    for leaf in jax.tree.leaves(opt_state):
        dtype = getattr(leaf, "dtype", None)
        shape = getattr(leaf, "shape", None)
        if dtype is None or shape is None:
            continue
        itemsize = jnp.dtype(dtype).itemsize
        g += math.prod(shape) * itemsize
        sh = getattr(leaf, "sharding", None)
        local = (math.prod(sh.shard_shape(tuple(shape)))
                 if sh is not None else math.prod(shape))
        per += local * itemsize
    return {"global": g, "per_device": per}
