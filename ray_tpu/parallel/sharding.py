"""Logical-axis sharding rules.

The TPU-native replacement for per-framework model wrappers like the
reference's DDP/FSDP `prepare_model`
(ray: python/ray/train/torch/train_loop_utils.py:74,100): models annotate
parameters and activations with *logical* axis names ("embed", "mlp",
"heads", "batch", "seq", ...) and a rule table maps those to mesh axes.
Changing the parallelism layout (dp↔fsdp↔tp↔sp↔ep) is a rule-table edit,
not a model edit — the GSPMD partitioner does the rest.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# rule: logical axis name -> mesh axis | tuple of mesh axes | None (replicate)
Rules = Dict[str, Union[str, Tuple[str, ...], None]]

# Default rule table for transformer LMs.  Matches how the flagship models
# in ray_tpu.models name their dimensions.
DEFAULT_RULES: Rules = {
    # data
    "batch": ("dp", "fsdp"),
    "seq": "sp",
    # params
    "vocab": "tp",
    "embed": "fsdp",
    "embed_tp": "tp",     # activations' feature dim under tensor parallel
    "heads": "tp",
    "kv_heads": "tp",
    "head_dim": None,
    "mlp": "tp",
    "expert": "ep",
    "layers": None,       # used by scan-stacked params; pp handles stages
    # state-space models
    "state": None,
    # ZeRO weight-update sharding (train/zero.py): the axes optimizer
    # state and the fused update shard over.
    "zero": ("dp", "fsdp"),
}

# Hybrid DCN×ICI meshes: when the target mesh carries a dcn_* axis,
# the matching in-slice axis expands to (dcn pair, axis) MECHANICALLY
# at spec time — rule tables stay written in the flat six-axis
# vocabulary and bare spec_for() calls keep their historical meaning.
# "tp" → "dcn_tp" serves the multi-host serving meshes
# (mesh.create_serving_mesh): a shard-group replica's weights shard
# over both the cross-daemon and the in-host tensor axes from the same
# serving rule table.
_DCN_EXPANSION = {"dp": "dcn_dp", "fsdp": "dcn_fsdp", "pp": "dcn_pp",
                  "tp": "dcn_tp"}


def spec_for(logical_axes: Sequence[Optional[str]],
             rules: Optional[Rules] = None, *,
             mesh_axes: Optional[frozenset] = None) -> P:
    """Map a tuple of logical axis names (None = replicated dim) to a
    PartitionSpec.  ``mesh_axes``: the target mesh's axis names — used
    to expand dp/fsdp/pp over their DCN partners on hybrid meshes and
    to drop axes the mesh doesn't carry."""
    rules = {**DEFAULT_RULES, **(rules or {})}
    out = []
    used = set()
    for name in logical_axes:
        if name is None:
            out.append(None)
            continue
        if name not in rules:
            raise KeyError(f"no sharding rule for logical axis {name!r}")
        axes = rules[name]
        if axes is None:
            out.append(None)
            continue
        if isinstance(axes, str):
            axes = (axes,)
        if mesh_axes is not None:
            expanded = []
            for a in axes:
                dcn = _DCN_EXPANSION.get(a)
                if dcn is not None and dcn in mesh_axes:
                    expanded.append(dcn)
                expanded.append(a)
            axes = tuple(a for a in expanded if a in mesh_axes)
        # A mesh axis may appear only once in a PartitionSpec.
        axes = tuple(a for a in axes if a not in used)
        used.update(axes)
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(axes)
    return P(*out)


def sharding_for(
    mesh: Mesh,
    logical_axes: Sequence[Optional[str]],
    rules: Optional[Rules] = None,
) -> NamedSharding:
    return NamedSharding(
        mesh, spec_for(logical_axes, rules,
                       mesh_axes=frozenset(mesh.axis_names)))


def tree_shardings(
    mesh: Mesh,
    logical_tree: Any,
    rules: Optional[Rules] = None,
) -> Any:
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings.

    ``logical_tree`` mirrors the param pytree, with each leaf a tuple of
    logical axis names (or None) per dimension.
    """
    return jax.tree.map(
        lambda axes: sharding_for(mesh, axes, rules),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(e, (str, type(None))) for e in x),
    )


def constrain(x: jax.Array, logical_axes: Sequence[Optional[str]],
              rules: Optional[Rules] = None) -> jax.Array:
    """with_sharding_constraint by logical axes — use inside jitted code.
    A no-op outside any mesh context, so model code runs unchanged
    single-device (e.g. unit tests, one-chip serving).

    Under ``with mesh:`` (the trainer's idiom) only the *physical*
    thread-resources mesh is populated — the abstract mesh stays empty —
    so a bare-PartitionSpec constraint would either raise or be
    dropped; bind the spec to the concrete mesh instead.
    ``current_mesh`` resolves either kind."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = spec_for(logical_axes, rules,
                    mesh_axes=frozenset(mesh.axis_names))
    if isinstance(mesh, Mesh):
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)


def current_mesh():
    """The mesh enclosing the current trace — the abstract mesh when one
    is set, else the thread-resources physical mesh (the trainer's
    ``with mesh:`` idiom), else None.  Lets traced code adapt its
    sharding constraints to whatever mesh it is being partitioned for
    (train/optim8.py runs its kernel only where nothing is partitioned)."""
    from jax._src import mesh as _mesh_lib

    abstract = jax.sharding.get_abstract_mesh()
    if not abstract.empty:
        return abstract
    physical = _mesh_lib.thread_resources.env.physical_mesh
    return None if physical.empty else physical


def shard_tree(mesh: Mesh, tree: Any, logical_tree: Any,
               rules: Optional[Rules] = None) -> Any:
    """Device-put a host pytree onto the mesh with the given logical layout."""
    shardings = tree_shardings(mesh, logical_tree, rules)
    return jax.device_put(tree, shardings)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
