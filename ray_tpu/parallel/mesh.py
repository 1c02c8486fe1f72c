"""Device meshes and parallelism axes.

This replaces the reference's out-of-band NCCL/Gloo collective groups
(ray: python/ray/util/collective/collective.py:120-531) with the TPU-native
model: a named ``jax.sharding.Mesh`` over the slice's chips, with XLA
emitting collectives over ICI/DCN.  Where Ray Train's backends set up a
torch ProcessGroup per worker (ray: python/ray/train/torch/config.py:63),
here a single SPMD program spans the mesh and per-axis collectives are
compiler-inserted.

Canonical axis names (order matters — outer axes map to slower/DCN-ish
dimensions, inner axes to fastest ICI rings):

    pp    pipeline stages       (cross-host ok; p2p ppermute traffic)
    dp    pure data parallel    (gradient psum only; DCN-tolerant)
    fsdp  ZeRO-sharded data     (params all-gathered per layer; wants ICI)
    ep    expert parallel       (all_to_all token routing; wants ICI)
    sp    sequence/context      (ring attention ppermute; wants an ICI ring)
    tp    tensor parallel       (per-matmul collectives; innermost, fastest ICI)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_ORDER: Tuple[str, ...] = ("pp", "dp", "fsdp", "ep", "sp", "tp")

# Hybrid DCN×ICI axes (SURVEY §5.8 plane 3, megascale-style): the
# outer, slower network dimension hosts only collective-light
# parallelism — pure gradient psum (dcn_dp), ZeRO gathers amortized
# per layer (dcn_fsdp), stage-boundary p2p (dcn_pp).  Model axes
# (tp/sp/ep) stay strictly within a slice's ICI.  Present in a mesh
# only when a hybrid spec asks for them, so flat single-slice meshes
# keep their canonical six axes.
#
# dcn_tp is the deliberate serving-plane exception to "model axes stay
# in-slice": a multi-host shard-group replica tensor-parallels its
# weights across node daemons, and the per-layer decode allreduce
# crosses DCN int8-quantized (parallel/collectives.dcn_allreduce,
# EQuARX-style) so the cross-host leg stays off the network roofline.
# It sits LAST so existing hybrid train meshes keep their leading
# (dcn_pp, dcn_dp, dcn_fsdp) axis positions.
DCN_AXIS_ORDER: Tuple[str, ...] = ("dcn_pp", "dcn_dp", "dcn_fsdp", "dcn_tp")

# Axes over which a replica of the model parameters is complete.  Data is
# split over these; params are replicated (dp) or sharded-and-gathered (fsdp).
DATA_AXES: Tuple[str, ...] = ("dcn_dp", "dcn_fsdp", "dp", "fsdp")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative parallelism layout.

    Sizes of -1 mean "absorb remaining devices" (at most one axis may be
    -1).  Axes of size 1 are still present in the mesh so sharding rules
    can always refer to every canonical axis.

    ``dcn_*`` sizes > 1 request a HYBRID DCN×ICI mesh: devices group by
    host/slice (jax ``process_index``/``slice_index``), the dcn axes
    index the groups, and the canonical axes lay out each group's ICI —
    the layout ``jax.experimental.mesh_utils.create_hybrid_device_mesh``
    builds, expressed in this spec language.
    """

    pp: int = 1
    dp: int = -1
    fsdp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1
    dcn_pp: int = 1
    dcn_dp: int = 1
    dcn_fsdp: int = 1
    dcn_tp: int = 1

    @property
    def hybrid(self) -> bool:
        return any(getattr(self, a) != 1 for a in DCN_AXIS_ORDER)

    def dcn_sizes(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in DCN_AXIS_ORDER}

    def sizes(self, num_devices: int) -> Dict[str, int]:
        for a, s in self.dcn_sizes().items():
            if s < 1:
                raise ValueError(
                    f"{a}={s}: DCN axes take explicit sizes >= 1 (the "
                    f"-1 wildcard applies to in-slice axes only)")
        n_groups = math.prod(self.dcn_sizes().values())
        if num_devices % n_groups:
            raise ValueError(
                f"{num_devices} devices not divisible into {n_groups} "
                f"DCN groups")
        per_group = num_devices // n_groups
        sizes = {a: getattr(self, a) for a in AXIS_ORDER}
        wild = [a for a, s in sizes.items() if s == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one axis may be -1, got {wild}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wild:
            if per_group % fixed:
                raise ValueError(
                    f"{per_group} devices/group not divisible by fixed "
                    f"axes product {fixed}"
                )
            sizes[wild[0]] = per_group // fixed
        elif fixed != per_group:
            raise ValueError(
                f"mesh wants {fixed} devices per group but {per_group} "
                f"are available"
            )
        if self.hybrid:
            sizes.update(self.dcn_sizes())
        return sizes

    def with_axes(self, **kwargs) -> "MeshSpec":
        return dataclasses.replace(self, **kwargs)


def _order_devices_for_ici(devices: List[jax.Device]) -> List[jax.Device]:
    """Order devices so that inner mesh axes land on ICI neighbors.

    On TPU backends, jax device coords encode the physical torus; sorting
    by (slice_index, coords, core) keeps the innermost mesh axis (tp)
    on physically adjacent chips.  The reference's analogue is NCCL ring
    construction from CUDA device topology — here the torus is explicit.
    """

    def key(d):
        coords = getattr(d, "coords", None)
        slice_index = getattr(d, "slice_index", 0) or 0
        core = getattr(d, "core_on_chip", 0) or 0
        if coords is None:
            return (slice_index, d.id, core)
        return (slice_index, *coords, core)

    return sorted(devices, key=key)


def _group_devices_for_dcn(devs: List[jax.Device],
                           n_groups: int) -> List[List[jax.Device]]:
    """Split devices into DCN groups: by ``process_index`` when the
    world really spans processes, by ``slice_index`` when the backend
    labels slices, else contiguous equal chunks (the virtual-CPU test
    shape, where grouping is synthetic by construction)."""
    for attr in ("process_index", "slice_index"):
        keys = sorted({getattr(d, attr, None) or 0 for d in devs})
        if len(keys) == n_groups:
            groups = {k: [] for k in keys}
            for d in devs:
                groups[getattr(d, attr, None) or 0].append(d)
            counts = {len(g) for g in groups.values()}
            if len(counts) == 1:
                return [groups[k] for k in keys]
    if len(devs) % n_groups:
        raise ValueError(
            f"{len(devs)} devices not divisible into {n_groups} groups")
    per = len(devs) // n_groups
    return [devs[i * per:(i + 1) * per] for i in range(n_groups)]


def create_mesh(
    spec: Optional[MeshSpec] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    axis_names: Tuple[str, ...] = AXIS_ORDER,
) -> Mesh:
    """Build a Mesh laying canonical axes over ICI-ordered devices.

    A hybrid spec (any dcn_* > 1) produces a mesh named
    (dcn_pp, dcn_dp, dcn_fsdp) + the canonical axes: DCN axes index
    host/slice groups, canonical axes lay out each group's ICI."""
    spec = spec or MeshSpec()
    devs = list(devices) if devices is not None else list(jax.devices())
    sizes = spec.sizes(len(devs))
    if spec.hybrid:
        n_groups = math.prod(sizes[a] for a in DCN_AXIS_ORDER)
        groups = _group_devices_for_dcn(devs, n_groups)
        inner_shape = tuple(sizes[a] for a in axis_names)
        stacked = np.stack([
            np.asarray(_order_devices_for_ici(g), dtype=object)
            .reshape(inner_shape)
            for g in groups
        ])
        dcn_shape = tuple(sizes[a] for a in DCN_AXIS_ORDER)
        arr = stacked.reshape(dcn_shape + inner_shape)
        return Mesh(arr, DCN_AXIS_ORDER + tuple(axis_names))
    devs = _order_devices_for_ici(devs)
    shape = tuple(sizes[a] for a in axis_names)
    arr = np.asarray(devs, dtype=object).reshape(shape)
    return Mesh(arr, axis_names)


def shard_map_unchecked(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking disabled (our mapped
    bodies produce per-device values by construction)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def create_serving_mesh(shards: int, tp: int, *,
                        devices: Optional[Sequence[jax.Device]] = None
                        ) -> Mesh:
    """Mesh for a multi-host tensor-parallel serving replica: ``shards``
    shard-group members along ``dcn_tp`` (one per node daemon, grouped
    by ``process_index`` in a real jax.distributed world, contiguous
    chunks on the virtual-CPU test backend) × ``tp`` chips of ICI
    inside each.  Weights shard over (dcn_tp, tp); per-layer decode
    allreduces split into an ICI psum over ``tp`` plus a quantized DCN
    leg over ``dcn_tp``.  Extra devices beyond ``shards * tp`` are left
    out rather than absorbed — a serving replica owns exactly its
    shard-group's chips."""
    devs = list(devices) if devices is not None else list(jax.devices())
    need = shards * tp
    if len(devs) < need:
        raise ValueError(
            f"serving mesh wants {shards}x{tp}={need} devices, have "
            f"{len(devs)}")
    devs = _order_devices_for_ici(devs)[:need]
    return create_mesh(MeshSpec(dp=1, tp=tp, dcn_tp=shards), devices=devs)


def serving_mesh_shape(mesh: Mesh) -> str:
    """Human/CLI form of a serving mesh's layout ("dcn_tp=2 x tp=4"),
    the mesh-shape column `raytpu list replicas` prints."""
    parts = []
    for a in ("dcn_tp", "tp"):
        if mesh.shape.get(a, 1) >= 1:
            parts.append(f"{a}={mesh.shape.get(a, 1)}")
    return " x ".join(parts)


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    dev = device or jax.devices()[0]
    return create_mesh(MeshSpec(dp=1), devices=[dev])


def data_axis_size(mesh: Mesh) -> int:
    return math.prod(mesh.shape[a] for a in DATA_AXES if a in mesh.shape)


def model_axes(mesh: Mesh) -> List[str]:
    return [a for a in ("tp", "sp", "ep", "pp") if mesh.shape.get(a, 1) > 1]


@dataclasses.dataclass(frozen=True)
class TpuTopology:
    """Slice topology as the scheduler and mesh builder see it.

    Parity: the reference detects TPU pods via env/metadata and exposes
    `TPU-{version}-{pod}-head` resources
    (ray: python/ray/_private/accelerator.py:20-191); here the topology
    also drives mesh construction, not just resource bookkeeping.
    """

    generation: str  # e.g. "v5p"
    chips: int
    hosts: int
    chips_per_host: int

    @property
    def name(self) -> str:
        return f"{self.generation}-{self.chips}"


def detect_topology() -> TpuTopology:
    """The slice this process computes on.  Raises LookupError on a
    device utils/accelerator has no entry for (a CPU backend): a
    topology of an unknown chip is not a TPU topology."""
    from ray_tpu.utils.accelerator import chip_spec

    devs = jax.devices()
    n = len(devs)
    gen = chip_spec(devs[0].device_kind)["chip"].removeprefix("TPU-")
    num_hosts = jax.process_count()
    return TpuTopology(
        generation=gen,
        chips=n,
        hosts=num_hosts,
        chips_per_host=max(1, n // num_hosts),
    )


def default_spec_for(num_devices: int, *, model_bytes: int = 0) -> MeshSpec:
    """Heuristic layout: shard params over fsdp up to what fits, keep tp
    within a host-sized group, rest to dp."""
    if num_devices == 1:
        return MeshSpec(dp=1)
    # Default: pure FSDP over all chips — best tokens/sec for dense LLMs
    # that fit once sharded; callers override for tp/pp needs.
    return MeshSpec(dp=1, fsdp=num_devices)
