"""Collective communication — XLA collectives over ICI/DCN.

API parity with the reference's collective layer
(ray: python/ray/util/collective/collective.py — allreduce:258,
broadcast:373, allgather:423, reducescatter:472, send/recv:531+), but
TPU-native: instead of out-of-band NCCL communicators bound to actor
groups (ray: util/collective/collective_group/nccl_collective_group.py:127),
collectives here are XLA ops over named mesh axes, used inside
``shard_map``/``pjit`` programs, and ride the ICI torus.

Two layers:
  * functional ops (`allreduce`, `allgather`, ...) — thin, traceable,
    for use inside shard-mapped code;
  * `CollectiveGroup` — the reference's named-group API surface for code
    structured around explicit groups; it carries a mesh axis name.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

AxisName = Union[str, Sequence[str]]


def _reduce_fn(op: str) -> Callable:
    try:
        return {
            "sum": lax.psum,
            "max": lax.pmax,
            "min": lax.pmin,
            "mean": lax.pmean,
        }[op]
    except KeyError:
        raise ValueError(f"unsupported reduce op: {op!r}") from None


def allreduce(x: jax.Array, axis: AxisName, op: str = "sum") -> jax.Array:
    return _reduce_fn(op)(x, axis_name=axis)


def allgather(x: jax.Array, axis: AxisName, *, tiled_axis: int = 0) -> jax.Array:
    return lax.all_gather(x, axis_name=axis, axis=tiled_axis, tiled=True)


def reducescatter(x: jax.Array, axis: AxisName, *, scatter_axis: int = 0,
                  op: str = "sum") -> jax.Array:
    if op not in ("sum", "mean"):
        raise ValueError("reducescatter supports sum/mean")
    out = lax.psum_scatter(x, axis_name=axis, scatter_dimension=scatter_axis,
                           tiled=True)
    if op == "mean":
        out = out / axis_size(axis)
    return out


def broadcast(x: jax.Array, axis: AxisName, root: int = 0) -> jax.Array:
    """Every member gets root's value.  XLA form: select root then psum."""
    idx = lax.axis_index(axis)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name=axis)


def all_to_all(x: jax.Array, axis: AxisName, *, split_axis: int,
               concat_axis: int) -> jax.Array:
    return lax.all_to_all(x, axis_name=axis, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def permute(x: jax.Array, axis: AxisName, perm: Sequence[tuple]) -> jax.Array:
    return lax.ppermute(x, axis_name=axis, perm=list(perm))


def shift(x: jax.Array, axis: AxisName, offset: int = 1) -> jax.Array:
    """Ring shift by ``offset`` (the ring-attention building block)."""
    n = axis_size(axis)
    perm = [(i, (i + offset) % n) for i in range(n)]
    return lax.ppermute(x, axis_name=axis, perm=perm)


def send_recv(x: jax.Array, axis: AxisName, src: int, dst: int) -> jax.Array:
    """Point-to-point: dst receives src's x; everyone else receives zeros.
    Parity with reference send/recv (collective.py:531+) in SPMD form."""
    return lax.ppermute(x, axis_name=axis, perm=[(src, dst)])


def axis_index(axis: AxisName) -> jax.Array:
    return lax.axis_index(axis)


def axis_size(axis: AxisName) -> int:
    """Concrete size of a named mesh axis inside shard_map."""
    return lax.axis_size(axis)


# --- quantized DCN collectives ---------------------------------------------
#
# EQuARX-style (PAPERS.md) int8 allreduce for the data-center-network
# legs of a decode allreduce: each member quantizes its partial sum to
# int8 with one f32 absmax scale per ``chunk`` elements, exchanges the
# int8 payload + scales, and dequantizes locally.  Wire traffic drops
# from itemsize bytes/element to ~(1 + 4/chunk) bytes/element — ~3.9x
# at the default chunk of 256 against fp32, which is what keeps a
# cross-host tensor-parallel decode step off the DCN roofline.

DEFAULT_QUANT_CHUNK = 256


def quantized_allreduce(x: jax.Array, axis: AxisName, *,
                        chunk: int = DEFAULT_QUANT_CHUNK) -> jax.Array:
    """int8 sum-allreduce with per-chunk absmax scales.

    Traceable inside shard_map.  The payload is flattened and padded to
    a chunk multiple (the ragged tail is zero-padded; zeros quantize
    and dequantize exactly), each chunk carries one f32 scale
    (absmax/127, floored so an all-zero chunk divides safely and still
    dequantizes to exact zeros), and the exchange is an all_gather of
    (int8 payload, scales) followed by a local dequantize-and-sum —
    the XLA-traceable form of a quantized allreduce, with wire cost
    counted by :func:`allreduce_wire_bytes`."""
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    shape, dtype = x.shape, x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    n = flat.shape[0]
    pad = (-n) % chunk
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(-1, chunk)
    absmax = jnp.max(jnp.abs(chunks), axis=1)
    scale = jnp.maximum(absmax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(chunks / scale[:, None]), -127, 127)
    q = q.astype(jnp.int8)
    # all_gather untiled: [world, n_chunks, chunk] / [world, n_chunks].
    qs = lax.all_gather(q, axis_name=axis, axis=0, tiled=False)
    ss = lax.all_gather(scale, axis_name=axis, axis=0, tiled=False)
    total = jnp.sum(qs.astype(jnp.float32) * ss[..., None], axis=0)
    out = total.reshape(-1)
    if pad:
        out = out[:n]
    return out.reshape(shape).astype(dtype)


def dcn_allreduce(x: jax.Array, axis: AxisName, *, quantized: bool = True,
                  chunk: int = DEFAULT_QUANT_CHUNK) -> jax.Array:
    """Sum-allreduce for a DCN mesh axis: int8-quantized by default,
    exact ``lax.psum`` when ``quantized=False`` (the bf16-fallback
    config path — on TPU the wire dtype of an exact psum of bf16
    activations is bf16; on the CPU test backend it is bit-exact
    fp32, which is what the byte-identical serving tests pin)."""
    if not quantized:
        return lax.psum(x, axis_name=axis)
    return quantized_allreduce(x, axis, chunk=chunk)


def allreduce_wire_bytes(n_elements: int, *, axis_size: int,
                         quantized: bool, itemsize: int = 4,
                         chunk: int = DEFAULT_QUANT_CHUNK) -> int:
    """Bytes one member puts on the link per allreduce of ``n_elements``
    (payload exchanged with the ``axis_size - 1`` peers; 0 for a
    size-1 axis).  The quantized form counts the padded int8 payload
    plus one f32 scale per chunk; the exact form counts
    ``itemsize``-byte elements.  This is the accounting the serve
    telemetry counters and the MULTICHIP/bench records use — analytic
    by design, so CPU emulation and real DCN report the same number."""
    if axis_size <= 1 or n_elements <= 0:
        return 0
    peers = axis_size - 1
    if not quantized:
        return n_elements * itemsize * peers
    n_chunks = -(-n_elements // chunk)
    return (n_chunks * chunk * 1 + n_chunks * 4) * peers


def reducescatter_wire_bytes(n_elements: int, *, axis_size: int,
                             itemsize: int = 4) -> int:
    """Bytes one member puts on the link per reduce-scatter of
    ``n_elements``: each member ends with n/k elements, exchanging its
    k-1 foreign shards.  Same accounting family as
    ``allreduce_wire_bytes`` (per-member payload, analytic), which is
    what makes the ZeRO dryrun's RS-vs-AR comparison apples-to-apples:
    reduce-scatter + all-gather each cost (n/k)*(k-1) where the
    all-reduce costs n*(k-1)."""
    if axis_size <= 1 or n_elements <= 0:
        return 0
    return (n_elements // axis_size) * itemsize * (axis_size - 1)


def allgather_wire_bytes(n_elements: int, *, axis_size: int,
                         itemsize: int = 4) -> int:
    """Bytes one member puts on the link per all-gather producing
    ``n_elements``: it sends its n/k shard to the k-1 peers."""
    return reducescatter_wire_bytes(n_elements, axis_size=axis_size,
                                    itemsize=itemsize)


def page_transfer_wire_bytes(n_pages: int, elements_per_page: int, *,
                             quantized: bool, itemsize: int = 4,
                             scales_per_page: int = 1) -> int:
    """Bytes a KV page migration (serve/kv_transfer) puts on the wire
    for one pool tensor: point-to-point, so no peer multiplier.
    Quantized ships 1 int8 byte per element plus one f32 scale per
    (page, scale column); exact ships the storage bytes.  Analytic for
    the same reason `allreduce_wire_bytes` is: CPU emulation and a real
    DCN fabric must report identical accounting."""
    if n_pages <= 0:
        return 0
    if quantized:
        return n_pages * (elements_per_page * 1 + scales_per_page * 4)
    return n_pages * elements_per_page * itemsize


class CollectiveGroup:
    """Named-group API surface (reference: init_collective_group
    collective.py:120 / create_collective_group :151).

    A group is a mesh axis.  Methods are traceable functions usable inside
    shard_map over that mesh; `run` wraps a function in shard_map with
    fully-replicated in/out specs for quick group-wide programs.
    """

    def __init__(self, mesh: Mesh, axis: str):
        if axis not in mesh.axis_names:
            raise ValueError(f"axis {axis!r} not in mesh {mesh.axis_names}")
        self.mesh = mesh
        self.axis = axis

    @property
    def world_size(self) -> int:
        return self.mesh.shape[self.axis]

    def allreduce(self, x, op: str = "sum"):
        return allreduce(x, self.axis, op)

    def allgather(self, x, tiled_axis: int = 0):
        return allgather(x, self.axis, tiled_axis=tiled_axis)

    def reducescatter(self, x, scatter_axis: int = 0, op: str = "sum"):
        return reducescatter(x, self.axis, scatter_axis=scatter_axis, op=op)

    def broadcast(self, x, root: int = 0):
        return broadcast(x, self.axis, root)

    def all_to_all(self, x, split_axis: int, concat_axis: int):
        return all_to_all(x, self.axis, split_axis=split_axis,
                          concat_axis=concat_axis)

    def shift(self, x, offset: int = 1):
        return shift(x, self.axis, offset)

    def run(self, fn: Callable, *args, in_specs=None, out_specs=None):
        """Run ``fn`` shard-mapped over this group's axis."""
        from ray_tpu.parallel.mesh import shard_map_unchecked

        in_specs = in_specs if in_specs is not None else P()
        out_specs = out_specs if out_specs is not None else P()
        mapped = shard_map_unchecked(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
        )
        return mapped(*args)


_NAMED_GROUPS: dict = {}


def init_collective_group(mesh: Mesh, axis: str, group_name: str = "default"
                          ) -> CollectiveGroup:
    """Register a named group (reference: collective.py:120)."""
    group = CollectiveGroup(mesh, axis)
    _NAMED_GROUPS[group_name] = group
    return group


def get_group(group_name: str = "default") -> CollectiveGroup:
    return _NAMED_GROUPS[group_name]


def destroy_collective_group(group_name: str = "default") -> None:
    _NAMED_GROUPS.pop(group_name, None)
