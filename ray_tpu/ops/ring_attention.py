"""Ring attention — sequence-parallel causal attention over an ICI ring.

Absent from the reference (SURVEY.md §5.7: no SP/CP anywhere in it);
built TPU-first: the sequence axis is sharded over the mesh's "sp" axis,
each device holds a contiguous sequence chunk, and k/v chunks rotate
around the ring via ``lax.ppermute`` while every device accumulates its
queries' attention with the flash kernels (ray_tpu.ops.flash_attention)
chunk by chunk, merging partial results in log-sum-exp space.

Causal structure (device index i, incoming chunk j = (i - t) mod n at
ring step t):
  t == 0          j == i   diagonal chunk  → causal flash
  t >= 1, i >= t  j <  i   past chunk      → non-causal flash
  t >= 1, i <  t  j >  i   future chunk    → masked out of the merge

The kernels are invoked unconditionally (SPMD — every device runs the
same program) and future chunks are dropped by giving them -inf
log-sum-exp weight in the merge; the gradient pass zeroes their
contributions the same way.  This is the plain ring schedule — the
~2× load imbalance of causal rings (zigzag/striped variants fix it)
is accepted for now.

The whole fwd+bwd is one custom_vjp so the backward runs its own ring
pass (k/v and their gradient accumulators rotate together; after n
steps the accumulators arrive back at their home device).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.parallel.collectives import axis_size
from ray_tpu.parallel.mesh import shard_map_unchecked

from ray_tpu.ops.flash_attention import (
    DEFAULT_BLOCK_KV,
    DEFAULT_BLOCK_Q,
    _flash_backward,
    _flash_forward,
)

NEG_INF = -1e30


def _rotate(x, axis_name: str):
    n = axis_size(axis_name)
    return lax.ppermute(x, axis_name, [(i, (i + 1) % n) for i in range(n)])


def _merge(o_a, lse_a, o_b, lse_b):
    """Merge two normalized partial attentions in lse space (f32)."""
    lse_max = jnp.maximum(lse_a, lse_b)
    wa = jnp.exp(lse_a - lse_max)
    wb = jnp.exp(lse_b - lse_max)
    denom = wa + wb
    lse_out = lse_max + jnp.log(denom)
    o_out = (o_a * wa + o_b * wb) / denom
    return o_out, lse_out


def _ring_fwd_local(q, k, v, *, axis_name, block_q, block_kv):
    """Per-device fwd. q/k/v [B,H,Sl,D] (local chunks) → (o, lse)."""
    n = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    scale = q.shape[-1] ** -0.5

    # t = 0: the diagonal (own) chunk, causal.
    o, lse = _flash_forward(q, k, v, scale=scale, causal=True,
                            block_q=block_q, block_kv=block_kv)
    o = o.astype(jnp.float32)

    k_t, v_t = k, v
    for t in range(1, n):
        k_t = _rotate(k_t, axis_name)
        v_t = _rotate(v_t, axis_name)
        o_t, lse_t = _flash_forward(q, k_t, v_t, scale=scale, causal=False,
                                    block_q=block_q, block_kv=block_kv)
        # devices with idx < t are looking at a future chunk: drop it
        visible = (idx >= t)
        lse_t = jnp.where(visible, lse_t, NEG_INF)
        o, lse = _merge(o, lse, o_t.astype(jnp.float32), lse_t)
    return o, lse


def _ring_bwd_local(q, k, v, o, lse, do, *, axis_name, block_q, block_kv):
    """Per-device bwd ring pass → (dq, dk, dv) for the local chunks."""
    n = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    scale = q.shape[-1] ** -0.5
    H = q.shape[1]
    KVH = k.shape[1]
    group = H // KVH

    def _expand(x):
        return jnp.repeat(x, group, axis=1) if group > 1 else x

    def _reduce_group(g):
        if group == 1:
            return g
        B, _, S, D = g.shape
        return g.reshape(B, KVH, group, S, D).sum(axis=2)

    def _chunk_bwd(k_chunk, v_chunk, lse_in, causal):
        dq_t, dk_t, dv_t = _flash_backward(
            q, _expand(k_chunk), _expand(v_chunk), o, lse_in, do,
            scale=scale, causal=causal, block_q=block_q, block_kv=block_kv,
        )
        return (dq_t.astype(jnp.float32),
                _reduce_group(dk_t.astype(jnp.float32)),
                _reduce_group(dv_t.astype(jnp.float32)))

    # t = 0: diagonal chunk.
    dq, dk_acc, dv_acc = _chunk_bwd(k, v, lse, causal=True)

    k_t, v_t = k, v  # KVH-sized tensors ride the ring (not the expansion)
    for t in range(1, n):
        # rotate kv and their grad accumulators together
        k_t = _rotate(k_t, axis_name)
        v_t = _rotate(v_t, axis_name)
        dk_acc = _rotate(dk_acc, axis_name)
        dv_acc = _rotate(dv_acc, axis_name)
        # Mask invisible (future) chunks BEFORE the kernel's exp(s - lse):
        # a huge lse drives p to exactly 0, so their gradients vanish
        # without ever forming inf (inf * 0 would be NaN).
        visible = idx >= t
        lse_in = jnp.where(visible, lse, -NEG_INF)
        dq_t, dk_t, dv_t = _chunk_bwd(k_t, v_t, lse_in, causal=False)
        dq = dq + dq_t
        dk_acc = dk_acc + dk_t
        dv_acc = dv_acc + dv_t
    # one more rotation brings accumulators home (n total rotations)
    dk_acc = _rotate(dk_acc, axis_name)
    dv_acc = _rotate(dv_acc, axis_name)
    return dq.astype(q.dtype), dk_acc.astype(k.dtype), dv_acc.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ring_attention_local(q, k, v, axis_name, block_q, block_kv):
    """Causal ring attention for use INSIDE shard_map.

    q [B,H,Sl,D], k/v [B,KVH,Sl,D] — Sl is this device's sequence chunk;
    chunks are contiguous slices of the global sequence in ring order.
    """
    o, _ = _ring_fwd_local(q, k, v, axis_name=axis_name, block_q=block_q,
                           block_kv=block_kv)
    return o.astype(q.dtype)


def _ring_vjp_fwd(q, k, v, axis_name, block_q, block_kv):
    o, lse = _ring_fwd_local(q, k, v, axis_name=axis_name, block_q=block_q,
                             block_kv=block_kv)
    o = o.astype(q.dtype)
    return o, (q, k, v, o, lse)


def _ring_vjp_bwd(axis_name, block_q, block_kv, res, do):
    q, k, v, o, lse = res
    return _ring_bwd_local(q, k, v, o, lse, do, axis_name=axis_name,
                           block_q=block_q, block_kv=block_kv)


ring_attention_local.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Optional[Mesh] = None,
    *,
    axis: str = "sp",
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
) -> jax.Array:
    """Causal attention with the sequence sharded over ``axis``.

    q [B,S,H,D], k/v [B,S,KVH,D] in the canonical model layout; batch is
    sharded over (dp, fsdp), heads over tp, sequence over ``axis``.
    Works inside jit — shard_map nests under GSPMD.
    """
    if mesh is None:
        mesh = _ambient_mesh()
    n = mesh.shape[axis]
    S = q.shape[1]
    if S % n:
        raise ValueError(f"seq len {S} not divisible by {axis} size {n}")
    s_local = S // n
    bq = min(block_q, s_local)
    bk = min(block_kv, s_local)
    if s_local % bq or s_local % bk:
        raise ValueError(
            f"local seq {s_local} not divisible by blocks ({bq}, {bk})"
        )

    def local_fn(q, k, v):
        # [B,S/n,H,D] → kernel layout [B,H,S/n,D]
        qt = q.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        out = ring_attention_local(qt, kt, vt, axis, bq, bk)
        return out.transpose(0, 2, 1, 3)

    data = ("dp", "fsdp")
    spec_q = P(data, axis, "tp", None)
    spec_kv = P(data, axis, "tp", None)
    mapped = shard_map_unchecked(
        local_fn, mesh=mesh,
        in_specs=(spec_q, spec_kv, spec_kv),
        out_specs=spec_q,
    )
    return mapped(q, k, v)


def _ambient_mesh() -> Mesh:
    from jax._src import mesh as _mesh_lib

    mesh = _mesh_lib.thread_resources.env.physical_mesh
    if mesh.empty:
        raise ValueError(
            "ring_attention needs a mesh — pass one explicitly or call "
            "inside `with mesh:`"
        )
    return mesh
