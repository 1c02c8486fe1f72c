"""Attention ops.

The XLA einsum path below is the portable reference; the Pallas flash
kernel (ray_tpu/ops/flash_attention.py) overrides it on TPU for long
sequences.  No reference counterpart exists — the reference delegates
attention to user frameworks (see SURVEY.md §5.7); on TPU it is a core
op of this framework.

Conventions: q [B, S, H, D], k/v [B, S, KVH, D] with H a multiple of
KVH (grouped-query attention).  Masks are causal and/or segment-based
(packed sequences).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import platform


def _gqa_expand(k: jax.Array, groups: int) -> jax.Array:
    if groups == 1:
        return k
    return jnp.repeat(k, groups, axis=2)


def flash_partition_specs(mesh_shape, batch: int, heads: int, kv_heads: int):
    """(q spec, k/v spec) that split [B, S, H, D] attention over a mesh
    of ``mesh_shape`` (axis name -> size): batch over the data axes and
    heads over the tensor axes, by the rule table every other array
    follows (parallel/sharding.DEFAULT_RULES).  A dimension its axes do
    not divide stays whole, which costs an all-gather and nothing else;
    the sequence is never split here (that is ring attention's job)."""
    from ray_tpu.parallel.sharding import spec_for

    mesh_axes = frozenset(mesh_shape)

    def entry(logical, *dims):
        axes = spec_for((logical,), mesh_axes=mesh_axes)[0]
        names = (axes,) if isinstance(axes, str) else tuple(axes or ())
        size = 1
        for a in names:
            size *= mesh_shape[a]
        return axes if all(d % size == 0 for d in dims) else None

    b = entry("batch", batch)
    h = entry("heads", heads, kv_heads)
    return P(b, None, h, None), P(b, None, h, None)


def _flash_over_mesh(q, k, v):
    """The flash kernel, entered per shard.  Mosaic kernels cannot be
    partitioned by GSPMD, so under a mesh of more than one device the
    call goes through shard_map over the ambient mesh (the pattern of
    ops/paged_attention.paged_decode_attention_tp); attention is
    independent per (batch row, head), so no collective is needed.
    With no mesh, or already inside a shard_map body, it is a plain
    call."""
    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.parallel.mesh import shard_map_unchecked
    from ray_tpu.parallel.sharding import current_mesh

    mesh = current_mesh()
    if mesh is None or mesh.size == 1 or getattr(mesh, "manual_axes", ()):
        return flash_attention(q, k, v, causal=True)
    q_spec, kv_spec = flash_partition_specs(
        dict(mesh.shape), q.shape[0], q.shape[2], k.shape[2])
    mapped = shard_map_unchecked(
        partial(flash_attention, causal=True), mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec), out_specs=q_spec)
    return mapped(q, k, v)


def _flash_eligible(q, k, causal, segment_ids, logits_soft_cap) -> bool:
    from ray_tpu.ops.flash_attention import DEFAULT_BLOCK_KV, DEFAULT_BLOCK_Q

    B, S, H, D = q.shape
    # must mirror flash_attention's own validation: blocks clamp to S
    bq = min(DEFAULT_BLOCK_Q, S)
    bk = min(DEFAULT_BLOCK_KV, S)
    return (
        causal
        and segment_ids is None
        and logits_soft_cap is None
        and k.shape[1] == S  # no decode-offset (k longer than q) support
        and S % bq == 0
        and S % bk == 0
        and S >= 256
        and H % k.shape[2] == 0
        and not platform.interpret_mode()
    )


@partial(jax.jit, static_argnames=("causal",))
def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    logits_soft_cap: Optional[float] = None,
) -> jax.Array:
    """Softmax attention with GQA and optional packing.

    Dispatches to the Pallas flash kernel on TPU when eligible (causal,
    unpacked, block-divisible seq); otherwise the einsum path below,
    computed in float32 regardless of input dtype.
    """
    if _flash_eligible(q, k, causal, segment_ids, logits_soft_cap):
        return _flash_over_mesh(q, k, v)
    orig_dtype = q.dtype
    *_, n_heads, head_dim = q.shape
    n_kv = k.shape[2]
    groups = n_heads // n_kv
    k = _gqa_expand(k, groups)
    v = _gqa_expand(v, groups)

    scale = head_dim**-0.5
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if logits_soft_cap is not None:
        logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)

    q_len, k_len = logits.shape[-2], logits.shape[-1]
    mask = None
    if causal:
        # offset supports decode: q positions are the last q_len of k_len
        offset = k_len - q_len
        qi = jnp.arange(q_len)[:, None] + offset
        ki = jnp.arange(k_len)[None, :]
        mask = qi >= ki
    if segment_ids is not None:
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]
        seg_mask = seg_mask[:, None, :, :]
        mask = seg_mask if mask is None else (mask[None, None] & seg_mask)
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None, None]
        logits = jnp.where(mask, logits, -1e30)

    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(orig_dtype)
