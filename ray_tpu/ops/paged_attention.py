"""Paged decode attention — Pallas TPU kernel over a block-table KV cache.

The serving-side attention primitive (no reference counterpart — the
reference's serve layer runs user torch code; this is the TPU analogue
of vLLM-style PagedAttention, cf. PAPERS.md ragged paged attention):
the KV cache lives in fixed-size PAGES owned by a global pool, and each
sequence maps logical positions to physical pages through a block
table.  Decode attention then reads exactly the pages a sequence owns —
memory grows with actual lengths, slots are recycled without copying,
and long-context batches don't pay O(slots × max_len) bandwidth.

Kernel layout (one q token per sequence, GQA):
  q            [B, H, D]        → reshaped [B, KVH, qpg, D]
  k/v pages    [KVH, P, page, D]  (kv-head major: the page block is then
                                   [page, D], which satisfies the TPU
                                   (8,128) tiling constraint)
  block_table  [B, maxp] int32  (physical page per logical page; unused
                                 entries MUST hold a valid id, e.g. 0)
  lengths      [B] int32        (tokens already in cache, incl. current)

Grid (B, maxp): the page axis is innermost-sequential with online
softmax (m, l, acc) in VMEM scratch; every kv head is processed inside
one program (static unroll) — a per-head grid axis would multiply the
program count and the launch overhead dominates at decode sizes.
Block tables + lengths ride the scalar-prefetch channel so the k/v
BlockSpec index maps can chase the indirection
(pltpu.PrefetchScalarGridSpec).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import platform

NEG_INF = -1e30
_MIN_QPG = 8  # sublane floor: pad the per-kv-head q group to 8 rows


def _tp_axis_size(mesh, axis) -> int:
    """Total shard count over ``axis``, which is one mesh axis name or
    a tuple of them (the hybrid serving case, ("dcn_tp", "tp"))."""
    if isinstance(axis, str):
        return mesh.shape.get(axis, 1)
    size = 1
    for a in axis:
        size *= mesh.shape.get(a, 1)
    return size


def _kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, page: int, scale: float,
            soft_cap: Optional[float], kvh: int, qpg_p: int):
    b = pl.program_id(0)
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    @pl.when(p * page < length)
    def _compute():
        for h in range(kvh):  # static unroll: all kv heads, one program
            lo, hi = h * qpg_p, (h + 1) * qpg_p
            q = q_ref[0, h]      # [qpg_p, D]
            k = k_ref[h, 0]      # [page, D]
            s = lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale            # [qpg_p, page]
            if soft_cap is not None:
                s = soft_cap * jnp.tanh(s / soft_cap)
            pos = p * page + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < length, s, NEG_INF)
            m_prev = m_scr[lo:hi]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            probs = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[lo:hi] = (corr * l_scr[lo:hi]
                            + jnp.sum(probs, axis=-1, keepdims=True))
            v = v_ref[h, 0]      # [page, D]
            pv = lax.dot_general(
                probs.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_scr[lo:hi] = acc_scr[lo:hi] * corr + pv
            m_scr[lo:hi] = m_new

    @pl.when(p == n_pages - 1)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        for h in range(kvh):
            lo, hi = h * qpg_p, (h + 1) * qpg_p
            o_ref[0, h] = (acc_scr[lo:hi] / l_safe[lo:hi]).astype(
                o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_table: jax.Array,
    lengths: jax.Array,
    *,
    soft_cap: Optional[float] = None,
) -> jax.Array:
    """q [B, H, D], k/v_pages [KVH, P, page, D], block_table [B, maxp],
    lengths [B] → out [B, H, D]."""
    B, H, D = q.shape
    KVH, P, page, _ = k_pages.shape
    maxp = block_table.shape[1]
    qpg = H // KVH
    qpg_p = max(qpg, _MIN_QPG)
    scale = D ** -0.5

    # [B, KVH, qpg_p, D] with sublane padding for tiny GQA groups.
    qg = q.reshape(B, KVH, qpg, D)
    if qpg_p != qpg:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, qpg_p - qpg), (0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_table, lengths
        grid=(B, maxp),
        in_specs=[
            pl.BlockSpec((1, KVH, qpg_p, D),
                         lambda b, p, bt, ln: (b, 0, 0, 0)),
            # Clamp the page index: unallocated block-table entries
            # hold an OOB sentinel (== P); their grid cells are
            # compute-masked (p*page >= length) but the BlockSpec DMA
            # still runs, so the fetch must stay in bounds.
            pl.BlockSpec((KVH, 1, page, D),
                         lambda b, p, bt, ln: (
                             0, jnp.minimum(bt[b, p], P - 1), 0, 0)),
            pl.BlockSpec((KVH, 1, page, D),
                         lambda b, p, bt, ln: (
                             0, jnp.minimum(bt[b, p], P - 1), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KVH, qpg_p, D),
                               lambda b, p, bt, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KVH * qpg_p, 1), jnp.float32),
            pltpu.VMEM((KVH * qpg_p, 1), jnp.float32),
            pltpu.VMEM((KVH * qpg_p, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, page=page, scale=scale,
                          soft_cap=soft_cap, kvh=KVH, qpg_p=qpg_p),
        name="paged_attention_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, qpg_p, D), q.dtype),
        interpret=platform.interpret_mode(),
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, k_pages, v_pages)
    return out[:, :, :qpg, :].reshape(B, H, D)


def _kernel_partial(*refs, page: int, scale: float,
                    soft_cap: Optional[float], kvh: int, qpg_p: int,
                    pages_per_cell: int = 1, quantized: bool = False):
    """Layered flash partials: UNNORMALIZED accumulator + running max
    and denominator per (kv-head, q row) — the caller folds in the
    current token's self-attention term and normalizes.  The pools are
    strictly read-only here, which is what lets the decode scan carry
    them without XLA cloning the multi-GB buffers.

    ``pages_per_cell`` G > 1 statically unrolls G pages per grid cell,
    each its own BlockSpec'd input: the per-cell fixed cost (DMA setup,
    sequential grid step) dominated decode at wide block tables, so
    fewer, fatter cells win.

    ``quantized``: the pools are INT8 with one f32 scale per physical
    page riding the scalar-prefetch channel (SMEM); true values are
    ``k_int8 * k_scale[page]``.  The scale folds into the score matrix
    after the q·k dot and into the accumulator after probs·v, so HBM
    moves only int8 bytes.  int8→bf16 conversion is exact (|x| ≤ 127),
    keeping the dots on the MXU in bf16 like the unquantized path."""
    G = pages_per_cell
    if quantized:
        (bt_ref, len_ref, _ly_ref, ks_ref, vs_ref), rest = \
            refs[:5], refs[5:]
    else:
        (bt_ref, len_ref, _ly_ref), rest = refs[:3], refs[3:]
        ks_ref = vs_ref = None
    q_ref = rest[0]
    k_refs = rest[1:1 + G]
    v_refs = rest[1 + G:1 + 2 * G]
    acc_ref, m_ref, l_ref = rest[1 + 2 * G:4 + 2 * G]
    m_scr, l_scr, acc_scr = rest[4 + 2 * G:]

    b = pl.program_id(0)
    pc = pl.program_id(1)
    n_cells = pl.num_programs(1)

    @pl.when(pc == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    last = jnp.maximum(length - 1, 0) // page

    for g in range(G):
        p = pc * G + g

        @pl.when(p * page < length)
        def _compute(p=p, k_ref=k_refs[g], v_ref=v_refs[g]):
            if quantized:
                pid = bt_ref[b, jnp.minimum(p, last)]
            for h in range(kvh):
                lo, hi = h * qpg_p, (h + 1) * qpg_p
                q = q_ref[0, h]
                k = k_ref[0, h, 0]
                s = lax.dot_general(
                    q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale
                if quantized:
                    s = s * ks_ref[pid, h]
                if soft_cap is not None:
                    s = soft_cap * jnp.tanh(s / soft_cap)
                pos = p * page + lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                s = jnp.where(pos < length, s, NEG_INF)
                m_prev = m_scr[lo:hi]
                m_new = jnp.maximum(
                    m_prev, jnp.max(s, axis=-1, keepdims=True))
                probs = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_scr[lo:hi] = (corr * l_scr[lo:hi]
                                + jnp.sum(probs, axis=-1, keepdims=True))
                v = v_ref[0, h, 0]
                vd = v.astype(q.dtype) if quantized else v
                pv = lax.dot_general(
                    probs.astype(vd.dtype), vd, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                if quantized:
                    pv = pv * vs_ref[pid, h]
                acc_scr[lo:hi] = acc_scr[lo:hi] * corr + pv
                m_scr[lo:hi] = m_new

    @pl.when(pc == n_cells - 1)
    def _finalize():
        for h in range(kvh):
            lo, hi = h * qpg_p, (h + 1) * qpg_p
            acc_ref[0, h] = acc_scr[lo:hi]
            m_ref[0, h] = m_scr[lo:hi]
            l_ref[0, h] = l_scr[lo:hi]


def paged_decode_attention_partial(
    q: jax.Array,
    k_pools: jax.Array,
    v_pools: jax.Array,
    layer: jax.Array,
    block_table: jax.Array,
    lengths: jax.Array,
    *,
    soft_cap: Optional[float] = None,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    pages_per_cell: Optional[int] = None,
):
    """Read-only layered attention over PAST tokens only:
    q [B, H, D], pools [L, KVH, P, page, D], lengths = tokens already
    in the cache → (acc [B, H, D] f32 unnormalized, m [B, H, 1],
    l [B, H, 1]).  Combine with the new token's self term via
    ``combine_with_self``.

    INT8 pools: pass ``k_scales``/``v_scales`` [L, P, KVH, 1] (one f32
    scale per physical page per kv head); they ride the
    scalar-prefetch channel per layer.  ``pages_per_cell`` batches G
    pages into one grid cell (default: up to 4) to amortize per-cell
    fixed cost."""
    B, H, D = q.shape
    L, KVH, P, page, _ = k_pools.shape
    maxp = block_table.shape[1]
    qpg = H // KVH
    qpg_p = max(qpg, _MIN_QPG)
    scale = D ** -0.5
    quantized = k_scales is not None
    G = pages_per_cell or min(4, maxp)
    while maxp % G:
        G -= 1
    cells = maxp // G

    qg = q.reshape(B, KVH, qpg, D)
    if qpg_p != qpg:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, qpg_p - qpg), (0, 0)))

    n_pre = 5 if quantized else 3

    def page_map_g(g):
        def page_map(b, pc, bt, ln, ly, *scales):
            # Pages past the sequence's last used page repeat that
            # page: consecutive identical block indices make Mosaic
            # skip the DMA, so a short stream in a wide block-table
            # row fetches its ~3 live pages, not all maxp (the full
            # sweep was ~8 ms/step of dead HBM traffic at 8B).
            last = jnp.maximum(ln[b] - 1, 0) // page
            pe = jnp.minimum(pc * G + g, last)
            return (ly[0], 0, jnp.minimum(bt[b, pe], P - 1), 0, 0)

        return page_map

    def q_map(b, pc, *args):
        return (b, 0, 0, 0)

    kv_spec = [pl.BlockSpec((1, KVH, 1, page, D), page_map_g(g))
               for g in range(G)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pre,
        grid=(B, cells),
        in_specs=[pl.BlockSpec((1, KVH, qpg_p, D), q_map)]
        + kv_spec + kv_spec,
        out_specs=[
            pl.BlockSpec((1, KVH, qpg_p, D), q_map),
            pl.BlockSpec((1, KVH, qpg_p, 1), q_map),
            pl.BlockSpec((1, KVH, qpg_p, 1), q_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((KVH * qpg_p, 1), jnp.float32),
            pltpu.VMEM((KVH * qpg_p, 1), jnp.float32),
            pltpu.VMEM((KVH * qpg_p, D), jnp.float32),
        ],
    )
    ly = jnp.asarray(layer, jnp.int32).reshape(1)
    prefetch = [block_table.astype(jnp.int32), lengths.astype(jnp.int32),
                ly]
    if quantized:
        # Per-layer scale tables land in SMEM: [P, KVH] f32, ~12 KB at
        # 8B shapes (scales are page-major [L, P, KVH, 1]).
        ly_s = jnp.asarray(layer, jnp.int32)
        prefetch += [k_scales[ly_s, :, :, 0], v_scales[ly_s, :, :, 0]]
    acc, m, l = pl.pallas_call(
        functools.partial(_kernel_partial, page=page, scale=scale,
                          soft_cap=soft_cap, kvh=KVH, qpg_p=qpg_p,
                          pages_per_cell=G, quantized=quantized),
        name="paged_attention_partial",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, KVH, qpg_p, D), jnp.float32),
            jax.ShapeDtypeStruct((B, KVH, qpg_p, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, KVH, qpg_p, 1), jnp.float32),
        ],
        interpret=platform.interpret_mode(),
    )(*prefetch, qg, *([k_pools] * G), *([v_pools] * G))
    acc = acc[:, :, :qpg, :].reshape(B, H, D)
    m = m[:, :, :qpg, :].reshape(B, H, 1)
    l = l[:, :, :qpg, :].reshape(B, H, 1)
    return acc, m, l


def combine_with_self(q, k_new, v_new, acc, m, l, *,
                      scale: Optional[float] = None,
                      soft_cap: Optional[float] = None) -> jax.Array:
    """Fold the CURRENT token's self-attention into flash partials:
    q [B, H, D], k_new/v_new [B, KVH, D] (GQA-expanded here),
    (acc, m, l) from paged_decode_attention_partial → out [B, H, D]."""
    B, H, D = q.shape
    KVH = k_new.shape[1]
    group = H // KVH
    kx = jnp.repeat(k_new, group, axis=1).astype(jnp.float32)
    vx = jnp.repeat(v_new, group, axis=1).astype(jnp.float32)
    scale = scale if scale is not None else D ** -0.5
    s = jnp.sum(q.astype(jnp.float32) * kx, axis=-1,
                keepdims=True) * scale                       # [B, H, 1]
    if soft_cap is not None:
        s = soft_cap * jnp.tanh(s / soft_cap)
    m_new = jnp.maximum(m, s)
    corr = jnp.exp(m - m_new)
    p_self = jnp.exp(s - m_new)
    out = (acc * corr + p_self * vx) / (l * corr + p_self)
    return out.astype(q.dtype)


def _append_kernel(pids_ref, offs_ref, knew_ref, vnew_ref,
                   kin_ref, vin_ref, kout_ref, vout_ref):
    b = pl.program_id(0)
    # Masked FULL-page overwrite of the appended row (copy-through +
    # where-select): dynamic single-row stores land in the sublane
    # dim, which Mosaic requires 8-aligned — the iota select sidesteps
    # that.  knew arrives pre-broadcast to the page shape (built
    # outside; Mosaic rejects in-kernel rank-ups).  Sentinel slots
    # write garbage into the dedicated SCRATCH page (never a live
    # page), so no grid cell can clobber another's append.
    off = offs_ref[b]
    cur_k = kin_ref[...]
    cur_v = vin_ref[...]
    rows = lax.broadcasted_iota(jnp.int32, cur_k.shape, 3)
    kout_ref[...] = jnp.where(rows == off, knew_ref[0], cur_k)
    vout_ref[...] = jnp.where(rows == off, vnew_ref[0], cur_v)


def _append_kernel_q(pids_ref, offs_ref, knew_ref, vnew_ref,
                     kin_ref, vin_ref, ksin_ref, vsin_ref,
                     kout_ref, vout_ref, ksout_ref, vsout_ref,
                     sm_scr, *, kvh: int):
    """INT8 append with per-page scales: if the new row fits the page's
    current scale, only the row is (re)written; if it doesn't, the
    scale grows to fit and the page requantizes IN VMEM — the
    copy-through already has the whole page resident, so growing costs
    no extra HBM traffic, and while the scale is stable the stored
    int8 values are never touched (no cumulative requant error).

    A write at page offset 0 means the page is starting FRESH (decode
    fills pages sequentially): the scale RESETS to the new row's own
    and the stale occupant's data is zeroed — recycled pages must not
    inherit the previous request's (only-ever-growing) scale."""
    b = pl.program_id(0)
    off = offs_ref[b]

    for h in range(kvh):
        for (new_r, in_r, sc_in, out_r, sc_out) in (
                (knew_ref, kin_ref, ksin_ref, kout_ref, ksout_ref),
                (vnew_ref, vin_ref, vsin_ref, vout_ref, vsout_ref)):
            row = new_r[0, 0, h, 0]                 # [page, D] bf16,
            cur = in_r[0, h, 0]                     # rows identical
            # Vector→scalar via SMEM round-trip (Mosaic cannot
            # broadcast a (1,1) VECTOR to both sublanes and lanes;
            # true SREG scalars splat fine).
            sm_scr[0, 0] = jnp.sum(sc_in[0, 0, h:h + 1, 0:1])
            sm_scr[1, 0] = jnp.max(jnp.abs(row.astype(jnp.float32)))
            old_scale = sm_scr[0, 0]
            needed = sm_scr[1, 0] / 127.0
            fresh = off == 0
            new_scale = jnp.where(fresh, needed,
                                  jnp.maximum(old_scale, needed))
            safe = jnp.where(new_scale == 0.0, 1.0, new_scale)
            factor = jnp.where(fresh, 0.0,
                               jnp.where(new_scale > old_scale,
                                         old_scale / safe, 1.0))
            requant = jnp.round(cur.astype(jnp.float32) * factor)
            row_q = jnp.clip(
                jnp.round(row.astype(jnp.float32) * (1.0 / safe)),
                -127, 127)
            rows = lax.broadcasted_iota(jnp.int32, cur.shape, 0)
            out = jnp.where(rows == off, row_q, requant)
            out_r[0, h, 0] = jnp.clip(out, -127, 127).astype(
                out_r.dtype)
            sc_out[0, 0, h:h + 1, 0:1] = jnp.full((1, 1), new_scale,
                                                  sc_out.dtype)


def paged_append_quantized(k_pools, v_pools, k_scales, v_scales,
                           k_new, v_new, pids, offs):
    """In-place int8 append for every layer at once: pools int8
    [L, KVH, P, page, D], scales f32 [L, P, KVH, 1] (page-major so a
    cell's scale block is one page's column — a shape Mosaic tiles),
    k_new/v_new [L, B, KVH, D] bf16.  Same aliasing contract as
    paged_append."""
    L, KVH, P, page, D = k_pools.shape
    B = pids.shape[0]
    knew = jnp.broadcast_to(
        k_new.transpose(1, 0, 2, 3)[:, :, :, None, None, :],
        (B, L, KVH, 1, page, D))
    vnew = jnp.broadcast_to(
        v_new.transpose(1, 0, 2, 3)[:, :, :, None, None, :],
        (B, L, KVH, 1, page, D))

    def pool_map(b, l, pi, of):
        return (l, 0, jnp.minimum(pi[b], P - 1), 0, 0)

    def scale_map(b, l, pi, of):
        return (l, jnp.minimum(pi[b], P - 1), 0, 0)

    new_map = lambda b, l, pi, of: (b, l, 0, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # pids, offs
        grid=(B, L),
        in_specs=[
            pl.BlockSpec((1, 1, KVH, 1, page, D), new_map),
            pl.BlockSpec((1, 1, KVH, 1, page, D), new_map),
            pl.BlockSpec((1, KVH, 1, page, D), pool_map),
            pl.BlockSpec((1, KVH, 1, page, D), pool_map),
            pl.BlockSpec((1, 1, KVH, 1), scale_map),
            pl.BlockSpec((1, 1, KVH, 1), scale_map),
        ],
        out_specs=[
            pl.BlockSpec((1, KVH, 1, page, D), pool_map),
            pl.BlockSpec((1, KVH, 1, page, D), pool_map),
            pl.BlockSpec((1, 1, KVH, 1), scale_map),
            pl.BlockSpec((1, 1, KVH, 1), scale_map),
        ],
        scratch_shapes=[pltpu.SMEM((2, 1), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_append_kernel_q, kvh=KVH),
        name="paged_attention_kv_append",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k_pools.shape, k_pools.dtype),
            jax.ShapeDtypeStruct(v_pools.shape, v_pools.dtype),
            jax.ShapeDtypeStruct(k_scales.shape, k_scales.dtype),
            jax.ShapeDtypeStruct(v_scales.shape, v_scales.dtype),
        ],
        # Scalar-prefetch args first: pids=0, offs=1, knew=2, vnew=3,
        # k_pools=4, v_pools=5, k_scales=6, v_scales=7.
        input_output_aliases={4: 0, 5: 1, 6: 2, 7: 3},
        interpret=platform.interpret_mode(),
    )(pids.astype(jnp.int32), offs.astype(jnp.int32), knew, vnew,
      k_pools, v_pools, k_scales, v_scales)


def paged_append(k_pools: jax.Array, v_pools: jax.Array,
                 k_new: jax.Array, v_new: jax.Array,
                 pids: jax.Array, offs: jax.Array):
    """In-place append of one token per slot into the page pools, for
    EVERY layer at once: pools [L, KVH, P, page, D],
    k_new/v_new [L, B, KVH, D], pids/offs [B] (pids == P → skip, the
    OOB convention for inactive slots).  Uses pallas
    ``input_output_aliases`` so the multi-GB pools update in place —
    the jnp scatter equivalents kept making XLA clone the pools inside
    the decode loop."""
    L, KVH, P, page, D = k_pools.shape
    B = pids.shape[0]
    # Pre-broadcast the new rows to the page-block shape (tiny: one
    # page column per slot) so the kernel's masked write needs no
    # in-kernel reshape/broadcast.
    knew = jnp.broadcast_to(
        k_new.transpose(1, 0, 2, 3)[:, :, :, None, None, :],
        (B, L, KVH, 1, page, D))
    vnew = jnp.broadcast_to(
        v_new.transpose(1, 0, 2, 3)[:, :, :, None, None, :],
        (B, L, KVH, 1, page, D))

    # Grid over (slot, layer): one page column per cell keeps VMEM use
    # at ~6 x page-block (a whole-L block was 32 MB and blew the 16 MB
    # scoped-vmem budget at 8B).
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # pids, offs
        grid=(B, L),
        in_specs=[
            pl.BlockSpec((1, 1, KVH, 1, page, D),
                         lambda b, l, pi, of: (b, l, 0, 0, 0, 0)),
            pl.BlockSpec((1, 1, KVH, 1, page, D),
                         lambda b, l, pi, of: (b, l, 0, 0, 0, 0)),
            pl.BlockSpec((1, KVH, 1, page, D),
                         lambda b, l, pi, of: (
                             l, 0, jnp.minimum(pi[b], P - 1), 0, 0)),
            pl.BlockSpec((1, KVH, 1, page, D),
                         lambda b, l, pi, of: (
                             l, 0, jnp.minimum(pi[b], P - 1), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, KVH, 1, page, D),
                         lambda b, l, pi, of: (
                             l, 0, jnp.minimum(pi[b], P - 1), 0, 0)),
            pl.BlockSpec((1, KVH, 1, page, D),
                         lambda b, l, pi, of: (
                             l, 0, jnp.minimum(pi[b], P - 1), 0, 0)),
        ],
    )
    return pl.pallas_call(
        _append_kernel,
        name="paged_attention_kv_append",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k_pools.shape, k_pools.dtype),
            jax.ShapeDtypeStruct(v_pools.shape, v_pools.dtype),
        ],
        # Inputs count scalar-prefetch args first: pids=0, offs=1,
        # knew=2, vnew=3, k_pools=4, v_pools=5.
        input_output_aliases={4: 0, 5: 1},
        interpret=platform.interpret_mode(),
    )(pids.astype(jnp.int32), offs.astype(jnp.int32), knew, vnew,
      k_pools, v_pools)


def paged_append_tp(k_pools, v_pools, k_new, v_new, pids, offs, *,
                    axis: str = "tp"):
    """paged_append under tensor parallelism (pools + new rows sharded
    on KVH; per-shard appends are independent)."""
    from ray_tpu.ops.ring_attention import _ambient_mesh

    try:
        mesh = _ambient_mesh()
    except Exception:
        mesh = None
    if mesh is None or _tp_axis_size(mesh, axis) == 1:
        return paged_append(k_pools, v_pools, k_new, v_new, pids, offs)
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import shard_map_unchecked

    mapped = shard_map_unchecked(
        paged_append,
        mesh=mesh,
        in_specs=(P(None, axis), P(None, axis),
                  P(None, None, axis), P(None, None, axis), P(), P()),
        out_specs=(P(None, axis), P(None, axis)),
    )
    return mapped(k_pools, v_pools, k_new, v_new, pids, offs)


def paged_append_quantized_tp(k_pools, v_pools, k_scales, v_scales,
                              k_new, v_new, pids, offs, *,
                              axis: str = "tp"):
    """paged_append_quantized under tensor parallelism (pools, scales
    and new rows sharded on KVH; per-shard appends are independent)."""
    from ray_tpu.ops.ring_attention import _ambient_mesh

    try:
        mesh = _ambient_mesh()
    except Exception:
        mesh = None
    if mesh is None or _tp_axis_size(mesh, axis) == 1:
        return paged_append_quantized(k_pools, v_pools, k_scales,
                                      v_scales, k_new, v_new, pids, offs)
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import shard_map_unchecked

    mapped = shard_map_unchecked(
        paged_append_quantized,
        mesh=mesh,
        in_specs=(P(None, axis), P(None, axis),
                  P(None, None, axis), P(None, None, axis),
                  P(None, None, axis), P(None, None, axis), P(), P()),
        out_specs=(P(None, axis), P(None, axis),
                   P(None, None, axis), P(None, None, axis)),
    )
    return mapped(k_pools, v_pools, k_scales, v_scales, k_new, v_new,
                  pids, offs)


def paged_decode_attention_partial_tp(
    q, k_pools, v_pools, layer, block_table, lengths, *,
    soft_cap: Optional[float] = None, axis: str = "tp",
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
):
    """Partial layered kernel under tensor parallelism (heads/KVH
    sharded; partials come back sharded on H — the combine is local)."""
    from ray_tpu.ops.ring_attention import _ambient_mesh

    try:
        mesh = _ambient_mesh()
    except Exception:
        mesh = None
    if mesh is None or _tp_axis_size(mesh, axis) == 1:
        return paged_decode_attention_partial(
            q, k_pools, v_pools, layer, block_table, lengths,
            soft_cap=soft_cap, k_scales=k_scales, v_scales=v_scales)
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import shard_map_unchecked

    if k_scales is None:
        mapped = shard_map_unchecked(
            lambda qq, kk, vv, ly, bt, ln:
            paged_decode_attention_partial(
                qq, kk, vv, ly, bt, ln, soft_cap=soft_cap),
            mesh=mesh,
            in_specs=(P(None, axis, None), P(None, axis), P(None, axis),
                      P(), P(), P()),
            out_specs=(P(None, axis, None), P(None, axis, None),
                       P(None, axis, None)),
        )
        return mapped(q, k_pools, v_pools, layer, block_table, lengths)
    mapped = shard_map_unchecked(
        lambda qq, kk, vv, ks, vs, ly, bt, ln:
        paged_decode_attention_partial(
            qq, kk, vv, ly, bt, ln, soft_cap=soft_cap,
            k_scales=ks, v_scales=vs),
        mesh=mesh,
        in_specs=(P(None, axis, None), P(None, axis), P(None, axis),
                  P(None, None, axis), P(None, None, axis),
                  P(), P(), P()),
        out_specs=(P(None, axis, None), P(None, axis, None),
                   P(None, axis, None)),
    )
    return mapped(q, k_pools, v_pools, k_scales, v_scales, layer,
                  block_table, lengths)


def paged_decode_attention_reference(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_table: jax.Array,
    lengths: jax.Array,
    *,
    soft_cap: Optional[float] = None,
) -> jax.Array:
    """Dense einsum reference: gather pages into [B, maxp*page, KVH, D]
    then masked attention — for tests and as the CPU fallback."""
    B, H, D = q.shape
    KVH, P, page, _ = k_pages.shape
    maxp = block_table.shape[1]
    k = k_pages[:, block_table]  # [KVH, B, maxp, page, D]
    v = v_pages[:, block_table]
    k = k.transpose(1, 2, 3, 0, 4).reshape(B, maxp * page, KVH, D)
    v = v.transpose(1, 2, 3, 0, 4).reshape(B, maxp * page, KVH, D)
    group = H // KVH
    kx = jnp.repeat(k, group, axis=2)
    vx = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                   kx.astype(jnp.float32)) * (D ** -0.5)
    if soft_cap is not None:
        s = soft_cap * jnp.tanh(s / soft_cap)
    ki = jnp.arange(maxp * page)[None, None, :]
    s = jnp.where(ki < lengths[:, None, None], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", probs, vx.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_decode_attention_tp(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_table: jax.Array,
    lengths: jax.Array,
    *,
    soft_cap: Optional[float] = None,
    axis: str = "tp",
) -> jax.Array:
    """Tensor-parallel paged attention: heads are embarrassingly
    parallel, so the pallas kernel runs per shard inside shard_map over
    the ambient mesh's ``axis`` — q sharded on H, pages on KVH, block
    tables/lengths replicated, NO collectives (the surrounding
    projections carry the psum under GSPMD).  Falls back to the plain
    kernel when no mesh (or a size-1 axis) is ambient, so model code
    can call this unconditionally under cfg.tensor_parallel."""
    from ray_tpu.ops.ring_attention import _ambient_mesh

    try:
        mesh = _ambient_mesh()
    except Exception:
        mesh = None
    if mesh is None or _tp_axis_size(mesh, axis) == 1:
        return paged_decode_attention(q, k_pages, v_pages, block_table,
                                      lengths, soft_cap=soft_cap)
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import shard_map_unchecked

    mapped = shard_map_unchecked(
        lambda qq, kk, vv, bt, ln: paged_decode_attention(
            qq, kk, vv, bt, ln, soft_cap=soft_cap),
        mesh=mesh,
        in_specs=(P(None, axis, None), P(axis), P(axis), P(), P()),
        out_specs=P(None, axis, None),
    )
    return mapped(q, k_pages, v_pages, block_table, lengths)
