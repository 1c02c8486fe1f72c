"""Ragged paged attention — one kernel, one batch for mixed
prefill + decode.

Serving used to run TWO device programs per engine loop iteration:
bucketed/chunked prefill and per-slot paged decode.  A long prompt
therefore head-of-line-blocked every running stream for at least a
chunk (the 1B ladder showed TTFT p95 exploding to 50s under prefill
pressure).  Following "Ragged Paged Attention: A High-Performance and
Flexible LLM Inference Kernel for TPU" (PAPERS.md), this module serves
BOTH phases from a single ragged token batch:

    tokens   [T]            one flat buffer of up to ``token_budget``
                            tokens packed from R rows
    rows     (slot, start_pos, num_tokens, buffer_offset) x R
                            decode rows have num_tokens == 1, prefill
                            rows carry a chunk of their prompt

and computes, per layer, causal attention of every packed token
against the shared KV page pool (int8 or bf16) PLUS the intra-row
causal self attention among the row's own fresh tokens — the part of
the context that is not in the pool yet.  The fresh K/V rides out and
ONE aliased append per step writes every layer's new rows into the
pages (``ragged_paged_append*``), preserving the deferred-append
contract of models/llama.decode_slots_paged: pools are STRICTLY
read-only inside the layer scan (in-loop pool mutation made XLA clone
the multi-GB pools), and the append kernels alias in place.  The append
walks, layer by layer, a list of the pages the step's fresh tokens land
in (``live_append_cells``) and its grid ends where the list ends: a
decode row is one cell a layer and a padding row none, where the grid
used to be ``max_slots x layers x pages-per-row`` whatever the rows
held (PERF.md, PR 31).

Kernel shape (mirrors ops/paged_attention.py's idioms):

  * ``pltpu.PrefetchScalarGridSpec`` carries the row metadata, block
    tables and (int8) page scales on the scalar-prefetch channel so
    BlockSpec index maps can chase pages;
  * the grid walks a LIST of the cells that hold work
    (``live_page_cells``: row r's pooled pages in ascending order, then
    its SELF cell: intra-row causal attention against the fresh k/v
    buffer, which also finalizes the online softmax and writes the
    output rows), under a dynamic bound: a page no row reaches and a
    padding row are no grid step, where the grid used to be ``(R,
    maxp + 1)`` whatever the rows held (PERF.md, PR 36).  The Pallas
    interpreter takes no dynamic bound: there the grid keeps the list's
    capacity and the steps past its end do nothing, the same body;
  * a step makes TWO calls, chosen by ``row_len``: rows of ONE token
    go through a window of that token alone, rows of more through a
    static window [w, w + Cq) of the flat buffer with w aligned down to
    the sublane (8); masks do the raggedness, so rows can start at any
    offset.  Each call's output is defined at its own rows' tokens; a
    call whose list is empty has no grid step;
  * the query heads of one KV head are stacked into the ROWS of one
    product against its page (``[Cq * QP, hd] x [hd, page]``, QP the
    group rounded up to a sublane tile: 24 for Jamba's 20 heads over
    one KV head, 8 for a group of 4), the page read and cast once a
    cell; a cell loops over the KV heads, whose pages arrive together;
  * flash state (m, l, acc) lives in VMEM scratch, per KV head and
    stacked row, reset at a row's first cell;
  * ``fused_ragged_layer``'s attention phase has the same arithmetic in
    ONE call (it is one grid over all phases): the row's path is chosen
    in the cell from ``row_len``, and its window's stack is head-major
    (``qpg`` aligned ``[Cq, hd]`` slices of queries the kernel itself
    laid out by KV head, no pad where ``Cq`` is a multiple of 8).

``fused_ragged_layer`` folds the PR-2 per-layer decode megakernel
(ops/fused_decode.py) over the ragged batch: the same phase-indexed
1-D grid (qkv tiles | attention cells | o-proj | MLP), with the
attention phase iterating (row, page) cells instead of (slot, page) —
so the fused path serves ragged batches too.  That phase walks a list
of the cells that hold the step's rows (``live_page_cells``) and the
grid ends where the list ends: a cell the rows do not reach is no grid
step, where it used to cost 0.6 us in every layer (PERF.md, PR 28).
A cell does a KV head's work once, as the kernel above does (PERF.md,
PR 38): the query heads of a KV head are the rows of one product
against its page, the page read and cast once a KV head, the KV heads
looped inside the cell; a row of ONE token takes that token alone (its
group's heads padded to a sublane tile, gathered at the row's first
cell), a row of more the step's window, ``qpg x Cq`` stacked rows
head-major; the flash state is per KV head and stacked row and is reset
at a row's first cell.  One kernel and one call a layer: the two row
paths are ``pl.when`` bodies chosen by ``row_len`` in the cell, side by
side and never one inside another (nested, the kernel took the chip's
host twice as long to trace, 11 s of a replica's set-up: PERF.md, PR 38).
The append's grid follows a list of its own the same way.  The layer
kernel takes the STACKED layer tree and a layer index: the weights
reach the kernel the way the KV pools do, whole, and each BlockSpec
squeezes the layer axis and picks the layer from the scalar-prefetched
index.  XLA cannot fuse a slice into a Pallas call's operand, so a
slice taken in front of it is a copy of the layer's weights, every
layer of every step (16.6 ms of a 46 ms step at Mistral-7B int8 before
PR 25; PERF.md).

Interpret-mode (CPU) numerics are tier-1 tested against the unfused
paged reference for fp32 / int8-weight / int8-KV
(tests/test_ragged_paged_attention.py); tests/test_mosaic_aot.py
compiles every kernel here for a v5e and chip_smoke.py checks them
against the references on the chip.  Tile tuning is follow-up, as for
ops/fused_decode.py.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import platform
from ray_tpu.ops.fused_decode import (
    _assemble_gateup,
    _assemble_qkv,
    _pick_tile,
    _qdict,
)
from ray_tpu.ops.paged_attention import NEG_INF

# scoped VMEM of the attention calls whose chunk window holds every head's
# float32 state at once (the default, 16 MiB, holds a decode window only)
VMEM_LIMIT = 100 * 1024 * 1024


def _round8(n: int) -> int:
    return max(8, -(-n // 8) * 8)


def window_size(T: int, max_row_tokens: Optional[int]) -> int:
    """Static q-window width: wide enough to hold any row's tokens
    starting at any (8-aligned-down) buffer offset."""
    cap = T if max_row_tokens is None else min(max_row_tokens, T)
    return min(_round8(T), _round8(cap) + 8)


# --------------------------------------------------------------------------
# pure-jax reference (per layer) — the oracle for the Pallas kernel and
# the documentation of the semantics
# --------------------------------------------------------------------------


def ragged_attention_reference(
    q: jax.Array,            # [T, H, D]  RoPE'd queries, flat buffer
    k_new: jax.Array,        # [T, KVH, D] this step's keys (RoPE'd)
    v_new: jax.Array,        # [T, KVH, D]
    k_pages: jax.Array,      # [KVH, P, page, D] one layer's pool
    v_pages: jax.Array,
    row_slot: jax.Array,     # [R] int32
    row_start: jax.Array,    # [R] absolute position of the row's first
                             #     fresh token (== tokens already pooled)
    row_len: jax.Array,      # [R] fresh tokens this step (0 = padding)
    row_off: jax.Array,      # [R] offset of the row in the flat buffer
    block_tables: jax.Array,  # [slots, maxp]
    *,
    soft_cap: Optional[float] = None,
    k_scales: Optional[jax.Array] = None,   # [P, KVH, 1] (int8 pools)
    v_scales: Optional[jax.Array] = None,
) -> jax.Array:
    """Dense gather reference: for each row, attention of its fresh
    tokens over (pooled past) + (intra-row causal fresh), f32 out
    [T, H, D].  Buffer rows not covered by any row come back zero."""
    T, H, D = q.shape
    KVH, P, page, _ = k_pages.shape
    maxp = block_tables.shape[1]
    R = int(row_slot.shape[0])
    group = H // KVH
    out = jnp.zeros((T, H, D), jnp.float32)
    kf = k_pages.astype(jnp.float32)
    vf = v_pages.astype(jnp.float32)
    if k_scales is not None:
        kf = k_pages.astype(jnp.float32) * k_scales.transpose(1, 0, 2)[
            :, :, None, :]
        vf = v_pages.astype(jnp.float32) * v_scales.transpose(1, 0, 2)[
            :, :, None, :]
    for r in range(R):
        slot, start, nt, off = (row_slot[r], row_start[r], row_len[r],
                                row_off[r])
        pages = jnp.clip(block_tables[slot], 0, P - 1)     # [maxp]
        kc = kf[:, pages].transpose(1, 2, 0, 3).reshape(
            maxp * page, KVH, D)                           # [ctx, KVH, D]
        vc = vf[:, pages].transpose(1, 2, 0, 3).reshape(
            maxp * page, KVH, D)
        # fresh rows of THIS row, gathered from the flat buffer
        ti = jnp.arange(T)
        trel = ti - off
        in_row = (trel >= 0) & (trel < nt)
        ctx = maxp * page
        kpos = jnp.arange(ctx)
        qs = q.astype(jnp.float32)
        kx = jnp.repeat(kc, group, axis=1)                 # [ctx, H, D]
        vx = jnp.repeat(vc, group, axis=1)
        s_pool = jnp.einsum("thd,khd->thk", qs, kx) * (D ** -0.5)
        knf = jnp.repeat(k_new.astype(jnp.float32), group, axis=1)
        vnf = jnp.repeat(v_new.astype(jnp.float32), group, axis=1)
        s_self = jnp.einsum("thd,uhd->thu", qs, knf) * (D ** -0.5)
        if soft_cap is not None:
            s_pool = soft_cap * jnp.tanh(s_pool / soft_cap)
            s_self = soft_cap * jnp.tanh(s_self / soft_cap)
        m_pool = in_row[:, None, None] & (kpos < start)[None, None, :]
        urel = ti - off
        key_in_row = (urel >= 0) & (urel < nt)
        m_self = (in_row[:, None, None] & key_in_row[None, None, :]
                  & (urel[None, None, :] <= trel[:, None, None]))
        s = jnp.concatenate(
            [jnp.where(m_pool, s_pool, NEG_INF),
             jnp.where(m_self, s_self, NEG_INF)], axis=-1)
        p = jax.nn.softmax(s, axis=-1)
        o = (jnp.einsum("thk,khd->thd", p[..., :ctx], vx)
             + jnp.einsum("thu,uhd->thd", p[..., ctx:], vnf))
        out = jnp.where(in_row[:, None, None], o, out)
    return out


def ragged_append_reference(
    k_pages: jax.Array,      # [KVH, P, page, D]
    v_pages: jax.Array,
    k_new: jax.Array,        # [T, KVH, D]
    v_new: jax.Array,
    row_slot, row_start, row_len, row_off,
    block_tables: jax.Array,
):
    """Scatter reference for the append: one layer, bf16/f32 pools."""
    T = k_new.shape[0]
    KVH, P, page, D = k_pages.shape
    maxp = block_tables.shape[1]
    R = int(row_slot.shape[0])
    for r in range(R):
        slot, start, nt, off = (row_slot[r], row_start[r], row_len[r],
                                row_off[r])
        ti = jnp.arange(T)
        trel = ti - off
        in_row = (trel >= 0) & (trel < nt)
        pos = start + trel
        pid = jnp.take(jnp.clip(block_tables[slot], 0, P - 1),
                       jnp.clip(pos // page, 0, maxp - 1))
        pid = jnp.where(in_row, pid, P - 1)   # scratch page for pads
        offp = jnp.where(in_row, pos % page, 0)
        k_pages = k_pages.at[:, pid, offp].set(
            jnp.where(in_row[None, :, None],
                      k_new.transpose(1, 0, 2).astype(k_pages.dtype),
                      k_pages[:, pid, offp]))
        v_pages = v_pages.at[:, pid, offp].set(
            jnp.where(in_row[None, :, None],
                      v_new.transpose(1, 0, 2).astype(v_pages.dtype),
                      v_pages[:, pid, offp]))
    return k_pages, v_pages


# --------------------------------------------------------------------------
# the ragged attention kernel
# --------------------------------------------------------------------------


def live_attention_cells(row_start: jax.Array, row_len: jax.Array,
                         row_off: jax.Array, T: int, maxp: int, page: int):
    """What ``ragged_paged_attention``'s two calls walk for these rows,
    one entry a call (rows of ONE token, then rows of more): ``(live_ci,
    n_live, mine)``, the call's ``live_page_cells`` and the buffer
    positions ``bool[round8(T)]`` its rows' tokens stand at.  The same
    in every layer: a step builds it once, in front of its layer loop."""
    row_len = row_len.astype(jnp.int32)
    trel = jnp.arange(_round8(T))[:, None] - row_off[None, :]     # [T, R]
    in_row = (trel >= 0) & (trel < row_len[None, :])
    return tuple(
        live_page_cells(row_start, row_len, maxp, page, takes)
        + (jnp.any(in_row & takes[None, :], axis=1),)
        for takes in (row_len == 1, row_len > 1))


def _ragged_kernel(*refs, T: int, Cq: int, KVH: int, QP: int, hd: int,
                   page: int, Pt: int, maxp: int, scale: float,
                   soft_cap: Optional[float], quantized: bool):
    slot_r, start_r, len_r, off_r, bt_r, _ly_r, live_r, nl_r = refs[:8]
    n_pre = 8
    ks_r = vs_r = None
    if quantized:
        ks_r, vs_r = refs[8:10]
        n_pre = 10
    (q_ref, kn_ref, vn_ref, kp_ref, vp_ref, out_ref,
     m_s, l_s, acc_s) = refs[n_pre:]

    i = pl.program_id(0)
    rows = Cq * QP
    Ck = max(Cq, 8)             # the self cell's keys: a sublane tile

    def capped(s):
        if soft_cap is not None:
            return soft_cap * jnp.tanh(s / soft_cap)
        return s

    # i < n_live always holds under Mosaic, whose grid ends at n_live;
    # the interpreter's grid is the list's capacity.
    @pl.when(i < nl_r[0])
    def _cell():
        ci = live_r[i]
        r = ci // (maxp + 1)
        pc = ci % (maxp + 1)
        start, nt, off = start_r[r], len_r[r], off_r[r]
        # the keys' window starts on a sublane tile; a query window of
        # one token is that token (tokens are the queries' leading axis)
        wk = pl.multiple_of(jnp.minimum((off // 8) * 8, T - Ck), 8)
        w = off if Cq == 1 else wk
        # stacked row j is token j // QP of the window, head j % QP of
        # the KV head's group
        tj = lax.broadcasted_iota(jnp.int32, (Cq, QP, 1), 0).reshape(rows, 1)
        trel = w + tj - off                    # row-relative token index
        valid_q = (trel >= 0) & (trel < nt)    # [rows, 1]

        @pl.when((pc == 0) | (start == 0))
        def _first():
            m_s[...] = jnp.full_like(m_s, NEG_INF)
            l_s[...] = jnp.zeros_like(l_s)
            acc_s[...] = jnp.zeros_like(acc_s)

        def scores(g, keys):
            qg = q_ref[pl.ds(w, Cq), g].astype(jnp.float32).reshape(rows, hd)
            return lax.dot_general(
                qg, keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

        def flash_update(g, s, v, vscale):
            """Masked online-softmax update of KV head g's stacked
            rows; rows whose scores are fully NEG_INF must leave the
            state untouched (the window overlaps NEIGHBOR rows'
            tokens)."""
            m_prev = m_s[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            m_new = jnp.where(valid_q, m_new, m_prev)
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = corr * l_s[g] + jnp.sum(p, axis=-1, keepdims=True)
            pv = lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            if vscale is not None:
                pv = pv * vscale
            a_new = acc_s[g] * corr + pv
            l_s[g] = jnp.where(valid_q, l_new, l_s[g])
            acc_s[g] = jnp.where(valid_q, a_new, acc_s[g])
            m_s[g] = m_new
            return l_new, a_new

        # ---- pool cell: one live page of the row's PAST --------------
        @pl.when(pc < maxp)
        def _pool_cell():
            pid = jnp.minimum(bt_r[slot_r[r], pc], Pt - 1)
            kpos = pc * page + lax.broadcasted_iota(jnp.int32, (1, page), 1)
            mask = valid_q & (kpos < start)
            for g in range(KVH):
                s = scores(g, kp_ref[0, g, 0].astype(jnp.float32))
                if quantized:
                    s = s * ks_r[pid, g]
                flash_update(g, jnp.where(mask, capped(s), NEG_INF),
                             vp_ref[0, g, 0].astype(jnp.float32),
                             vs_r[pid, g] if quantized else None)

        # ---- self cell: intra-row causal attention + finalize --------
        @pl.when(pc == maxp)
        def _self_cell():
            krel = wk + lax.broadcasted_iota(jnp.int32, (1, Ck), 1) - off
            mask = valid_q & (krel >= 0) & (krel < nt) & (krel <= trel)
            for g in range(KVH):
                s = scores(g, kn_ref[g, pl.ds(wk, Ck), :].astype(jnp.float32))
                l_new, a_new = flash_update(
                    g, jnp.where(mask, capped(s), NEG_INF),
                    vn_ref[g, pl.ds(wk, Ck), :].astype(jnp.float32), None)
                o = a_new / jnp.maximum(l_new, 1e-30)
                cur = out_ref[pl.ds(w, Cq), g].reshape(rows, hd)
                out_ref[pl.ds(w, Cq), g] = jnp.where(
                    valid_q, o, cur).reshape(Cq, QP, hd)


def _ragged_call(q, k_new, v_new, k_pools, v_pools, rows, scales, live_ci,
                 n_live, *, Cq: int, soft_cap: Optional[float]):
    """One call: the rows whose cells ``live_ci`` lists, through a window
    of ``Cq`` tokens.  ``q`` [T, KVH, QP, hd], ``k_new`` / ``v_new``
    [KVH, T, hd], ``rows`` the six scalar-prefetched row arrays (slot,
    start, len, off, block tables, layer), ``scales`` the layer's two
    tables of page scales or none; returns float32 like ``q``, defined
    at the tokens of the rows walked and nowhere else."""
    T, KVH, QP, hd = q.shape
    Pt, page = k_pools.shape[2:4]
    maxp = rows[4].shape[1]
    prefetch = rows + [live_ci, n_live] + scales

    def const4(i, *pf):
        return (0, 0, 0, 0)

    def const3(i, *pf):
        return (0, 0, 0)

    def pool_map(i, slot_p, start_p, _ln, _of, bt, ly, live, nl, *sc):
        ci = live[jnp.minimum(i, jnp.maximum(nl[0] - 1, 0))]
        r = ci // (maxp + 1)
        # the self cell repeats the row's last page: no DMA for it
        last = jnp.maximum(start_p[r] - 1, 0) // page
        pe = jnp.minimum(jnp.minimum(ci % (maxp + 1), maxp - 1), last)
        return (ly[0], 0, jnp.minimum(bt[slot_p[r], pe], Pt - 1), 0, 0)

    interpret = platform.interpret_mode()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(live_ci.shape[0] if interpret else n_live[0],),
        in_specs=[
            pl.BlockSpec((T, KVH, QP, hd), const4),
            pl.BlockSpec((KVH, T, hd), const3),
            pl.BlockSpec((KVH, T, hd), const3),
            pl.BlockSpec((1, KVH, 1, page, hd), pool_map),
            pl.BlockSpec((1, KVH, 1, page, hd), pool_map),
        ],
        out_specs=pl.BlockSpec((T, KVH, QP, hd), const4),
        scratch_shapes=[
            pltpu.VMEM((KVH, Cq * QP, 1), jnp.float32),
            pltpu.VMEM((KVH, Cq * QP, 1), jnp.float32),
            pltpu.VMEM((KVH, Cq * QP, hd), jnp.float32),
        ],
    )
    kern = functools.partial(
        _ragged_kernel, T=T, Cq=Cq, KVH=KVH, QP=QP, hd=hd, page=page,
        Pt=Pt, maxp=maxp, scale=hd ** -0.5, soft_cap=soft_cap,
        quantized=bool(scales))
    return pl.pallas_call(
        kern,
        name="ragged_paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, KVH, QP, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*prefetch, q, k_new, v_new, k_pools, v_pools)


def ragged_paged_attention(
    q: jax.Array,            # [T, H, D]
    k_new: jax.Array,        # [T, KVH, D]
    v_new: jax.Array,
    k_pools: jax.Array,      # [L, KVH, P, page, D] (P includes scratch)
    v_pools: jax.Array,
    layer: jax.Array,
    row_slot: jax.Array,     # [R]
    row_start: jax.Array,
    row_len: jax.Array,
    row_off: jax.Array,
    block_tables: jax.Array,  # [slots, maxp]
    *,
    soft_cap: Optional[float] = None,
    k_scales: Optional[jax.Array] = None,   # [L, P, KVH, 1]
    v_scales: Optional[jax.Array] = None,
    max_row_tokens: Optional[int] = None,
    live_cells=None,
) -> jax.Array:
    """Causal attention of a ragged token batch against the page pool
    of ONE layer (selected via scalar-prefetched ``layer``), f32 out
    [T, H, D]; zero at positions no row covers.  Pools are read-only;
    append the fresh K/V afterwards with ragged_paged_append*.  Rows
    must occupy DISTINCT slots (the engine packs at most one row per
    slot per step).

    Two calls, chosen by ``row_len``: rows of ONE token through a window
    of that token alone, rows of more through the step's window.  Each
    walks ``live_cells``' list of its rows' cells (``live_attention_
    cells``; a caller with a layer loop builds them once in front of it,
    None builds them here) and its grid ends where the list ends."""
    T, H, hd = q.shape
    KVH = k_pools.shape[1]
    maxp = block_tables.shape[1]
    qpg = H // KVH
    QP = _round8(qpg)
    T_p = _round8(T)
    if live_cells is None:
        live_cells = live_attention_cells(
            row_start, row_len, row_off, T, maxp, k_pools.shape[3])
    # the query heads of one KV head are the rows of one product against
    # its page: [T, KVH, QP, hd], a token's group padded to a sublane
    # tile so that a window's stacked rows are a view of it
    q = jnp.pad(q.reshape(T, KVH, qpg, hd),
                ((0, T_p - T), (0, 0), (0, QP - qpg), (0, 0)))
    k_new, v_new = (jnp.pad(a, ((0, T_p - T), (0, 0), (0, 0))
                            ).transpose(1, 0, 2) for a in (k_new, v_new))
    ly = jnp.asarray(layer, jnp.int32)
    rows = [row_slot.astype(jnp.int32), row_start.astype(jnp.int32),
            row_len.astype(jnp.int32), row_off.astype(jnp.int32),
            block_tables.astype(jnp.int32), ly.reshape(1)]
    scales = ([] if k_scales is None
              else [k_scales[ly, :, :, 0], v_scales[ly, :, :, 0]])
    out = jnp.zeros((T_p, KVH, QP, hd), jnp.float32)
    # each call's output is defined at its own rows' tokens and nowhere
    # else (a call with no row writes nothing)
    for Cq, (live_ci, n_live, mine) in zip(
            (1, window_size(T_p, max_row_tokens)), live_cells):
        got = _ragged_call(q, k_new, v_new, k_pools, v_pools, rows, scales,
                           live_ci, n_live, Cq=Cq, soft_cap=soft_cap)
        out = jnp.where(mine[:, None, None, None], got, out)
    return out[:T, :, :qpg].reshape(T, H, hd)


# --------------------------------------------------------------------------
# ragged append — all layers at once, in place
# --------------------------------------------------------------------------


def _pages_per_row(max_row_tokens: int, page: int) -> int:
    """Static bound on pages one row's fresh tokens can touch."""
    return (max_row_tokens + page - 2) // page + 1


def _listed(live: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The set cells of a boolean table as a scalar-prefetch list: their
    flat indices in ascending order ``int32[live.size]`` (past the count
    it is padding) and the count ``int32[1]``."""
    flat = live.reshape(-1)
    (cells,) = jnp.nonzero(flat, size=flat.shape[0], fill_value=0)
    return (cells.astype(jnp.int32),
            jnp.sum(flat, dtype=jnp.int32).reshape(1))


def live_append_cells(row_start: jax.Array, row_len: jax.Array, npr: int,
                      page: int) -> Tuple[jax.Array, jax.Array]:
    """The cells of the ragged append that write a page for these rows:
    ``(live_ci, n_live)``.  Cell ``r * npr + j`` is the ``j``-th page
    row ``r``'s fresh tokens touch, counted from the page its first one
    lands in; it is live where the row has tokens and the page is not
    past the one its last token lands in.  No two live cells name one
    page.  Like ``live_page_cells`` it follows from the row arrays
    alone, so it is the same in every layer."""
    j = jnp.arange(npr, dtype=jnp.int32)
    first = row_start // page
    last = (row_start + row_len - 1) // page
    return _listed((row_len[:, None] > 0)
                   & (first[:, None] + j <= last[:, None]))


def append_cell_count(row_start, row_len, page: int) -> int:
    """``live_append_cells``' ``n_live`` on the host, from the packed
    row arrays: the pages each live row's fresh tokens touch."""
    start, nlen = np.asarray(row_start), np.asarray(row_len)
    return int(np.sum((nlen > 0) * (
        (start + nlen - 1) // page - start // page + 1)))


def _ragged_append_kernel(*refs, T: int, Cq: int, KVH: int, page: int,
                          NPR: int, quantized: bool):
    _slot_r, start_r, len_r, off_r, _bt_r, live_r, n_live_r = refs[:7]
    if quantized:
        (kn_ref, vn_ref, kp_ref, vp_ref, ks_ref, vs_ref,
         kp_out, vp_out, ks_out, vs_out) = refs[7:]
    else:
        kn_ref, vn_ref, kp_ref, vp_ref, kp_out, vp_out = refs[7:]

    i = pl.program_id(1)

    # i < n_live always holds under Mosaic, whose grid ends at n_live;
    # the interpreter's grid is the capacity (see ``_ragged_append``).
    @pl.when(i < n_live_r[0])
    def _cell():
        ci = live_r[i]
        r = ci // NPR
        j = ci % NPR
        start = start_r[r]
        nt = len_r[r]
        off = off_r[r]
        w = jnp.minimum((off // 8) * 8, T - Cq)
        w = pl.multiple_of(w, 8)

        sp = start // page
        pg = sp + j
        base = pg * page
        live = (base < start + nt) & (nt > 0)
        rows_i = lax.broadcasted_iota(jnp.int32, (page, 1), 0)
        tpage = base + rows_i - start          # token index landing here
        mask_w = (tpage >= 0) & (tpage < nt) & live          # [page, 1]
        cols = lax.broadcasted_iota(jnp.int32, (1, Cq), 1)
        krel = w + cols - off                  # window col → token index
        # one-hot gather: page row i takes window col c with token
        # tpage[i]
        oh = ((tpage == krel) & (krel >= 0) & (krel < nt)
              & live).astype(jnp.float32)      # [page, Cq]

        for h in range(KVH):
            kw = kn_ref[0, pl.ds(w, Cq), h, :].astype(jnp.float32)
            vw = vn_ref[0, pl.ds(w, Cq), h, :].astype(jnp.float32)
            newk = lax.dot_general(oh, kw, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
            newv = lax.dot_general(oh, vw, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
            curk = kp_ref[0, h, 0]
            curv = vp_ref[0, h, 0]
            if not quantized:
                kp_out[0, h, 0] = jnp.where(
                    mask_w, newk, curk.astype(jnp.float32)).astype(
                        kp_out.dtype)
                vp_out[0, h, 0] = jnp.where(
                    mask_w, newv, curv.astype(jnp.float32)).astype(
                        vp_out.dtype)
                continue
            # int8 pools: grow-only per-page-per-kv-head scale.  A page
            # the row writes from offset 0 this step is FRESH (reset); a
            # page extended past existing rows keeps old int8 values
            # bit-stable unless the scale must grow (no cumulative
            # requant error).  Every per-page quantity below is a SCALAR
            # (full reductions), not a [1, 1] vector: Mosaic splats a
            # scalar over a [page, hd] tile but refuses to broadcast a
            # [1, 1] vector in both sublanes and lanes.
            wrote = jnp.max(mask_w.astype(jnp.float32)) > 0.0
            fresh = (base >= start)
            for (new, cur, sc_in, sc_out) in (
                    (newk, curk, ks_ref, ks_out),
                    (newv, curv, vs_ref, vs_out)):
                s_old = jnp.sum(
                    sc_in[0, 0, h:h + 1, 0:1].astype(jnp.float32))
                amax = jnp.max(jnp.where(mask_w, jnp.abs(new), 0.0))
                needed = jnp.maximum(amax / 127.0, 1e-8)
                grown = jnp.where(fresh, needed,
                                  jnp.maximum(s_old, needed))
                s_new = jnp.where(wrote, grown, jnp.maximum(s_old, 1e-8))
                factor = jnp.where(fresh & wrote, 0.0,
                                   jnp.where(s_new > s_old,
                                             s_old / s_new, 1.0))
                requant = jnp.round(cur.astype(jnp.float32) * factor)
                row_q = jnp.clip(jnp.round(new / s_new), -127, 127)
                outp = jnp.where(mask_w, row_q, requant)
                if new is newk:
                    kp_out[0, h, 0] = jnp.clip(outp, -127, 127).astype(
                        kp_out.dtype)
                else:
                    vp_out[0, h, 0] = jnp.clip(outp, -127, 127).astype(
                        vp_out.dtype)
                sc_out[0, 0, h:h + 1, 0:1] = jnp.full(
                    (1, 1), jnp.where(wrote, s_new, s_old), sc_out.dtype)


def _ragged_append(pools, scales, k_new, v_new, row_slot, row_start,
                   row_len, row_off, block_tables,
                   max_row_tokens: Optional[int]):
    """The append behind ``ragged_paged_append`` (``scales`` empty) and
    ``ragged_paged_append_quantized``: grid ``(L, n_live)`` over
    ``live_append_cells``, the layer outermost so that a layer's fresh
    rows are fetched once.  The bound follows the step's fresh tokens: a
    decode row is one cell a layer, a chunk one cell a page it touches,
    a padding row none, where the walk over ``(R, L, NPR)`` cost 2.6 us
    a cell whatever the rows held (PERF.md, PR 31).  The Pallas
    interpreter takes no dynamic grid bound, so there the grid keeps the
    capacity ``R * NPR`` and the steps past the end do nothing: the same
    body either way."""
    L, KVH, Pt, page, D = pools[0].shape
    T = k_new.shape[1]
    R = row_slot.shape[0]
    maxp = block_tables.shape[1]
    T_p = _round8(T)
    if T_p != T:
        k_new = jnp.pad(k_new, ((0, 0), (0, T_p - T), (0, 0), (0, 0)))
        v_new = jnp.pad(v_new, ((0, 0), (0, T_p - T), (0, 0), (0, 0)))
    Cq = window_size(T_p, max_row_tokens)
    NPR = _pages_per_row(Cq, page)
    row_start = row_start.astype(jnp.int32)
    row_len = row_len.astype(jnp.int32)
    live_ci, n_live = live_append_cells(row_start, row_len, NPR, page)
    prefetch = [row_slot.astype(jnp.int32), row_start, row_len,
                row_off.astype(jnp.int32), block_tables.astype(jnp.int32),
                live_ci, n_live]

    def pool_map(l, i, slot_p, start_p, len_p, _off, bt, cells, nl):
        ci = cells[i]
        r = ci // NPR
        start = start_p[r]
        nt = len_p[r]
        pg = start // page + ci % NPR
        lastp = (start + jnp.maximum(nt, 1) - 1) // page
        pid = jnp.minimum(bt[slot_p[r], jnp.minimum(pg, maxp - 1)], Pt - 1)
        # A cell that writes nothing (a step past the list's end, which
        # only the interpreter has; a listed cell the rows do not reach,
        # which ``live_append_cells`` does not list) must name the
        # scratch page, never a live one: its aliased copy-through reads
        # a stale input block (an earlier cell's write is not visible
        # through the alias) and would clobber a fresh append.  Scratch
        # is garbage-tolerant.
        live = (i < nl[0]) & (nt > 0) & (pg <= lastp)
        return (l, 0, jnp.where(live, pid, Pt - 1), 0, 0)

    def scale_map(l, i, *pf):
        return (l, pool_map(l, i, *pf)[2], 0, 0)

    new_spec = pl.BlockSpec((1, T_p, KVH, D), lambda l, i, *pf: (l, 0, 0, 0))
    state = list(pools) + list(scales)
    state_specs = ([pl.BlockSpec((1, KVH, 1, page, D), pool_map)] * 2
                   + [pl.BlockSpec((1, 1, KVH, 1), scale_map)] * len(scales))
    interpret = platform.interpret_mode()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(L, R * NPR if interpret else n_live[0]),
        in_specs=[new_spec, new_spec] + state_specs,
        out_specs=state_specs,
    )
    kern = functools.partial(
        _ragged_append_kernel, T=T_p, Cq=Cq, KVH=KVH, page=page, NPR=NPR,
        quantized=bool(scales))
    first = len(prefetch) + 2       # k_new and v_new sit in front
    return pl.pallas_call(
        kern,
        name="ragged_kv_append",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in state],
        input_output_aliases={first + n: n for n in range(len(state))},
        interpret=interpret,
    )(*prefetch, k_new, v_new, *state)


def ragged_paged_append(
    k_pools: jax.Array,      # [L, KVH, P, page, D]
    v_pools: jax.Array,
    k_new: jax.Array,        # [L, T, KVH, D]
    v_new: jax.Array,
    row_slot, row_start, row_len, row_off,
    block_tables: jax.Array,
    *,
    max_row_tokens: Optional[int] = None,
):
    """In-place append of every row's fresh tokens into its pages, all
    layers at once (aliased pools — same contract as paged_append)."""
    return _ragged_append(
        (k_pools, v_pools), (), k_new, v_new, row_slot, row_start,
        row_len, row_off, block_tables, max_row_tokens)


def ragged_paged_append_quantized(
    k_pools: jax.Array,      # int8 [L, KVH, P, page, D]
    v_pools: jax.Array,
    k_scales: jax.Array,     # f32 [L, P, KVH, 1] page-major
    v_scales: jax.Array,
    k_new: jax.Array,        # [L, T, KVH, D] bf16/f32
    v_new: jax.Array,
    row_slot, row_start, row_len, row_off,
    block_tables: jax.Array,
    *,
    max_row_tokens: Optional[int] = None,
):
    """int8 ragged append: pages covered from their offset 0 this step
    re-quantize fresh; extended pages grow their scale only when a new
    row's absmax demands it (existing int8 values stay bit-stable
    otherwise — the paged_append_quantized policy, per multi-token
    page)."""
    return _ragged_append(
        (k_pools, v_pools), (k_scales, v_scales), k_new, v_new, row_slot,
        row_start, row_len, row_off, block_tables, max_row_tokens)


# --------------------------------------------------------------------------
# fused megakernel over the ragged batch (PR-2 fold)
# --------------------------------------------------------------------------


def _fused_ragged_kernel(*refs, T: int, Cq: int, D: int, H: int,
                         KVH: int, qpg: int, hd: int, page: int,
                         Pt: int, maxp: int, M: int, tq: int,
                         to: int, tm: int, eps: float, scale: float,
                         soft_cap: Optional[float], quantized: bool,
                         dot_dt):
    n_pre = 10 if quantized else 8
    (slot_r, start_r, len_r, off_r, bt_r, _ly_r, live_r,
     n_live_r) = refs[:8]
    ks_r, vs_r = refs[8:10] if quantized else (None, None)
    (x_ref, xt_ref, ln_a_ref, ln_m_ref, sin_ref, cos_ref,
     wqkv_ref, sqkv_ref, kp_ref, vp_ref, wo_ref, so_ref,
     wg_g_ref, wg_u_ref, sg_g_ref, sg_u_ref, wd_ref, sd_ref,
     xo_ref, kn_ref, vn_ref,
     xn_s, qkv_s, qs, q1_s, m_s, l_s, acc_s, ao_s, h_s, y_s) = refs[n_pre:]

    half = hd // 2
    QP = _round8(qpg)
    Tq = ((H + 2 * KVH) * hd) // tq
    To = D // to
    Tm = M // tm
    cells = maxp + 1
    # The attention phase is as long as the step's live-cell list
    # (``live_page_cells``), so the later phases start where it ends.
    n_live = n_live_r[0]
    S1 = Tq
    S2 = S1 + n_live
    S3 = S2 + To
    S4 = S3 + Tm
    t = pl.program_id(0)

    def head_slice(hq: int):
        base = hq * hd
        j, off = divmod(base, tq)
        return qkv_s[j][:, off:off + hd]

    def rope(xh):
        x1, x2 = xh[:, :half], xh[:, half:]
        sn = sin_ref[...].astype(jnp.float32)
        cs = cos_ref[...].astype(jnp.float32)
        return jnp.concatenate([x1 * cs - x2 * sn, x2 * cs + x1 * sn],
                               axis=-1)

    def capped(s):
        if soft_cap is not None:
            return soft_cap * jnp.tanh(s / soft_cap)
        return s

    # ---- phase 0: RMSNorm + qkv tiles (identical to fused_decode) ----
    @pl.when(t == 0)
    def _norm_in():
        x32 = x_ref[...].astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        xn_s[...] = (x32 * lax.rsqrt(var + eps)
                     * ln_a_ref[...].astype(jnp.float32))

    @pl.when(t < S1)
    def _qkv_tile():
        wm = wqkv_ref[...].astype(dot_dt)
        res = lax.dot_general(
            xn_s[...].astype(dot_dt), wm, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        qkv_s[t] = res * sqkv_ref[...].astype(jnp.float32)

    # ---- phase 1 start: RoPE; the queries stacked by KV head ----------
    @pl.when(t == S1)
    def _attn_setup():
        ao_s[...] = jnp.zeros_like(ao_s)
        q1_s[...] = jnp.zeros_like(q1_s)
        # head-major: KV head g's stack holds its group's heads one
        # after the other, T rows each, so a window's stacked rows are
        # qpg aligned slices of it
        for h in range(H):
            g, j = divmod(h, qpg)
            qs[g, j * T:(j + 1) * T] = rope(head_slice(h))
        for h in range(KVH):
            lo, hi = h * hd, (h + 1) * hd
            kn_ref[:, lo:hi] = rope(head_slice(H + h)).astype(
                kn_ref.dtype)
            vn_ref[:, lo:hi] = head_slice(H + KVH + h).astype(
                vn_ref.dtype)

    # ---- phase 1: ragged attention, one (row, page/self) per cell ----
    in_attn = (t >= S1) & (t < S2)
    ci = live_r[jnp.clip(t - S1, 0, jnp.maximum(n_live - 1, 0))]
    r = ci // cells
    pc = ci % cells
    start = start_r[r]
    nt = len_r[r]
    off = off_r[r]

    def flash_update(g, n, valid, s, v, vscale):
        """Masked online-softmax update of KV head g's first ``n``
        stacked rows; rows that are not ``valid`` (a neighbour's tokens
        in the window, the pad of a group) keep their state."""
        m_prev = m_s[g, :n]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_new = jnp.where(valid, m_new, m_prev)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_prev = l_s[g, :n]
        l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        if vscale is not None:
            pv = pv * vscale
        a_prev = acc_s[g, :n]
        a_new = a_prev * corr + pv
        m_s[g, :n] = m_new
        l_s[g, :n] = jnp.where(valid, l_new, l_prev)
        acc_s[g, :n] = jnp.where(valid, a_new, a_prev)
        return l_new, a_new

    # a row's cells are consecutive in the list: its pool pages in
    # ascending order, then its self cell.  Every body below is a
    # ``pl.when`` of the kernel's top level: one inside another doubled
    # the time the chip's host takes to trace this kernel.
    first = (pc == 0) | (start == 0)
    pool = (pc < maxp) & (pc * page < start)

    def row_cells(on, n, rows_of, q_of, wk, Ck, put):
        """The cell's three bodies for the rows ``on`` selects, whose
        queries are ``n`` stacked rows a KV head (``q_of(g)`` [n, hd];
        ``rows_of()`` gives which stacked rows are the row's own and
        their row-relative token index): the state reset at the row's
        first cell, one product a KV head against the cell's page, and
        in the self cell against the ``Ck`` fresh keys from ``wk``,
        where ``put(g, o, valid_q)`` writes the row's finished tokens."""

        def scores(g, keys):
            return lax.dot_general(
                q_of(g), keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

        @pl.when(on & first)
        def _first():
            m_s[:, :n] = jnp.full((KVH, n, 1), NEG_INF, jnp.float32)
            l_s[:, :n] = jnp.zeros((KVH, n, 1), jnp.float32)
            acc_s[:, :n] = jnp.zeros((KVH, n, hd), jnp.float32)

        @pl.when(on & pool)
        def _pool_cell():
            valid_q, _ = rows_of()
            s_idx = slot_r[r]
            last = jnp.maximum(start - 1, 0) // page
            pid = jnp.minimum(bt_r[s_idx, jnp.minimum(pc, last)], Pt - 1)
            kpos = pc * page + lax.broadcasted_iota(jnp.int32, (1, page), 1)
            mask = valid_q & (kpos < start)
            for g in range(KVH):
                s = scores(g, kp_ref[0, g, 0].astype(jnp.float32))
                if quantized:
                    s = s * ks_r[pid, g]
                flash_update(g, n, valid_q,
                             jnp.where(mask, capped(s), NEG_INF),
                             vp_ref[0, g, 0].astype(jnp.float32),
                             vs_r[pid, g] if quantized else None)

        @pl.when(on & (pc == maxp))
        def _self_cell():
            valid_q, trel = rows_of()
            krel = wk + lax.broadcasted_iota(jnp.int32, (1, Ck), 1) - off
            mask = valid_q & (krel >= 0) & (krel < nt) & (krel <= trel)
            for g in range(KVH):
                lo, hi = g * hd, (g + 1) * hd
                s = scores(g, kn_ref[pl.ds(wk, Ck), lo:hi].astype(
                    jnp.float32))
                l_new, a_new = flash_update(
                    g, n, valid_q, jnp.where(mask, capped(s), NEG_INF),
                    vn_ref[pl.ds(wk, Ck), lo:hi].astype(jnp.float32), None)
                put(g, a_new / jnp.maximum(l_new, 1e-30), valid_q)

    # A row of ONE token takes that token alone: its group's heads are
    # the stacked rows, padded to a sublane tile and gathered once, at
    # the row's first cell.
    one = in_attn & (nt == 1)
    w1 = pl.multiple_of((off // 8) * 8, 8)

    @pl.when(one & first)
    def _gather():
        for h in range(H):
            g, j = divmod(h, qpg)
            q1_s[g, j:j + 1] = qs[g, pl.ds(j * T + off, 1)]

    def group_rows():
        return lax.broadcasted_iota(jnp.int32, (QP, 1), 0) < qpg, 0

    # Mosaic stores no single row at a dynamic offset: the token's
    # sublane tile is read, the row put in, the tile written back
    def put_token(g, o, _valid):
        at_off = lax.broadcasted_iota(jnp.int32, (8, 1), 0) == off - w1
        for j in range(qpg):
            lo = (g * qpg + j) * hd
            ao_s[pl.ds(w1, 8), lo:lo + hd] = jnp.where(
                at_off, jnp.broadcast_to(o[j:j + 1], (8, hd)),
                ao_s[pl.ds(w1, 8), lo:lo + hd])

    row_cells(one, QP, group_rows, lambda g: q1_s[g], w1, 8, put_token)

    # A row of more takes the static window [w, w + Cq) of the buffer,
    # w aligned down to the sublane: qpg x Cq stacked rows, head-major;
    # masks do the raggedness.
    w = pl.multiple_of(jnp.minimum((off // 8) * 8, T - Cq), 8)

    def window_rows():
        trel = w + lax.broadcasted_iota(jnp.int32, (Cq, 1), 0) - off
        valid = (trel >= 0) & (trel < nt)
        return (jnp.concatenate([valid] * qpg, axis=0),
                jnp.concatenate([trel] * qpg, axis=0))

    def window_q(g):
        return jnp.concatenate(
            [qs[g, pl.ds(j * T + w, Cq)] for j in range(qpg)], axis=0)

    def put_window(g, o, valid):
        for j in range(qpg):
            lo = (g * qpg + j) * hd
            ao_s[pl.ds(w, Cq), lo:lo + hd] = jnp.where(
                valid[:Cq], o[j * Cq:(j + 1) * Cq],
                ao_s[pl.ds(w, Cq), lo:lo + hd])

    row_cells(in_attn & (nt > 1), qpg * Cq, window_rows, window_q, w, Cq,
              put_window)

    # ---- phase 2: o-proj tiles + residual add ------------------------
    @pl.when((t >= S2) & (t < S3))
    def _oproj_tile():
        wm = wo_ref[...].astype(dot_dt)
        o = lax.dot_general(
            ao_s[...].astype(dot_dt), wm, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o = o * so_ref[...].astype(jnp.float32)
        h_s[t - S2] = xt_ref[...].astype(jnp.float32) + o

    # ---- phase 3: second norm + fused gate/up/down -------------------
    @pl.when(t == S3)
    def _mlp_norm():
        ss = jnp.zeros((T, 1), jnp.float32)
        for j in range(To):
            hj = h_s[j]
            ss = ss + jnp.sum(hj * hj, axis=-1, keepdims=True)
        rr = lax.rsqrt(ss / D + eps)
        for j in range(To):
            sl = slice(j * to, (j + 1) * to)
            xn_s[:, sl] = h_s[j] * rr * ln_m_ref[:, sl].astype(
                jnp.float32)
        y_s[...] = jnp.zeros_like(y_s)

    # t < S4 always holds under Mosaic, whose grid ends at S4; the
    # interpreter's grid is the capacity (see ``fused_ragged_layer``).
    @pl.when((t >= S3) & (t < S4))
    def _mlp_tile():
        hn = xn_s[...].astype(dot_dt)
        g = lax.dot_general(
            hn, wg_g_ref[...].astype(dot_dt), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        g = g * sg_g_ref[...].astype(jnp.float32)
        u = lax.dot_general(
            hn, wg_u_ref[...].astype(dot_dt), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        u = u * sg_u_ref[...].astype(jnp.float32)
        act = (g * jax.nn.sigmoid(g)) * u
        y_s[...] += lax.dot_general(
            act.astype(dot_dt), wd_ref[...].astype(dot_dt),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == S4 - 1)
    def _final():
        for j in range(To):
            sl = slice(j * to, (j + 1) * to)
            xo_ref[:, sl] = (
                h_s[j] + y_s[:, sl] * sd_ref[:, sl].astype(jnp.float32)
            ).astype(xo_ref.dtype)


def layer_slice(tree, li):
    """One layer's leaves of a stacked ``[L, ...]`` tree, under the
    scope ``weight_slice``.  XLA fuses such a slice into an einsum that
    reads it; in front of a Pallas call it is a copy of the leaf."""
    with jax.named_scope("weight_slice"):
        return jax.tree.map(
            lambda w: lax.dynamic_index_in_dim(w, li, 0, keepdims=False),
            tree)


def weight_routes(layers) -> Dict[str, List[str]]:
    """How ``fused_ragged_layer`` reads each operand of the stacked
    layer tree ``layers``: ``in_place`` (the stored ``[L, ...]`` leaf
    goes to the kernel whole and its index maps pick the layer) or
    ``sliced`` (XLA takes the layer's slice first, a copy).  It follows
    from the tree alone: an operand that is one stored leaf is read in
    place; one that has to be built from several leaves (separate
    ``wq/wk/wv``, ``w_gate/w_up``) is built from that layer's slices,
    because concatenating the stacks would copy the model every step.
    The norm vectors, a few kilobytes, are always sliced."""
    routes: Dict[str, List[str]] = {"in_place": ["w_down", "wo"],
                                    "sliced": ["ln_attn", "ln_mlp"]}
    routes["in_place" if "wqkv" in layers["attn"]
           else "sliced"].append("wqkv")
    routes["in_place" if "w_gateup" in layers["mlp"]
           else "sliced"].append("w_gateup")
    return {k: sorted(v) for k, v in routes.items()}


def _stored(leaf, rows: int):
    """Kernel operands of one stored stacked leaf, as views: the matrix
    ``[L, rows, N]`` and its per-output-channel scale ``[L, 1, N]``
    (``[1, 1, N]`` ones for a plain leaf)."""
    if _qdict(leaf):
        L = leaf["q"].shape[0]
        return (leaf["q"].reshape(L, rows, -1),
                leaf["scale"].reshape(L, 1, -1).astype(jnp.float32))
    w = leaf.reshape(leaf.shape[0], rows, -1)
    return w, jnp.ones((1, 1, w.shape[2]), jnp.float32)


def _built(pair):
    """The same, of a matrix and scale assembled from one layer's
    slices: a leading axis of 1."""
    w, s = pair
    return w[None], s[None]


def live_page_cells(row_start: jax.Array, row_len: jax.Array, maxp: int,
                    page: int, takes: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """The attention cells that hold work for these rows: ``(live_ci,
    n_live)``.  Cell ``r * (maxp + 1) + pc`` is row ``r``'s pool page
    ``pc``, or its self cell where ``pc == maxp``; it is live where the
    walk takes the row (``takes`` [R]; None: every row with tokens,
    ``row_len > 0``) and, for a pool page, where the page holds pooled
    tokens of the row (``pc * page < row_start``).  ``live_ci``
    ``[R * (maxp + 1)]`` lists the live cells in ascending order, so a
    row's pool pages come before its self cell, which finalises the row;
    past ``n_live`` ``[1]`` it is padding.  It follows from the row
    arrays alone, not from the layer: a step builds it once, in front of
    its layer loop."""
    pc = jnp.arange(maxp + 1, dtype=jnp.int32)
    if takes is None:
        takes = row_len > 0
    return _listed(takes[:, None] & (
        (pc == maxp) | (pc * page < row_start[:, None])))


def live_cell_count(row_start, row_len, page: int) -> int:
    """``live_page_cells``' ``n_live`` on the host, from the packed row
    arrays: each live row's pooled pages plus its self cell.  Both calls
    of ``ragged_paged_attention`` together walk as many."""
    start, nlen = np.asarray(row_start), np.asarray(row_len)
    return int(np.sum((nlen > 0) * (-(-start // page) + 1)))


def fused_ragged_layer(
    x: jax.Array,            # [T, D] residual stream of the flat batch
    layers,                  # the stacked [L, ...] layer tree
    k_pools: jax.Array,
    v_pools: jax.Array,
    layer_idx: jax.Array,
    row_slot, row_start, row_len, row_off,
    block_tables: jax.Array,
    sin: jax.Array,          # [T, hd // 2] per-token rope rows
    cos: jax.Array,
    *,
    eps: float,
    n_heads: int,
    n_kv_heads: int,
    soft_cap: Optional[float] = None,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    max_row_tokens: Optional[int] = None,
    live_cells: Optional[Tuple[jax.Array, jax.Array]] = None,
    tile_qkv: int = 256,
    tile_out: int = 256,
    tile_mlp: int = 128,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The PR-2 per-layer decode megakernel folded over a ragged
    batch: one pallas_call runs RMSNorm -> qkv -> RoPE -> ragged paged
    attention (pool pages + intra-row self phase) -> o-proj -> MLP for
    every packed token.  Pools read-only; fresh k/v rows ([T, KVH*hd])
    ride out for the post-scan ragged append.

    ``layers`` is the whole stacked tree and ``layer_idx`` the layer to
    run.  The weights go the way the KV pools go: every operand that is
    one stored leaf is handed to the kernel stacked, and its BlockSpec
    squeezes the layer axis and picks the layer with the
    scalar-prefetched index, so the step reads each weight once, where
    it lies (``weight_routes`` says which operands those are).  A slice
    taken in XLA in front of the call would be a copy of the layer:
    that is kept for what has to be assembled from several leaves, and
    for the norm vectors.

    The attention phase walks ``live_cells``, the ``live_page_cells`` of
    the row arrays (a caller with a layer loop builds them once in front
    of it; None builds them here), and the grid ends where the work
    ends: its bound is ``Tq + n_live + To + Tm``, a value of the step
    and not of the page table's capacity ``R * (maxp + 1)``.  The Pallas
    interpreter takes no dynamic grid bound, so there the grid keeps the
    capacity and the steps past the end do nothing: the same body
    either way.

    A cell makes one product a KV head, whose rows are the head's group
    of ``n_heads / n_kv_heads`` query heads: for a row of one token
    that token's heads, padded to a sublane tile; for a row of more the
    step's window ``[w, w + Cq)`` of each head, one head after the
    other.  The page is read and cast to float32 once a KV head; the
    flash state is per KV head and stacked row, reset at a row's first
    cell (a row's cells are consecutive in the list); the self cell
    finalises the row and writes its tokens for the o-proj phase.  MHA
    is the group of one, MQA the group of ``n_heads``."""
    T, D = x.shape
    H, KVH = n_heads, n_kv_heads
    hd = D // H
    L, KVH_p, Pt, page, _ = k_pools.shape
    assert KVH_p == KVH, (KVH_p, KVH)
    maxp = block_tables.shape[1]
    R = row_slot.shape[0]
    attn, mlp = layers["attn"], layers["mlp"]
    M = (mlp["w_down"]["q"] if _qdict(mlp["w_down"])
         else mlp["w_down"]).shape[1]
    qpg = H // KVH
    quantized = k_scales is not None
    dt = x.dtype
    Cw = (H + 2 * KVH) * hd
    ly_s = jnp.asarray(layer_idx, jnp.int32)

    in_place = weight_routes(layers)["in_place"]
    wqkv, sqkv = (_stored(attn["wqkv"], D) if "wqkv" in in_place
                  else _built(_assemble_qkv(layer_slice(
                      {k: attn[k] for k in ("wq", "wk", "wv")}, ly_s),
                      H, KVH, hd, dt)))
    wg, sg = (_stored(mlp["w_gateup"], D) if "w_gateup" in in_place
              else _built(_assemble_gateup(layer_slice(
                  {k: mlp[k] for k in ("w_gate", "w_up")}, ly_s), dt)))
    # wo contracts over (heads, head_dim): fold both into rows.
    wo, so = _stored(attn["wo"], H * hd)
    wd, sd = _stored(mlp["w_down"], M)
    ln_a, ln_m = (v.reshape(1, D).astype(jnp.float32) for v in layer_slice(
        (layers["ln_attn"], layers["ln_mlp"]), ly_s))

    T_p = _round8(T)
    if T_p != T:
        pad = T_p - T
        x = jnp.pad(x, ((0, pad), (0, 0)))
        sin = jnp.pad(sin, ((0, pad), (0, 0)))
        cos = jnp.pad(cos, ((0, pad), (0, 0)))
    Cq = window_size(T_p, max_row_tokens)
    # stacked rows a KV head: a one-token row's group padded to a
    # sublane tile, a longer row's group times the window
    QP = _round8(qpg)
    SR = max(QP, qpg * Cq)

    tq = _pick_tile(Cw, tile_qkv, multiple=hd)
    to = _pick_tile(D, tile_out, multiple=128 if D % 128 == 0 else 1)
    tm = _pick_tile(M, tile_mlp, multiple=128 if M % 128 == 0 else 1)
    Tq, To, Tm = Cw // tq, D // to, M // tm
    cells = maxp + 1
    if live_cells is None:
        live_cells = live_page_cells(row_start, row_len, maxp, page)
    live_ci, n_live = live_cells
    # Phase starts, as the kernel body has them: the attention phase is
    # n_live (scalar prefetch 7) steps long.
    S1 = Tq

    def s2(pf):
        return S1 + pf[7][0]

    def s3(pf):
        return s2(pf) + To

    def clip(v, n):
        return jnp.clip(v, 0, n - 1)

    def const2(t, *pf):
        return (0, 0)

    def pool_map(t, slot_p, start_p, len_p, off_p, bt, ly, live, nl, *sc):
        ci = live[jnp.clip(t - S1, 0, jnp.maximum(nl[0] - 1, 0))]
        r = ci // cells
        pc = jnp.minimum(ci % cells, maxp - 1)
        s = slot_p[r]
        last = jnp.maximum(start_p[r] - 1, 0) // page
        pe = jnp.minimum(pc, last)
        pid = jnp.minimum(bt[s, pe], Pt - 1)
        return (ly[0], 0, jnp.where(len_p[r] > 0, pid, Pt - 1), 0, 0)

    def wspec(operand, block, at):
        """Block of a ``[L or 1, rows, cols]`` operand: the leading axis
        is squeezed, and picks the layer (scalar prefetch 5) unless the
        operand holds one layer only; ``at(t, pf)`` is the block's (row,
        column)."""
        stacked = operand.shape[0] > 1
        return pl.BlockSpec(
            (None,) + block,
            lambda t, *pf: (pf[5][0] if stacked else 0,) + at(t, pf))

    def qkv_at(t, pf):
        return (0, clip(t, Tq))

    def out_at(t, pf):
        return (0, clip(t - s2(pf), To))

    def gate_at(t, pf):
        return (0, clip(t - s3(pf), Tm))

    def up_at(t, pf):
        return (0, Tm + clip(t - s3(pf), Tm))

    in_specs = [
        pl.BlockSpec((T_p, D), const2),                        # x (norm)
        pl.BlockSpec((T_p, to), lambda t, *pf: out_at(t, pf)), # x (resid)
        pl.BlockSpec((1, D), const2),                          # ln_attn
        pl.BlockSpec((1, D), const2),                          # ln_mlp
        pl.BlockSpec((T_p, hd // 2), const2),                  # sin
        pl.BlockSpec((T_p, hd // 2), const2),                  # cos
        wspec(wqkv, (D, tq), qkv_at),
        wspec(sqkv, (1, tq), qkv_at),
        pl.BlockSpec((1, KVH, 1, page, hd), pool_map),         # k pages
        pl.BlockSpec((1, KVH, 1, page, hd), pool_map),         # v pages
        wspec(wo, (H * hd, to), out_at),
        wspec(so, (1, to), out_at),
        wspec(wg, (D, tm), gate_at),                           # w gate
        wspec(wg, (D, tm), up_at),                             # w up
        wspec(sg, (1, tm), gate_at),                           # s gate
        wspec(sg, (1, tm), up_at),                             # s up
        wspec(wd, (tm, D),
              lambda t, pf: (clip(t - s3(pf), Tm), 0)),        # w_down
        wspec(sd, (1, D), lambda t, pf: (0, 0)),               # sd
    ]
    out_specs = [
        pl.BlockSpec((T_p, D), const2),
        pl.BlockSpec((T_p, KVH * hd), const2),
        pl.BlockSpec((T_p, KVH * hd), const2),
    ]
    scratch = [
        pltpu.VMEM((T_p, D), jnp.float32),                 # xn_s
        pltpu.VMEM((Tq, T_p, tq), jnp.float32),            # qkv_s
        pltpu.VMEM((KVH, qpg * T_p, hd), jnp.float32),     # qs
        pltpu.VMEM((KVH, QP, hd), jnp.float32),            # q1_s
        pltpu.VMEM((KVH, SR, 1), jnp.float32),             # m_s
        pltpu.VMEM((KVH, SR, 1), jnp.float32),             # l_s
        pltpu.VMEM((KVH, SR, hd), jnp.float32),            # acc_s
        pltpu.VMEM((T_p, H * hd), jnp.float32),            # ao_s
        pltpu.VMEM((To, T_p, to), jnp.float32),            # h_s
        pltpu.VMEM((T_p, D), jnp.float32),                 # y_s
    ]
    prefetch = [row_slot.astype(jnp.int32), row_start.astype(jnp.int32),
                row_len.astype(jnp.int32), row_off.astype(jnp.int32),
                block_tables.astype(jnp.int32), ly_s.reshape(1),
                live_ci, n_live]
    if quantized:
        with jax.named_scope("weight_slice"):
            prefetch += [k_scales[ly_s, :, :, 0], v_scales[ly_s, :, :, 0]]
    interpret = platform.interpret_mode()
    steps = Tq + (R * cells if interpret else n_live[0]) + To + Tm
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(steps,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    kern = functools.partial(
        _fused_ragged_kernel, T=T_p, Cq=Cq, D=D, H=H, KVH=KVH, qpg=qpg,
        hd=hd, page=page, Pt=Pt, maxp=maxp, M=M, tq=tq, to=to,
        tm=tm, eps=eps, scale=hd ** -0.5, soft_cap=soft_cap,
        quantized=quantized, dot_dt=dt)
    x_out, k_new, v_new = pl.pallas_call(
        kern,
        name="fused_ragged_layer",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((T_p, D), dt),
            jax.ShapeDtypeStruct((T_p, KVH * hd), dt),
            jax.ShapeDtypeStruct((T_p, KVH * hd), dt),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=48 * 2**20),
        interpret=interpret,
    )(*prefetch, x, x, ln_a, ln_m, sin.astype(jnp.float32),
      cos.astype(jnp.float32), wqkv, sqkv, k_pools, v_pools, wo, so,
      wg, wg, sg, sg, wd, sd)
    return (x_out[:T], k_new[:T].reshape(T, KVH, hd),
            v_new[:T].reshape(T, KVH, hd))


# --------------------------------------------------------------------------
# host-side packing helper
# --------------------------------------------------------------------------


def pack_ragged_batch(rows, token_budget: int, max_slots: int,
                      with_adapters: bool = False):
    """Host-side packer: ``rows`` is a list of dicts with keys
    ``slot``, ``start``, ``tokens`` (list[int] for prefill chunks, or
    None for decode rows whose token lives on device).  Returns numpy
    arrays sized (token_budget, max_slots):

        host_toks, decode_mask, tok_slot, tok_pos  [T]
        row_slot, row_start, row_len, row_off      [R]

    With ``with_adapters`` a ninth array ``tok_adapter`` [T] is
    appended: each row's optional ``adapter`` key (an index into the
    step's adapter gather set, ops/segmented_lora) broadcast over its
    tokens — 0 (the null adapter) for rows without one and for padding,
    so base-model and padding tokens gather the pool's zero scratch
    page.  Padding rows get len 0 / slot 0; padding tokens get pos 0."""
    T, R = token_budget, max_slots
    host_toks = np.zeros(T, np.int32)
    decode_mask = np.zeros(T, bool)
    tok_slot = np.zeros(T, np.int32)
    tok_pos = np.zeros(T, np.int32)
    tok_adapter = np.zeros(T, np.int32)
    row_slot = np.zeros(R, np.int32)
    row_start = np.zeros(R, np.int32)
    row_len = np.zeros(R, np.int32)
    row_off = np.zeros(R, np.int32)
    cursor = 0
    for i, row in enumerate(rows):
        toks = row.get("tokens")
        n = 1 if toks is None else len(toks)
        assert cursor + n <= T and i < R, "packer overflow"
        row_slot[i] = row["slot"]
        row_start[i] = row["start"]
        row_len[i] = n
        row_off[i] = cursor
        tok_slot[cursor:cursor + n] = row["slot"]
        tok_pos[cursor:cursor + n] = row["start"] + np.arange(n)
        tok_adapter[cursor:cursor + n] = row.get("adapter", 0)
        if toks is None:
            decode_mask[cursor] = True
        else:
            host_toks[cursor:cursor + n] = np.asarray(toks, np.int32)
        cursor += n
    out = (host_toks, decode_mask, tok_slot, tok_pos,
           row_slot, row_start, row_len, row_off)
    return out + (tok_adapter,) if with_adapters else out
