"""Ragged attention over a LATENT page pool (multi-head latent attention
in its absorbed form), and the pool's append.

A latent cache holds one vector a token and layer, ``c | kr``: the
compressed key/value (``rank`` lanes) followed by the one rotary key all
heads share.  With the up-projections absorbed into the query and the
output, every head attends over the same keys, whose first ``rank``
lanes are also the values:

    score_h(t, s) = q_h(t) . (c | kr)(s) * scale        q_h [rank + rope]
    out_h(t)      = sum_s softmax_s(score_h)(t, s) c(s)            [rank]

so the pool is ONE operand ``[L, 1, P, page, rank + rope]`` that a cell
reads once, and the heads are stacked into the ROWS of one matmul against
it (``[HG * Cq, 576] x [576, G * page]``), as ``ragged_paged_attention``
stacks the heads of one KV head.  The batch is the engine's ragged
one (see ``ops/ragged_paged_attention``): rows of (slot, start, len,
offset) over a flat token buffer, each row's past in the pool under its
block table, its fresh tokens beside it in ``new``; the pool is read-only
here and ``ragged_latent_append`` writes the fresh rows afterwards, all
layers at once, in place.

The grid walks a LIST of the cells that hold work
(``live_latent_cells``: head group, row, pool cell or self), under a
dynamic bound, so a page no row reaches is no grid step.  A POOL CELL
spans ``G = cell_pages(page, maxp)`` consecutive pages of the row's block
table (``CELL_KEYS`` keys: four pages of 64): the pool is passed ``G``
times, a page an operand, the pages are joined into one ``[G * page, W]``
key block, and a cell makes ONE ``[rows, G * page]`` score tile and ONE
online-softmax update of the flash state.  A page of a cell past the
row's last page repeats that page (no DMA, and no page another sequence
owns is read) and lies past ``start``, so position masks it.  What a
cell pays whatever it holds (the state read, scaled and written back;
a row maximum and a row sum across lanes for every stacked row; the grid
step) is paid once for 256 keys, and both products fill the 128-wide
MXU.  A step makes two calls: rows of ONE token through a window of that
token alone, all heads in one group (32 stacked rows a cell: a decode
row through a window of 32 tokens cost 5.6 us a cell and 17.8 ms a step,
PERF.md PR 34), the others through the step's whole window with the
heads in groups of ``CHUNK_HEADS`` (outermost in the list, so that a
group's output block stays where it is; the chunk's flash state then
fits the chip's VMEM).  One geometry serves all three calls (``G``
follows from the page size and the table's width alone).  A call whose
list is empty has no grid step.

SPARSE attention (a learned indexer selects, for every query, the cached
positions it may attend to: ``ops/dsa_index``) is
``ragged_sparse_latent_attention``.  A row of ONE token reads the
selected pool rows and no other: they are gathered through the block
table (``selected_rows_attention``, plain XLA: a gather of ``topk`` rows
of the pool and two small products).  A row of more tokens (a prompt
chunk) keeps the walk over every live cell of its context ONCE, under
the selection as a mask: 512 queries' selections cover most of a context
between them, and the same function is computed; what it costs past the
selected share is the arithmetic on the masked scores (reading a chunk's
union of selections once is ROADMAP Queue 2's).  In that call the heads
of a group are stacked head-major (stacked row ``h * T + t``), so that a
cell's ``[T, G * page]`` block of the selection tiles over them, and its
window is the whole step.
The Pallas interpreter takes no dynamic bound: there the grid keeps the
list's capacity and the steps past its end do nothing, the same body.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import platform
from ray_tpu.ops.paged_attention import NEG_INF
from ray_tpu.ops.ragged_paged_attention import (
    _listed,
    _pages_per_row,
    _round8,
    live_append_cells,
    window_size,
)

# heads a group of the chunk call stacks: 16 x a window of 288 rows of
# float32 state is 9.4 MB of VMEM, all 32 would be 19
CHUNK_HEADS = 16
# heads a group of the masked (sparse) chunk call stacks: the step's
# whole window is its query window (520 positions x 8 heads of float32
# state and the self cell's [rows, 520] scores are 60 MB of VMEM; 16 heads
# would not fit)
SPARSE_CHUNK_HEADS = 8
# keys a pool cell spans: CELL_KEYS // page consecutive pages of the row's
# block table, one score tile and one update of the flash state a cell.
# On a v5e at pages of 64 (PERF.md PR 40), by pages a cell 1 / 2 / 4 / 8:
# the masked chunk call (4160 stacked rows) 18.1 / 11.7 / 5.5 / 6.2 us a
# pooled page (a cell of one, two or four pages costs the same 18 to 23
# us: the state's rescale and 4160 row maxima and sums, not the keys; at
# eight the [4160, 512] tiles cost more than they save); the unmasked
# chunk call (4608 rows, a 256-token chunk beside nine rows, seven layers)
# 7.9 / 6.2 / 4.8 / 24.0 ms; the one-token call (32 rows, 164 pages, seven
# layers) 1.65 / 1.15 / 0.86 / 0.79 ms.  Four pages serve all three.
CELL_KEYS = 256
VMEM_LIMIT = 100 * 1024 * 1024


def cell_pages(page: int, maxp: int) -> int:
    """G, the pages a pool cell of the walk spans: what ``CELL_KEYS``
    holds of them, and no more than a block table has."""
    return max(1, min(CELL_KEYS // page, maxp))


# --------------------------------------------------------------------------
# plain references
# --------------------------------------------------------------------------

def ragged_latent_attention_reference(
        q: jax.Array,           # [T, H, rank + rope]
        new: jax.Array,         # [T, rank + rope] this step's c | kr
        pages: jax.Array,       # [P, page, rank + rope] one layer's pool
        row_slot, row_start, row_len, row_off,
        block_tables: jax.Array,    # [slots, maxp]
        *, scale: float, rank: int) -> jax.Array:
    """Dense gather twin: each row's fresh tokens over (its pooled past)
    + (the row's own fresh tokens, causally), float32 [T, H, rank].
    Buffer positions no row covers come back zero."""
    T, H, _ = q.shape
    P, page, W = pages.shape
    maxp = block_tables.shape[1]
    ctx = maxp * page
    f32 = jnp.float32
    qf, nf, pf = q.astype(f32), new.astype(f32), pages.astype(f32)
    ti = jnp.arange(T)
    out = jnp.zeros((T, H, rank), f32)
    for r in range(int(row_slot.shape[0])):
        start, nt, off = row_start[r], row_len[r], row_off[r]
        kc = pf[jnp.clip(block_tables[row_slot[r]], 0, P - 1)].reshape(ctx, W)
        trel = ti - off
        in_row = (trel >= 0) & (trel < nt)
        s_pool = jnp.einsum("thc,kc->thk", qf, kc) * scale
        s_self = jnp.einsum("thc,uc->thu", qf, nf) * scale
        m_pool = in_row[:, None, None] & (jnp.arange(ctx) < start)[None, None]
        m_self = (in_row[:, None, None] & in_row[None, None, :]
                  & (trel[None, None, :] <= trel[:, None, None]))
        s = jnp.concatenate([jnp.where(m_pool, s_pool, NEG_INF),
                             jnp.where(m_self, s_self, NEG_INF)], -1)
        p = jax.nn.softmax(s, -1)
        o = (jnp.einsum("thk,kc->thc", p[..., :ctx], kc[:, :rank])
             + jnp.einsum("thu,uc->thc", p[..., ctx:], nf[:, :rank]))
        out = jnp.where(in_row[:, None, None], o, out)
    return out


def ragged_latent_append_reference(pool, new, row_slot, row_start, row_len,
                                   row_off, block_tables):
    """Scatter twin of the append: ``pool`` [L, 1, P, page, W], ``new``
    [L, T, W]; padding lands in the scratch page (the last)."""
    L, _, P, page, W = pool.shape
    T = new.shape[1]
    maxp = block_tables.shape[1]
    ti = jnp.arange(T)
    for r in range(int(row_slot.shape[0])):
        trel = ti - row_off[r]
        in_row = (trel >= 0) & (trel < row_len[r])
        pos = row_start[r] + trel
        pid = jnp.take(jnp.clip(block_tables[row_slot[r]], 0, P - 1),
                       jnp.clip(pos // page, 0, maxp - 1))
        pid = jnp.where(in_row, pid, P - 1)
        offp = jnp.where(in_row, pos % page, 0)
        pool = pool.at[:, 0, pid, offp].set(
            jnp.where(in_row[None, :, None], new.astype(pool.dtype),
                      pool[:, 0, pid, offp]))
    return pool


# --------------------------------------------------------------------------
# the cells a step walks
# --------------------------------------------------------------------------

def live_latent_cells(row_start: jax.Array, row_len: jax.Array,
                      takes: jax.Array, groups: int, maxp: int,
                      page: int) -> Tuple[jax.Array, jax.Array]:
    """``(live_ci, n_live)`` of one call: cell ``(g * R + r) * (NC + 1)
    + pc`` is head group ``g``, row ``r``, the pool cell of pages
    ``pc * G`` to ``pc * G + G - 1`` of the row's block table (``G =
    cell_pages(page, maxp)``, ``NC = ceil(maxp / G)``) or the row's self
    cell where ``pc == NC``.  Live where the call takes the row
    (``takes`` [R]) and, for a pool cell, where its first page holds
    pooled tokens of the row.  Ascending order puts a group's rows
    together and a row's pool cells before its self cell, which
    finalises the row."""
    G = cell_pages(page, maxp)
    nc = -(-maxp // G)
    pc = jnp.arange(nc + 1, dtype=jnp.int32)
    live = takes[:, None] & (
        (pc == nc) | (pc * (G * page) < row_start[:, None]))
    return _listed(jnp.broadcast_to(live[None], (groups,) + live.shape))


def _calls(T: int, H: int, max_row_tokens: Optional[int]):
    """The calls of a step of ``T`` positions: (query window, heads a
    group, takes rows of one token / of more).  A window of 1 is the
    row's token itself."""
    return [(1, H, "one"),
            (window_size(T, max_row_tokens), min(H, CHUNK_HEADS), "more")]


def _takes(row_len, which: str):
    return {"one": row_len == 1, "more": row_len > 1}[which]


def _pages_walked(row_start, page: int, maxp: int):
    """The PAGES a row's cells span, on the host: ``G`` a pool cell (the
    last one's tail past the row's pages included), one for the self
    cell."""
    G = cell_pages(page, maxp)
    return -(-np.asarray(row_start) // (G * page)) * G + 1


def latent_cell_count(row_start, row_len, page: int, H: int,
                      maxp: int) -> int:
    """The pages ``ragged_latent_attention``'s cells span for a step's
    packed rows, on the host: each live row's pool cells (``G`` pages
    each) plus its self cell, once for each head group of the call that
    takes it."""
    nlen = np.asarray(row_len)
    groups = np.where(nlen > 1, H // min(H, CHUNK_HEADS), 1)
    return int(np.sum((nlen > 0) * groups
                      * _pages_walked(row_start, page, maxp)))


def sparse_cell_count(row_start, row_len, page: int, H: int,
                      maxp: int) -> int:
    """The pages ``ragged_sparse_latent_attention``'s masked walk spans
    for a step's packed rows, on the host: the pool cells and the self
    cell of each row of more than one token, once a head group; a row of
    one token walks none (its rows are gathered)."""
    groups = H // min(H, SPARSE_CHUNK_HEADS)
    return int(np.sum((np.asarray(row_len) > 1) * groups
                      * _pages_walked(row_start, page, maxp)))


# --------------------------------------------------------------------------
# the kernel
# --------------------------------------------------------------------------

def _latent_kernel(slot_r, start_r, len_r, off_r, bt_r, ly_r, live_r, nl_r,
                   q_ref, new_ref, *rest, T: int, Cq: int, HG: int, R: int,
                   G: int, NC: int, page: int, rank: int, scale: float,
                   masked: bool = False):
    del slot_r, bt_r, ly_r      # the index maps' own
    pool_refs, rest = rest[:G], rest[G:]
    if masked:      # the selection: this cell's columns, the step's own
        selp_ref, sels_ref, out_ref, m_s, l_s, acc_s = rest
    else:
        out_ref, m_s, l_s, acc_s = rest
    i = pl.program_id(0)
    rows = Cq * HG
    Ck = max(Cq, 8)             # the self cell's keys: a sublane tile
    Kp = G * page               # a pool cell's keys
    shift = HG.bit_length() - 1

    # i < n_live always holds under Mosaic, whose grid ends at n_live;
    # the interpreter's grid is the list's capacity.
    @pl.when(i < nl_r[0])
    def _cell():
        ci = live_r[i]
        pc = ci % (NC + 1)
        r = (ci // (NC + 1)) % R
        start, nt, off = start_r[r], len_r[r], off_r[r]
        # the keys' window starts on a sublane tile; a query window of
        # one token is that token (its HG stacked rows are aligned)
        if masked:
            # the whole step is the window; stacked row j is head j // T,
            # token j % T, so a [T, keys] mask tiles over the heads
            wk = w = wr = 0
            tj = lax.rem(lax.broadcasted_iota(jnp.int32, (rows, 1), 0), T)
        else:
            wk = pl.multiple_of(jnp.minimum((off // 8) * 8, T - Ck), 8)
            w = off if Cq == 1 else wk
            wr = pl.multiple_of(w * HG, HG if Cq == 1 else 8 * HG)
            # stacked row j is token j // HG of the window, head j % HG
            tj = lax.shift_right_logical(
                lax.broadcasted_iota(jnp.int32, (rows, 1), 0), shift)
        trel = w + tj - off
        valid_q = (trel >= 0) & (trel < nt)

        @pl.when((pc == 0) | (start == 0))
        def _first():
            m_s[...] = jnp.full_like(m_s, NEG_INF)
            l_s[...] = jnp.zeros_like(l_s)
            acc_s[...] = jnp.zeros_like(acc_s)

        qs = q_ref[0, pl.ds(wr, rows), :]

        def selected(ref_value):
            return jnp.concatenate([ref_value] * HG, axis=0) > 0

        def flash_update(keys, keep):
            """One online-softmax update over a cell's keys, the scores
            kept where ``keep`` [rows, keys] says.  A stacked row that
            keeps nothing (not this row's token, or a query whose
            selection names no key of the cell: its maximum may still be
            NEG_INF, and exp(s - m) of a masked score would read 1)
            leaves its state as it was: its ``p`` is zeroed."""
            s = lax.dot_general(
                qs, keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            m_prev = m_s[...]
            m_new = jnp.maximum(m_prev, jnp.max(
                jnp.where(keep, s, NEG_INF), -1, keepdims=True))
            p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = corr * l_s[...] + jnp.sum(p, -1, keepdims=True)
            a_new = acc_s[...] * corr + lax.dot_general(
                p.astype(keys.dtype), keys[:, :rank],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m_s[...], l_s[...], acc_s[...] = m_new, l_new, a_new
            return l_new, a_new

        @pl.when(pc < NC)
        def _pool_cell():
            # the cell's G pages as one key block; a page past the row's
            # last repeats it, and lies past ``start``
            keys = jnp.concatenate([ref[0, 0, 0] for ref in pool_refs], 0)
            kpos = pc * Kp + lax.broadcasted_iota(jnp.int32, (1, Kp), 1)
            keep = valid_q & (kpos < start)
            if masked:
                keep = keep & selected(selp_ref[0])
            flash_update(keys, keep)

        @pl.when(pc == NC)
        def _self_cell():
            keys = new_ref[pl.ds(wk, Ck), :]
            krel = wk + lax.broadcasted_iota(jnp.int32, (1, Ck), 1) - off
            keep = valid_q & (krel >= 0) & (krel < nt) & (krel <= trel)
            if masked:
                keep = keep & selected(sels_ref[...])
            l_new, a_new = flash_update(keys, keep)
            o = a_new / jnp.maximum(l_new, 1e-30)
            cur = out_ref[0, pl.ds(wr, rows), :]
            out_ref[0, pl.ds(wr, rows), :] = jnp.where(valid_q, o, cur)


def _latent_call(q, new, pool, layer, rows, block_tables, takes, *,
                 Cq: int, HG: int, scale: float, rank: int, sel=None):
    """One call: the rows ``takes`` marks, through a window of ``Cq``
    tokens, the heads in groups of ``HG``, a pool cell ``G`` pages of the
    row's block table (the pool is passed ``G`` times, a page each).
    Returns [T, H, rank] float32, defined at the tokens of the rows taken
    and nowhere else.  ``sel`` (``(pool [T, maxp * page], self [T, T])``
    bool) keeps a query to the keys it marks; the window is then the
    step."""
    T, H, W = q.shape
    L, _, Pt, page, _ = pool.shape
    row_slot, row_start, row_len, row_off = rows
    R = row_slot.shape[0]
    maxp = block_tables.shape[1]
    G = cell_pages(page, maxp)
    NC = -(-maxp // G)
    NG = H // HG
    assert NG * HG == H and HG & (HG - 1) == 0, (H, HG)
    masked = sel is not None
    if masked:
        assert Cq == T, (Cq, T)
        # [NG, HG * T, W]: stacked row h * T + t of group g
        q2 = q.reshape(T, NG, HG, W).transpose(1, 2, 0, 3).reshape(
            NG, HG * T, W)
        # [NC, T, G * page]: a cell's columns, padded past the table
        sel_pool = jnp.pad(sel[0], ((0, 0), (0, (NC * G - maxp) * page)))
        sel_in = [sel_pool.reshape(T, NC, G * page).transpose(
            1, 0, 2).astype(jnp.float32), sel[1].astype(jnp.float32)]
    else:
        # [NG, T * HG, W]: stacked row t * HG + h of group g
        q2 = q.reshape(T, NG, HG, W).transpose(1, 0, 2, 3).reshape(
            NG, T * HG, W)
        sel_in = []
    live_ci, n_live = live_latent_cells(row_start, row_len, takes, NG,
                                        maxp, page)
    cap = live_ci.shape[0]
    prefetch = [row_slot, row_start, row_len, row_off,
                block_tables.astype(jnp.int32),
                jnp.asarray(layer, jnp.int32).reshape(1), live_ci, n_live]

    def cell(i, live, nl):
        return live[jnp.minimum(i, jnp.maximum(nl[0] - 1, 0))]

    def group_map(i, _s, _st, _ln, _of, _bt, _ly, live, nl):
        return (cell(i, live, nl) // ((NC + 1) * R), 0, 0)

    def pool_map(j):
        def page_j(i, slot_p, start_p, _ln, _of, bt, ly, live, nl):
            ci = cell(i, live, nl)
            r = (ci // (NC + 1)) % R
            # the self cell repeats the row's last pool cell, and a page
            # past the row's last that page: no DMA for either
            last = jnp.minimum(jnp.maximum(start_p[r] - 1, 0) // page,
                               maxp - 1)
            pe = jnp.minimum(
                jnp.minimum(ci % (NC + 1), last // G) * G + j, last)
            return (ly[0], 0, jnp.minimum(bt[slot_p[r], pe], Pt - 1), 0, 0)
        return page_j

    def selp_map(i, *pf):
        live, nl = pf[-2:]
        return (jnp.minimum(cell(i, live, nl) % (NC + 1), NC - 1), 0, 0)

    sel_specs = [pl.BlockSpec((1, T, G * page), selp_map),
                 pl.BlockSpec((T, T), lambda i, *pf: (0, 0))] if masked else []
    interpret = platform.interpret_mode()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(cap if interpret else n_live[0],),
        in_specs=[
            pl.BlockSpec((1, T * HG, W), group_map),
            pl.BlockSpec((T, W), lambda i, *pf: (0, 0)),
        ] + [pl.BlockSpec((1, 1, 1, page, W), pool_map(j))
             for j in range(G)] + sel_specs,
        out_specs=pl.BlockSpec((1, T * HG, rank), group_map),
        scratch_shapes=[
            pltpu.VMEM((Cq * HG, 1), jnp.float32),
            pltpu.VMEM((Cq * HG, 1), jnp.float32),
            pltpu.VMEM((Cq * HG, rank), jnp.float32),
        ],
    )
    kern = functools.partial(
        _latent_kernel, T=T, Cq=Cq, HG=HG, R=R, G=G, NC=NC, page=page,
        rank=rank, scale=scale, masked=masked)
    out = pl.pallas_call(
        kern,
        name="ragged_latent_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((NG, T * HG, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*prefetch, q2, new, *[pool] * G, *sel_in)
    if masked:
        return out.reshape(NG, HG, T, rank).transpose(2, 0, 1, 3).reshape(
            T, H, rank)
    return out.reshape(NG, T, HG, rank).transpose(1, 0, 2, 3).reshape(
        T, H, rank)


def ragged_latent_attention(
        q: jax.Array,            # [T, H, rank + rope]
        new: jax.Array,          # [T, rank + rope]
        pool: jax.Array,         # [L, 1, P, page, rank + rope], scratch last
        layer: jax.Array,
        row_slot, row_start, row_len, row_off,
        block_tables: jax.Array,     # [slots, maxp]
        *, scale: float, rank: int,
        max_row_tokens: Optional[int] = None) -> jax.Array:
    """Causal attention of a ragged token batch over ONE layer of the
    latent pool (picked through the scalar-prefetched ``layer``), float32
    [T, H, rank]; zero at positions no row covers.  ``q`` and ``new``
    share the pool's dtype.  Rows occupy distinct slots."""
    T, H, _ = q.shape
    T_p = _round8(T)
    if T_p != T:
        q = jnp.pad(q, ((0, T_p - T), (0, 0), (0, 0)))
        new = jnp.pad(new, ((0, T_p - T), (0, 0)))
    rows = tuple(a.astype(jnp.int32)
                 for a in (row_slot, row_start, row_len, row_off))
    row_len, row_off = rows[2], rows[3]
    trel = jnp.arange(T_p)[:, None] - row_off[None, :]       # [T, R]
    out = jnp.zeros((T_p, H, rank), jnp.float32)
    # each call's output is defined at its own rows' tokens and nowhere
    # else (a call with no row writes nothing)
    for Cq, HG, which in _calls(T_p, H, max_row_tokens):
        takes = _takes(row_len, which)
        got = _latent_call(q, new, pool, layer, rows, block_tables, takes,
                           Cq=Cq, HG=HG, scale=scale, rank=rank)
        mine = jnp.any((trel >= 0) & (trel < row_len[None, :])
                       & takes[None, :], axis=1)
        out = jnp.where(mine[:, None, None], got, out)
    return out[:T]


# --------------------------------------------------------------------------
# sparse: a selection a query
# --------------------------------------------------------------------------

def selected_rows_attention(q, new, pool, layer, row_slot, row_start,
                            row_off, block_tables, idx, ok, *,
                            scale: float, rank: int) -> jax.Array:
    """Rows of ONE token over the pool rows their selection names:
    ``idx [R, K]`` positions of the row's sequence, ``ok [R, K]`` which
    of them count.  The position ``row_start`` is the row's own fresh
    token (``new``: the pool does not hold it yet).  Reads ``K`` pool
    rows a row and nothing else of the pool.  Returns [R, H, rank]
    float32 (garbage where a row has no selection: the caller keeps it
    to rows of one token)."""
    T = q.shape[0]
    L, _, Pt, page, W = pool.shape
    t = jnp.clip(row_off, 0, T - 1)
    q1, new1 = q[t], new[t]                                   # [R, H, W]
    tables = block_tables[row_slot]                           # [R, maxp]
    pg = jnp.minimum(jnp.take_along_axis(tables, idx // page, axis=1),
                     Pt - 1)
    lat = pool[layer, 0, pg, idx % page]                      # [R, K, W]
    lat = jnp.where((idx == row_start[:, None])[..., None],
                    new1[:, None, :].astype(lat.dtype), lat)
    s = jnp.einsum("rhw,rkw->rhk", q1, lat,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(ok[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("rhk,rkc->rhc", p.astype(lat.dtype), lat[..., :rank],
                      preferred_element_type=jnp.float32)


def ragged_sparse_latent_attention(
        q: jax.Array, new: jax.Array, pool: jax.Array, layer,
        row_slot, row_start, row_len, row_off, block_tables: jax.Array,
        sel, *, scale: float, rank: int) -> jax.Array:
    """``ragged_latent_attention`` with every query kept to the keys a
    selection marks (``ops/dsa_index.Selection``): rows of one token
    through ``selected_rows_attention`` (``sel.one_idx``), the others
    (``sel.more``: the rows the masked walk takes) over their whole
    context once under ``sel.pool`` / ``sel.self`` as a mask, the same
    function.  float32 [T, H, rank]; zero at positions no row covers."""
    T, H, _ = q.shape
    T_p = _round8(T)
    if T_p != T:
        q = jnp.pad(q, ((0, T_p - T), (0, 0), (0, 0)))
        new = jnp.pad(new, ((0, T_p - T), (0, 0)))
    pad = ((0, T_p - T), (0, 0))
    sel_pool = jnp.pad(sel.pool, pad)
    sel_self = jnp.pad(sel.self, ((0, T_p - T), (0, T_p - T)))
    rows = tuple(a.astype(jnp.int32)
                 for a in (row_slot, row_start, row_len, row_off))
    row_slot, row_start, row_len, row_off = rows
    HG = min(H, SPARSE_CHUNK_HEADS)
    out = _latent_call(q, new, pool, layer, rows, block_tables, sel.more,
                       Cq=T_p, HG=HG, scale=scale, rank=rank,
                       sel=(sel_pool, sel_self))
    trel = jnp.arange(T_p)[:, None] - row_off[None, :]       # [T, R]
    mine = jnp.any((trel >= 0) & (trel < row_len[None, :])
                   & sel.more[None, :], axis=1)
    out = jnp.where(mine[:, None, None], out, 0.0)
    one = (row_len == 1) & ~sel.more
    o1 = selected_rows_attention(
        q, new, pool, layer, row_slot, row_start, row_off, block_tables,
        sel.one_idx, sel.one_ok, scale=scale, rank=rank)
    # a row's one token sits at row_off; rows not taken write nowhere
    at = jnp.where(one, row_off, T_p)
    out = out.at[at].set(o1, mode="drop")
    return out[:T]


# --------------------------------------------------------------------------
# the append
# --------------------------------------------------------------------------

def _append_kernel(_slot_r, start_r, len_r, off_r, _bt_r, live_r, nl_r,
                   new_ref, pool_ref, pool_out, *, T: int, Cq: int,
                   page: int, NPR: int):
    i = pl.program_id(1)

    @pl.when(i < nl_r[0])
    def _cell():
        ci = live_r[i]
        r, j = ci // NPR, ci % NPR
        start, nt, off = start_r[r], len_r[r], off_r[r]
        w = pl.multiple_of(jnp.minimum((off // 8) * 8, T - Cq), 8)
        base = (start // page + j) * page
        live = (base < start + nt) & (nt > 0)
        tpage = base + lax.broadcasted_iota(jnp.int32, (page, 1), 0) - start
        mask_w = (tpage >= 0) & (tpage < nt) & live
        krel = w + lax.broadcasted_iota(jnp.int32, (1, Cq), 1) - off
        # one-hot gather of the window's rows into the page's: exact in
        # the pool's own dtype, one term a row
        fresh = new_ref[0, pl.ds(w, Cq), :]
        oh = ((tpage == krel) & (krel >= 0) & (krel < nt) & live)
        got = lax.dot_general(oh.astype(fresh.dtype), fresh,
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
        pool_out[0, 0, 0] = jnp.where(
            mask_w, got, pool_ref[0, 0, 0].astype(jnp.float32)).astype(
                pool_out.dtype)


def ragged_latent_append(pool: jax.Array,     # [L, 1, P, page, W]
                         new: jax.Array,      # [L, T, W]
                         row_slot, row_start, row_len, row_off,
                         block_tables: jax.Array, *,
                         max_row_tokens: Optional[int] = None) -> jax.Array:
    """In-place append of every row's fresh ``c | kr`` into its pages,
    all layers at once: ``ragged_paged_append``'s walk (grid ``(L,
    n_live)`` over ``live_append_cells``) for a pool of one leaf and one
    head."""
    L, _, Pt, page, W = pool.shape
    T = new.shape[1]
    R = row_slot.shape[0]
    maxp = block_tables.shape[1]
    T_p = _round8(T)
    if T_p != T:
        new = jnp.pad(new, ((0, 0), (0, T_p - T), (0, 0)))
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
        start, nt = start_p[r], len_p[r]
        pg = start // page + ci % NPR
        lastp = (start + jnp.maximum(nt, 1) - 1) // page
        pid = jnp.minimum(bt[slot_p[r], jnp.minimum(pg, maxp - 1)], Pt - 1)
        # a cell that writes nothing names the scratch page, never a live
        # one (see ``ragged_paged_attention._ragged_append``)
        live = (i < nl[0]) & (nt > 0) & (pg <= lastp)
        return (l, 0, jnp.where(live, pid, Pt - 1), 0, 0)

    interpret = platform.interpret_mode()
    pool_spec = pl.BlockSpec((1, 1, 1, page, W), pool_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(L, R * NPR if interpret else n_live[0]),
        in_specs=[pl.BlockSpec((1, T_p, W), lambda l, i, *pf: (l, 0, 0)),
                  pool_spec],
        out_specs=pool_spec,
    )
    kern = functools.partial(_append_kernel, T=T_p, Cq=Cq, page=page,
                             NPR=NPR)
    return pl.pallas_call(
        kern,
        name="ragged_latent_append",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={len(prefetch) + 1: 0},
        interpret=interpret,
    )(*prefetch, new.astype(pool.dtype), pool)
