"""Paged attention that selects its keys BY BLOCKS: a query attends to
the ``topk`` pages it scores highest, through a pool of compressed keys
(InfLLM v2, the ``minicpm4`` mixer).

The block is the engine's page, so a selection is a set of pages, per
token and KV head.  For query ``t`` of KV head ``g`` (query heads ``h``
in its group):

    kc_g[j]   = mean(k_g[stride j : stride j + kernel])   complete windows,
                                      visible when stride j + kernel - 1 <= t
    p_h[t, :] = softmax_j(q_h[t] . kc_g[j] * scale)       over the visible j
    r_g[t, j] = sum_h p_h[t, j]
    b_g[t, m] = max r_g[t, j] over the windows that touch block m
    B_g(t)    = the topk blocks m <= t // block of largest b, block 0 and
                the window / block blocks ending at t's own forced

and ``t`` attends to the tokens ``s <= t`` of ``B_g(t)``; below
``dense_len`` to every ``s <= t``.  ``kernel == 2 * stride``, so a window
is two HALVES of ``stride`` tokens and ``q . kc[j] = (q . kh[j] + q .
kh[j + 1]) / 2`` with ``kh[i]`` the mean of tokens ``[stride i, stride
(i + 1))``: the cached leaf holds the halves, one entry a ``stride``
tokens, and no window reaches over a page's edge.

**The compressed pool** ``kh [L, (P + 1) * page / stride, KVH * hd]``
float32 (a row an entry, both KV heads in its lanes: the table of a
lookup, which XLA gathers from and scatters into where it lies; with the
entries of a page as an axis of their own it chose another layout and
copied the pool twice a step) lies under the same block tables as ``k`` /
``v``, entry ``e`` of page ``p`` at row ``p * page / stride + e``, and is
written at the
step's end (``compressed_append``) from the step's group sums: a group
that begins in the step is set, one that continues is added to, so a
page that changes hands needs no clearing.  Inside the layer loop it is
read-only; what the step's own tokens add to a row's halves rides beside
it (``half_keys``), as the fresh k / v ride beside their pools.

**Scores and the top-k** are float32 (``precision=HIGHEST``: a page's
rank is as discontinuous as a router's choice) and plain XLA: a row of
ONE token gathers its row's halves through the block table, a row of
more is scored one row at a time under a dynamic trip count.

**The walk** (kernel ``block_sparse_walk``) is ``ragged_paged_
attention``'s flash walk over LISTS that differ by KV head.  A UNIT is a
(row, KV head); its list is the pages it selected, in ascending order: a
row of one token lists the pages it selected and no other, a row of more
the pages ANY of its tokens selected, and masks each token's own away
inside the cell (``tok_mask``).  A POOL CELL is ``G = cell_pages(maxp)``
consecutive ENTRIES of a unit's list (``CELL_PAGES``: eight pages, 512 keys
at the model's block of 64), whichever pages those are: the ``k`` and
``v`` pools are passed ``G`` times, a page an operand with an index map
each (and for a row of more so is the mask, the block of 128 pages that
holds each entry's), the pages are joined into one ``[G * page, hd]`` key block, and
a cell makes ONE ``[rows, G * page]`` score tile, one mask and ONE update
of the flash state.  A list's tail short of ``G`` is masked inside the
cell and each of its operands stands on the page it held a cell before,
so nothing is fetched for it; the unit's self cell (its fresh tokens,
causally, then the division and the write) does the same with the last
pool cell.  The flash state and the scores lie BY HEAD, ``[QP, Cq, .]``,
so that a mask of ``[Cq, keys]``, the same for every head of a token,
broadcasts over the stacked heads.  So a row of one token walks
``ceil(topk / G)`` pool cells a KV head whatever its context.  The house
rules of ``ops/ragged_paged_attention.py`` hold: lists under a dynamic
bound, pools read-only, one aliased append at the step's end.

What the walk counts (``block_sparse_attention`` returns both, the
model's cache keeps their sums in ``sel_pages`` and ``walk_cells``, ``[2]``
each: by rows of one token, by rows of more): the PAGES its lists hold,
which for a row of one token is what it selected of the pages that hold
pooled tokens (``walk_page_count`` on the host) and for a row of more
the union of its tokens' picks, no page beside them; and the pool CELLS
it walked them in, ``ceil(pages / G)`` a unit: pages over cells is the
pages a cell holds, ``G`` at best.

``G`` is a constant of the op, chosen on a v5e (PERF.md PR 42; ONE
layer, ms, the op with its lists, median of five; PR 41's one-page cell
first, then this walk at 1 / 2 / 4 / 8 pages a cell):

    a 512-token row at 16k of context     6.97    8.04 / 4.09 / 4.83 / 2.86
    the same at 64k                      27.20   31.39 / 15.72 / 18.57 / 10.67
    eight one-token rows past 16,384      0.954   0.619 / 0.466 / 0.378 / 0.357

A chunk cell pays for its ``[8320, .]`` float32 tiles by the 128 LANES:
a cell of one page and of two cost the same 15.3 us, of four 36, of
eight 42, so eight pages are 5.2 us a page where PR 41's cell was 13.3.
The one-token call's 16 stacked rows pay about 0.3 us a grid step (1,040
steps at one page a cell, 144 at eight); the 0.31 ms left at eight are
its 1,024 pages' DMAs and the lists' XLA.  Both calls want the eight, so
``G`` is no function of the call; ``ops/latent_attention.cell_pages``
holds four (its ``[4160, 512]`` tiles lost at eight), so this op keeps
its own two lines.  The constant counts PAGES: the table was measured at
the published block of 64, the only page the engine takes for the model
(``minicpm_sala.init_cache``), and a test's toy pages walk the same
eight operands a cell as the chip does.

A row's fresh tokens must lie inside their own forced window, so that
the self cell is plain causal attention: ``row_len <= max_row_tokens(sp)
= window - block + 1`` (a chunk of 512 under a window of 2048).  The
engine holds its token budget to that (``PagedEngineAdapter.
max_row_tokens``); past it a query beyond ``dense_len`` would attend to
fresh keys of a block it did not select.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import platform
from ray_tpu.ops.dsa_index import topk_masks
from ray_tpu.ops.paged_attention import NEG_INF
from ray_tpu.ops.power_retention import token_rows
from ray_tpu.ops.ragged_paged_attention import (
    VMEM_LIMIT,
    _listed,
    _round8,
    window_size,
)

_HI = lax.Precision.HIGHEST
_FORCED = 1e30
# entries of a (row, KV head)'s list of selected pages that a pool cell of
# the walk spans.  The table is in the module docstring.
CELL_PAGES = 8


def cell_pages(maxp: int) -> int:
    """G, the entries a pool cell of the walk spans: ``CELL_PAGES``, and no
    more than a block table has."""
    return max(1, min(CELL_PAGES, maxp))


@dataclasses.dataclass(frozen=True)
class BlockSparse:
    """MiniCPM4's published ``sparse_config``."""
    block: int = 64
    kernel: int = 32
    stride: int = 16
    topk: int = 64
    window: int = 2048
    init_blocks: int = 1
    dense_len: int = 8192

    def __post_init__(self):
        assert self.kernel == 2 * self.stride, "windows of two halves"
        assert self.block % self.stride == 0

    @property
    def entries(self) -> int:
        """Halves a page holds."""
        return self.block // self.stride

    @property
    def max_row_tokens(self) -> int:
        """The longest row of fresh tokens a step may carry: all of it
        inside the forced window of its last token."""
        return self.window - self.block + 1


# --------------------------------------------------------------------------
# the step's groups of ``stride`` tokens: sums, and the append
# --------------------------------------------------------------------------

class Groups(NamedTuple):
    """The (row, half) pairs a step's fresh tokens fall into, in buffer
    order, padded to a static count ``NG``."""
    onehot: jax.Array     # [NG, T] float32: token t belongs to group g
    row: jax.Array        # [NG] the packed row
    idx: jax.Array        # [NG] the half's index in its sequence
    begins: jax.Array     # [NG] its first token is this step's
    valid: jax.Array      # [NG]


def step_groups(row_start, row_len, row_off, T: int, stride: int) -> Groups:
    """The same in every layer: a step builds it once."""
    R = row_start.shape[0]
    NG = _round8(T // stride + 2 * R)
    tok_row, valid = token_rows(row_len, row_off, T)
    t = jnp.arange(T, dtype=jnp.int32)
    pos = row_start[tok_row] + t - row_off[tok_row]
    first = valid & ((t == row_off[tok_row]) | (pos % stride == 0))
    gid = jnp.cumsum(first) - 1
    (at,) = jnp.nonzero(first, size=NG, fill_value=T)
    ok = at < T
    at = jnp.minimum(at, T - 1)
    onehot = ((gid[None, :] == jnp.arange(NG)[:, None])
              & valid[None, :]).astype(jnp.float32)
    return Groups(onehot, tok_row[at], pos[at] // stride,
                  pos[at] % stride == 0, ok)


def group_sums(k_new: jax.Array, groups: Groups, stride: int) -> jax.Array:
    """``k_new`` [T, KVH, hd] -> what the step adds to each group's mean
    ``[NG, KVH, hd]`` float32."""
    return jnp.einsum("gt,tkd->gkd", groups.onehot,
                      k_new.astype(jnp.float32), precision=_HI) / stride


def compressed_append(kh_pool: jax.Array, sums: jax.Array, groups: Groups,
                      row_slot, block_tables, sp: BlockSparse) -> jax.Array:
    """``kh_pool`` [L, (P + 1) * E, KVH * hd] with the step's ``sums``
    [L, NG, KVH, hd] written: a group that begins in the step is set, one
    that continues added to.  In place where the pool is donated."""
    L, rows, lanes = kh_pool.shape
    E = sp.entries
    maxp = block_tables.shape[1]
    page_i = jnp.clip(groups.idx // E, 0, maxp - 1)
    pid = jnp.clip(block_tables[row_slot[groups.row], page_i], 0,
                   rows // E - 1)
    # what belongs to no group lands in the scratch page
    at = jnp.where(groups.valid, pid * E + groups.idx % E, rows - E)
    old = kh_pool[:, at]                               # [L, NG, KVH * hd]
    new = (jnp.where(groups.begins[None, :, None], 0.0, old)
           + sums.reshape(L, -1, lanes))
    return kh_pool.at[:, at].set(new.astype(kh_pool.dtype))


def half_keys(kh_pool: jax.Array, layer, tables: jax.Array,
              starts: jax.Array, row_ids: jax.Array, sums_l: jax.Array,
              groups: Groups, sp: BlockSparse) -> jax.Array:
    """The halves ``[N, NE, KVH * hd]`` of N rows as their queries see
    them (both KV heads in a row's lanes, as the pool holds them: an axis
    of two beside the lanes is one XLA lays out elsewhere and copies): layer ``layer`` of the pool ``kh_pool`` [L, (P + 1) * E, KVH *
    hd] through the rows' block tables ``tables`` [N, maxp] (one gather:
    a slice of the layer first would be a copy of it), kept where the
    half began before the step (``starts`` [N]), plus what the step's own
    tokens add (``sums_l`` [NG, KVH, hd])."""
    N, maxp = tables.shape
    NG, KVH, hd = sums_l.shape
    E = sp.entries
    NE = maxp * E
    pages = jnp.clip(tables, 0, kh_pool.shape[1] // E - 1)
    at = (pages[:, :, None] * E + jnp.arange(E)[None, None, :]).reshape(N, NE)
    pooled = kh_pool[layer, at]                        # [N, NE, KVH * hd]
    e = jnp.arange(NE, dtype=jnp.int32)
    kept = e[None, :] * sp.stride < starts[:, None]    # [N, NE]
    lands = ((groups.idx[None, None, :] == e[None, :, None])
             & (groups.row[None, None, :] == row_ids[:, None, None])
             & groups.valid[None, None, :]).astype(jnp.float32)
    fresh = jnp.einsum("neg,gl->nel", lands, sums_l.reshape(NG, KVH * hd),
                       precision=_HI)
    return jnp.where(kept[:, :, None], pooled, 0.0) + fresh


# --------------------------------------------------------------------------
# scores and the selection
# --------------------------------------------------------------------------

def compressed_keys(kh: jax.Array) -> jax.Array:
    """``kc[j] = (kh[j] + kh[j + 1]) / 2`` along the entries' axis (the
    last but one): a window of two halves.  The last entry wraps; its
    window is complete for no query."""
    return 0.5 * (kh + jnp.roll(kh, -1, axis=-2))


def _window_weights(s: jax.Array, t_pos: jax.Array, sp: BlockSparse):
    """``r`` [M, NE]: the softmax of ``s`` [M, G, NE] over the windows
    visible at ``t_pos`` [M], summed over the G heads of ONE KV head.
    Queries are rows and windows lanes, a head group at a time: with
    both KV heads in one array XLA laid the windows out behind the heads
    and the softmax ran at a hundredth of the memory's rate."""
    NE = s.shape[-1]
    e = jnp.arange(NE, dtype=jnp.int32)
    vis = (e[None, :] * sp.stride + sp.kernel - 1
           <= t_pos[:, None])[:, None, :]
    s = jnp.where(vis, s, NEG_INF)
    p = jnp.where(vis, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.sum(p, axis=1)


def _blocks_of(r: jax.Array, sp: BlockSparse) -> jax.Array:
    """``b`` [M, KVH, maxp] from ``r`` [M, KVH, NE]: a block scores the
    largest weight among the windows that touch it, its own E and the
    one before them."""
    M, KVH, NE = r.shape
    E = sp.entries
    r = r.reshape(M, KVH, NE // E, E)
    before = jnp.pad(r[:, :, :-1, E - 1], ((0, 0), (0, 0), (1, 0)))
    return jnp.maximum(jnp.max(r, axis=-1), before)


def block_scores(q: jax.Array, kh: jax.Array, t_pos: jax.Array,
                 sp: BlockSparse, scale: float) -> jax.Array:
    """``b`` [M, KVH, maxp] float32 for M query tokens of ONE sequence:
    ``q`` [M, KVH, G, hd] float32, their positions ``t_pos`` [M], the
    sequence's halves ``kh`` [NE, KVH * hd] (``half_keys``).  One plain
    product a KV head, ``[M * G, hd] x [hd, NE]``."""
    M, KVH, G, hd = q.shape
    kc = compressed_keys(kh)
    r = [_window_weights(
        jnp.dot(q[:, g].reshape(M * G, hd), kc[:, g * hd:(g + 1) * hd].T,
                precision=_HI).reshape(M, G, -1) * scale, t_pos, sp)
         for g in range(KVH)]
    return _blocks_of(jnp.stack(r, axis=1), sp)


def row_block_scores(q: jax.Array, kh: jax.Array, t_pos: jax.Array,
                     sp: BlockSparse, scale: float) -> jax.Array:
    """``block_scores`` for N rows of ONE token, each against its own
    sequence's halves: ``q`` [N, KVH, G, hd], ``kh`` [N, NE, KVH * hd],
    ``t_pos`` [N].  One plain product a KV head over ALL rows' windows,
    ``[N * G, hd] x [hd, N * NE]``, of which a row keeps its own block: N
    times the products (33 MFLOP a layer at eight rows), and no batch of
    small products, which took a millisecond a layer."""
    N, KVH, G, hd = q.shape
    NE = kh.shape[1]
    kc = compressed_keys(kh)
    own = jnp.arange(N)
    r = []
    for g in range(KVH):
        keys = kc[:, :, g * hd:(g + 1) * hd].reshape(N * NE, hd)
        s = jnp.dot(q[:, g].reshape(N * G, hd), keys.T, precision=_HI)
        s = s.reshape(N, G, N, NE)[own, :, own, :] * scale     # [N, G, NE]
        r.append(_window_weights(s, t_pos, sp))
    return _blocks_of(jnp.stack(r, axis=1), sp)


def select_blocks(b: jax.Array, t_pos: jax.Array, sp: BlockSparse):
    """The blocks each query attends to, ``bool[M, KVH, maxp]``, from
    ``b`` [M, KVH, maxp]: every block up to its own below ``dense_len``,
    else the ``topk`` of largest ``b`` with the forced ones among them
    (ties by lower block)."""
    maxp = b.shape[-1]
    m = jnp.arange(maxp, dtype=jnp.int32)[None, None, :]
    own = (t_pos // sp.block)[:, None, None]
    upto = m <= own
    forced = (m < sp.init_blocks) | (m > own - sp.window // sp.block)
    cand = jnp.where(upto, jnp.where(forced, _FORCED, b), -1.0)
    shape = cand.shape
    cand, ok = cand.reshape(-1, maxp), jnp.broadcast_to(upto, shape).reshape(
        -1, maxp)
    # the k-th largest by bisection (``dsa_index.topk_masks``: 32 counts
    # over [queries, maxp], where a sort of a chunk's 1040 x 1040 took
    # 2.9 ms a layer), then the ties at it in order of their blocks
    (atleast,) = topk_masks([(cand, ok)], sp.topk)
    kth = jnp.min(jnp.where(atleast, cand, jnp.inf), axis=-1, keepdims=True)
    above = atleast & (cand > kth)
    ties = atleast & (cand == kth)
    room = (jnp.minimum(jnp.sum(ok, axis=-1, keepdims=True), sp.topk)
            - jnp.sum(above, axis=-1, keepdims=True))
    sel = (above | (ties & (jnp.cumsum(ties, axis=-1) <= room))).reshape(shape)
    return jnp.where((t_pos < sp.dense_len)[:, None, None], upto, sel)


def select_pages(q: jax.Array, kh_pool: jax.Array, layer,
                 sums_l: jax.Array, groups: Groups, row_slot, row_start,
                 row_len, row_off, block_tables, sp: BlockSparse) -> jax.Array:
    """The pages every packed token attends to, ``bool[T, KVH, maxp]``,
    in ONE layer: ``q`` [T, KVH, G, hd] (normed), layer ``layer`` of the
    compressed pool ``kh_pool`` [L, (P + 1) * E, KVH * hd] and the step's
    group sums ``sums_l``.  Rows of one token are scored together, each against its
    own row's halves; rows of more one at a time, under a dynamic trip
    count, and only where the row reaches past ``dense_len``."""
    T, KVH, G, hd = q.shape
    R, maxp = row_slot.shape[0], block_tables.shape[1]
    T_p = _round8(T)
    Cq = window_size(T_p, None)
    scale = hd ** -0.5
    i32 = jnp.int32
    qf = jnp.pad(q.astype(jnp.float32),
                 ((0, T_p - T), (0, 0), (0, 0), (0, 0)))
    at = jnp.clip(row_off, 0, T - 1)
    with jax.named_scope("bsa_compress"):
        kh_rows = half_keys(kh_pool, layer, block_tables[row_slot], row_start,
                            jnp.arange(R, dtype=i32), sums_l, groups, sp)
    with jax.named_scope("bsa_score"):
        b1 = row_block_scores(qf[at], kh_rows, row_start, sp, scale)
    with jax.named_scope("bsa_select"):
        tok_row, valid = token_rows(row_len, row_off, T_p)
        mask = (select_blocks(b1, row_start, sp)[tok_row]
                & (valid & (row_len == 1)[tok_row])[:, None, None])
    rows_l, n_many = _listed(row_len > 1)
    m_idx = jnp.arange(maxp, dtype=i32)[None, None, :]

    def body(i, mask):
        r = rows_l[i]
        start, n, off = row_start[r], row_len[r], row_off[r]
        w = jnp.minimum((off // 8) * 8, T_p - Cq)
        t_pos = start + w + jnp.arange(Cq, dtype=i32) - off
        inside = (t_pos >= start) & (t_pos < start + n)

        def sparse():
            with jax.named_scope("bsa_compress"):
                kh = half_keys(kh_pool, layer, block_tables[row_slot[r]][None],
                               start[None], r[None], sums_l, groups, sp)[0]
            with jax.named_scope("bsa_score"):
                qw = lax.dynamic_slice(qf, (w, 0, 0, 0), (Cq, KVH, G, hd))
                b = block_scores(qw, kh, t_pos, sp, scale)
            with jax.named_scope("bsa_select"):
                return select_blocks(b, t_pos, sp)

        def dense():
            return jnp.broadcast_to(
                m_idx <= (t_pos // sp.block)[:, None, None], (Cq, KVH, maxp))

        picked = lax.cond(start + n > sp.dense_len, sparse, dense)
        old = lax.dynamic_slice(mask, (w, 0, 0), (Cq, KVH, maxp))
        return lax.dynamic_update_slice(
            mask, jnp.where(inside[:, None, None], picked, old), (w, 0, 0))

    return lax.fori_loop(0, n_many[0], body, mask)[:T]


def sel_token_count(row_start, row_len, sp: BlockSparse) -> int:
    """Keys a step's query tokens attend to, summed, on the host:
    ``t + 1`` below ``dense_len``, else the tokens ``s <= t`` of its
    ``topk`` blocks."""
    total = 0
    for start, n in zip(row_start, row_len):
        if int(n) <= 0:
            continue
        t = np.arange(int(start), int(start) + int(n), dtype=np.int64)
        blocks = np.minimum(t // sp.block + 1, sp.topk)
        sparse = (blocks - 1) * sp.block + t % sp.block + 1
        total += int(np.sum(np.where(t < sp.dense_len, t + 1, sparse)))
    return total


def walk_page_count(row_start, row_len, kv_heads: int, sp: BlockSparse,
                    page: int) -> int:
    """Pool pages the walk reads in ONE layer for a step's packed rows,
    summed over rows and KV heads, on the host: exact for a row of one
    token (what it selected of the pages that hold pooled tokens), the
    whole context for a row of more (the union of its tokens' picks is
    data; this is its bound)."""
    total = 0
    for start, n in zip(row_start, row_len):
        start, n = int(start), int(n)
        if n <= 0:
            continue
        pooled = -(-start // page)
        if n == 1 and start >= sp.dense_len:
            # its topk blocks, less its own where that holds no pooled
            # token yet
            pooled = min(pooled, sp.topk - (start % page == 0))
        total += kv_heads * pooled
    return total


# --------------------------------------------------------------------------
# the walk
# --------------------------------------------------------------------------

def walk_lists(tok_mask: jax.Array, in_row: jax.Array, row_start,
               takes, maxp: int, page: int, G: int):
    """The lists one call walks, for the rows ``takes`` [R]; a UNIT is a
    (row, KV head), ``u = r * KVH + g``:

    ``ent`` ``int32[U * maxp]``  unit ``u``'s selected pages (indices into
        the row's block table) in ascending order from ``u * maxp``: a
        page is listed where it holds pooled tokens of the row and any of
        the row's tokens (``in_row`` [T, R]) selected it (``tok_mask``
        [KVH, T, MP] float32).  A sort of each unit's ``maxp`` candidates:
        29 us for 16 units of 1040 where ``jnp.nonzero`` over them took
        185, a scatter of every candidate (PERF.md PR 42);
    ``cnt`` ``int32[U]``  a unit's entries;
    ``live_ci`` ``int32[U * (NC + 1)]``, ``n_live`` ``int32[1]``  the grid:
        cell ``u * (NC + 1) + c`` is entries ``c * G`` to ``c * G + G - 1``
        of unit ``u`` (``NC = ceil(maxp / G)``), or its self cell at ``c ==
        NC``; a unit's ``ceil(cnt / G)`` pool cells are adjacent and end
        with its self cell;
    ``n_pages``, ``n_cells`` ``int32[]``  entries and pool cells in all."""
    i32 = jnp.int32
    KVH = tok_mask.shape[0]
    NC = -(-maxp // G)
    taken = (in_row & takes[None, :]).astype(jnp.float32)
    picked = jnp.einsum("tr,gtm->rgm", taken, tok_mask[:, :, :maxp]) > 0.0
    m = jnp.arange(maxp, dtype=i32)
    pooled = m[None, :] * page < row_start[:, None]
    pool = (picked & pooled[:, None, :] & takes[:, None, None]).reshape(
        -1, maxp)                                                 # [U, maxp]
    ent = jnp.minimum(jnp.sort(jnp.where(pool, m[None, :], maxp), axis=-1),
                      maxp - 1)
    cnt = jnp.sum(pool, axis=-1, dtype=i32)
    U = cnt.shape[0]
    cells = -(-cnt // G)
    steps = cells + jnp.repeat(takes.astype(i32), KVH)
    end = jnp.cumsum(steps)
    # grid step i is a cell of the unit whose steps hold it
    i = jnp.arange(U * (NC + 1), dtype=i32)
    u = jnp.minimum(jnp.sum(i[:, None] >= end[None, :], axis=1, dtype=i32),
                    U - 1)
    mine = u[:, None] == jnp.arange(U, dtype=i32)[None, :]
    c = i - jnp.sum(jnp.where(mine, (end - steps)[None, :], 0), axis=1)
    pool_c = jnp.sum(jnp.where(mine, cells[None, :], 0), axis=1)
    live_ci = u * (NC + 1) + jnp.where(c < pool_c, c, NC)
    return (ent.reshape(-1), cnt, live_ci, end[-1:], jnp.sum(cnt),
            jnp.sum(cells))


def _div(a, b: int):
    """``a // b`` and, below, ``a % b`` of what is never negative: the
    floored forms lower to a division, a sign and a select each, a few
    hundred times over a call's index maps, which a step's set-up pays."""
    return lax.div(a, jnp.int32(b))


def _rem(a, b: int):
    return lax.rem(a, jnp.int32(b))


def _entry(ci, j: int, cnt_r, ent_r, *, G: int, NC: int, maxp: int):
    """The page (index into the row's block table) under operand ``j`` of
    cell ``ci``: entry ``c * G + j`` of its unit's list.  The self cell
    stands on the unit's last pool cell, and an entry past the list's end
    on the one ``G`` before it: the operand's page of the step before, so
    neither is fetched (the kernel masks both)."""
    u, c = _div(ci, NC + 1), _rem(ci, NC + 1)
    n = cnt_r[u]
    c = jnp.minimum(c, jnp.maximum(_div(n + G - 1, G) - 1, 0))
    e = c * G + j
    return ent_r[u * maxp + jnp.where(e < n, e, jnp.maximum(e - G, 0))]


def _walk_kernel(slot_r, start_r, len_r, off_r, bt_r, ly_r, live_r, nl_r,
                 cnt_r, ent_r, q_ref, kn_ref, vn_ref, *refs, T: int, Cq: int,
                 KVH: int, QP: int, hd: int, page: int, G: int, NC: int,
                 maxp: int, scale: float):
    del slot_r, bt_r, ly_r      # the index maps' own
    msk_refs, refs = (refs[:G], refs[G:]) if Cq > 1 else ((), refs)
    kp_refs, vp_refs = refs[:G], refs[G:2 * G]
    out_ref, m_s, l_s, acc_s = refs[2 * G:]
    i = pl.program_id(0)
    rows = QP * Cq
    Ck = max(Cq, 8)
    Kp = G * page               # a pool cell's keys
    f32 = jnp.float32

    def by_head(x):
        """``[rows, n]`` as ``[QP, Cq, n]`` (a window is whole sublane
        tiles, so no element moves): what is the same for every head of
        a token, ``[Cq, n]``, then broadcasts over the heads."""
        return x if Cq == 1 else x.reshape(QP, Cq, x.shape[-1])

    # i < n_live always holds under Mosaic, whose grid ends at n_live;
    # the interpreter's grid is the list's capacity.
    @pl.when(i < nl_r[0])
    def _cell():
        ci = live_r[i]
        u, pc = _div(ci, NC + 1), _rem(ci, NC + 1)
        r, g = _div(u, KVH), _rem(u, KVH)
        start, nt, off = start_r[r], len_r[r], off_r[r]
        wk = pl.multiple_of(jnp.minimum(_div(off, 8) * 8, T - Ck), 8)
        w = off if Cq == 1 else wk
        # the window's tokens, row-relative, and which of them are the row's
        trel = w + lax.broadcasted_iota(jnp.int32, (Cq, 1), 0) - off
        valid_q = (trel >= 0) & (trel < nt)
        # the window's stacked queries [rows, hd], head-major
        qs = (q_ref[g, w] if Cq == 1 else
              q_ref[g, :, pl.ds(w, Cq), :].reshape(rows, hd))

        @pl.when((i == 0) | (_div(live_r[jnp.maximum(i - 1, 0)], NC + 1) != u))
        def _first():
            m_s[...] = jnp.full_like(m_s, NEG_INF)
            l_s[...] = jnp.zeros_like(l_s)
            acc_s[...] = jnp.zeros_like(acc_s)

        def flash_update(keys, vals, keep):
            """One online-softmax update over a cell's keys; ``keep``
            ``[Cq, keys]`` is every head's."""
            s = by_head(lax.dot_general(
                qs, keys, (((1,), (1,)), ((), ())),
                preferred_element_type=f32) * scale)
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m_s[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # a stacked row the mask leaves nothing adds nothing
            p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = corr * l_s[...] + jnp.sum(p, axis=-1, keepdims=True)
            a_new = acc_s[...] * corr + by_head(jnp.dot(
                p.astype(vals.dtype).reshape(rows, -1), vals,
                preferred_element_type=f32))
            m_s[...], l_s[...], acc_s[...] = m_new, l_new, a_new
            return l_new, a_new

        # ---- pool cell: G selected pages of the row's PAST -----------
        @pl.when(pc < NC)
        def _pool_cell():
            lane = lax.broadcasted_iota(jnp.int32, (1, Kp), 1)
            held = _div(lane, page)     # the operand a key came under
            pages = [_entry(ci, j, cnt_r, ent_r, G=G, NC=NC, maxp=maxp)
                     for j in range(G)]
            pg = pages[0]
            for j in range(1, G):
                pg = jnp.where(held == j, pages[j], pg)
            # an entry past the list's end repeats a page: masked
            keep = (valid_q & (pg * page + _rem(lane, page) < start)
                    & (held < cnt_r[u] - pc * G))
            if msk_refs:                # each token's own selection
                lane128 = lax.broadcasted_iota(jnp.int32, (Cq, 128), 1)
                sel = jnp.zeros((Cq, Kp), f32)
                for j in range(G):
                    blk = msk_refs[j][0, pl.ds(w, Cq), :]      # [Cq, 128]
                    col = jnp.sum(
                        jnp.where(lane128 == _rem(pages[j], 128), blk, 0.0),
                        axis=1, keepdims=True)
                    sel = jnp.where(held == j, col, sel)
                keep = keep & (sel > 0.0)
            flash_update(jnp.concatenate([k[0, 0, 0] for k in kp_refs], 0),
                         jnp.concatenate([v[0, 0, 0] for v in vp_refs], 0),
                         keep)

        # ---- self cell: intra-row causal attention + finalize --------
        @pl.when(pc == NC)
        def _self_cell():
            krel = wk + lax.broadcasted_iota(jnp.int32, (1, Ck), 1) - off
            l_new, a_new = flash_update(
                kn_ref[g, pl.ds(wk, Ck), :], vn_ref[g, pl.ds(wk, Ck), :],
                valid_q & (krel >= 0) & (krel < nt) & (krel <= trel))
            o = a_new / jnp.maximum(l_new, 1e-30)
            at = (g, w) if Cq == 1 else (g, slice(None), pl.ds(w, Cq))
            out_ref[at] = jnp.where(valid_q, o, out_ref[at])


def _walk_call(q, k_new, v_new, tok_mask, k_pools, v_pools, rows, lists, *,
               Cq: int, G: int):
    """One call: the units whose cells ``lists`` names (``walk_lists``),
    through a window of ``Cq`` tokens, a pool cell ``G`` entries of the
    unit's list (the pools are passed ``G`` times, a page each, and where
    ``Cq > 1`` so is the mask, the block of 128 pages that holds each
    entry's).  ``q`` is ``[KVH, T, QP, hd]`` where ``Cq == 1`` and ``[KVH,
    QP, T, hd]`` otherwise; the output has its layout, float32, defined at
    the tokens of the rows walked."""
    KVH, hd = q.shape[0], q.shape[-1]
    T, QP = (q.shape[1:3] if Cq == 1 else q.shape[2:0:-1])
    Pt, page = k_pools.shape[2:4]
    maxp = rows[4].shape[1]
    NC = -(-maxp // G)
    ent, cnt, live_ci, n_live = lists
    # the flash state, by head where a window holds more than one token
    state = (QP,) if Cq == 1 else (QP, Cq)
    prefetch = rows + [live_ci, n_live, cnt, ent]

    def whole(ndim):
        return lambda i, *pf: (0,) * ndim

    def entry(i, j, live, nl, *unit_lists):
        ci = live[jnp.minimum(i, jnp.maximum(nl[0] - 1, 0))]
        return _div(ci, NC + 1), _entry(ci, j, *unit_lists, G=G, NC=NC,
                                        maxp=maxp)

    def pool_map(j):
        def page_j(i, slot_p, _st, _ln, _of, bt, ly, *lists_p):
            u, pe = entry(i, j, *lists_p)
            return (ly[0], _rem(u, KVH),
                    jnp.clip(bt[slot_p[_div(u, KVH)], pe], 0, Pt - 1), 0, 0)
        return page_j

    def mask_map(j):
        def block_j(i, _s, _st, _ln, _of, _bt, _ly, *lists_p):
            u, pe = entry(i, j, *lists_p)
            return (_rem(u, KVH), 0, _div(pe, 128))
        return block_j

    in_specs = [pl.BlockSpec(q.shape, whole(4)),
                pl.BlockSpec(k_new.shape, whole(3)),
                pl.BlockSpec(v_new.shape, whole(3))]
    operands = [q, k_new, v_new]
    if Cq > 1:
        in_specs += [pl.BlockSpec((1, T, 128), mask_map(j)) for j in range(G)]
        operands += [tok_mask] * G
    in_specs += [pl.BlockSpec((1, 1, 1, page, hd), pool_map(j))
                 for _pool in (k_pools, v_pools) for j in range(G)]
    interpret = platform.interpret_mode()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(live_ci.shape[0] if interpret else n_live[0],),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(q.shape, whole(4)),
        scratch_shapes=[pltpu.VMEM(state + (n,), jnp.float32)
                        for n in (1, 1, hd)],
    )
    kern = functools.partial(
        _walk_kernel, T=T, Cq=Cq, KVH=KVH, QP=QP, hd=hd, page=page, G=G,
        NC=NC, maxp=maxp, scale=hd ** -0.5)
    return pl.pallas_call(
        kern,
        name="block_sparse_walk",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*prefetch, *operands, *[k_pools] * G, *[v_pools] * G)


def block_sparse_attention(
    q: jax.Array,            # [T, H, hd]
    k_new: jax.Array,        # [T, KVH, hd]
    v_new: jax.Array,
    k_pools: jax.Array,      # [L, KVH, P + 1, page, hd]
    v_pools: jax.Array,
    layer: jax.Array,
    row_slot: jax.Array,     # [R]
    row_start: jax.Array,
    row_len: jax.Array,
    row_off: jax.Array,
    block_tables: jax.Array,  # [slots, maxp]
    tok_mask: jax.Array,     # [T, KVH, maxp] bool: token t attends page m
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Causal attention of a ragged token batch against the pages each
    token selected, ONE layer's pools.  Returns (out [T, H, hd] float32,
    zero where no row covers; pool pages read ``int32[2]``: by the rows
    of one token, by the rows of more; pool cells walked ``int32[2]``, the
    same two).  The pages are the lists' entries: what a row of one token
    selected, and for a row of more the union of its tokens' picks, no
    page beside them.  Two calls, chosen by ``row_len``, as
    ``ragged_paged_attention``."""
    return _attention(
        q, k_new, v_new, k_pools, v_pools, layer, row_slot, row_start,
        row_len, row_off, block_tables, tok_mask,
        per_cell=cell_pages(block_tables.shape[1]))


# a function of its own under ``jit``, so that a step's sparse layers share
# ONE trace and ONE lowering of the two kernels: with a page an operand, a
# kernel's trace and its 2 or 3 x G index maps are set-up time a layer, and
# a serving cell's set-up lowers six steps (PERF.md PR 42)
@functools.partial(jax.jit, static_argnames=("per_cell",))
def _attention(q, k_new, v_new, k_pools, v_pools, layer, row_slot, row_start,
               row_len, row_off, block_tables, tok_mask, *, per_cell: int):
    T, H, hd = q.shape
    KVH, page = k_pools.shape[1], k_pools.shape[3]
    maxp = block_tables.shape[1]
    G = H // KVH
    QP = _round8(G)
    T_p = _round8(T)
    MP = -(-maxp // 128) * 128
    i32 = jnp.int32
    row_slot, row_start, row_len, row_off = (
        a.astype(i32) for a in (row_slot, row_start, row_len, row_off))
    mask = jnp.pad(tok_mask.astype(jnp.float32),
                   ((0, T_p - T), (0, 0), (0, MP - maxp))).transpose(1, 0, 2)
    qg = jnp.pad(q.reshape(T, KVH, G, hd),
                 ((0, T_p - T), (0, 0), (0, QP - G), (0, 0)))
    k_new, v_new = (jnp.pad(a, ((0, T_p - T), (0, 0), (0, 0))
                            ).transpose(1, 0, 2) for a in (k_new, v_new))
    rows = [row_slot, row_start, row_len, row_off,
            block_tables.astype(i32), jnp.asarray(layer, i32).reshape(1)]
    t = jnp.arange(T_p, dtype=i32)[:, None] - row_off[None, :]
    in_row = (t >= 0) & (t < row_len[None, :])                 # [T_p, R]
    out = jnp.zeros((T_p, KVH, QP, hd), jnp.float32)
    pages, cells = [], []
    Cq = window_size(T_p, None)
    for cq, takes in ((1, row_len == 1), (Cq, row_len > 1)):
        *lists, n_pages, n_cells = walk_lists(mask, in_row, row_start, takes,
                                              maxp, page, per_cell)
        pages.append(n_pages)
        cells.append(n_cells)
        if cq == 1:
            got = _walk_call(qg.transpose(1, 0, 2, 3), k_new, v_new, None,
                             k_pools, v_pools, rows, lists, Cq=1,
                             G=per_cell).transpose(1, 0, 2, 3)
        else:
            got = _walk_call(qg.transpose(1, 2, 0, 3), k_new, v_new, mask,
                             k_pools, v_pools, rows, lists, Cq=cq,
                             G=per_cell).transpose(2, 0, 1, 3)
        mine = jnp.any(in_row & takes[None, :], axis=1)
        out = jnp.where(mine[:, None, None, None], got, out)
    return (out[:T, :, :G].reshape(T, H, hd), jnp.stack(pages),
            jnp.stack(cells))


def block_sparse_attention_reference(q, k_new, v_new, k_pages, v_pages,
                                     row_slot, row_start, row_len, row_off,
                                     block_tables, tok_mask):
    """Dense gather reference of ``block_sparse_attention``, ONE layer's
    pools ``[KVH, P + 1, page, hd]``: each row's fresh tokens over the
    pooled tokens of the pages they selected plus the row's own fresh
    tokens, causally; float32 ``[T, H, hd]``."""
    T, H, hd = q.shape
    KVH, P1, page, _ = k_pages.shape
    maxp = block_tables.shape[1]
    G = H // KVH
    f32 = jnp.float32
    out = jnp.zeros((T, H, hd), f32)
    qs = q.astype(f32)
    ti = jnp.arange(T)
    kpos = jnp.arange(maxp * page)
    sel = jnp.repeat(jnp.repeat(tok_mask, page, axis=2), G, axis=1)
    for r in range(int(row_slot.shape[0])):
        pages = jnp.clip(block_tables[row_slot[r]], 0, P1 - 1)
        kc, vc = (jnp.repeat(a.astype(f32)[:, pages].transpose(1, 2, 0, 3)
                             .reshape(maxp * page, KVH, hd), G, axis=1)
                  for a in (k_pages, v_pages))
        kn, vn = (jnp.repeat(a.astype(f32), G, axis=1)
                  for a in (k_new, v_new))
        trel = ti - row_off[r]
        in_row = (trel >= 0) & (trel < row_len[r])
        s_pool = jnp.einsum("thd,khd->thk", qs, kc, precision=_HI)
        s_self = jnp.einsum("thd,uhd->thu", qs, kn, precision=_HI)
        m_pool = (in_row[:, None, None] & sel
                  & (kpos < row_start[r])[None, None, :])
        m_self = (in_row[:, None, None] & in_row[None, None, :]
                  & (trel[None, None, :] <= trel[:, None, None]))
        s = jnp.concatenate([jnp.where(m_pool, s_pool, NEG_INF),
                             jnp.where(m_self, s_self, NEG_INF)],
                            axis=-1) * hd ** -0.5
        p = jax.nn.softmax(s, axis=-1)
        o = (jnp.einsum("thk,khd->thd", p[..., :maxp * page], vc,
                        precision=_HI)
             + jnp.einsum("thu,uhd->thd", p[..., maxp * page:], vn,
                          precision=_HI))
        out = jnp.where(in_row[:, None, None], o, out)
    return out
