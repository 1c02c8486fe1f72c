"""The indexer of learned sparse attention: a step's index scores against
the rows' pooled index keys, and the selection of the ``topk`` cached
positions each query may attend to.

Beside the attention's own cache such a model keeps ONE small key a token
and layer (``kI``, 128 lanes), in a page pool of its own under the same
block tables.  A query ``t`` scores every cached position ``s <= t`` of
its sequence through a few light heads,

    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])

and attends only to the ``topk`` positions of largest ``I`` (all of them
while it has no more).  The batch is the engine's ragged one (rows of
slot, start, len, offset over a flat token buffer: see
``ops/ragged_paged_attention``); a row's past lies in the pool, its fresh
tokens' keys beside the step in ``new`` (the pool is read-only inside
the layers and appended once after them, ``latent_attention.
ragged_latent_append``).

Two kinds of row, as in ``latent_attention``:

* a row of ONE token (``one``) scores its context in position space,
  ``[R, maxp * page]``, the position ``row_start`` being its own fresh
  token; its selection leaves as a list of positions
  (``Selection.one_idx``) for the gather that reads those rows and no
  other;
* a row of more tokens (``more``: a prompt chunk) scores the pool
  ``[T, maxp * page]`` and the step's fresh keys ``[T, T]`` apart, in
  blocks of the context under a bound that follows the row's length (a
  ``[T, heads, context]`` array is never made), and its selection leaves
  as two masks for the attention's masked walk.

Everything here is plain XLA.  The selection is exact, never an
approximate top-k.  For a list (rows of one token, a few rows of a few
tens of thousands of scores) it is ``lax.top_k``.  For the masks (hundreds
of queries) the ``k``-th largest score of a query is found by bisection on
the scores' bit patterns, 32 counting passes of one fused read each, and
scores that are bit-equal to the k-th are all kept.  That bisection reads
``[T, maxp * page]`` whatever the rows hold, and the one-token rows'
scores are sized by the block table too: selection by blocks is ROADMAP
Queue 2's.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# context positions a block of the chunk rows' scores covers, at most
SCORE_BLOCK = 2048


class Scores(NamedTuple):
    """``pool [T, C]`` / ``self [T, T]``: scores of the tokens of rows of
    more than one token against pooled positions and against the step's
    fresh keys; ``one [R, C]``: of each one-token row in position space.
    ``*_ok``: which entries are a cached position the query may see.
    ``more [R]``: the rows the first two describe."""
    pool: jax.Array
    pool_ok: jax.Array
    self: jax.Array
    self_ok: jax.Array
    one: jax.Array
    one_ok: jax.Array
    more: jax.Array


class Selection(NamedTuple):
    """What the attention takes: masks for the rows ``more`` marks, a
    list of positions (``one_idx [R, K]``, ``one_ok``) for rows of one
    token (``one_mask`` turns it into a mask in position space)."""
    pool: jax.Array
    self: jax.Array
    one_idx: jax.Array
    one_ok: jax.Array
    more: jax.Array


def sel_token_count(row_start, row_len, topk: int) -> int:
    """Sum over a step's query tokens of ``min(position + 1, topk)``: the
    cached rows a sparse attention has to read for them, on the host."""
    total = 0
    for start, n in zip(row_start, row_len):
        lo, hi = int(start) + 1, int(start) + int(n)    # p + 1 over the row
        under = min(hi, topk)
        if under >= lo:
            total += (lo + under) * (under - lo + 1) // 2
        total += topk * max(0, hi - max(lo, topk + 1) + 1)
    return total


def head_scores(q, w, keys):
    """``sum_j w[.., j] relu(q[.., j, :] . keys[s])``: q [.., J, D], w
    [.., J] float32, keys [S, D] -> [.., S] float32."""
    s = jnp.einsum("...jd,sd->...js", q, keys,
                   preferred_element_type=jnp.float32)
    return jnp.sum(w[..., None] * jax.nn.relu(s), axis=-2)


def _score_block(ctx: int, page: int) -> int:
    """The largest multiple of ``page`` that divides ``ctx`` and is at
    most SCORE_BLOCK."""
    maxp = ctx // page
    return page * max(d for d in range(1, maxp + 1)
                      if maxp % d == 0 and d * page <= max(SCORE_BLOCK, page))


def index_scores(qI: jax.Array,          # [T, J, D] rotated index queries
                 wI: jax.Array,          # [T, J] float32 head weights
                 newI: jax.Array,        # [T, D] this step's index keys
                 pool: jax.Array,        # [L, 1, P + 1, page, D]
                 layer,
                 row_slot, row_start, row_len, row_off,
                 block_tables: jax.Array) -> Scores:
    """The step's index scores."""
    T = qI.shape[0]
    _, _, Pt, page, D = pool.shape
    R = row_slot.shape[0]
    maxp = block_tables.shape[1]
    C = maxp * page
    row_start, row_len, row_off = (a.astype(jnp.int32)
                                   for a in (row_start, row_len, row_off))
    more, one = row_len > 1, row_len == 1
    tables = jnp.clip(block_tables[row_slot], 0, Pt - 1)        # [R, maxp]
    trel = jnp.arange(T, dtype=jnp.int32)[:, None] - row_off[None, :]
    in_row = (trel >= 0) & (trel < row_len[None, :])            # [T, R]
    in_more = in_row & more[None, :]
    tok_more = jnp.any(in_more, axis=1)
    tok_start = jnp.sum(jnp.where(in_more, row_start[None, :], 0), axis=1)
    tok_row = jnp.argmax(in_more, axis=1)
    tok_rel = jnp.sum(jnp.where(in_more, trel, 0), axis=1)
    pos = jnp.arange(C, dtype=jnp.int32)

    # rows of more tokens against the pool: a row at a time (a step
    # holds one or two), a block of its context at a time
    CB = _score_block(C, page)

    def row_scores(r, acc):
        keys = pool[layer, 0, tables[r]].reshape(C, D)

        def block(b, acc):
            kb = lax.dynamic_slice(keys, (b * CB, 0), (CB, D))
            got = head_scores(qI, wI, kb)                # [T, CB]
            old = lax.dynamic_slice(acc, (0, b * CB), (T, CB))
            return lax.dynamic_update_slice(
                acc, jnp.where(in_more[:, r, None], got, old), (0, b * CB))

        return lax.fori_loop(0, -(-row_start[r] // CB), block, acc)

    s_pool = jnp.zeros((T, C), jnp.float32)
    for r in range(R):
        s_pool = lax.cond(more[r], lambda a, r=r: row_scores(r, a),
                          lambda a: a, s_pool)
    pool_ok = tok_more[:, None] & (pos[None, :] < tok_start[:, None])
    s_self = head_scores(qI, wI, newI)                   # [T, T]
    self_ok = (tok_more[:, None] & tok_more[None, :]
               & (tok_row[:, None] == tok_row[None, :])
               & (tok_rel[None, :] <= tok_rel[:, None]))

    # rows of one token, in position space
    t1 = jnp.clip(row_off, 0, T - 1)
    q1, w1 = qI[t1], wI[t1]                                     # [R, J, D]
    keys1 = pool[layer, 0, tables].reshape(R, C, D)
    s_one = jax.vmap(head_scores)(q1, w1, keys1)                # [R, C]
    own = jax.vmap(lambda q, w, k: head_scores(q, w, k[None])[0])(
        q1, w1, newI[t1])
    s_one = jnp.where(pos[None, :] == row_start[:, None], own[:, None],
                      s_one)
    one_ok = one[:, None] & (pos[None, :] <= row_start[:, None])
    return Scores(s_pool, pool_ok, s_self, self_ok, s_one, one_ok, more)


# --------------------------------------------------------------------------
# the selection
# --------------------------------------------------------------------------

def _sortable(x: jax.Array, ok: jax.Array) -> jax.Array:
    """float32 -> uint32 whose order is the floats'; 0 where not ``ok``
    (under every number's pattern)."""
    b = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    key = jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)
    u = lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(0x80000000)
    return jnp.where(ok, u, jnp.uint32(0))


def topk_masks(parts: Sequence[Tuple[jax.Array, jax.Array]],
               k: int) -> Tuple[jax.Array, ...]:
    """For each query (leading axis) the ``min(k, candidates)`` largest
    of its candidates, which lie in several ``(scores [N, M_i], ok [N,
    M_i])`` parts: a mask a part.  Exact: the k-th largest is found bit
    by bit from the top, each bit one count of the candidates at or
    above a trial threshold.  Candidates bit-equal to the k-th are all
    kept, so a mask may hold more than ``k``."""
    us = [_sortable(x, ok) for x, ok in parts]
    n_ok = sum(jnp.sum(ok, axis=-1, dtype=jnp.int32) for _x, ok in parts)
    kk = jnp.minimum(n_ok, k)[:, None]                          # [N, 1]

    def bit(i, thr):
        cand = thr | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        cnt = sum(jnp.sum(u >= cand, axis=-1, keepdims=True,
                          dtype=jnp.int32) for u in us)
        return jnp.where(cnt >= kk, cand, thr)

    thr = lax.fori_loop(0, 32, bit,
                        jnp.zeros((us[0].shape[0], 1), jnp.uint32))
    return tuple(ok & (u >= thr) & (kk > 0)
                 for u, (_x, ok) in zip(us, parts))


def topk_masks_reference(parts, k: int):
    """The same by a sort of each query's candidates."""
    x = jnp.concatenate([jnp.where(ok, s, -jnp.inf) for s, ok in parts], -1)
    ok = jnp.concatenate([o for _s, o in parts], -1)
    n = jnp.minimum(jnp.sum(ok, -1), k)
    srt = jnp.sort(x, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(srt, jnp.maximum(n - 1, 0)[:, None], axis=-1)
    mask = ok & (x >= kth) & (n > 0)[:, None]
    sizes = [s.shape[-1] for s, _ok in parts]
    return tuple(jnp.split(mask, list(jnp.cumsum(jnp.asarray(sizes))[:-1]),
                           axis=-1))


def top_list(scores: jax.Array, ok: jax.Array,
             k: int) -> Tuple[jax.Array, jax.Array]:
    """The ``min(k, candidates)`` largest candidates of each row of
    ``scores [R, C]`` as a list: ``(idx [R, k] int32, good [R, k])``,
    ``idx`` 0 past a row's count.  An exact top-k (``lax.top_k``: a row
    of a few tens of thousands sorts in less than the bisection's
    thirty-two passes cost in launches alone)."""
    _vals, idx = lax.top_k(jnp.where(ok, scores, -jnp.inf), k)
    count = jnp.minimum(jnp.sum(ok, axis=-1, dtype=jnp.int32), k)
    good = jnp.arange(k, dtype=jnp.int32)[None, :] < count[:, None]
    return jnp.where(good, idx, 0).astype(jnp.int32), good


def one_mask(sel: Selection, C: int) -> jax.Array:
    """``[R, C]`` bool: the positions ``sel.one_idx`` lists (the checks')."""
    R = sel.one_idx.shape[0]
    return jnp.zeros((R, C), bool).at[
        jnp.arange(R)[:, None], sel.one_idx].max(sel.one_ok)


def select(scores: Scores, topk: int) -> Selection:
    """The selection of every query of the step.  The masks of rows of
    more tokens are made only in a step that holds such a row."""
    sel_pool, sel_self = lax.cond(
        jnp.any(scores.more),
        lambda: topk_masks([(scores.pool, scores.pool_ok),
                            (scores.self, scores.self_ok)], topk),
        lambda: (jnp.zeros_like(scores.pool_ok),
                 jnp.zeros_like(scores.self_ok)))
    idx, ok = top_list(scores.one, scores.one_ok,
                       min(topk, scores.pool.shape[1]))
    return Selection(sel_pool, sel_self, idx, ok, scores.more)


# --------------------------------------------------------------------------
# plain reference
# --------------------------------------------------------------------------

def index_select_reference(qI, wI, newI, pages, row_slot, row_start, row_len,
                           row_off, block_tables, topk: int):
    """Dense twin in position space: ``(scores [T, C + T] float32, mask
    [T, C + T])`` of every token of every live row over its row's
    positions, column ``p`` the sequence's position ``p`` (the pooled
    past below ``row_start``, the row's fresh tokens from there on);
    rows a token at a time, no blocks, the selection by a sort.
    ``pages`` [P, page, D] is one layer's pool."""
    T = qI.shape[0]
    P, page, D = pages.shape
    maxp = block_tables.shape[1]
    C = maxp * page
    f32 = jnp.float32
    scores = jnp.zeros((T, C + T), f32)
    ok = jnp.zeros((T, C + T), bool)
    for r in range(int(row_slot.shape[0])):
        start, n, off = int(row_start[r]), int(row_len[r]), int(row_off[r])
        if n == 0:
            continue
        past = pages[jnp.clip(block_tables[row_slot[r]], 0, P - 1)].reshape(
            C, D)[:start]
        keys = jnp.concatenate([past, newI[off:off + n]]).astype(f32)
        s = jnp.einsum("tjd,sd->tjs", qI[off:off + n].astype(f32), keys)
        got = jnp.sum(wI[off:off + n, :, None] * jax.nn.relu(s), axis=1)
        scores = scores.at[off:off + n, :start + n].set(got)
        ok = ok.at[off:off + n, :start + n].set(
            jnp.arange(start + n)[None, :] <= start + jnp.arange(n)[:, None])
    (mask,) = topk_masks_reference([(scores, ok)], topk)
    return scores, mask
