"""Power retention (degree 2) over a ragged token batch: attention whose
weight is the SQUARE of the score, decayed by a learned gate, and
therefore computable from a state of fixed size.

Per KV head ``j`` (query heads ``h`` in its group), token ``t``:

    S_t = g_t S_{t-1} + phi(k_t) v_t^T          [D, d]
    z_t = g_t z_{t-1} + phi(k_t)                [D]
    y_t,h = S_t^T phi(q_t,h) / (z_t . phi(q_t,h) + eps)

with ``phi(a) . phi(b) == (a . b)^2``: the symmetric degree-2 feature
map, ``a_i^2`` and ``sqrt(2) a_i a_i'`` for ``i < i'`` (``D = d(d+1)/2``).
Written out over a sequence that is ``y_t = sum_s a_ts v_s / (sum_s a_ts
+ eps)`` with ``a_ts = (q_t . k_s)^2 exp(sum_{r=s+1..t} log g_r)``.

**The layout of phi.**  ``d`` is cut in blocks of ``bs`` (16 at ``d``
128); a feature is ``(pair (a <= b) of blocks, i in a, i' in b)`` and
holds ``u_i u_i'``, times ``sqrt(2)`` where ``a < b``.  A diagonal pair
holds both orders of ``i != i'``, each with weight 1, which sums to the
same ``(a . b)^2``.  That is ``D' = nb(nb+1)/2 * bs^2`` features (9216
at ``d`` 128 against the deduplicated 8256: 12% of padding) in which
every pair is ``bs^2`` whole features: ``phi(u) = (u E) * (u F) * w``
with two 0/1 matrices ``E, F [d, D']`` (``feature_maps``; ``features``
forms it so for the decode tokens and the twins), and a state block of
1024 features is four whole pairs.  With the FEATURES ON SUBLANES and the
tokens on lanes a pair needs no selection at all: for each ``i`` in
``a``, row ``i`` of ``u^T`` spread over the sublanes of block ``b``'s
``[bs, tokens]`` slab, times the slab, times the pair's one weight
(``pair_features_t``): the same float32 products in the same order, bit
for bit.  ``to_canonical`` maps a state in this layout to the
deduplicated one.

**The state** of all slots and layers is ``ret_s [L, slots + 1, KVH, D',
d]`` and ``ret_z [L, slots + 1, KVH, D']`` in float32, updated in place;
the last slot is scratch.  A row with ``row_start == 0`` starts from
zero, so a slot is reset by the first row of whoever takes it.

Two kernels, each over the live rows of its kind only (a list and a
dynamic grid bound, as ``ragged_kv_append``): with no such row the grid
is empty and the state untouched.

``retention_decode``: rows of one token.  The state streams through
VMEM once in blocks of ``D'`` (read, decayed, updated, read against
``phi(q)``, written back through the alias): 2 x 4 x D' x d bytes a row
and KV head, which is the kernel's bound.  A grid step moves one
``[1024, d]`` block in and one out (0.5 MB each at ``d`` 128, 72 steps a
row and layer).  On a v5e that stream runs at the rate the chip gives any
read-and-write stream, 0.65 TB/s of the 0.82 its memory is sold at (a
plain copy of the same bytes, by Pallas or by XLA, reads 0.63 to 0.65;
reads alone 0.68, writes alone 0.60): a block of 3072, 4608 or the whole
``D'`` costs the same to the per cent, and so does the body with both its
products taken out, so neither a grid step's fixed part nor the
arithmetic is in the way (PERF.md, PR 50).  ``z`` goes out through an
alias as ``S`` does, a row's ``[KV heads, 1024]`` block at a time (the
heads are the innermost grid dimension, each writes its row of the
block): as rows scattered by XLA it cost a pass a layer, and XLA re-laid
the chunk kernel's eightfold normaliser out whole beside it.  ``phi`` of
the few decode tokens is formed outside (a selection matmul with 8 rows
would be bound by the MXU's weight loads), by ``decode_features``: the
operands are
bfloat16 already and the selections 0/1, so ONE pass with a float32 sum
is ``features`` bit for bit, where ``features``' ``HIGHEST`` costs six.
The operand is ``[rows, KV heads, 8, D']`` for every row of capacity
(28 MB at 12 rows, which XLA keeps in VMEM): its two selections are
bound by the MXU's 768 rows, not by memory.

``retention_chunk``: rows of several tokens (prompt chunks).  Per row,
KV head and block of ``D'``: ``phi(Q)`` of the row's token tiles against
the carried state, decayed to each position, and the state's update
``exp(G_end) S + phi(K)^T (decayed V)``; ``phi`` is formed in VMEM (in
HBM ``phi(Q)`` of a 512-token chunk would be 380 MB a layer), TRANSPOSED:
``q`` and ``k`` come in as ``[tiles, heads * d, 128 tokens]`` (``v`` by
token: only the MXU reads it) and ``phi^T [D' block, tokens]`` is made
pair by pair on the vector unit.  The MXU does only what the
mathematics needs: ``phi^T(K) (decayed V)`` into the state's update and
``S^T phi^T(Q)`` into a numerator kept transposed (``S^T`` in bfloat16
once a grid step; the wrapper turns the numerator back); the normaliser
``phi(Q) . z`` and ``z``'s own update are sums over sublanes and lanes
of float32 products.  With the features on lanes, as the kernel first
had them, forming a pair takes lane repeats and tiles, and it went
through the MXU as two 0/1 selection products that were two thirds of
the kernel's operations.  The heads and a block's pairs are unrolled
(the heads by ``fori_loop(unroll=True)``, one trace), so that one head's
vector products overlap another's matmuls.  At the first block also the
masked quadratic part inside the row, keys on sublanes and queries on
lanes, from the same one layout of each operand (``K^T`` and ``V``
contracted over their first dimension).  Matmul operands are bfloat16,
sums and the state float32.

``retention_decode_reference`` and ``retention_chunk_reference`` are the
same contracts in plain ``jax.numpy``, one token at a time.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import platform
from ray_tpu.ops.ragged_paged_attention import _listed

# rows of the decode kernel's feature operand: the group's query heads,
# then the key, then zeros
FEAT_ROWS = 8
_NEG = -1e30


def feature_block(d: int) -> int:
    """Block size of the feature layout: 16 at the published head size,
    a quarter of ``d`` below it so that small sizes have several pairs."""
    return 16 if d >= 64 else max(1, d // 4)


def feature_dim(d: int) -> int:
    """``D'``: features of one head in the kernels' layout."""
    bs = feature_block(d)
    nb = d // bs
    return nb * (nb + 1) // 2 * bs * bs


def state_block(dp: int) -> int:
    """Features of the state one grid step moves."""
    for blk in (1024, 32):
        if dp % blk == 0:
            return blk
    return dp


@functools.lru_cache(maxsize=None)
def _feature_index(d: int):
    """For each feature of the layout: (i, i', weight)."""
    bs = feature_block(d)
    nb = d // bs
    assert nb * bs == d, f"head size {d} is no multiple of {bs}"
    ii, jj, ww = [], [], []
    for a in range(nb):
        for b in range(a, nb):
            for il in range(bs):
                for jl in range(bs):
                    ii.append(a * bs + il)
                    jj.append(b * bs + jl)
                    ww.append(1.0 if a == b else np.sqrt(2.0))
    return np.asarray(ii), np.asarray(jj), np.asarray(ww, np.float32)


@functools.lru_cache(maxsize=None)
def feature_maps(d: int):
    """``(E, F, w)``: 0/1 matrices ``[d, D']`` selecting each feature's
    two factors, and the features' weights ``[D']``."""
    ii, jj, ww = _feature_index(d)
    dp = len(ii)
    e = np.zeros((d, dp), np.float32)
    f = np.zeros((d, dp), np.float32)
    e[ii, np.arange(dp)] = 1.0
    f[jj, np.arange(dp)] = 1.0
    return e, f, ww


def features(u: jax.Array) -> jax.Array:
    """``phi(u)`` in the kernels' layout: ``[..., d] -> [..., D']``,
    float32.  ``features(a) . features(b) == (a . b)^2``."""
    e, f, w = feature_maps(u.shape[-1])
    u = u.astype(jnp.float32)
    hi = lax.Precision.HIGHEST
    return (jnp.dot(u, e, precision=hi) * jnp.dot(u, f, precision=hi)) * w


def decode_features(u: jax.Array) -> jax.Array:
    """``features`` of bfloat16 ``u`` in one MXU pass a selection:
    bfloat16 operands and a float32 sum.  ``E`` and ``F`` are 0/1, so each
    selected value is one bfloat16 times 1.0 plus zeros and the result is
    ``features(u)`` bit for bit, at a sixth of its ``HIGHEST`` passes."""
    assert u.dtype == jnp.bfloat16, u.dtype
    e, f, w = feature_maps(u.shape[-1])
    f32, bf = jnp.float32, jnp.bfloat16
    return (jnp.dot(u, e.astype(bf), preferred_element_type=f32)
            * jnp.dot(u, f.astype(bf), preferred_element_type=f32)) * w


def to_canonical(state: np.ndarray, d: int) -> np.ndarray:
    """A state ``[D', ...]`` in the kernels' layout as the deduplicated
    ``[D, ...]``: first the ``d`` squares, then ``sqrt(2) u_i u_i'`` for
    ``i < i'`` in row-major order.  On the host, for checks."""
    state = np.asarray(state, np.float64)
    ii, jj, ww = _feature_index(d)
    # inside a diagonal pair ``u_i u_i'`` stands twice with weight 1:
    # each is 1 / sqrt(2) of the canonical feature
    scale = np.where((ii != jj) & (ww == 1.0), np.sqrt(0.5), 1.0)
    full = np.zeros((d, d) + state.shape[1:])
    np.add.at(full, (ii, jj),
              state * scale.reshape((-1,) + (1,) * (state.ndim - 1)))
    full = full + np.swapaxes(full, 0, 1)
    diag = full[np.arange(d), np.arange(d)] / 2.0
    return np.concatenate([diag, full[np.triu_indices(d, 1)]], axis=0)


def token_rows(row_len: jax.Array, row_off: jax.Array, T: int):
    """For each position of the flat buffer: the packed row that holds
    it and whether any does.  Padding rows (length 0) may stand anywhere
    among the live ones."""
    t = jnp.arange(T, dtype=jnp.int32)[:, None]
    holds = (row_off[None, :] <= t) & (t < (row_off + row_len)[None, :])
    return (jnp.argmax(holds, axis=1).astype(jnp.int32),
            jnp.any(holds, axis=1))


def row_gates(log_g: jax.Array, row_len: jax.Array, row_off: jax.Array):
    """Cumulative log gate of each token inside its own row (itself
    included) ``[T, KVH]``, and each row's total ``[R, KVH]``."""
    T = log_g.shape[0]
    tok_row, valid = token_rows(row_len, row_off, T)
    cs = jnp.cumsum(jnp.where(valid[:, None], log_g, 0.0), axis=0)
    before = jnp.where((row_off > 0)[:, None],
                       cs[jnp.clip(row_off - 1, 0, T - 1)], 0.0)
    gc = cs - before[tok_row]
    g_end = gc[jnp.clip(row_off + row_len - 1, 0, T - 1)]
    return gc, g_end


def _listed_row(i, rows_p, n_p, past):
    """The ``i``-th listed row, or ``past`` for a grid step beyond the
    list's end (only the interpreter, whose grid is the capacity, makes
    such steps)."""
    return jnp.where(i < n_p[0], rows_p[i], past)


def _listed_slot(i, rows_p, n_p, slot_p, scratch):
    """The slot of the ``i``-th listed row, or the scratch slot."""
    return jnp.where(i < n_p[0], slot_p[rows_p[i]], scratch)


def _scatter_z(ret_z, layer, z_rows, row_slot, live):
    """Write each live row's new normaliser into its slot."""
    oob = ret_z.shape[1]
    slots = jnp.where(live, row_slot, oob)
    return ret_z.at[layer, slots].set(z_rows, mode="drop")


# -- rows of one token ------------------------------------------------------

def _decode_kernel(rows_r, n_r, slot_r, start_r, ly_r,
                   feat_ref, v_ref, g_ref, s_in, z_in,
                   num_ref, den_ref, z_out, s_out, *, G: int):
    del slot_r, ly_r                       # index maps read them
    i, b, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    # i < n always holds under Mosaic, whose grid ends at n; the
    # interpreter's grid is the capacity.
    @pl.when(i < n_r[0])
    def _row():
        fresh = start_r[rows_r[i]] == 0
        f8 = feat_ref[0, 0]                                # [8, Db]
        g = g_ref[0, 0]                                    # [8, d]
        s = jnp.where(fresh, 0.0, s_in[0, 0, 0]) * g[0:1, :]
        rowid = lax.broadcasted_iota(jnp.int32, f8.shape, 0)
        fk = jnp.where(rowid == G, f8, 0.0)
        s_new = s + lax.dot_general(
            fk, v_ref[0, 0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [Db, d]
        s_out[0, 0, 0] = s_new
        z = jnp.where(fresh, 0.0, z_in[0, 0, pl.ds(j, 1), :])
        z_new = z * g[0:1, 0:1] + f8[G:G + 1, :]           # [1, Db]
        z_out[0, 0, pl.ds(j, 1), :] = z_new
        num = jnp.dot(f8.astype(jnp.bfloat16), s_new.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)  # [8, d]
        den = jnp.broadcast_to(
            jnp.sum(f8 * z_new, axis=1, keepdims=True), num.shape)

        @pl.when(b == 0)
        def _first():
            num_ref[0, j] = num
            den_ref[0, j] = den

        @pl.when(b > 0)
        def _more():
            num_ref[0, j] += num
            den_ref[0, j] += den


def retention_decode(
    q: jax.Array,            # [T, H, d]
    k: jax.Array,            # [T, KVH, d]
    v: jax.Array,            # [T, KVH, d]
    log_g: jax.Array,        # [T, KVH] float32, log of the gate
    ret_s: jax.Array,        # [L, S + 1, KVH, D', d] float32, in place
    ret_z: jax.Array,        # [L, S + 1, KVH, D'] float32, in place
    layer: jax.Array,
    row_slot: jax.Array,     # [R]
    row_start: jax.Array,
    row_len: jax.Array,
    row_off: jax.Array,
    *,
    eps: float = 1e-6,
):
    """The rows of ONE token: each slot's state decayed, updated with
    the token's key and value, read against its queries.  Returns (y
    [T, H, d] float32, zero at the tokens of other rows; ret_s; ret_z).
    Rows occupy distinct slots."""
    T, H, d = q.shape
    L, S1, KVH, Dp, _ = ret_s.shape
    R = row_slot.shape[0]
    G = H // KVH
    assert G + 1 <= FEAT_ROWS and Dp == feature_dim(d)
    Db = state_block(Dp)
    f32, i32 = jnp.float32, jnp.int32
    bf = jnp.bfloat16
    row_slot, row_start, row_len, row_off = (
        jnp.asarray(a, i32) for a in (row_slot, row_start, row_len, row_off))
    one = row_len == 1
    rows, n = _listed(one)
    at = jnp.clip(row_off, 0, T - 1)
    # what the matmuls see is bfloat16, here as in the chunk kernel
    qd = q[at].astype(bf).reshape(R, KVH, G, d)
    kd = k[at].astype(bf)[:, :, None, :]
    pad = jnp.zeros((R, KVH, FEAT_ROWS - G - 1, d), bf)
    feat = decode_features(
        jnp.concatenate([qd, kd, pad], axis=2))            # [R,KVH,8,D']
    v8 = jnp.broadcast_to(v[at].astype(bf).astype(f32)[:, :, None, :],
                          (R, KVH, FEAT_ROWS, d))
    g8 = jnp.broadcast_to(jnp.exp(log_g[at])[:, :, None, None],
                          (R, KVH, FEAT_ROWS, d)).astype(f32)

    def by_row(i, b, j, rows_p, n_p, *pf):
        return (_listed_row(i, rows_p, n_p, 0), j, 0, 0)

    def feat_map(i, b, j, rows_p, n_p, *pf):
        return (_listed_row(i, rows_p, n_p, 0), j, 0, b)

    def s_map(i, b, j, rows_p, n_p, slot_p, start_p, ly):
        return (ly[0], _listed_slot(i, rows_p, n_p, slot_p, S1 - 1), j, b, 0)

    def z_map(i, b, j, rows_p, n_p, slot_p, start_p, ly):
        return (ly[0], _listed_slot(i, rows_p, n_p, slot_p, S1 - 1), 0, b)

    # what a step past the list's end writes lands in row R, which
    # nobody reads
    def y_map(i, b, j, rows_p, n_p, *pf):
        return (_listed_row(i, rows_p, n_p, R), 0, 0, 0)

    interpret = platform.interpret_mode()
    s_spec = pl.BlockSpec((1, 1, 1, Db, d), s_map)
    # a row's KV heads are the innermost grid dimension, so a block of
    # ``z`` stays in VMEM while each head writes its row of it
    z_spec = pl.BlockSpec((1, 1, KVH, Db), z_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(R if interpret else n[0], Dp // Db, KVH),
        in_specs=[
            pl.BlockSpec((1, 1, FEAT_ROWS, Db), feat_map),
            pl.BlockSpec((1, 1, FEAT_ROWS, d), by_row),
            pl.BlockSpec((1, 1, FEAT_ROWS, d), by_row),
            s_spec,
            z_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, KVH, FEAT_ROWS, d), y_map),
            pl.BlockSpec((1, KVH, FEAT_ROWS, d), y_map),
            z_spec,
            s_spec,
        ],
    )
    num, den, ret_z, ret_s = pl.pallas_call(
        functools.partial(_decode_kernel, G=G),
        name="retention_decode",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R + 1, KVH, FEAT_ROWS, d), f32),
                   jax.ShapeDtypeStruct((R + 1, KVH, FEAT_ROWS, d), f32),
                   jax.ShapeDtypeStruct(ret_z.shape, ret_z.dtype),
                   jax.ShapeDtypeStruct(ret_s.shape, ret_s.dtype)],
        # prefetch: rows=0 n=1 slot=2 start=3 layer=4, then feat=5 v=6
        # g=7 ret_s=8 ret_z=9
        input_output_aliases={8: 3, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=48 * 2**20),
        interpret=interpret,
    )(rows, n, row_slot, row_start, jnp.asarray(layer, i32).reshape(1),
      feat, v8, g8, ret_s, ret_z)
    y_rows = (num[:R, :, :G] / (den[:R, :, :G] + eps)).reshape(R, H, d)
    tok_row, valid = token_rows(row_len, row_off, T)
    mine = valid & one[tok_row]
    y = jnp.where(mine[:, None, None], y_rows[tok_row], 0.0)
    return y, ret_s, ret_z


# -- rows of several tokens -------------------------------------------------

@functools.lru_cache(maxsize=None)
def _block_pairs(d: int):
    """The pairs ``(a <= b)`` of blocks in the layout's order: row 0 the
    block of each pair's first factor, row 1 of its second."""
    nb = d // feature_block(d)
    return np.asarray([(a, b) for a in range(nb) for b in range(a, nb)],
                      np.int32).T                          # [2, pairs]


def pair_features_t(ua: jax.Array, ub: jax.Array, weight) -> jax.Array:
    """``phi^T`` of one pair of blocks for a tile of tokens, features on
    sublanes and tokens on lanes: ``ua, ub [bs, TT]`` float32 (the tile's
    ``u^T`` at the blocks ``a`` and ``b``) -> ``[bs * bs, TT]`` float32
    whose row ``i * bs + i'`` holds ``(ua[i] * ub[i']) * weight``.  Each
    row of ``ua`` is spread over the sublanes of ``ub``: vector products
    and no lane moves.  One broadcast product, not ``bs`` slices: the
    kernel's trace is host time of every set-up."""
    bs, tt = ua.shape
    return ((ua[:, None, :] * ub[None, :, :]) * weight).reshape(bs * bs, tt)


def _chunk_kernel(rows_r, n_r, slot_r, start_r, len_r, off_r, ly_r, pair_r,
                  qt_ref, kt_ref, v_ref, gc_ref, gr_ref, ge_ref, s_in, z_in,
                  num_ref, den_ref, zu_ref, s_out,
                  ds_ref, dz_ref, st_ref, zb_ref,
                  *, G: int, d: int, TT: int, bs: int):
    del slot_r, ly_r
    j, i, b = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    f32, bf = jnp.float32, jnp.bfloat16
    Db = ds_ref.shape[0]
    pf = bs * bs                           # features of a pair of blocks
    ppb = Db // pf                         # whole pairs in a state block

    @pl.when((i == 0) & (b == 0))
    def _init():
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    def pair_phi(u_ref, t, base, p):
        """``phi^T`` of the tile ``t`` of ``u_ref`` (rows from ``base``)
        at the ``p``-th pair of this state block: [pf, TT] float32."""
        a, bb = pair_r[0, b * ppb + p], pair_r[1, b * ppb + p]
        ua = u_ref[t, pl.ds(pl.multiple_of(base + a * bs, bs), bs), :]
        ub = u_ref[t, pl.ds(pl.multiple_of(base + bb * bs, bs), bs), :]
        weight = jnp.where(a == bb, 1.0, np.float32(np.sqrt(2.0)))
        return pair_features_t(ua.astype(f32), ub.astype(f32), weight)

    @pl.when(i < n_r[0])
    def _row():
        r = rows_r[i]
        off, n = off_r[r], len_r[r]
        fresh = start_r[r] == 0
        lo, hi = lax.div(off, TT), lax.div(off + n - 1, TT) + 1
        ge = ge_ref[0, 0][0:1, :]                          # [1, d]
        s_prev = jnp.where(fresh, 0.0, s_in[0, 0, 0])      # [Db, d]
        z_prev = jnp.where(fresh, 0.0, z_in[0, 0, pl.ds(j, 1), :])
        # the carried state as every tile and head reads it: S^T in
        # bfloat16, z down the sublanes and across a tile's lanes
        st_ref[...] = s_prev.T.astype(bf)                  # [d, Db]
        zb_ref[...] = jnp.broadcast_to(z_prev, (TT, Db)).T  # [Db, TT]
        ds_ref[...] = jnp.zeros_like(ds_ref)
        dz_ref[...] = jnp.zeros_like(dz_ref)

        def tile(t, carry):
            t0 = pl.multiple_of(t * TT, TT)
            # v decayed to the row's end, tokens on sublanes as it came
            tok = t0 + lax.broadcasted_iota(jnp.int32, (TT, d), 0)
            gc = gc_ref[0, pl.ds(t0, TT), :]               # [TT, d]
            vd = (v_ref[pl.ds(t0, TT), :].astype(f32) * jnp.where(
                (tok >= off) & (tok < off + n), jnp.exp(ge - gc),
                0.0)).astype(bf)
            # the decays of phi^T's columns, tokens on lanes
            tok_l = t0 + lax.broadcasted_iota(jnp.int32, (1, TT), 1)
            mine = (tok_l >= off) & (tok_l < off + n)
            gl = gr_ref[0, t, 0:1, :]                      # [1, TT]
            q_dec = jnp.where(mine, jnp.exp(gl), 0.0)
            k_dec = jnp.where(mine, jnp.exp(ge[:, 0:1] - gl), 0.0)

            for p in range(ppb):
                rows = slice(p * pf, (p + 1) * pf)
                fk = pair_phi(kt_ref, t, 0, p)             # [pf, TT]
                ds_ref[rows, :] += jnp.dot(fk.astype(bf), vd,
                                           preferred_element_type=f32)
                dz_ref[rows, :] += fk * k_dec

            # the heads unrolled (traced once): one head's products
            # overlap another's matmuls only inside one block of code
            def head(h, carry):
                base = pl.multiple_of(h * d, d)
                num = jnp.zeros((d, TT), f32)
                den = jnp.zeros((1, TT), f32)
                for p in range(ppb):
                    rows = slice(p * pf, (p + 1) * pf)
                    fq = pair_phi(qt_ref, t, base, p)
                    num += jnp.dot(st_ref[:, rows], fq.astype(bf),
                                   preferred_element_type=f32)
                    den += jnp.sum(fq * zb_ref[rows, :], axis=0,
                                   keepdims=True)
                num_ref[t, pl.ds(base, d), :] += q_dec * num
                den_ref[0, t, pl.ds(h, 1), :] += q_dec * den
                return carry

            return lax.fori_loop(0, G, head, carry, unroll=True)

        lax.fori_loop(lo, hi, tile, 0)
        eg = jnp.exp(ge)
        s_out[0, 0, 0] = s_prev * eg + ds_ref[...]
        dz = jnp.sum(dz_ref[...].T, axis=0, keepdims=True)  # [1, Db]
        zu_ref[0, 0] = jnp.broadcast_to(z_prev * eg[0:1, 0:1] + dz,
                                        zu_ref.shape[2:])

        @pl.when(b == 0)
        def _inside():
            # the quadratic part between the row's own tokens, keys on
            # sublanes and queries on lanes: K^T and V are contracted
            # over their first dimension
            def q_tile(tq, carry):
                tok_q = tq * TT + lax.broadcasted_iota(
                    jnp.int32, (TT, TT), 1)
                gq = gr_ref[0, tq, 0:1, :]                 # [1, TT]

                def k_tile(tk, carry):
                    k0 = pl.multiple_of(tk * TT, TT)
                    tok_k = k0 + lax.broadcasted_iota(
                        jnp.int32, (TT, TT), 0)
                    seen = ((tok_q >= tok_k) & (tok_k >= off)
                            & (tok_q < off + n))
                    gk = gc_ref[0, pl.ds(k0, TT), :][:, 0:1]  # [TT, 1]
                    decay = jnp.exp(jnp.where(seen, gq - gk, _NEG))
                    kt = kt_ref[tk]                        # [d, TT]
                    vk = v_ref[pl.ds(k0, TT), :]           # [TT, d]

                    def head(h, carry):
                        at = pl.ds(pl.multiple_of(h * d, d), d)
                        s = lax.dot_general(
                            kt, qt_ref[tq, at, :], (((0,), (0,)), ((), ())),
                            preferred_element_type=f32)
                        a = s * s * decay                  # [keys, queries]
                        num_ref[tq, at, :] += lax.dot_general(
                            vk, a.astype(bf), (((0,), (0,)), ((), ())),
                            preferred_element_type=f32)
                        den_ref[0, tq, pl.ds(h, 1), :] += jnp.sum(
                            a, axis=0, keepdims=True)
                        return carry

                    return lax.fori_loop(0, G, head, carry, unroll=True)

                return lax.fori_loop(lo, tq + 1, k_tile, carry)

            lax.fori_loop(lo, hi, q_tile, 0)


def retention_chunk(
    q: jax.Array,            # [T, H, d]
    k: jax.Array,            # [T, KVH, d]
    v: jax.Array,            # [T, KVH, d]
    log_g: jax.Array,        # [T, KVH] float32
    ret_s: jax.Array,        # [L, S + 1, KVH, D', d] float32, in place
    ret_z: jax.Array,        # [L, S + 1, KVH, D'] float32
    layer: jax.Array,
    row_slot: jax.Array,     # [R]
    row_start: jax.Array,
    row_len: jax.Array,
    row_off: jax.Array,
    *,
    eps: float = 1e-6,
):
    """The rows of SEVERAL tokens (prompt chunks): every token's output
    from the carried state and the row's earlier tokens, and the state
    after the row's last.  Returns as ``retention_decode``."""
    T, H, d = q.shape
    L, S1, KVH, Dp, _ = ret_s.shape
    R = row_slot.shape[0]
    G = H // KVH
    assert G <= FEAT_ROWS and Dp == feature_dim(d)
    Db = state_block(Dp)
    bs = feature_block(d)
    assert Db % (bs * bs) == 0, (Db, bs)
    f32, i32, bf = jnp.float32, jnp.int32, jnp.bfloat16
    # a tile of tokens lies along the lanes: a lane width where the blocks
    # are the published 16, 8 at the tests' small heads (the interpreter)
    TT = 128 if bs >= 16 else 8
    Tp = -(-T // TT) * TT
    NT = Tp // TT
    row_slot, row_start, row_len, row_off = (
        jnp.asarray(a, i32) for a in (row_slot, row_start, row_len, row_off))
    many = row_len > 1
    rows, n = _listed(many)
    gc, g_end = row_gates(log_g.astype(f32), row_len, row_off)

    def padded(a):
        return jnp.pad(a, ((0, Tp - T),) + ((0, 0),) * (a.ndim - 1))

    def tiles_t(a):
        """``[Tp, C] -> [NT, C, TT]``: each tile of tokens transposed."""
        return a.reshape(NT, TT, -1).transpose(0, 2, 1)

    qt = tiles_t(padded(q.astype(bf).reshape(T, H * d)))
    kt = tiles_t(padded(k.astype(bf).reshape(T, KVH * d)))
    v2 = padded(v.astype(bf).reshape(T, KVH * d))
    gc = padded(gc).T                                      # [KVH, Tp]
    gc_col = jnp.broadcast_to(gc[:, :, None], (KVH, Tp, d))
    gc_row = jnp.broadcast_to(gc.reshape(KVH, NT, 1, TT),
                              (KVH, NT, FEAT_ROWS, TT))
    ge8 = jnp.broadcast_to(g_end[:, :, None, None], (R, KVH, FEAT_ROWS, d))

    def s_map(j, i, b, rows_p, n_p, slot_p, st, ln, of, ly, *pf):
        return (ly[0], _listed_slot(i, rows_p, n_p, slot_p, S1 - 1), j, b, 0)

    def z_map(j, i, b, rows_p, n_p, slot_p, st, ln, of, ly, *pf):
        return (ly[0], _listed_slot(i, rows_p, n_p, slot_p, S1 - 1), 0, b)

    def zu_map(j, i, b, rows_p, n_p, *pf):
        return (_listed_row(i, rows_p, n_p, R), j, 0, b)

    def by_head(j, i, b, *pf):
        return (0, j, 0)

    interpret = platform.interpret_mode()
    s_spec = pl.BlockSpec((1, 1, 1, Db, d), s_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(KVH, R if interpret else n[0], Dp // Db),
        in_specs=[
            pl.BlockSpec((NT, G * d, TT), by_head),
            pl.BlockSpec((NT, d, TT), by_head),
            pl.BlockSpec((Tp, d), lambda j, i, b, *pf: (0, j)),
            pl.BlockSpec((1, Tp, d), lambda j, i, b, *pf: (j, 0, 0)),
            pl.BlockSpec((1, NT, FEAT_ROWS, TT),
                         lambda j, i, b, *pf: (j, 0, 0, 0)),
            pl.BlockSpec((1, 1, FEAT_ROWS, d),
                         lambda j, i, b, rows_p, n_p, *pf:
                         (_listed_row(i, rows_p, n_p, 0), j, 0, 0)),
            s_spec,
            pl.BlockSpec((1, 1, KVH, Db), z_map),
        ],
        out_specs=[
            pl.BlockSpec((NT, G * d, TT), by_head),
            pl.BlockSpec((1, NT, FEAT_ROWS, TT),
                         lambda j, i, b, *pf: (j, 0, 0, 0)),
            pl.BlockSpec((1, 1, FEAT_ROWS, Db), zu_map),
            s_spec,
        ],
        scratch_shapes=[pltpu.VMEM((Db, d), f32),
                        pltpu.VMEM((Db, TT), f32),
                        pltpu.VMEM((d, Db), bf),
                        pltpu.VMEM((Db, TT), f32)],
    )
    num, den, z_rows, ret_s = pl.pallas_call(
        functools.partial(_chunk_kernel, G=G, d=d, TT=TT, bs=bs),
        name="retention_chunk",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((NT, H * d, TT), f32),
                   jax.ShapeDtypeStruct((KVH, NT, FEAT_ROWS, TT), f32),
                   jax.ShapeDtypeStruct((R + 1, KVH, FEAT_ROWS, Dp), f32),
                   jax.ShapeDtypeStruct(ret_s.shape, ret_s.dtype)],
        # prefetch: rows=0 n=1 slot=2 start=3 len=4 off=5 layer=6 and the
        # pairs' blocks 7; then q^T=8 k^T=9 v=10 gc_col=11 gc_row=12
        # g_end=13 ret_s=14 ret_z=15
        input_output_aliases={14: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=64 * 2**20),
        interpret=interpret,
    )(rows, n, row_slot, row_start, row_len, row_off,
      jnp.asarray(layer, i32).reshape(1), jnp.asarray(_block_pairs(d)),
      qt, kt, v2, gc_col, gc_row, ge8,
      ret_s, ret_z)
    ret_z = _scatter_z(ret_z, layer, z_rows[:R, :, 0], row_slot, many)
    num = num.transpose(0, 2, 1).reshape(Tp, H, d)[:T]
    den = den[:, :, :G].transpose(1, 3, 0, 2).reshape(Tp, H)[:T]
    tok_row, valid = token_rows(row_len, row_off, T)
    mine = valid & many[tok_row]
    y = jnp.where(mine[:, None, None], num / (den[:, :, None] + eps), 0.0)
    return y, ret_s, ret_z


def retention(q, k, v, log_g, ret_s, ret_z, layer, row_slot, row_start,
              row_len, row_off, *, eps: float = 1e-6):
    """Every packed row through the kernel of its kind.  Returns (y
    [T, H, d] float32, ret_s, ret_z); padding rows touch nothing."""
    rows = (row_slot, row_start, row_len, row_off)
    y1, ret_s, ret_z = retention_decode(
        q, k, v, log_g, ret_s, ret_z, layer, *rows, eps=eps)
    yc, ret_s, ret_z = retention_chunk(
        q, k, v, log_g, ret_s, ret_z, layer, *rows, eps=eps)
    return y1 + yc, ret_s, ret_z


# -- the jnp twins ----------------------------------------------------------

def _retention_reference(q, k, v, log_g, ret_s, ret_z, layer, row_slot,
                         row_start, row_len, row_off, eps, pick):
    """One token at a time through the flat buffer, float32 (operands
    rounded to bfloat16 first, as the kernels see them), for the rows
    ``pick(row_len)`` selects."""
    T, H, d = q.shape
    KVH = k.shape[1]
    G = H // KVH
    f32, bf = jnp.float32, jnp.bfloat16
    row_slot, row_start, row_len, row_off = (
        jnp.asarray(a, jnp.int32)
        for a in (row_slot, row_start, row_len, row_off))
    tok_row, valid = token_rows(row_len, row_off, T)
    fq = features(q.astype(bf).reshape(T, KVH, G, d))
    fk = features(k.astype(bf))
    v = v.astype(bf).astype(f32)
    g = jnp.exp(log_g.astype(f32))
    hi = lax.Precision.HIGHEST

    def step(carry, t):
        s_all, z_all = carry
        r = tok_row[t]
        slot = row_slot[r]
        use = valid[t] & pick(row_len[r])
        first = (t == row_off[r]) & (row_start[r] == 0)
        s = jnp.where(first, 0.0, s_all[slot]) * g[t][:, None, None]
        z = jnp.where(first, 0.0, z_all[slot]) * g[t][:, None]
        s = s + fk[t][:, :, None] * v[t][:, None, :]
        z = z + fk[t]
        num = jnp.einsum("jgf,jfd->jgd", fq[t], s, precision=hi)
        den = jnp.einsum("jgf,jf->jg", fq[t], z, precision=hi)
        y = (num / (den[..., None] + eps)).reshape(H, d)
        s_all = jnp.where(use, s_all.at[slot].set(s), s_all)
        z_all = jnp.where(use, z_all.at[slot].set(z), z_all)
        return (s_all, z_all), jnp.where(use, y, 0.0)

    (s_all, z_all), y = lax.scan(step, (ret_s[layer], ret_z[layer]),
                                 jnp.arange(T))
    return y, ret_s.at[layer].set(s_all), ret_z.at[layer].set(z_all)


def retention_decode_reference(q, k, v, log_g, ret_s, ret_z, layer,
                               row_slot, row_start, row_len, row_off, *,
                               eps: float = 1e-6):
    """Plain form of ``retention_decode``."""
    return _retention_reference(q, k, v, log_g, ret_s, ret_z, layer,
                                row_slot, row_start, row_len, row_off, eps,
                                lambda n: n == 1)


def retention_chunk_reference(q, k, v, log_g, ret_s, ret_z, layer,
                              row_slot, row_start, row_len, row_off, *,
                              eps: float = 1e-6):
    """Plain form of ``retention_chunk``."""
    return _retention_reference(q, k, v, log_g, ret_s, ret_z, layer,
                                row_slot, row_start, row_len, row_off, eps,
                                lambda n: n > 1)


def state_bytes(d: int, kv_heads: int) -> Tuple[int, int]:
    """Float32 bytes of one slot and layer: (matrix state, normaliser)."""
    dp = feature_dim(d)
    return kv_heads * dp * d * 4, kv_heads * dp * 4
