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
every pair is ``bs^2`` whole lanes, and ``phi(u) = (u E) * (u F) * w``
with two 0/1 matrices ``E, F [d, D']``: a product the MXU does exactly.
``to_canonical`` maps a state in this layout to the deduplicated one.

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
and KV head, which is the kernel's bound.  ``phi`` of the few decode
tokens is formed outside (a selection matmul with 8 rows would be bound
by the MXU's weight loads).

``retention_chunk``: rows of several tokens (prompt chunks).  Per row,
KV head and block of ``D'``: ``phi(Q)`` of the row's token tiles against
the carried state, decayed to each position, and the state's update
``exp(G_end) S + phi(K)^T (decayed V)``; ``phi`` is formed in VMEM from
``E`` and ``F`` (in HBM ``phi(Q)`` of a 512-token chunk would be 380 MB a
layer).  At the first block also the masked quadratic part inside the
row.  Matmul operands are bfloat16, sums and the state float32.

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
                   num_ref, den_ref, zu_ref, s_out, *, G: int):
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
        zu_ref[0, pl.ds(j, 1), :] = z_new
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
    ret_z: jax.Array,        # [L, S + 1, KVH, D'] float32
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
    feat = features(jnp.concatenate([qd, kd, pad], axis=2))  # [R,KVH,8,D']
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

    def zu_map(i, b, j, rows_p, n_p, *pf):
        return (_listed_row(i, rows_p, n_p, R), 0, b)

    interpret = platform.interpret_mode()
    s_spec = pl.BlockSpec((1, 1, 1, Db, d), s_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(R if interpret else n[0], Dp // Db, KVH),
        in_specs=[
            pl.BlockSpec((1, 1, FEAT_ROWS, Db), feat_map),
            pl.BlockSpec((1, 1, FEAT_ROWS, d), by_row),
            pl.BlockSpec((1, 1, FEAT_ROWS, d), by_row),
            s_spec,
            pl.BlockSpec((1, 1, KVH, Db), z_map),
        ],
        out_specs=[
            pl.BlockSpec((1, KVH, FEAT_ROWS, d), y_map),
            pl.BlockSpec((1, KVH, FEAT_ROWS, d), y_map),
            pl.BlockSpec((1, KVH, Db), zu_map),
            s_spec,
        ],
    )
    num, den, z_rows, ret_s = pl.pallas_call(
        functools.partial(_decode_kernel, G=G),
        name="retention_decode",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R + 1, KVH, FEAT_ROWS, d), f32),
                   jax.ShapeDtypeStruct((R + 1, KVH, FEAT_ROWS, d), f32),
                   jax.ShapeDtypeStruct((R + 1, KVH, Dp), f32),
                   jax.ShapeDtypeStruct(ret_s.shape, ret_s.dtype)],
        # prefetch: rows=0 n=1 slot=2 start=3 layer=4, then feat=5 v=6
        # g=7 ret_s=8 ret_z=9
        input_output_aliases={8: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=48 * 2**20),
        interpret=interpret,
    )(rows, n, row_slot, row_start, jnp.asarray(layer, i32).reshape(1),
      feat, v8, g8, ret_s, ret_z)
    ret_z = _scatter_z(ret_z, layer, z_rows[:R], row_slot, one)
    y_rows = (num[:R, :, :G] / (den[:R, :, :G] + eps)).reshape(R, H, d)
    tok_row, valid = token_rows(row_len, row_off, T)
    mine = valid & one[tok_row]
    y = jnp.where(mine[:, None, None], y_rows[tok_row], 0.0)
    return y, ret_s, ret_z


# -- rows of several tokens -------------------------------------------------

def _chunk_kernel(rows_r, n_r, slot_r, start_r, len_r, off_r, ly_r,
                  q_ref, k_ref, v_ref, gc_ref, gr_ref, ge_ref,
                  e_ref, f_ref, w_ref, s_in, z_in,
                  num_ref, den_ref, zu_ref, s_out,
                  ds_ref, dz_ref, *, G: int, d: int, TT: int):
    del slot_r, ly_r
    j, i, b = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    f32, bf = jnp.float32, jnp.bfloat16

    @pl.when((i == 0) & (b == 0))
    def _init():
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    def phi(u):
        return (jnp.dot(u, e_ref[...], preferred_element_type=f32)
                * jnp.dot(u, f_ref[...], preferred_element_type=f32)
                * w_ref[0:1, :])

    def add_den(t0, h, col):
        lane = lax.broadcasted_iota(jnp.int32, (TT, d), 1)
        den_ref[0, pl.ds(t0, TT), :] += jnp.where(lane == h, col, 0.0)

    @pl.when(i < n_r[0])
    def _row():
        r = rows_r[i]
        off, n = off_r[r], len_r[r]
        fresh = start_r[r] == 0
        lo, hi = off // TT, (off + n - 1) // TT + 1
        ge = ge_ref[0, 0][0:1, :]                          # [1, d]
        s_prev = jnp.where(fresh, 0.0, s_in[0, 0, 0])      # [Db, d]
        z_prev = jnp.where(fresh, 0.0, z_in[0, 0, pl.ds(j, 1), :])
        sb = s_prev.astype(bf)
        ds_ref[...] = jnp.zeros_like(ds_ref)
        dz_ref[...] = jnp.zeros_like(dz_ref)

        def tile(t, carry):
            t0 = pl.multiple_of(t * TT, TT)
            tok = t0 + lax.broadcasted_iota(jnp.int32, (TT, d), 0)
            mine = (tok >= off) & (tok < off + n)
            gc = gc_ref[0, pl.ds(t0, TT), :]               # [TT, d]
            q_dec = jnp.where(mine, jnp.exp(gc), 0.0)
            k_dec = jnp.where(mine, jnp.exp(ge - gc), 0.0)
            fk = phi(k_ref[pl.ds(t0, TT), :])              # [TT, Db]
            vd = (v_ref[pl.ds(t0, TT), :].astype(f32) * k_dec).astype(bf)
            ds_ref[...] += lax.dot_general(
                fk.astype(bf), vd, (((0,), (0,)), ((), ())),
                preferred_element_type=f32)
            dz_ref[0:1, :] += jnp.sum(fk * k_dec[:, 0:1], axis=0,
                                      keepdims=True)
            for h in range(G):
                fq = phi(q_ref[pl.ds(t0, TT), h * d:(h + 1) * d])
                num_ref[pl.ds(t0, TT), h * d:(h + 1) * d] += q_dec * jnp.dot(
                    fq.astype(bf), sb, preferred_element_type=f32)
                add_den(t0, h, q_dec * jnp.sum(fq * z_prev, axis=1,
                                               keepdims=True))
            return carry

        lax.fori_loop(lo, hi, tile, 0)
        eg = jnp.exp(ge)
        s_out[0, 0, 0] = s_prev * eg + ds_ref[...]
        zu_ref[0, 0] = jnp.broadcast_to(
            z_prev * eg[0:1, 0:1] + dz_ref[0:1, :], zu_ref.shape[2:])

        @pl.when(b == 0)
        def _inside():
            # the quadratic part between the row's own tokens
            def q_tile(tq, carry):
                q0 = pl.multiple_of(tq * TT, TT)
                tok_q = q0 + lax.broadcasted_iota(jnp.int32, (TT, TT), 0)
                gq = gc_ref[0, pl.ds(q0, TT), :][:, 0:1]   # [TT, 1]

                def k_tile(tk, carry):
                    k0 = pl.multiple_of(tk * TT, TT)
                    tok_k = k0 + lax.broadcasted_iota(
                        jnp.int32, (TT, TT), 1)
                    seen = ((tok_q >= tok_k) & (tok_k >= off)
                            & (tok_q < off + n))
                    decay = jnp.exp(jnp.where(
                        seen, gq - gr_ref[0, tk, 0:1, :], _NEG))
                    kt = k_ref[pl.ds(k0, TT), :]
                    vt = v_ref[pl.ds(k0, TT), :]
                    for h in range(G):
                        s = lax.dot_general(
                            q_ref[pl.ds(q0, TT), h * d:(h + 1) * d], kt,
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=f32)    # [TT, TT]
                        a = s * s * decay
                        num_ref[pl.ds(q0, TT), h * d:(h + 1) * d] += \
                            jnp.dot(a.astype(bf), vt,
                                    preferred_element_type=f32)
                        add_den(q0, h, jnp.sum(a, axis=1, keepdims=True))
                    return carry

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
    assert G <= d and Dp == feature_dim(d)
    Db = state_block(Dp)
    f32, i32, bf = jnp.float32, jnp.int32, jnp.bfloat16
    TT = 128 if T >= 128 else 8
    Tp = -(-T // TT) * TT
    NT = Tp // TT
    row_slot, row_start, row_len, row_off = (
        jnp.asarray(a, i32) for a in (row_slot, row_start, row_len, row_off))
    many = row_len > 1
    rows, n = _listed(many)
    gc, g_end = row_gates(log_g.astype(f32), row_len, row_off)

    def padded(a):
        return jnp.pad(a, ((0, Tp - T),) + ((0, 0),) * (a.ndim - 1))

    q2 = padded(q.astype(bf).reshape(T, H * d))
    k2 = padded(k.astype(bf).reshape(T, KVH * d))
    v2 = padded(v.astype(bf).reshape(T, KVH * d))
    gc = padded(gc).T                                      # [KVH, Tp]
    gc_col = jnp.broadcast_to(gc[:, :, None], (KVH, Tp, d))
    gc_row = jnp.broadcast_to(gc.reshape(KVH, NT, 1, TT),
                              (KVH, NT, FEAT_ROWS, TT))
    ge8 = jnp.broadcast_to(g_end[:, :, None, None], (R, KVH, FEAT_ROWS, d))
    e, f, w = feature_maps(d)
    e, f = jnp.asarray(e, bf), jnp.asarray(f, bf)
    w8 = jnp.broadcast_to(jnp.asarray(w)[None, :], (FEAT_ROWS, Dp))

    def s_map(j, i, b, rows_p, n_p, slot_p, st, ln, of, ly):
        return (ly[0], _listed_slot(i, rows_p, n_p, slot_p, S1 - 1), j, b, 0)

    def z_map(j, i, b, rows_p, n_p, slot_p, st, ln, of, ly):
        return (ly[0], _listed_slot(i, rows_p, n_p, slot_p, S1 - 1), 0, b)

    def zu_map(j, i, b, rows_p, n_p, *pf):
        return (_listed_row(i, rows_p, n_p, R), j, 0, b)

    interpret = platform.interpret_mode()
    s_spec = pl.BlockSpec((1, 1, 1, Db, d), s_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(KVH, R if interpret else n[0], Dp // Db),
        in_specs=[
            pl.BlockSpec((Tp, G * d), lambda j, i, b, *pf: (0, j)),
            pl.BlockSpec((Tp, d), lambda j, i, b, *pf: (0, j)),
            pl.BlockSpec((Tp, d), lambda j, i, b, *pf: (0, j)),
            pl.BlockSpec((1, Tp, d), lambda j, i, b, *pf: (j, 0, 0)),
            pl.BlockSpec((1, NT, FEAT_ROWS, TT),
                         lambda j, i, b, *pf: (j, 0, 0, 0)),
            pl.BlockSpec((1, 1, FEAT_ROWS, d),
                         lambda j, i, b, rows_p, n_p, *pf:
                         (_listed_row(i, rows_p, n_p, 0), j, 0, 0)),
            pl.BlockSpec((d, Db), lambda j, i, b, *pf: (0, b)),
            pl.BlockSpec((d, Db), lambda j, i, b, *pf: (0, b)),
            pl.BlockSpec((FEAT_ROWS, Db), lambda j, i, b, *pf: (0, b)),
            s_spec,
            pl.BlockSpec((1, 1, KVH, Db), z_map),
        ],
        out_specs=[
            pl.BlockSpec((Tp, G * d), lambda j, i, b, *pf: (0, j)),
            pl.BlockSpec((1, Tp, d), lambda j, i, b, *pf: (j, 0, 0)),
            pl.BlockSpec((1, 1, FEAT_ROWS, Db), zu_map),
            s_spec,
        ],
        scratch_shapes=[pltpu.VMEM((Db, d), f32),
                        pltpu.VMEM((FEAT_ROWS, Db), f32)],
    )
    num, den, z_rows, ret_s = pl.pallas_call(
        functools.partial(_chunk_kernel, G=G, d=d, TT=TT),
        name="retention_chunk",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((Tp, H * d), f32),
                   jax.ShapeDtypeStruct((KVH, Tp, d), f32),
                   jax.ShapeDtypeStruct((R + 1, KVH, FEAT_ROWS, Dp), f32),
                   jax.ShapeDtypeStruct(ret_s.shape, ret_s.dtype)],
        # prefetch: rows=0 n=1 slot=2 start=3 len=4 off=5 layer=6, then
        # q=7 k=8 v=9 gc_col=10 gc_row=11 g_end=12 e=13 f=14 w=15
        # ret_s=16 ret_z=17
        input_output_aliases={16: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=64 * 2**20),
        interpret=interpret,
    )(rows, n, row_slot, row_start, row_len, row_off,
      jnp.asarray(layer, i32).reshape(1),
      q2, k2, v2, gc_col, gc_row, ge8, e, f, w8, ret_s, ret_z)
    ret_z = _scatter_z(ret_z, layer, z_rows[:R, :, 0], row_slot, many)
    den = den[:, :T, :G].transpose(1, 0, 2).reshape(T, H)
    tok_row, valid = token_rows(row_len, row_off, T)
    mine = valid & many[tok_row]
    y = jnp.where(mine[:, None, None],
                  num[:T].reshape(T, H, d) / (den[:, :, None] + eps), 0.0)
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
