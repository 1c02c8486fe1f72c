"""Lightning attention over a ragged token batch: linear attention with
the identity feature map and a CONSTANT scalar decay per head, computed
from a matrix state of fixed size.

Per head ``h`` (no grouping: every head has its own key and value),
token ``t`` of a sequence:

    S_t = lambda_h S_{t-1} + k_t^T v_t          [d, d] float32
    o_t = q_t S_t

with ``q`` already scaled.  Over a sequence that is ``o_t = sum_{s<=t}
lambda_h^(t-s) (q_t . k_s) v_s``: no softmax, no normaliser, and the
decay between two tokens of one packed row is a function of their
distance in the flat buffer alone, so no cumulative gate is carried
(``ops/power_retention`` has a learned gate per token and a feature map
of degree 2; this is the scalar-decay form beside it).

**The state** of all slots and layers is ``lin_s [L, slots + 1, H, d,
d]`` float32, updated in place; the last slot is scratch.  A row with
``row_start == 0`` starts from zero, so a slot is reset by the first row
of whoever takes it.

Two kernels, each over the live rows of its kind only (a list and a
dynamic grid bound, as ``ops/power_retention``): with no such row the
grid is empty and the state untouched.

``lightning_decode``: rows of one token.  A cell is one row and a block
of heads: the state streams through VMEM once (read, decayed, updated
with the token's outer product, read against its query, written back
through the alias), 2 x 4 x d x d bytes a row and head, which is the
kernel's bound.  Every head's state is its own matrix for ONE vector,
so the work is the VPU's, not the MXU's: the key and the query are
turned into columns (``[d, heads]``, one lane a head) in the cell
(transposed outside, XLA asked the projection for that layout and
re-laid the key's whole weight stack out every step).

``lightning_chunk``: rows of several tokens (prompt chunks).  A cell is
one head and one row: tile by tile the masked, decayed ``(Q K^T) V``
inside the row, ``Q S`` against the carried state decayed to each
position, and the state's update ``lambda^n S + (K decayed)^T V``.
Float32 operands: at 128 x 128 tiles the kernel is bound by its launch
and layout, not the MXU, so nothing is rounded to bfloat16.

``lightning_decode_reference`` and ``lightning_chunk_reference`` are the
same contracts in plain ``jax.numpy``, one token at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import platform
from ray_tpu.ops.power_retention import _listed_row, _listed_slot, token_rows
from ray_tpu.ops.ragged_paged_attention import _listed

# heads of the state one decode cell moves
HEAD_BLOCK = 8


def state_bytes(d: int, heads: int) -> int:
    """Float32 bytes of one slot and layer."""
    return heads * d * d * 4


def _int_rows(*rows):
    return tuple(jnp.asarray(a, jnp.int32) for a in rows)


# -- rows of one token ------------------------------------------------------

def _decode_kernel(rows_r, n_r, slot_r, start_r, ly_r,
                   q_ref, k_ref, v_ref, lam_ref, s_in, o_ref, s_out,
                   qt_s, kt_s, *, HB: int):
    del slot_r, ly_r                       # index maps read them
    i = pl.program_id(0)

    # i < n always holds under Mosaic, whose grid ends at n; the
    # interpreter's grid is the capacity.
    @pl.when(i < n_r[0])
    def _row():
        fresh = start_r[rows_r[i]] == 0
        # the query and the key as columns, a lane a head
        qt_s[...] = q_ref[0].T
        kt_s[...] = k_ref[0].T
        for hh in range(HB):
            s = jnp.where(fresh, 0.0, s_in[0, 0, hh])      # [d, d]
            s = (s * lam_ref[hh:hh + 1, :]
                 + kt_s[:, hh:hh + 1] * v_ref[0, hh:hh + 1, :])
            s_out[0, 0, hh] = s
            o_ref[0, hh:hh + 1, :] = jnp.sum(
                qt_s[:, hh:hh + 1] * s, axis=0, keepdims=True)


def lightning_decode(
    q: jax.Array,            # [T, H, d], scaled
    k: jax.Array,            # [T, H, d]
    v: jax.Array,            # [T, H, d]
    lam: jax.Array,          # [H] float32, the layer's decay per head
    lin_s: jax.Array,        # [L, S + 1, H, d, d] float32, in place
    layer: jax.Array,
    row_slot: jax.Array,     # [R]
    row_start: jax.Array,
    row_len: jax.Array,
    row_off: jax.Array,
):
    """The rows of ONE token: each slot's state decayed, updated with
    the token's key and value, read against its query.  Returns (o
    [T, H, d] float32, zero at the tokens of other rows; lin_s).  Rows
    occupy distinct slots."""
    T, H, d = q.shape
    S1 = lin_s.shape[1]
    R = row_slot.shape[0]
    HB = min(HEAD_BLOCK, H)
    assert H % HB == 0
    f32, i32 = jnp.float32, jnp.int32
    row_slot, row_start, row_len, row_off = _int_rows(
        row_slot, row_start, row_len, row_off)
    one = row_len == 1
    rows, n = _listed(one)
    at = jnp.clip(row_off, 0, T - 1)

    lam8 = jnp.broadcast_to(lam.astype(f32)[:, None], (H, d))

    def v_map(i, b, rows_p, n_p, *pf):
        return (_listed_row(i, rows_p, n_p, 0), b, 0)

    def s_map(i, b, rows_p, n_p, slot_p, start_p, ly):
        return (ly[0], _listed_slot(i, rows_p, n_p, slot_p, S1 - 1), b, 0, 0)

    # what a step past the list's end writes lands in row R, which
    # nobody reads
    def o_map(i, b, rows_p, n_p, *pf):
        return (_listed_row(i, rows_p, n_p, R), b, 0)

    interpret = platform.interpret_mode()
    s_spec = pl.BlockSpec((1, 1, HB, d, d), s_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(R if interpret else n[0], H // HB),
        in_specs=[
            pl.BlockSpec((1, HB, d), v_map),
            pl.BlockSpec((1, HB, d), v_map),
            pl.BlockSpec((1, HB, d), v_map),
            pl.BlockSpec((HB, d), lambda i, b, *pf: (b, 0)),
            s_spec,
        ],
        out_specs=[pl.BlockSpec((1, HB, d), o_map), s_spec],
        scratch_shapes=[pltpu.VMEM((d, HB), f32), pltpu.VMEM((d, HB), f32)],
    )
    o_rows, lin_s = pl.pallas_call(
        functools.partial(_decode_kernel, HB=HB),
        name="lightning_decode",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R + 1, H, d), f32),
                   jax.ShapeDtypeStruct(lin_s.shape, lin_s.dtype)],
        # prefetch: rows=0 n=1 slot=2 start=3 layer=4, then q=5 k=6
        # v=7 lam=8 lin_s=9
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 2),
        interpret=interpret,
    )(rows, n, row_slot, row_start, jnp.asarray(layer, i32).reshape(1),
      q[at].astype(f32), k[at].astype(f32), v[at].astype(f32), lam8, lin_s)
    tok_row, valid = token_rows(row_len, row_off, T)
    mine = valid & one[tok_row]
    o = jnp.where(mine[:, None, None], o_rows[:R][tok_row], 0.0)
    return o, lin_s


# -- rows of several tokens -------------------------------------------------

def _chunk_kernel(rows_r, n_r, slot_r, start_r, len_r, off_r, ly_r,
                  q_ref, k_ref, v_ref, c_ref, s_in, o_ref, s_out,
                  ds_ref, *, d: int, TT: int):
    del slot_r, ly_r
    i = pl.program_id(1)
    f32 = jnp.float32

    # the head's output block is zero before its first row adds to it
    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_r[0])
    def _row():
        r = rows_r[i]
        off, n = off_r[r], len_r[r]
        lo, hi = off // TT, (off + n - 1) // TT + 1
        c = c_ref[0, 0:1, :]               # [1, W]: -log(lambda), a lane
        c_t, c_d = c[:, :TT], c[:, :d]
        s_prev = jnp.where(start_r[r] == 0, 0.0, s_in[0, 0, 0])   # [d, d]
        ds_ref[...] = jnp.zeros_like(ds_ref)

        def q_tile(a, carry):
            q0 = pl.multiple_of(a * TT, TT)
            qa = q_ref[pl.ds(q0, TT), :]                   # [TT, d]
            tq = q0 + lax.broadcasted_iota(jnp.int32, (TT, 1), 0)
            mine = (tq >= off) & (tq < off + n)            # [TT, 1]
            # the carried state, decayed to each of the tile's positions
            acc = jnp.exp(-(tq - off + 1).astype(f32) * c_d) * jnp.dot(
                qa, s_prev, preferred_element_type=f32)

            def k_tile(b, acc):
                k0 = pl.multiple_of(b * TT, TT)
                tk = k0 + lax.broadcasted_iota(jnp.int32, (1, TT), 1)
                seen = (tq >= tk) & (tk >= off) & mine     # [TT, TT]
                # never positive where seen: the decay cannot overflow
                far = jnp.where(seen, tq - tk, 0).astype(f32)
                s = lax.dot_general(
                    qa, k_ref[pl.ds(k0, TT), :], (((1,), (1,)), ((), ())),
                    preferred_element_type=f32)
                w = jnp.where(seen, s * jnp.exp(-far * c_t), 0.0)
                return acc + jnp.dot(w, v_ref[pl.ds(k0, TT), :],
                                     preferred_element_type=f32)

            acc = lax.fori_loop(lo, a + 1, k_tile, acc)
            o_ref[pl.ds(q0, TT), :] += jnp.where(mine, acc, 0.0)
            # this tile's keys, decayed to the row's last position
            left = jnp.where(mine, off + n - 1 - tq, 0).astype(f32)
            kd = jnp.where(mine, k_ref[pl.ds(q0, TT), :]
                           * jnp.exp(-left * c_d), 0.0)
            ds_ref[...] += lax.dot_general(
                kd, v_ref[pl.ds(q0, TT), :], (((0,), (0,)), ((), ())),
                preferred_element_type=f32)
            return carry

        lax.fori_loop(lo, hi, q_tile, 0)
        s_out[0, 0, 0] = s_prev * jnp.exp(-n.astype(f32) * c_d) + ds_ref[...]


def lightning_chunk(
    q: jax.Array,            # [T, H, d], scaled
    k: jax.Array,            # [T, H, d]
    v: jax.Array,            # [T, H, d]
    lam: jax.Array,          # [H] float32
    lin_s: jax.Array,        # [L, S + 1, H, d, d] float32, in place
    layer: jax.Array,
    row_slot: jax.Array,     # [R]
    row_start: jax.Array,
    row_len: jax.Array,
    row_off: jax.Array,
):
    """The rows of SEVERAL tokens (prompt chunks): every token's output
    from the carried state and the row's earlier tokens, and the state
    after the row's last.  Returns as ``lightning_decode``."""
    T, H, d = q.shape
    S1 = lin_s.shape[1]
    R = row_slot.shape[0]
    f32, i32 = jnp.float32, jnp.int32
    TT = 128 if T >= 128 else 8
    Tp = -(-T // TT) * TT
    W = max(TT, d)
    row_slot, row_start, row_len, row_off = _int_rows(
        row_slot, row_start, row_len, row_off)
    many = row_len > 1
    rows, n = _listed(many)

    def flat(a):
        return jnp.pad(a.astype(f32).reshape(T, H * d),
                       ((0, Tp - T), (0, 0)))

    c8 = jnp.broadcast_to(-jnp.log(lam.astype(f32))[:, None, None],
                          (H, 8, W))

    def s_map(h, i, rows_p, n_p, slot_p, st, ln, of, ly):
        return (ly[0], _listed_slot(i, rows_p, n_p, slot_p, S1 - 1), h, 0, 0)

    def head_map(h, i, *pf):
        return (0, h)

    interpret = platform.interpret_mode()
    tok_spec = pl.BlockSpec((Tp, d), head_map)
    s_spec = pl.BlockSpec((1, 1, 1, d, d), s_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(H, R if interpret else n[0]),
        in_specs=[tok_spec, tok_spec, tok_spec,
                  pl.BlockSpec((1, 8, W), lambda h, i, *pf: (h, 0, 0)),
                  s_spec],
        out_specs=[tok_spec, s_spec],
        scratch_shapes=[pltpu.VMEM((d, d), f32)],
    )
    o, lin_s = pl.pallas_call(
        functools.partial(_chunk_kernel, d=d, TT=TT),
        name="lightning_chunk",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((Tp, H * d), f32),
                   jax.ShapeDtypeStruct(lin_s.shape, lin_s.dtype)],
        # prefetch: rows=0 n=1 slot=2 start=3 len=4 off=5 layer=6, then
        # q=7 k=8 v=9 c=10 lin_s=11
        input_output_aliases={11: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 2),
        interpret=interpret,
    )(rows, n, row_slot, row_start, row_len, row_off,
      jnp.asarray(layer, i32).reshape(1), flat(q), flat(k), flat(v), c8,
      lin_s)
    tok_row, valid = token_rows(row_len, row_off, T)
    mine = valid & many[tok_row]
    return jnp.where(mine[:, None, None], o[:T].reshape(T, H, d), 0.0), lin_s


def lightning_attention(q, k, v, lam, lin_s, layer, row_slot, row_start,
                        row_len, row_off):
    """Every packed row through the kernel of its kind.  Returns (o
    [T, H, d] float32, lin_s); padding rows touch nothing."""
    rows = (row_slot, row_start, row_len, row_off)
    o1, lin_s = lightning_decode(q, k, v, lam, lin_s, layer, *rows)
    oc, lin_s = lightning_chunk(q, k, v, lam, lin_s, layer, *rows)
    return o1 + oc, lin_s


# -- the jnp twins ----------------------------------------------------------

def _lightning_reference(q, k, v, lam, lin_s, layer, row_slot, row_start,
                         row_len, row_off, pick):
    """One token at a time through the flat buffer, float32, for the
    rows ``pick(row_len)`` selects."""
    T = q.shape[0]
    f32 = jnp.float32
    row_slot, row_start, row_len, row_off = _int_rows(
        row_slot, row_start, row_len, row_off)
    tok_row, valid = token_rows(row_len, row_off, T)
    q, k, v = (a.astype(f32) for a in (q, k, v))
    lam = lam.astype(f32)[:, None, None]
    hi = lax.Precision.HIGHEST

    def step(s_all, t):
        r = tok_row[t]
        slot = row_slot[r]
        use = valid[t] & pick(row_len[r])
        first = (t == row_off[r]) & (row_start[r] == 0)
        s = jnp.where(first, 0.0, s_all[slot]) * lam
        s = s + k[t][:, :, None] * v[t][:, None, :]
        o = jnp.einsum("hk,hkd->hd", q[t], s, precision=hi)
        return (jnp.where(use, s_all.at[slot].set(s), s_all),
                jnp.where(use, o, 0.0))

    s_all, o = lax.scan(step, lin_s[layer], jnp.arange(T))
    return o, lin_s.at[layer].set(s_all)


def lightning_decode_reference(q, k, v, lam, lin_s, layer, row_slot,
                               row_start, row_len, row_off):
    """Plain form of ``lightning_decode``."""
    return _lightning_reference(q, k, v, lam, lin_s, layer, row_slot,
                                row_start, row_len, row_off, lambda n: n == 1)


def lightning_chunk_reference(q, k, v, lam, lin_s, layer, row_slot,
                              row_start, row_len, row_off):
    """Plain form of ``lightning_chunk``."""
    return _lightning_reference(q, k, v, lam, lin_s, layer, row_slot,
                                row_start, row_len, row_off, lambda n: n > 1)
