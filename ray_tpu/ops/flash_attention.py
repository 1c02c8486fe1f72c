"""Flash attention — Pallas TPU kernels with custom VJP.

No reference counterpart (the reference delegates attention to torch;
SURVEY.md §5.7): on TPU this is a core framework op.  Blockwise
online-softmax attention in which every live (q block, kv block) pair
is visited once a pass and does only the work that pair needs:

  schedule: ``block_pairs`` lists the pairs of the causal triangle (or
            of the rectangle when not causal) and says which of them
            the diagonal crosses.  Both kernels walk that list as ONE
            flattened grid axis through scalar-prefetched tables, so a
            pair above the diagonal costs neither a grid step nor a
            DMA, and only a crossed pair runs the body with the mask
            (between equal blocks, without the quarter above the
            diagonal: ``_crossed_parts``).
  forward : grid (B, H, pairs), q-major; running (max, sum, acc) in
            VMEM f32 scratch, the statistics lane-replicated
            ``[bq, 128]``; GQA through the k/v index map (kv head =
            h / group), no k/v expansion in HBM.
  backward: ONE kernel, grid (B, KVH, group x pairs), kv-major.  A
            pair forms s, p, dp and ds once, transposed (``[bk, bq]``:
            lse and delta are then rows that broadcast down the
            sublanes, and dv and dk are plain products), and feeds
            dv, dk (VMEM scratch, written at the kv block's last
            pair) and dq, which stays resident in VMEM for the whole
            walk of a (batch row, kv head) and is written once.  The
            q heads of a kv head are walked against the one resident
            k/v block, so dk and dv are summed over the group in
            scratch.  Nothing S x S ever hits HBM.

All matmuls accumulate in float32 on the MXU
(preferred_element_type); inputs/outputs stay in the model dtype.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import platform

# What has to divide a sequence that takes the kernel
# (ops.attention._flash_eligible); a sequence that WIDE_BLOCK divides
# is walked in blocks of that (``default_blocks``).
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_KV = 512
WIDE_BLOCK = 1024
NEG_INF = -1e30
LANES = 128
VMEM_LIMIT = 100 * 1024 * 1024
# What one backward call may keep of dq in VMEM: the float32 accumulator
# of a kv head's q heads and the two buffers of its output block.  A
# longer sequence is walked a span of q blocks a call.
DQ_RESIDENT_BYTES = 32 * 1024 * 1024

# a table entry's flags: first and last pair of its run (a q block's kv
# blocks forward, a kv block's q blocks backward), crossed by the diagonal
_FIRST, _LAST, _MASKED = 1, 2, 4

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


# --------------------------------------------------------------------------
# the block schedule
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def block_pairs(S: int, block_q: int, block_kv: int, causal: bool,
                q_first: int = 0, q_blocks: int | None = None):
    """The (q block, kv block) pairs a pass over ``S`` positions visits,
    q-major, as ``(qi, ki, masked)``: under ``causal`` a pair wholly
    above the diagonal is left out and ``masked`` marks the pairs the
    diagonal crosses (positions decide, so unequal blocks are fine).
    ``q_first``/``q_blocks`` keep the q blocks of one span."""
    nq, nk = S // block_q, S // block_kv
    q_last = nq if q_blocks is None else q_first + q_blocks
    pairs = []
    for qi in range(q_first, q_last):
        q_lo, q_hi = qi * block_q, (qi + 1) * block_q - 1
        for ki in range(nk):
            k_lo, k_hi = ki * block_kv, (ki + 1) * block_kv - 1
            if causal and k_lo > q_hi:
                break
            pairs.append((qi, ki, causal and k_hi > q_lo))
    return tuple(pairs)


def default_blocks(S: int):
    """(block_q, block_kv) for a sequence of ``S`` when the caller names
    none.  On a v5e at [4, 4096, 16, 128] (PERF.md, PR 43) a forward
    pass takes 2.40 ms in 1024-row blocks and 2.77 in 512-row ones, the
    backward 4.59 and 5.10: a wider block streams more rows past each
    tile the MXU holds, and halving its crossed pairs gives back what
    the wider diagonal would waste."""
    if S % WIDE_BLOCK == 0:
        return WIDE_BLOCK, WIDE_BLOCK
    return min(DEFAULT_BLOCK_Q, S), min(DEFAULT_BLOCK_KV, S)


def pair_counts(S: int, block_q: int, block_kv: int, causal: bool):
    """(pairs walked, pairs masked, pairs of the rectangle): how far the
    schedule engages at a shape.  4096 / 512 / 512 causal: 36, 8, 64."""
    pairs = block_pairs(S, block_q, block_kv, causal)
    return (len(pairs), sum(m for _, _, m in pairs),
            (S // block_q) * (S // block_kv))


def _tables(entries):
    """``entries``: (run key, a, b, masked) in walk order.  Three int32
    tables: a, b and the flags of each step."""
    keys, a, b, masked = zip(*entries)
    last = len(keys) - 1
    flags = [(_FIRST if i == 0 or keys[i - 1] != key else 0)
             | (_LAST if i == last or keys[i + 1] != key else 0)
             | (_MASKED if masked[i] else 0)
             for i, key in enumerate(keys)]
    return tuple(jnp.asarray(np.array(t, np.int32)) for t in (a, b, flags))


def _lanes(x, n: int):
    """``x [r, 128]`` with the lanes of a row all equal -> ``[r, n]``."""
    if n % LANES == 0:
        return x if n == LANES else jnp.tile(x, (1, n // LANES))
    if n < LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _crossed_parts(block_q: int, block_kv: int):
    """What a pair the diagonal crosses has to form, as (q0, qn, k0, kn)
    within its blocks.  Between equal blocks the diagonal runs corner to
    corner and the quarter above it holds nothing visible: the first
    half of the kv rows meets every q row, the second half only the
    second half of the q rows (halves of whole lane tiles only)."""
    half = block_q // 2
    if block_q != block_kv or half % LANES:
        return ((0, block_q, 0, block_kv),)
    return ((0, block_q, 0, half), (half, half, half, half))


def _on_flag(flags, bit: int, want: bool = True):
    hit = (flags & bit) != 0
    return pl.when(hit if want else jnp.logical_not(hit))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(qi_ref, ki_ref, flag_ref, q_ref, k_ref, v_ref, o_ref,
                lse_ref, m_scr, l_scr, acc_scr, *, scale: float,
                block_q: int, block_kv: int, any_masked: bool):
    step = pl.program_id(2)
    flags = flag_ref[step]
    D = q_ref.shape[-1]

    @_on_flag(flags, _FIRST)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _meet(q0: int, qn: int, k0: int, kn: int, masked: bool):
        """Rows q0.. of the q block meet rows k0.. of the kv block."""
        rows = slice(q0, q0 + qn)
        v = v_ref[0, 0, k0:k0 + kn, :]
        s = lax.dot_general(q_ref[0, 0, rows, :], k_ref[0, 0, k0:k0 + kn, :],
                            _NT, preferred_element_type=jnp.float32) * scale
        if masked:
            # q_pos >= k_pos with the two blocks' offsets on the scalar side
            ahead = (lax.broadcasted_iota(jnp.int32, s.shape, 0)
                     - lax.broadcasted_iota(jnp.int32, s.shape, 1))
            lead = ki_ref[step] * block_kv - qi_ref[step] * block_q
            s = jnp.where(ahead >= lead + (k0 - q0), s, NEG_INF)
        m_prev = m_scr[rows]                   # [qn, 128]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, kn))
        alpha = jnp.exp(m_prev - m_next)
        l_scr[rows] = alpha * l_scr[rows] + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[rows] = m_next
        acc_scr[rows] = acc_scr[rows] * _lanes(alpha, D) + lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)

    @_on_flag(flags, _MASKED, False)
    def _below():
        _meet(0, block_q, 0, block_kv, False)

    if any_masked:
        @_on_flag(flags, _MASKED)
        def _crossed():
            for q0, qn, k0, kn in _crossed_parts(block_q, block_kv):
                _meet(q0, qn, k0, kn, True)

    @_on_flag(flags, _LAST)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / _lanes(l_safe, D)).astype(o_ref.dtype)
        # the statistics live down the sublanes; lse leaves as a row
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l_safe)).T[:1]


def _flash_forward(q, k, v, *, scale, causal, block_q, block_kv):
    """q [B,H,S,D], k/v [B,KVH,S,D] → (o [B,H,S,D], lse [B,H,S,1] f32)."""
    B, H, S, D = q.shape
    group = H // k.shape[1]
    pairs = block_pairs(S, block_q, block_kv, causal)
    tables = _tables([(qi, qi, ki, m) for qi, ki, m in pairs])

    def q_map(b, h, p, qi, ki, fl):
        return b, h, qi[p], 0

    def kv_map(b, h, p, qi, ki, fl):
        return b, lax.div(h, jnp.int32(group)), ki[p], 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, H, len(pairs)),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_map),
            pl.BlockSpec((1, 1, block_kv, D), kv_map),
            pl.BlockSpec((1, 1, block_kv, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_map),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda b, h, p, qi, ki, fl: (b, h, 0, qi[p])),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
    )
    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, block_q=block_q, block_kv=block_kv,
            any_masked=any(m for _, _, m in pairs)),
        name="flash_fwd",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=platform.interpret_mode(),
    )(*tables, q, k, v)
    return o, lse.reshape(B, H, S, 1)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _bwd_kernel(rq_ref, ki_ref, flag_ref, q_ref, k_ref, v_ref, do_ref,
                lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                dq_scr, dk_scr, dv_scr, *, scale: float, block_q: int,
                block_kv: int, nq: int, q_offset: int, any_masked: bool):
    """One (batch row, kv head)'s walk, kv-major.  ``rq`` is the q block
    among the ``group * nq`` blocks of the kv head's q heads (head-major),
    ``q_offset`` the position of the call's first q row."""
    step = pl.program_id(2)
    flags = flag_ref[step]

    @pl.when(step == 0)
    def _init_walk():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @_on_flag(flags, _FIRST)
    def _init_run():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _meet(q0: int, qn: int, k0: int, kn: int, masked: bool):
        """Rows q0.. of the q block meet rows k0.. of the kv block."""
        krows = slice(k0, k0 + kn)
        q, do = q_ref[0, 0, q0:q0 + qn, :], do_ref[0, 0, q0:q0 + qn, :]
        k = k_ref[0, 0, krows, :]
        s = lax.dot_general(k, q, _NT,
                            preferred_element_type=jnp.float32) * scale
        if masked:                             # k_pos <= q_pos, [kn, qn]
            qi = lax.rem(rq_ref[step], jnp.int32(nq))
            behind = (lax.broadcasted_iota(jnp.int32, s.shape, 0)
                      - lax.broadcasted_iota(jnp.int32, s.shape, 1))
            lead = q_offset + qi * block_q - ki_ref[step] * block_kv
            s = jnp.where(behind <= lead + (q0 - k0), s, NEG_INF)
        # lse, delta: rows [1, qn]
        p = jnp.exp(s - lse_ref[0, 0, :, q0:q0 + qn])
        dp = lax.dot_general(v_ref[0, 0, krows, :], do, _NT,
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, 0, :, q0:q0 + qn])).astype(q.dtype)
        dv_scr[krows] += lax.dot_general(
            p.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dk_scr[krows] += lax.dot_general(
            ds, q, _NN, preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(rq_ref[step] * block_q + q0, qn), qn)
        dq_scr[rows, :] += lax.dot_general(
            ds, k, _TN, preferred_element_type=jnp.float32)

    @_on_flag(flags, _MASKED, False)
    def _below():
        _meet(0, block_q, 0, block_kv, False)

    if any_masked:
        @_on_flag(flags, _MASKED)
        def _crossed():
            for q0, qn, k0, kn in _crossed_parts(block_q, block_kv):
                _meet(q0, qn, k0, kn, True)

    @_on_flag(flags, _LAST)
    def _finalize_run():
        dk_ref[0, 0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize_walk():
        def store(r, carry):
            rows = pl.ds(pl.multiple_of(r * block_q, block_q), block_q)
            dq_ref[0, 0, rows, :] = (
                dq_scr[rows, :] * scale).astype(dq_ref.dtype)
            return carry

        lax.fori_loop(0, dq_scr.shape[0] // block_q, store, 0)


def _bwd_span(q, k, v, do, lse, delta, pairs, *, scale, block_q, block_kv,
              q_offset, kv_dtype):
    """The kernel over the q rows it is given (``q_offset`` is where they
    start) against the kv rows they can see.  q/do [B,KVH,G*Sq,D] (a kv
    head's q heads one after another), k/v [B,KVH,Skv,D], lse/delta
    [B,KVH,1,G*Sq]; ``pairs`` as ``block_pairs`` gives them for these
    rows.  → dq like q, dk/dv like k in ``kv_dtype``."""
    B, KVH, GSq, D = q.shape
    Skv = k.shape[2]
    q_first = pairs[0][0]
    nq = pairs[-1][0] - q_first + 1
    heads = GSq // (nq * block_q)
    tables = _tables(sorted(
        (ki, g * nq + qi - q_first, ki, m)
        for qi, ki, m in pairs for g in range(heads)))

    def q_map(b, h, p, rq, ki, fl):
        return b, h, rq[p], 0

    def kv_map(b, h, p, rq, ki, fl):
        return b, h, ki[p], 0

    def row_map(b, h, p, rq, ki, fl):
        return b, h, 0, rq[p]

    def walk_map(b, h, p, rq, ki, fl):
        return b, h, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KVH, len(pairs) * heads),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_map),
            pl.BlockSpec((1, 1, block_kv, D), kv_map),
            pl.BlockSpec((1, 1, block_kv, D), kv_map),
            pl.BlockSpec((1, 1, block_q, D), q_map),
            pl.BlockSpec((1, 1, 1, block_q), row_map),
            pl.BlockSpec((1, 1, 1, block_q), row_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, GSq, D), walk_map),
            pl.BlockSpec((1, 1, block_kv, D), kv_map),
            pl.BlockSpec((1, 1, block_kv, D), kv_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((GSq, D), jnp.float32),
            pltpu.VMEM((block_kv, D), jnp.float32),
            pltpu.VMEM((block_kv, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _bwd_kernel, scale=scale, block_q=block_q, block_kv=block_kv,
            nq=nq, q_offset=q_offset,
            any_masked=any(m for _, _, m in pairs)),
        # the name the benchmark's reader sums beside flash_fwd; the
        # kernel is the whole backward (dq, dk and dv)
        name="flash_bwd_dkv",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, KVH, Skv, D), kv_dtype),
            jax.ShapeDtypeStruct((B, KVH, Skv, D), kv_dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=platform.interpret_mode(),
    )(*tables, q, k, v, do, lse, delta)


def _flash_backward(q, k, v, o, lse, do, *, scale, causal, block_q,
                    block_kv):
    """q/o/do [B,H,S,D], k/v [B,KVH,S,D], lse [B,H,S,1] → (dq, dk, dv)
    shaped like q, k, v.  dq is resident in VMEM for a (batch row, kv
    head)'s walk where ``DQ_RESIDENT_BYTES`` holds it; a longer sequence
    is walked a span of q blocks a call, each against the kv blocks its
    rows can see, and the calls' dk and dv are summed."""
    B, H, S, D = q.shape
    KVH = k.shape[1]
    G = H // KVH
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)

    def by_kv_head(x, s):                      # [B,H,s,D] -> [B,KVH,G*s,D]
        return x.reshape(B, KVH, G * s, D)

    def rows(x, s):                            # [B,H,s] -> [B,KVH,1,G*s]
        return x.reshape(B, KVH, 1, G * s)

    nq = S // block_q
    row_bytes = G * D * (4 + 2 * q.dtype.itemsize)
    span = max(1, min(nq, DQ_RESIDENT_BYTES // (row_bytes * block_q)))
    lse = lse.reshape(B, H, S)
    calls = []
    for first in range(0, nq, span):
        pairs = block_pairs(S, block_q, block_kv, causal, first,
                            min(span, nq - first))
        lo, hi = first * block_q, (pairs[-1][0] + 1) * block_q
        seen = (max(ki for _, ki, _ in pairs) + 1) * block_kv
        dq_s, dk_s, dv_s = _bwd_span(
            by_kv_head(q[:, :, lo:hi], hi - lo), k[:, :, :seen],
            v[:, :, :seen], by_kv_head(do[:, :, lo:hi], hi - lo),
            rows(lse[:, :, lo:hi], hi - lo),
            rows(delta[:, :, lo:hi], hi - lo), pairs,
            scale=scale, block_q=block_q, block_kv=block_kv, q_offset=lo,
            kv_dtype=k.dtype if span == nq else jnp.float32)
        calls.append((seen, dq_s.reshape(B, H, hi - lo, D), dk_s, dv_s))
    if span == nq:
        (_, dq, dk, dv), = calls
        return dq, dk, dv
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    for seen, _, dk_s, dv_s in calls:
        dk = dk.at[:, :, :seen].add(dk_s)
        dv = dv.at[:, :, :seen].add(dv_s)
    return (jnp.concatenate([c[1] for c in calls], axis=2),
            dk.astype(k.dtype), dv.astype(v.dtype))


# --------------------------------------------------------------------------
# custom-vjp wrapper
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, block_q, block_kv):
    o, _ = _flash_forward(
        q, k, v, scale=q.shape[-1] ** -0.5, causal=causal,
        block_q=block_q, block_kv=block_kv,
    )
    return o


def _flash_fwd_rule(q, k, v, causal, block_q, block_kv):
    o, lse = _flash_forward(
        q, k, v, scale=q.shape[-1] ** -0.5, causal=causal,
        block_q=block_q, block_kv=block_kv,
    )
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, block_q, block_kv, residuals, do):
    q, k, v, o, lse = residuals
    return _flash_backward(
        q, k, v, o, lse, do, scale=q.shape[-1] ** -0.5,
        causal=causal, block_q=block_q, block_kv=block_kv,
    )


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
) -> jax.Array:
    """Blockwise attention. q [B,S,H,D], k/v [B,S,KVH,D] → [B,S,H,D].

    Requirements: S divisible by the block sizes (``default_blocks(S)``
    where none is named), H divisible by KVH.  Callers
    (ops.attention.dot_product_attention) fall back to the XLA path
    otherwise.
    """
    B, S, H, D = q.shape
    KVH = k.shape[2]
    if H % KVH:
        raise ValueError(f"n_heads {H} not divisible by kv heads {KVH}")
    wide_q, wide_kv = default_blocks(S)
    block_q = min(block_q or wide_q, S)
    block_kv = min(block_kv or wide_kv, S)
    if S % block_q or S % block_kv:
        raise ValueError(f"seq len {S} not divisible by block sizes "
                         f"({block_q}, {block_kv})")
    qt = q.transpose(0, 2, 1, 3)  # [B,H,S,D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash(qt, kt, vt, causal, block_q, block_kv)
    return out.transpose(0, 2, 1, 3)
