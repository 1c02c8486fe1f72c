"""Flash attention — Pallas TPU kernels with custom VJP.

No reference counterpart (the reference delegates attention to torch;
SURVEY.md §5.7): on TPU this is a core framework op.  Standard
blockwise online-softmax algorithm:

  forward : grid (B, H, nq, nk), nk innermost-sequential; running
            (max, sum, acc) in VMEM f32 scratch; causal blocks with
            ki > qi skipped via pl.when; GQA handled by the k/v
            BlockSpec index_map (kv head = h // group) — no k/v
            expansion in HBM.
  backward: two kernels — dq over (nq, nk) and dk/dv over (nk, nq) —
            recomputing p from the saved log-sum-exp, so nothing
            S×S ever hits HBM.

All matmuls accumulate in float32 on the MXU
(preferred_element_type); inputs/outputs stay in the model dtype.
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

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_KV = 512
NEG_INF = -1e30


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale: float, causal: bool, block_q: int, block_kv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0, 0]  # [bq, D]
        k = k_ref[0, 0]  # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk]

        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0
            )
            k_pos = ki * block_kv + lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)

        m_prev = m_scr[:]                      # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                 # [bq, bk]
        correction = jnp.exp(m_prev - m_new)   # [bq, 1]
        l_new = correction * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0]                        # [bk, D]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = acc_scr[:] * correction + pv
        m_scr[:] = m_new
        l_scr[:] = l_new

    if causal:
        # skip blocks entirely above the diagonal (position comparison —
        # block indices alone are wrong when block_q != block_kv)
        pl.when(ki * block_kv <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:] + jnp.log(l_safe)


def _flash_forward(q, k, v, *, scale, causal, block_q, block_kv):
    """q [B,H,S,D], k/v [B,KVH,S,D] → (o [B,H,S,D], lse [B,H,S] f32)."""
    B, H, S, D = q.shape
    KVH = k.shape[1]
    group = H // KVH
    nq = pl.cdiv(S, block_q)
    nk = pl.cdiv(S, block_kv)

    grid = (B, H, nq, nk)
    out_shape = [
        jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
    ]
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_kv=block_kv,
    )
    return pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        out_shape=out_shape,
        interpret=platform.interpret_mode(),
    )(q, k, v)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale, causal, block_q, block_kv):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            k_pos = ki * block_kv + lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        lse = lse_ref[0, 0]                   # [bq, 1]
        p = jnp.exp(s - lse)                  # [bq, bk]
        do = do_ref[0, 0]
        dp = jax.lax.dot_general(
            do, v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                      # [bq, bk]
        delta = delta_ref[0, 0]               # [bq, 1]
        ds = p * (dp - delta)                 # [bq, bk]
        dq_scr[:] += scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(ki * block_kv <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                block_q, block_kv):
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                              # [bq, bk]
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            k_pos = ki * block_kv + lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        lse = lse_ref[0, 0]                   # [bq, 1]
        p = jnp.exp(s - lse)                   # [bq, bk]
        do = do_ref[0, 0]                      # [bq, D]
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                      # [bk, D]
        dp = jax.lax.dot_general(
            do, v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                      # [bq, bk]
        delta = delta_ref[0, 0]               # [bq, 1]
        ds = p * (dp - delta)                  # [bq, bk]
        dk_scr[:] += scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                      # [bk, D]

    if causal:
        pl.when(qi * block_q + block_q - 1 >= ki * block_kv)(_compute)
    else:
        _compute()

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_backward(q, k_exp, v_exp, o, lse, do, *, scale, causal,
                    block_q, block_kv):
    """k_exp/v_exp are expanded to H heads; returns dq, dk_exp, dv_exp."""
    B, H, S, D = q.shape
    nq = pl.cdiv(S, block_q)
    nk = pl.cdiv(S, block_kv)

    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1,
                    keepdims=True)

    common_in = [
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, block_kv, D), lambda b, h, i, j: (b, h, j, 0)),
        pl.BlockSpec((1, 1, block_kv, D), lambda b, h, i, j: (b, h, j, 0)),
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
    ]
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_kv=block_kv),
        name="flash_bwd_dq",
        grid=(B, H, nq, nk),
        in_specs=common_in,
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        interpret=platform.interpret_mode(),
    )(q, k_exp, v_exp, do, lse, delta)

    # dk/dv: swap loop order — kv blocks outer, q blocks inner
    kv_in = [
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, j, 0)),
        pl.BlockSpec((1, 1, block_kv, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, block_kv, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, j, 0)),
        pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, j, 0)),
        pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, j, 0)),
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_kv=block_kv),
        name="flash_bwd_dkv",
        grid=(B, H, nk, nq),
        in_specs=kv_in,
        out_specs=[
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, i, j: (b, h, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, D), jnp.float32),
            pltpu.VMEM((block_kv, D), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        ],
        interpret=platform.interpret_mode(),
    )(q, k_exp, v_exp, do, lse, delta)
    return dq, dk, dv


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
    H = q.shape[1]
    KVH = k.shape[1]
    group = H // KVH
    # GQA backward: expand k/v to H heads, reduce grads over the group.
    k_exp = jnp.repeat(k, group, axis=1) if group > 1 else k
    v_exp = jnp.repeat(v, group, axis=1) if group > 1 else v
    dq, dk_exp, dv_exp = _flash_backward(
        q, k_exp, v_exp, o, lse, do, scale=q.shape[-1] ** -0.5,
        causal=causal, block_q=block_q, block_kv=block_kv,
    )
    if group > 1:
        B, _, S, D = dk_exp.shape
        dk = dk_exp.reshape(B, KVH, group, S, D).sum(axis=2)
        dv = dv_exp.reshape(B, KVH, group, S, D).sum(axis=2)
    else:
        dk, dv = dk_exp, dv_exp
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
) -> jax.Array:
    """Blockwise attention. q [B,S,H,D], k/v [B,S,KVH,D] → [B,S,H,D].

    Requirements: S divisible by the block sizes, H divisible by KVH.
    Callers (ops.attention.dot_product_attention) fall back to the XLA
    path otherwise.
    """
    B, S, H, D = q.shape
    KVH = k.shape[2]
    if H % KVH:
        raise ValueError(f"n_heads {H} not divisible by kv heads {KVH}")
    block_q = min(block_q, S)
    block_kv = min(block_kv, S)
    if S % block_q or S % block_kv:
        raise ValueError(f"seq len {S} not divisible by block sizes "
                         f"({block_q}, {block_kv})")
    qt = q.transpose(0, 2, 1, 3)  # [B,H,S,D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash(qt, kt, vt, causal, block_q, block_kv)
    return out.transpose(0, 2, 1, 3)
