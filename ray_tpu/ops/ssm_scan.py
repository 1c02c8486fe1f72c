"""Selective-scan (Mamba-1) state update over a ragged token batch.

One layer of a Mamba-1 mixer keeps, for every sequence, a state
``s [d_state, d_inner]`` that each token updates:

    s_t = exp(delta_t * A) * s_{t-1} + (delta_t * x_t) (x) B_t
    y_t = sum_n s_t[n] * C_t[n]

(``delta_t``, ``x_t`` are [d_inner]; ``B_t``, ``C_t`` are [d_state];
``A`` is [d_state, d_inner]; the skip term ``D * x_t`` is the caller's).
In the engine's ragged step the tokens of a step are one flat buffer
packed from R rows (ops/ragged_paged_attention.py's descriptors): a
decode row brings one token, a prompt chunk brings many, and each row
continues the sequence that lives in its slot.  The states of all slots
and layers are one array ``ssm [layers, slots + 1, d_state, d_inner]``
in float32 that the step updates in place; the last slot is scratch,
which is where padding rows point so that they touch nothing real.

``ssm_scan`` is the Pallas kernel.  Its grid walks the rows; for a row
it takes the slot's state into VMEM (zero where the row starts a
sequence, ``row_start == 0``: a slot someone else just left is reset by
its first row), walks the row's own tokens in a loop whose trip count is
the row's length, and writes the state back.  So a step costs what the
tokens it carries cost: a decode row is one update of
``[d_state, d_inner]``, a padding row is an empty grid cell, and nothing
depends on the token budget the program was compiled for except the one
read of the ``[T, d_inner]`` operands.  The state is walked in lane
chunks so that the chunk being updated stays in registers across the
row's tokens.

``ssm_scan_reference`` is the same contract in plain ``jax.numpy``, one
token at a time: the oracle of the kernel's tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import platform

# Lanes of the state updated at a time: [16, 1024] float32 is 16 vector
# registers, which leaves room for the token's operands beside it.
LANE_CHUNK = 1024


def token_rows(row_len: jax.Array, row_off: jax.Array, T: int):
    """For each position of the flat buffer: the packed row it belongs
    to and whether any row holds it (rows are packed back to back from
    position 0, padding rows have length 0)."""
    t = jnp.arange(T, dtype=jnp.int32)
    live = row_len > 0
    tok_row = jnp.sum(live[None, :] & (row_off[None, :] <= t[:, None]),
                      axis=1).astype(jnp.int32) - 1
    tok_row = jnp.maximum(tok_row, 0)
    valid = t < jnp.sum(row_len)
    return tok_row, valid


def ssm_scan_reference(delta, x, b, c, a, ssm, layer, row_slot, row_start,
                       row_len, row_off):
    """Plain form of ``ssm_scan``: one token at a time through the flat
    buffer, float32.  Returns (y [T, C], ssm)."""
    T, C = delta.shape
    row_slot, row_start, row_len, row_off = (
        jnp.asarray(v, jnp.int32)
        for v in (row_slot, row_start, row_len, row_off))
    tok_row, valid = token_rows(row_len, row_off, T)
    states = ssm[layer]

    def step(states, t):
        r = tok_row[t]
        slot = row_slot[r]
        first = (t == row_off[r]) & (row_start[r] == 0)
        s = jnp.where(first, 0.0, states[slot])
        s = (jnp.exp(delta[t][None, :] * a) * s
             + (delta[t] * x[t])[None, :] * b[t][:, None])
        y = jnp.sum(s * c[t][:, None], axis=0)
        states = jnp.where(valid[t], states.at[slot].set(s), states)
        return states, jnp.where(valid[t], y, 0.0)

    states, y = lax.scan(step, states, jnp.arange(T))
    return y, ssm.at[layer].set(states)


def _ssm_scan_kernel(slot_r, start_r, len_r, off_r, ly_r,
                     delta_ref, x_ref, b_ref, c_ref, a_ref, s_in_ref,
                     y_ref, s_out_ref, *, C: int, chunk: int):
    del slot_r, ly_r                       # index maps read them
    r = pl.program_id(0)
    n = len_r[r]
    off = off_r[r]
    fresh = start_r[r] == 0

    @pl.when(r == 0)
    def _init_y():
        # positions no row holds are never written below
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(n > 0)
    def _row():
        for lo in range(0, C, chunk):
            cs = slice(lo, lo + chunk)
            a = a_ref[:, cs]                               # [N, chunk]
            s0 = jnp.where(fresh, 0.0, s_in_ref[0, 0, :, cs])

            def token(j, s, cs=cs, a=a):
                t = off + j
                d = delta_ref[pl.ds(t, 1), cs]             # [1, chunk]
                u = d * x_ref[pl.ds(t, 1), cs]
                s = jnp.exp(d * a) * s + u * b_ref[t]      # b: [N, 1]
                y_ref[pl.ds(t, 1), cs] = jnp.sum(
                    s * c_ref[t], axis=0, keepdims=True)
                return s

            s_out_ref[0, 0, :, cs] = lax.fori_loop(0, n, token, s0)


def ssm_scan(
    delta: jax.Array,        # [T, C] float32, after the softplus
    x: jax.Array,            # [T, C] float32, the convolution's output
    b: jax.Array,            # [T, N] float32
    c: jax.Array,            # [T, N] float32
    a: jax.Array,            # [N, C] float32, -exp(A_log) of this layer
    ssm: jax.Array,          # [L, S + 1, N, C] float32, updated in place
    layer: jax.Array,        # which of the L layers
    row_slot: jax.Array,     # [R]
    row_start: jax.Array,
    row_len: jax.Array,
    row_off: jax.Array,
):
    """The scan of every packed row from its slot's state (zero where
    the row starts a sequence).  Returns (y [T, C] float32, ssm) with
    each live row's slot holding the state after the row's last token;
    slots of no row, and every slot under padding rows, are untouched.
    Rows occupy distinct slots (the engine packs one row per slot)."""
    T, C = delta.shape
    L, S1, N, _ = ssm.shape
    R = row_slot.shape[0]
    chunk = min(C, LANE_CHUNK)
    assert C % chunk == 0 and chunk % 128 == 0, (
        "ssm_scan wants d_inner a multiple of 128 (and of 1024 above it)")
    f32 = jnp.float32

    def whole(r, *pf):
        return (0, 0)

    def whole3(r, *pf):
        return (0, 0, 0)

    def state_map(r, slot_p, start_p, len_p, off_p, ly):
        # padding rows point at the scratch slot
        return (ly[0], jnp.where(len_p[r] > 0, slot_p[r], S1 - 1), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(R,),
        in_specs=[
            pl.BlockSpec((T, C), whole),
            pl.BlockSpec((T, C), whole),
            pl.BlockSpec((T, N, 1), whole3),
            pl.BlockSpec((T, N, 1), whole3),
            pl.BlockSpec((N, C), whole),
            pl.BlockSpec((1, 1, N, C), state_map),
        ],
        out_specs=[
            pl.BlockSpec((T, C), whole),
            pl.BlockSpec((1, 1, N, C), state_map),
        ],
    )
    i32 = jnp.int32
    return pl.pallas_call(
        functools.partial(_ssm_scan_kernel, C=C, chunk=chunk),
        name="ssm_scan",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((T, C), f32),
                   jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)],
        # prefetch: slot=0 start=1 len=2 off=3 layer=4, then delta=5
        # x=6 b=7 c=8 a=9 ssm=10
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=96 * 2**20),
        interpret=platform.interpret_mode(),
    )(row_slot.astype(i32), row_start.astype(i32), row_len.astype(i32),
      row_off.astype(i32), jnp.asarray(layer, i32).reshape(1),
      delta.astype(f32), x.astype(f32), b.astype(f32)[:, :, None],
      c.astype(f32)[:, :, None], a.astype(f32), ssm)
