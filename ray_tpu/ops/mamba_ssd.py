"""Pallas TPU kernel for the Mamba-2 SSD chunked recurrence.

BASELINE.json's "state-space ops via Pallas": the einsum formulation in
models/mamba2.ssd_chunked materializes the [B, nc, H, c, c] decay mask
and the per-chunk states in HBM, and propagates chunk state with
``lax.associative_scan`` (log-depth, each level re-reading states from
HBM).  This kernel fuses one (batch, head) stream's whole pass: the
grid walks chunks SEQUENTIALLY with the running [N, P] state held in
VMEM scratch, so chunk state never touches HBM, the decay matrix is
built in registers, and every contraction is an MXU dot.  Numerics
match the einsum path (float32 state math).

Training: the kernel carries a custom VJP whose backward recomputes
through the reference einsum path (jax.vjp) — forward takes the fused
kernel, backward keeps autodiff correctness.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import platform


def _ssd_kernel(x_ref, la_ref, b_ref, c_ref, o_ref, state_scr, *,
                chunk: int, heads: int, head_dim: int):
    """One program per (batch, chunk): every head handled in a static
    loop so B/C load once per chunk and the launch count stays small
    (a per-head grid axis measured SLOWER than the XLA einsum path —
    1000+ tiny programs re-fetching the shared B/C blocks)."""
    z = pl.program_id(1)

    @pl.when(z == 0)
    def _init():
        state_scr[:] = jnp.zeros_like(state_scr)

    f32 = jnp.float32
    Cc = c_ref[0, 0].astype(f32)                 # [c, N]
    Bc = b_ref[0, 0].astype(f32)                 # [c, N]
    scores = jax.lax.dot_general(
        Cc, Bc, (((1,), (1,)), ((), ())), preferred_element_type=f32
    )                                            # [c, c] (head-shared)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = (ii >= jj).astype(f32)
    la_all = la_ref[0, 0].astype(f32)            # [c, H]
    # cumsum as a lower-triangular matmul (no cumsum lowering on TPU);
    # one dot covers every head.
    cum_all = jax.lax.dot_general(
        tri, la_all, (((1,), (0,)), ((), ())),
        preferred_element_type=f32)              # [c, H]

    N = state_scr.shape[0] // heads
    for h in range(heads):                       # static unroll
        lo, hi = h * head_dim, (h + 1) * head_dim
        cum = cum_all[:, h:h + 1]                # [c, 1]
        total = cum[chunk - 1:chunk, :]          # [1, 1]
        xc = x_ref[0, 0, :, lo:hi].astype(f32)   # [c, P]
        diff = cum - cum.reshape(1, chunk)       # [c, c]
        w = jnp.where(ii >= jj, scores * jnp.exp(diff), 0.0)
        state = state_scr[h * N:(h + 1) * N]     # [N, P]
        y = jax.lax.dot_general(
            w, xc, (((1,), (0,)), ((), ())), preferred_element_type=f32
        )
        y = y + jnp.exp(cum) * jax.lax.dot_general(
            Cc, state, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)
        dte = jnp.exp(total - cum)               # [c, 1]
        decay_all = jnp.exp(total[0, 0])         # scalar (2-D bcast ban)
        state_scr[h * N:(h + 1) * N] = (
            decay_all * state + jax.lax.dot_general(
                Bc * dte, xc, (((0,), (0,)), ((), ())),
                preferred_element_type=f32))
        o_ref[0, 0, :, lo:hi] = y.astype(o_ref.dtype)


def _ssd_pallas_fwd_impl(x, log_a, Bm, Cm, chunk: int):
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, f"seq {S} not divisible by chunk {chunk}"
    # The chunk size is an IMPLEMENTATION detail (the output is
    # chunk-invariant): prefer 256 when the sequence allows — fewer,
    # fatter programs.  Measured across chip states: at 256 the kernel
    # holds 1.6x over the associative-scan path on a fresh chip AND
    # ~1.3x when sustained load has inflated per-program overhead,
    # where the 128-chunk variant's 2x program count made it collapse
    # to parity.  (VMEM at 256: x/out blocks 512 KB each + B/C 128 KB
    # + state scratch — comfortably under budget.)
    if chunk < 256 and S % 256 == 0:
        chunk = 256
    nc = S // chunk
    # Feature-flattened layout [.., c, H*P]: the blocked (sublane,
    # lane) dims must be (chunk, features) — a separate head axis in
    # the block violates TPU (8, 128) tiling on real hardware.
    xc = x.reshape(B, nc, chunk, H * P)
    la = log_a.reshape(B, nc, chunk, H)
    Bc = Bm.reshape(B, nc, chunk, N)
    Cc = Cm.reshape(B, nc, chunk, N)

    grid = (B, nc)  # nc innermost: sequential chunk walk per batch
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk, heads=H,
                          head_dim=P),
        name="mamba_ssd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, H * P),
                         lambda b, z: (b, z, 0, 0)),
            pl.BlockSpec((1, 1, chunk, H),
                         lambda b, z: (b, z, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, z: (b, z, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, z: (b, z, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, H * P),
                               lambda b, z: (b, z, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nc, chunk, H * P),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((H * N, P), jnp.float32)],
        # Only the chunk walk is stateful; batches are independent so
        # Mosaic may split them across TensorCores.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=platform.interpret_mode(),
    )(xc, la, Bc, Cc)
    return out.reshape(B, S, H, P)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def ssd_pallas(x, log_a, Bm, Cm, chunk: int):
    """Drop-in for models/mamba2.ssd_chunked: y [B, S, H, P]."""
    return _ssd_pallas_fwd_impl(x, log_a, Bm, Cm, chunk)


def _fwd(x, log_a, Bm, Cm, chunk):
    return _ssd_pallas_fwd_impl(x, log_a, Bm, Cm, chunk), (x, log_a, Bm, Cm)


def _bwd(chunk, res, g) -> Tuple:
    # Backward recomputes through the reference einsum path — autodiff
    # of the fused kernel would need a second kernel; the reference's
    # VJP is correct and still matmul-dominated.
    from ray_tpu.models.mamba2 import ssd_chunked

    x, log_a, Bm, Cm = res
    _, vjp = jax.vjp(
        lambda *a: ssd_chunked(*a, chunk=chunk), x, log_a, Bm, Cm)
    return vjp(g.astype(jnp.float32))


ssd_pallas.defvjp(_fwd, _bwd)
