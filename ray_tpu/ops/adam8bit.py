"""Block-wise 8-bit Adam over a leaf where it lies, in one pass.

Adam's two moments of a leaf are int8 codes with one float32 scale a
block of (at most) 256 consecutive elements of the leaf in row-major
order; the second moment is kept as its root.  The codes lie as the leaf
does: a 2-D array ``[rows, cols]`` that is the leaf's own
``[prod(leading), last]`` (``view_shape``; a reshape that moves nothing
on the chip's tiled layout), so that a block is a run along a row:

* ``cols`` a multiple of 256: a row is whole blocks;
* ``cols == 128`` and an even number of rows (``wq``, ``wk``, ``wv`` as
  ``[L * d * heads, 128]``): a block is two consecutive rows;
* any other multiple of 128 (``lm_head``'s 92544 = 361 x 256 + 128): a row
  ends in one SHORT block of 128, so no block straddles two rows;
* anything else (a ragged or tiny last axis) is flattened and padded
  with zeros to ``[nb, 256]``, one block a row, as every leaf once was.

The scales are ``[ceil(cols / 256), rows]`` float32, the ROWS ON LANES:
``[rows, cols / 256]`` would pad its 8 or 32 columns to 128 lanes in the
chip's memory, 0.4 GiB for the 1.9B-parameter model.  Where a block is
two rows both rows hold its scale.

``adam8_update`` is the pass: the gradient, both moments (and the
parameter, for the fused chain) are read once, the moments dequantised,
updated, their block maxima taken, requantised and written where they
came from (``input_output_aliases``).  A grid step holds a tile
``(tr, tk)`` of the view and walks it in units of 128 rows by one block:
the unit's new moments stay in registers while a lane reduction finds
the block maxima they are requantised by.  A scale changes between
lanes (the block as stored) and sublanes (the rows of the unit) by a
masked reduction over the unit's ``[128, 128]`` diagonal, which also
pairs the rows where a block is two.  What rides in the pass is fixed at
trace time by ``Adam8``: the global-norm clip, weight decay, the step
size and the addition to the parameter, rounded to the leaf's dtype
where the chain of separate transformations, as XLA compiles it, rounds.

``adam8_update_reference`` is the same contract in plain ``jax.numpy``:
it serves leaves whose view is no whole number of units (norms, biases)
and a run whose mesh shards the state (a ``pallas_call`` is opaque to
the partitioner), and it is what the tests hold the kernel to, bit for
bit.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import platform

BLOCK = 256
HALF = BLOCK // 2
ROWS = 128                 # rows of one unit of the kernel's walk
TILE_COLS = 8 * BLOCK      # a tile's columns where a row is longer
TILE_ELEMS = 512 * 1024    # 14 bytes an element: 7 MB a grid step
N_SCALARS = 8              # c1, c2, norm, clipped?, step size, 3 spare

f32 = jnp.float32


class Adam8(NamedTuple):
    """What the pass computes, fixed at trace time.  With ``fused`` the
    chain clip -> Adam -> weight decay -> step size rides in it and the
    pass reads the parameter; ``apply`` then also adds the update to the
    parameter, which is what comes back."""
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    fused: bool = False
    clip: float = 0.0           # the largest global norm, 0 for no clip
    weight_decay: float = 0.0
    apply: bool = False


def view_shape(shape: Sequence[int]) -> Tuple[int, int]:
    """``[rows, cols]`` of the array a leaf's codes are kept as."""
    n = math.prod(shape)
    cols = shape[-1] if len(shape) else 1
    if n and cols % HALF == 0 and (cols > HALF or (n // cols) % 2 == 0):
        return n // cols, cols
    return -(-n // BLOCK), BLOCK


def scale_shape(rows: int, cols: int) -> Tuple[int, int]:
    return -(-cols // BLOCK), rows


def to_view(x: jax.Array, rows: int, cols: int) -> jax.Array:
    if x.size == rows * cols:
        return x.reshape(rows, cols)
    return jnp.pad(x.reshape(-1), (0, rows * cols - x.size)
                   ).reshape(rows, cols)


def from_view(x2: jax.Array, shape: Sequence[int]) -> jax.Array:
    n = math.prod(shape)
    if x2.size == n:
        return x2.reshape(shape)
    return x2.reshape(-1)[:n].reshape(shape)


def scalars(hp: Adam8, count, dtype, gnorm=None, step_size=None):
    """The traced numbers of one step, as the kernel's SMEM operand:
    the two bias corrections and, fused, the gradients' global norm,
    whether it clips, and the step size, the last three as ``dtype``
    holds them."""
    cf = count.astype(f32)
    vals = [1 - hp.b1 ** cf, 1 - hp.b2 ** cf]
    if hp.fused:
        gnorm = jnp.asarray(gnorm).astype(dtype)
        clips = jnp.logical_not(gnorm < hp.clip) if hp.clip else False
        vals += [gnorm, clips, jnp.asarray(step_size).astype(dtype)]
    vals = [jnp.asarray(v).astype(f32) for v in vals]
    return jnp.stack(vals + [jnp.zeros((), f32)] * (N_SCALARS - len(vals)))


# -- the arithmetic, shared by the kernel and the reference -----------------

def _rounder(dtype, *, kernel: bool):
    """Round a float32 value to what ``dtype`` holds, staying float32.
    Mosaic compiles the two casts as written; XLA may drop them inside a
    fusion (it is allowed more precision than asked for), so the plain
    form says ``reduce_precision``, which it keeps."""
    if dtype == f32:
        return lambda x: x
    if kernel:
        return lambda x: x.astype(dtype).astype(f32)
    info = jnp.finfo(dtype)
    return lambda x: jax.lax.reduce_precision(x, info.nexp, info.nmant)


def _clipped(g32, gnorm, clips, hp: Adam8, rnd):
    """``optax.clip_by_global_norm`` on one tile, in the leaf's dtype."""
    if not (hp.fused and hp.clip):
        return g32
    scaled = rnd(g32 / gnorm)
    if hp.clip != 1.0:
        scaled = rnd(scaled * hp.clip)
    return jnp.where(clips, scaled, g32)


def _adam(g32, m, u, c1, c2, hp: Adam8):
    """The moments' update: the direction, the new first moment and the
    ROOT of the new second one (linear int8 spans 127:1 a block; the
    root doubles the range in decades, or small ``v`` round to 0 and the
    update explodes)."""
    n = hp.b2 * (u * u) + (1 - hp.b2) * (g32 * g32)
    m = hp.b1 * m + (1 - hp.b1) * g32
    # (m / c1) / (sqrt(n / c2) + eps) as XLA's simplifier writes it
    out = m / (c1 * (jnp.sqrt(n / c2) + hp.eps))
    return jnp.clip(out, -10.0, 10.0), m, jnp.sqrt(n)


def _scale(block_max):
    return jnp.maximum(block_max / 127.0, 1e-12)


def _codes(x, scale):
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)


def _stepped(out, p32, step, hp: Adam8, dtype, rnd):
    """Weight decay, the step size and the addition to the parameter:
    in float32, rounded once.  ``optax``'s chain hands the clipped
    gradient and Adam's direction on in the leaf's dtype (``rnd`` at the
    callers), but its last parts and ``apply_updates`` are one fusion to
    XLA, which keeps float32 inside it: on the chip this is that
    program's parameter to the bit (PERF.md, PR 48).  The two constants
    are what the leaf's dtype holds, as there."""
    if not hp.fused:
        return out
    if hp.weight_decay:
        decay = float(np.asarray(hp.weight_decay, jnp.dtype(dtype)))
        out = out + decay * p32
    out = step * out
    return rnd(p32 + out if hp.apply else out)


# -- plain jax.numpy --------------------------------------------------------

def adam8_update_reference(scal, g, p, mq, ms, nq, ns, *, hp: Adam8):
    """``adam8_update`` in plain ``jax.numpy``, for any ``[rows, cols]``:
    every array is brought to ``[blocks, 256]``, a block a row (a short
    block padded with zeros, which no maximum of magnitudes sees)."""
    rows, cols = g.shape
    nslab = ms.shape[0]
    paired = cols == HALF
    dtype = g.dtype
    rnd = _rounder(dtype, kernel=False)

    def blocks(x):      # [rows, cols] -> [nb, BLOCK]
        if not paired:
            x = jnp.pad(x, ((0, 0), (0, nslab * BLOCK - cols)))
        return x.reshape(-1, BLOCK)

    def unblocks(xb):   # and back
        return xb.reshape(rows, -1)[:, :cols]

    def block_scales(s):    # [nslab, rows] -> [nb, 1]
        return (s[0, ::2] if paired else s.T).reshape(-1, 1)

    def scale_rows(sb):     # and back
        if paired:
            return jnp.repeat(sb.reshape(-1), 2).reshape(1, rows)
        return sb.reshape(rows, nslab).T

    g32 = _clipped(blocks(g).astype(f32), scal[2], scal[3] > 0, hp, rnd)
    out, m, un = _adam(g32,
                       blocks(mq).astype(f32) * block_scales(ms),
                       blocks(nq).astype(f32) * block_scales(ns),
                       scal[0], scal[1], hp)
    ms2 = _scale(jnp.max(jnp.abs(m), axis=1, keepdims=True))
    ns2 = _scale(jnp.max(un, axis=1, keepdims=True))
    p32 = blocks(p).astype(f32) if hp.fused else None
    out = _stepped(rnd(out), p32, scal[4], hp, dtype, rnd)
    return (unblocks(out.astype(dtype)),
            unblocks(_codes(m, ms2)), scale_rows(ms2),
            unblocks(_codes(un, ns2)), scale_rows(ns2))


# -- the kernel -------------------------------------------------------------

def _kernel(scal_ref, *refs, hp: Adam8, cols: int):
    g_ref, *refs = refs
    p_ref = refs.pop(0) if hp.fused else None
    (mq_ref, ms_ref, nq_ref, ns_ref,
     out_ref, mq_out, ms_out, nq_out, ns_out) = refs
    tr, tk = g_ref.shape
    dtype = out_ref.dtype
    rnd = _rounder(dtype, kernel=True)
    c1, c2, gnorm, step = (scal_ref[0], scal_ref[1], scal_ref[2],
                           scal_ref[4])
    clips = scal_ref[3] > 0.0
    paired = cols == HALF

    ri = jax.lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 0)
    li = jax.lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 1)
    diag = ri == li
    pair = (ri >> 1) == (li >> 1)

    def as_col(row):    # [1, ROWS] on lanes -> [ROWS, 1] on sublanes
        return jnp.sum(jnp.where(diag, row, 0.0), axis=1, keepdims=True)

    def block_scale(x):
        """The new scale of each row's block of ``x [ROWS, w] >= 0``, on
        sublanes for the codes and on lanes for the state."""
        mx = jnp.max(x, axis=1, keepdims=True)
        if not paired:
            col = _scale(mx)
            return col, jnp.sum(jnp.where(diag, col, 0.0), axis=0,
                                keepdims=True)
        # the larger of each two rows, to both
        row = _scale(jnp.max(jnp.where(pair, mx, 0.0), axis=0,
                             keepdims=True))
        return as_col(row), row

    srow = jax.lax.broadcasted_iota(jnp.int32, ms_ref.shape[:1] + (ROWS,), 0)

    def block(rs, s, w, old, new):
        """Rows ``rs``, block ``s`` of each of them, ``w`` wide; ``old``
        and ``new`` the two moments' scales of the rows' blocks."""
        c0 = s * BLOCK if isinstance(s, int) else pl.multiple_of(
            s * BLOCK, BLOCK)
        cs = pl.ds(c0, w)
        ms, ns = (as_col(jnp.sum(jnp.where(srow == s, x, 0.0), axis=0,
                                 keepdims=True)) for x in old)
        g32 = _clipped(g_ref[rs, cs].astype(f32), gnorm, clips, hp, rnd)
        out, m, un = _adam(g32, mq_ref[rs, cs].astype(f32) * ms,
                           nq_ref[rs, cs].astype(f32) * ns, c1, c2, hp)
        if cols % tk:
            # the tiles do not divide the row (lm_head): past the row's
            # end the last tile holds whatever was there
            at = (pl.program_id(1) * tk + c0
                  + jax.lax.broadcasted_iota(jnp.int32, m.shape, 1))
            m = jnp.where(at < cols, m, 0.0)
            un = jnp.where(at < cols, un, 0.0)
        ms2, ms_row = block_scale(jnp.abs(m))
        ns2, ns_row = block_scale(un)
        mq_out[rs, cs] = _codes(m, ms2)
        nq_out[rs, cs] = _codes(un, ns2)
        p32 = p_ref[rs, cs].astype(f32) if hp.fused else None
        out_ref[rs, cs] = _stepped(rnd(out), p32, step, hp, dtype,
                                   rnd).astype(dtype)
        return tuple(jnp.where(srow == s, row, x)
                     for row, x in zip((ms_row, ns_row), new))

    # A tile is walked in units of ROWS rows by one block.  On the chip
    # both walks are loops, ONE trace of ``block`` a kernel: a kernel's
    # trace and lowering are host time of every run's set-up, ten times
    # dearer inside the trainer's process than alone (PERF.md, PR 47,
    # 48).  Under the interpreter they are unrolled: XLA's CPU compiler
    # contracts ``a * b + c * d`` into a fused multiply-add round one
    # product or the other, program by program (the chip's vector unit
    # has none); unrolled, it chooses as it does in the plain form, and
    # the tests can hold the two to each other bit for bit.
    if platform.interpret_mode():
        def loop(n, body, carry):
            for i in range(n):
                carry = body(i, carry)
            return carry
    else:
        def loop(n, body, carry):
            return jax.lax.fori_loop(0, n, body, carry)

    whole, short = divmod(tk, BLOCK)

    def unit(i, _):
        r0 = i * ROWS if isinstance(i, int) else pl.multiple_of(
            i * ROWS, ROWS)
        rs = pl.ds(r0, ROWS)
        old = ms_ref[:, rs], ns_ref[:, rs]
        new = loop(whole, lambda s, new: block(rs, s, BLOCK, old, new),
                   old)
        if short:
            new = block(rs, whole, short, old, new)
        ms_out[:, rs], ns_out[:, rs] = new

    loop(tr // ROWS, unit, None)


def tile_shape(rows: int, cols: int) -> Optional[Tuple[int, int]]:
    """The kernel's tile of a ``[rows, cols]`` view, or None where the
    view is no whole number of units and the reference serves it."""
    if rows % ROWS or cols % HALF:
        return None
    tk = min(cols, TILE_COLS)
    tr = max(ROWS, TILE_ELEMS // tk // ROWS * ROWS)
    while rows % tr:
        tr -= ROWS
    return tr, tk


@functools.partial(jax.jit, static_argnames="hp")
def adam8_update(scal, g, p, mq, ms, nq, ns, *, hp: Adam8):
    """One Adam step of one leaf's view.  ``g [rows, cols]`` the
    gradient, ``p`` the parameter (``None`` unless ``hp.fused``), ``mq,
    nq`` int8 ``[rows, cols]`` and ``ms, ns`` float32 ``scale_shape``
    the moments, ``scal`` from ``scalars``.  Returns the update (the new
    parameter with ``hp.apply``) in ``g``'s dtype and the new moments,
    written over the old.  Jitted, so that leaves of one shape (``w_gate``
    and ``w_up``, ``wk`` and ``wv``) share one trace and one lowering of
    the kernel inside the step that calls it."""
    rows, cols = g.shape
    tile = tile_shape(rows, cols)
    if tile is None:
        return adam8_update_reference(scal, g, p, mq, ms, nq, ns, hp=hp)
    tr, tk = tile
    grid = (rows // tr, pl.cdiv(cols, tk))
    whole = pl.BlockSpec((tr, tk), lambda i, j: (i, j))
    scale = pl.BlockSpec(scale_shape(tr, tk), lambda i, j: (j, i))
    lead = [g, p] if hp.fused else [g]
    # after scal and the leading operands: mq, ms, nq, ns -> outputs 1..4
    aliases = {1 + len(lead) + k: 1 + k for k in range(4)}
    if hp.apply:
        aliases[2] = 0      # the parameter -> output 0
    return pl.pallas_call(
        functools.partial(_kernel, hp=hp, cols=cols),
        # Not its own name: benchmarks/harness/program_spans.op_label
        # books a Mosaic kernel under the kernel's name, not under its
        # scope, and the benchmark's reader of the update's device time
        # (optimizer_ms_per_step) sums the label ``optimizer``.  Under
        # any other name the reader would go on reading the norm's few
        # milliseconds and call that the optimizer (flash_bwd_dkv,
        # ops/flash_attention.py, is named by the same rule).
        name="optimizer",
        grid=grid,
        in_specs=([pl.BlockSpec(memory_space=pltpu.SMEM)]
                  + [whole] * len(lead) + [whole, scale, whole, scale]),
        out_specs=[whole, whole, scale, whole, scale],
        out_shape=[jax.ShapeDtypeStruct(g.shape, g.dtype),
                   jax.ShapeDtypeStruct(mq.shape, mq.dtype),
                   jax.ShapeDtypeStruct(ms.shape, ms.dtype),
                   jax.ShapeDtypeStruct(nq.shape, nq.dtype),
                   jax.ShapeDtypeStruct(ns.shape, ns.dtype)],
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=48 * 2**20),
        interpret=platform.interpret_mode(),
    )(scal, *lead, mq, ms, nq, ns)
