"""Routed experts with no token dropped.

``routed_experts`` computes, for every token, the weighted sum of the
SwiGLUs of the experts its router chose.  Nothing has a capacity: the
(token, expert) pairs are sorted by expert, an expert's group is as long
as the pairs that chose it, and every pair is computed
(``models/mixtral.moe_block`` is the capacity form, which drops the
pairs past an expert's share).  A pair whose token is padding
(``valid`` false) belongs to no group and costs nothing.

A chip that holds a SHARE of a layer's experts (expert parallelism: the
router scores every expert of the layer, this chip stores ids ``[first,
first + E)`` of them) says so with ``first``: the choices stay the
layer's ids, a pair whose expert lives on another chip belongs to no
group here, exactly as a padding token's, and the sum returned is this
chip's part of the layer's.  Nothing stands in for the other chips or
for the exchange that adds their parts.

The three grouped products are the kernel ``moe_grouped_ffn``.  Each
group is padded to tiles of ``TILE`` rows; the grid walks the list of live
tiles under a dynamic bound, a tile's expert named by a scalar-prefetched
list, so an expert no token chose is never read and one that several
tiles share is read once (consecutive tiles of one expert keep its
blocks).  The expert's three matrices stream through VMEM in blocks of
``FB`` intermediate columns.  (``lax.ragged_dot`` over the same sorted
rows is the same function; on a v5e XLA's kernel for it reads every
expert of the layer whatever the rows chose: PERF.md PR 34.)

The kernel takes one layer's experts whole, ``[E, D, F]`` and
``[E, F, D]`` leaves of their own: nothing fuses a slice of a stack over
layers into it, and a slice in front of it is a copy of every expert.

``routed_experts`` also returns the groups' sizes: what a step adds to
the engine's counters of pairs served by each expert.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import platform

TILE = 16           # rows of a tile: one packed bf16 sublane tile
FB = 512            # intermediate columns of a streamed block
VMEM_LIMIT = 64 * 1024 * 1024


def routed_experts_reference(u, choice, weight, experts: Dict[str, jax.Array],
                             valid=None, first: int = 0) -> jax.Array:
    """The loop over the experts held (ids ``first`` and up): every one
    of them on every token, masked."""
    f32 = jnp.float32
    uf = u.astype(f32)
    out = jnp.zeros(u.shape, f32)
    for e in range(experts["w_gate"].shape[0]):
        we = jnp.sum(jnp.where(choice == first + e, weight.astype(f32),
                               0.0), -1)
        g = uf @ experts["w_gate"][e].astype(f32)
        up = uf @ experts["w_up"][e].astype(f32)
        out = out + we[:, None] * (
            (jax.nn.silu(g) * up) @ experts["w_down"][e].astype(f32))
    return out if valid is None else jnp.where(valid[:, None], out, 0.0)


def sort_pairs(choice: jax.Array, valid: jax.Array, n_experts: int,
               first: int = 0):
    """The step's (token, expert) pairs in expert order: ``(order [P],
    group_sizes [E])`` with ``order`` indexing the flat pairs ``t * k +
    j``, over the ``n_experts`` held from id ``first``; pairs of padding
    tokens and of experts not held sort last and belong to no group."""
    local = choice - first
    here = valid[:, None] & (local >= 0) & (local < n_experts)
    e = jnp.where(here, local, n_experts).reshape(-1)
    order = jnp.argsort(e, stable=True).astype(jnp.int32)
    sizes = jnp.zeros((n_experts + 1,), jnp.int32).at[e].add(1)[:n_experts]
    return order, sizes


# --------------------------------------------------------------------------
# the kernel
# --------------------------------------------------------------------------

def _ffn_kernel(_te, nt_r, x_ref, wg_ref, wu_ref, wd_ref, out_ref):
    i, f = pl.program_id(0), pl.program_id(1)

    # i < n_tiles always holds under Mosaic; the interpreter's grid is
    # the capacity and its steps past the end do nothing.
    @pl.when(i < nt_r[0])
    def _tile():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * up).astype(x.dtype)
        part = jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32)

        @pl.when(f == 0)
        def _():
            out_ref[...] = part

        @pl.when(f > 0)
        def _():
            out_ref[...] += part


def tile_plan(sizes: jax.Array, n_pairs: int, tile: int = TILE):
    """The padded layout of the sorted pairs: ``(src [Pp], tile_expert
    [Pp / tile], n_tiles [1])``.  Padded row ``p`` holds sorted pair
    ``src[p]`` (``n_pairs`` = none: a zero row); each expert's group
    starts at a tile and fills whole tiles."""
    E = sizes.shape[0]
    cap = -(-(n_pairs + E * (tile - 1)) // tile)        # tiles at most
    padded = -(-sizes // tile) * tile
    p_end, u_end = jnp.cumsum(padded), jnp.cumsum(sizes)
    tiles = jnp.arange(cap, dtype=jnp.int32)
    te = jnp.searchsorted(p_end, tiles * tile, side="right").astype(jnp.int32)
    n_tiles = (p_end[-1] // tile).astype(jnp.int32).reshape(1)
    te_c = jnp.minimum(te, E - 1)
    rank = (jnp.arange(cap * tile, dtype=jnp.int32)
            - jnp.repeat((p_end - padded)[te_c], tile))
    live = (jnp.repeat(te, tile) < E) & (rank < jnp.repeat(sizes[te_c], tile))
    src = jnp.where(live, jnp.repeat((u_end - sizes)[te_c], tile) + rank,
                    n_pairs)
    # past the end the list repeats its last live expert: no DMA there
    last = te_c[jnp.maximum(n_tiles[0] - 1, 0)]
    return src, jnp.where(tiles < n_tiles[0], te_c, last), n_tiles


def _grouped_ffn(xs, sizes, wg, wu, wd):
    """``xs`` [P, D] sorted by expert -> [P, D] float32."""
    P, D = xs.shape
    F = wg.shape[-1]
    fb = min(FB, F)
    src, tile_expert, n_tiles = tile_plan(sizes, P)
    xp = jnp.concatenate([xs, jnp.zeros((1, D), xs.dtype)])[src]
    cap = tile_expert.shape[0]
    prefetch = [tile_expert, n_tiles]
    interpret = platform.interpret_mode()

    def tile_at(i, nt):
        return jnp.minimum(i, jnp.maximum(nt[0] - 1, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(cap if interpret else n_tiles[0], F // fb),
        in_specs=[
            pl.BlockSpec((TILE, D), lambda i, f, te, nt: (tile_at(i, nt), 0)),
            pl.BlockSpec((1, D, fb), lambda i, f, te, nt: (te[i], 0, f)),
            pl.BlockSpec((1, D, fb), lambda i, f, te, nt: (te[i], 0, f)),
            pl.BlockSpec((1, fb, D), lambda i, f, te, nt: (te[i], f, 0)),
        ],
        out_specs=pl.BlockSpec(
            (TILE, D), lambda i, f, te, nt: (tile_at(i, nt), 0)),
    )
    yp = pl.pallas_call(
        _ffn_kernel,
        name="moe_grouped_ffn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((cap * TILE, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*prefetch, xp, wg, wu, wd)
    # back to the sorted order: padded row of each sorted pair
    dest = jnp.zeros((P + 1,), jnp.int32).at[src].set(
        jnp.arange(cap * TILE, dtype=jnp.int32))[:P]
    total = jnp.sum(sizes)
    return jnp.where((jnp.arange(P) < total)[:, None], yp[dest], 0.0)


def routed_experts(u: jax.Array,            # [T, D]
                   choice: jax.Array,       # [T, k] int32
                   weight: jax.Array,       # [T, k] float32
                   experts: Dict[str, jax.Array],   # [E, D, F], [E, F, D]
                   valid: jax.Array,        # [T] bool
                   first: int = 0):
    """Returns ``(y [T, D] float32, group_sizes [E])``; ``y`` is zero at
    padding tokens.  ``experts`` are the ``E`` held, ids ``first`` to
    ``first + E`` of the layer's; ``choice`` names the layer's ids."""
    T, D = u.shape
    k = choice.shape[1]
    E = experts["w_gate"].shape[0]
    mats = tuple(experts[n] for n in ("w_gate", "w_up", "w_down"))
    order, sizes = sort_pairs(choice, valid, E, first)
    xs = u[order // k]
    ys = _grouped_ffn(xs, sizes, *mats)
    ys = ys * weight.reshape(-1)[order][:, None]
    inv = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32))
    return jnp.sum(ys[inv].reshape(T, k, D), axis=1), sizes
