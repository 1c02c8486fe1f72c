"""What the latent-attention, routed-expert model files share: rotary on
interleaved pairs, the absorbed MLA query and output, the sigmoid router
with a selection bias, a SwiGLU over a stacked leaf, and the token log a
step writes into the spare lanes of its latent pool rows.

``models/xing.py`` and ``models/glm5.py`` both call these; ``cfg`` is
either file's configuration (the attributes read here carry the same
names in both: ``dtype``, ``norm_eps``, ``n_heads``, ``nope_dim``,
``kv_rank``, ``latent_dim``, ``pool_width``, ``top_k``, ``route_scale``,
``first_dense``).  A change here moves every cell that serves one of
them.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import rms_norm

# a token's id and position in its first-layer pool row: digits to base
# 256, a lane each (bfloat16 holds the integers up to 256 exactly)
LOG_ID, LOG_POS = 3, 2


def pool_width(latent_dim: int, top_k: int) -> int:
    """Lanes of a token's row in the latent pool: ``latent_dim`` and the
    token's log (``LOG_ID + LOG_POS`` lanes and ``top_k``), rounded up to
    the chip's 128.  A tiled row-major array stores 576 lanes as 640
    whatever its shape says, and XLA, left to choose, lays a 576-lane
    pool out with another axis minor, which the kernels' blocks cannot
    read: every step then copies the pool.  The lanes past the log hold
    zeros."""
    return -(-(latent_dim + top_k + LOG_ID + LOG_POS) // 128) * 128


def rope_tables(inv_freq, pos: jax.Array):
    """positions [T] -> (sin, cos) [T, len(inv_freq)] float32."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
    return jnp.sin(ang), jnp.cos(ang)


def rope(x, sin, cos):
    """x [T, ..., rope_dim]: interleaved pairs turned in place."""
    shape = x.shape
    x = x.astype(jnp.float32).reshape(shape[:-1] + (shape[-1] // 2, 2))
    sin = sin.reshape((shape[0],) + (1,) * (len(shape) - 2) + sin.shape[1:])
    cos = cos.reshape(sin.shape)
    x0, x1 = x[..., 0], x[..., 1]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     -1).reshape(shape)


def route(u, router, bias, cfg, dtype=None):
    """The router on normed inputs u [T, D]: (scores [T, E] float32,
    choice [T, k] int32 sorted by expert, weights [T, k] float32).  The
    scores in float32 at full precision: a top-k is discontinuous, and a
    score rounded to bfloat16 ties experts that are not tied.  ``dtype``
    (the checks' control) computes them in that precision instead."""
    if dtype is None:
        s = jax.nn.sigmoid(jnp.dot(u.astype(jnp.float32), router,
                                   precision=lax.Precision.HIGHEST))
    else:
        s = jax.nn.sigmoid(jnp.dot(u.astype(dtype), router.astype(dtype))
                           ).astype(jnp.float32)
    _top, idx = lax.top_k(s + bias, cfg.top_k)
    choice = jnp.sort(idx, -1).astype(jnp.int32)
    picked = jnp.take_along_axis(s, choice, -1)
    w = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) * cfg.route_scale
    return s, choice, w


def _swiglu(u, m, i):
    dt = u.dtype
    g = jnp.dot(u, m["w_gate"][i].astype(dt))
    up = jnp.dot(u, m["w_up"][i].astype(dt))
    return jnp.dot(jax.nn.silu(g) * up, m["w_down"][i].astype(dt))


def absorbed_query(un, a, i, cfg, sin, cos, with_cq: bool = False):
    """(q [T, H, pool_width] with W_uk absorbed, new [T, pool_width]) of
    normed inputs ``un``: what the latent attention takes, zero in the
    lanes past ``rank + rope``.  ``with_cq`` also returns the normed
    query latent ``cq [T, q_rank]`` (a sparse-attention indexer projects
    its own queries from it)."""
    dt = cfg.dtype
    T, pad = un.shape[0], cfg.pool_width - cfg.latent_dim
    cq = rms_norm(jnp.dot(un, a["w_dq"][i].astype(dt)), a["q_norm"][i],
                  cfg.norm_eps)
    q = jnp.einsum("tc,chk->thk", cq, a["w_uq"][i].astype(dt))
    q_abs = jnp.einsum("thk,chk->thc", q[..., :cfg.nope_dim],
                       a["w_uk"][i].astype(dt))
    q = jnp.concatenate(
        [q_abs, rope(q[..., cfg.nope_dim:], sin, cos).astype(dt),
         jnp.zeros((T, cfg.n_heads, pad), dt)], -1)
    ckr = jnp.dot(un, a["w_dkv"][i].astype(dt))
    c = rms_norm(ckr[:, :cfg.kv_rank], a["kv_norm"][i], cfg.norm_eps)
    new = jnp.concatenate(
        [c, rope(ckr[:, cfg.kv_rank:], sin, cos).astype(dt),
         jnp.zeros((T, pad), dt)], -1)
    return (q, new, cq) if with_cq else (q, new)


def attention_out(o_lat, a, i, cfg):
    """[T, H, rank] float32 of the latent attention -> [T, D]."""
    dt = cfg.dtype
    o = jnp.einsum("thc,chk->thk", o_lat.astype(dt), a["w_uv"][i].astype(dt))
    return jnp.einsum("thk,hkd->td", o, a["w_o"][i].astype(dt))


def _digits(x, n: int):
    return jnp.stack([(x >> (8 * i)) & 255 for i in range(n)], -1)


def logged(fresh, routes, tokens, tok_pos, cfg):
    """The step's new pool rows ``fresh`` (a [T, pool_width] a layer)
    with the tokens' log in the lanes past ``latent_dim``: lanes
    ``[0, top_k)`` of a routed layer's row the experts it chose for the
    token, lanes ``[top_k, top_k + LOG_ID + LOG_POS)`` of the first
    layer's row the token's id and position."""
    at, k = cfg.latent_dim, cfg.top_k
    dt = fresh[0].dtype
    fresh = list(fresh)
    ident = jnp.concatenate([_digits(tokens, LOG_ID),
                             _digits(tok_pos, LOG_POS)], -1)
    fresh[0] = fresh[0].at[:, at + k:at + k + LOG_ID + LOG_POS].set(
        ident.astype(dt))
    for j, choice in enumerate(routes):
        i = cfg.first_dense + j
        fresh[i] = fresh[i].at[:, at:at + k].set(choice.astype(dt))
    return fresh


def token_log(cache: Dict[str, jax.Array], cfg):
    """What ``logged`` wrote, of every page: ``{"tokens" [P + 1, page]
    int32, "pos" [P + 1, page] int32, "routes" [Lm, P + 1, k, page]
    int16}``, new arrays (for ``LLMEngine.read_cache``), the page's
    tokens minor (a minor axis of ``k`` would be stored as 128 lanes).
    A page holds what the last sequence to own it wrote; rows nothing
    wrote read 0."""
    at, k = cfg.latent_dim, cfg.top_k
    lanes = jnp.moveaxis(
        cache["kv_c"][:, 0, :, :, at:at + k + LOG_ID + LOG_POS], -1, -2)
    ident = lanes[0, :, k:].astype(jnp.int32)

    def number(d):
        return sum(d[:, i] << (8 * i) for i in range(d.shape[1]))

    return {"tokens": number(ident[:, :LOG_ID]),
            "pos": number(ident[:, LOG_ID:]),
            "routes": lanes[cfg.first_dense:, :, :k].astype(jnp.int16)}
