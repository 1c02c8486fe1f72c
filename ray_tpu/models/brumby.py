"""Brumby: a dense decoder whose every mixer is a power-retention layer
(degree 2), for serving through the engine's ragged step.

The published model (manifestai/Brumby-14B-Base) keeps its backbone's
shapes: 40 pre-norm layers, hidden 5120, 40 query heads over 8 KV heads
of 128, a SwiGLU of 17408, RMSNorm on each head's q and k, rotary
positions, untied embedding and head:

    h = h + W_o retention(rms_norm(h, ln_in));  h = h + mlp(rms_norm(h, ln_ff))

The mixer replaces softmax attention by ``ops/power_retention``: the
weight of a past token is the SQUARE of the score, decayed by a learned
gate (one scalar per token and KV head, ``sigmoid(w_g . x + b_g)``), so
a sequence's past is one matrix ``S [D', 128]`` and one vector ``z [D']``
per KV head and layer, whatever its length.  No layer keeps K or V by
token: ``init_cache`` returns ``ret_s``/``ret_z`` indexed by slot and no
page pool, and the adapter says so (``PagedEngineAdapter.paged_kv``).

``ragged_step`` is the engine's unified step (see
``llama.ragged_step_paged`` for the contract).  Projections, head norms,
rotary and the gate run over the whole packed buffer (scope
``ret_proj``); the retention itself (scope ``retention``) takes each
packed row through the kernel of its kind.  A row with ``row_start ==
0`` starts from zero state, so a slot is reset by the first chunk of
whoever takes it.  Float32: the state, the gate and its sums, the norms;
weights and activations are ``cfg.dtype``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import (
    _head_matmul,
    _mlp_block,
    apply_rope,
    rms_norm,
    rope_table,
)
from ray_tpu.ops import power_retention as pr
from ray_tpu.ops.ragged_paged_attention import layer_slice

Params = Dict[str, Any]

# half-lives the seeded gates are drawn over, in tokens
HALF_LIFE = (64.0, 8192.0)


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    vocab_size: int = 151936
    dim: int = 5120
    n_layers: int = 40
    n_heads: int = 40
    n_kv_heads: int = 8
    head_dim: int = 128
    mlp_dim: int = 17408
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    ret_eps: float = 1e-6
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @property
    def feature_dim(self) -> int:
        return pr.feature_dim(self.head_dim)

    def state_bytes_per_slot(self) -> int:
        """Retention state one sequence holds, whatever its length: per
        layer and KV head the matrix and the normaliser, float32."""
        s, z = pr.state_bytes(self.head_dim, self.n_kv_heads)
        return self.n_layers * (s + z)


def init_params(rng: jax.Array, cfg: BrumbyConfig) -> Params:
    """Random weights, stacked per layer for the step's scan, made leaf
    by leaf where the arrays live.  The gate's bias is drawn so that the
    heads' half-lives spread log-uniformly over ``HALF_LIFE``: with a
    bias round zero a random model forgets in two tokens and no check
    would see the state at depth."""
    d, L, m = cfg.dim, cfg.n_layers, cfg.mlp_dim
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pd = cfg.param_dtype
    keys = iter(jax.random.split(rng, 16))

    def normal(shape, fan_in):
        # drawn in ``pd`` itself: a float32 draw of the stacked MLP leaf
        # would be a 3.6 GB temporary beside a 10 GB model
        return (jax.random.normal(next(keys), shape, pd)
                * fan_in ** -0.5).astype(pd)

    lo, hi = (jnp.log(x) for x in HALF_LIFE)
    half = jnp.exp(jax.random.uniform(next(keys), (L, KVH), jnp.float32)
                   * (hi - lo) + lo)
    keep = jnp.exp2(-1.0 / half)           # the gate at that half-life
    params: Params = {
        "tok_embed": normal((cfg.vocab_size, d), d),
        "final_norm": jnp.ones((d,), pd),
        "ln_in": jnp.ones((L, d), pd),
        "ln_ff": jnp.ones((L, d), pd),
        "mlp": {
            "w_gate": normal((L, d, m), d),
            "w_up": normal((L, d, m), d),
            "w_down": normal((L, m, d), m),
        },
        "ret": {
            "wq": normal((L, d, H, hd), d),
            "wk": normal((L, d, KVH, hd), d),
            "wv": normal((L, d, KVH, hd), d),
            "wo": normal((L, H, hd, d), H * hd),
            "q_norm": jnp.ones((L, hd), pd),
            "k_norm": jnp.ones((L, hd), pd),
            "w_g": normal((L, d, KVH), d),
            # sigmoid(b_g) == keep; float32, a logit of 9 wants the bits
            "b_g": jnp.log(keep) - jnp.log1p(-keep),
        },
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = normal((d, cfg.vocab_size), d)
    return params


def init_cache(cfg: BrumbyConfig, num_pages: int, page_size: int,
               max_slots: int) -> Dict[str, jax.Array]:
    """The retention state, by slot and layer, and nothing by page:
    ``ret_s [L, slots + 1, KVH, D', hd]`` and ``ret_z [L, slots + 1,
    KVH, D']`` in float32, with a scratch slot last for the kernels'
    steps that belong to no row.  ``num_pages`` and ``page_size`` are
    the adapter contract's; no layer here has a page to size."""
    del num_pages, page_size
    L, KVH, Dp = cfg.n_layers, cfg.n_kv_heads, cfg.feature_dim
    return {
        "ret_s": jnp.zeros((L, max_slots + 1, KVH, Dp, cfg.head_dim),
                           jnp.float32),
        "ret_z": jnp.zeros((L, max_slots + 1, KVH, Dp), jnp.float32),
    }


def _mixer_inputs(u, p, cfg: BrumbyConfig, sin, cos):
    """q, k, v and the gate's log for the packed buffer ``u`` [T, D]."""
    dt_, f32 = cfg.dtype, jnp.float32
    q = jnp.einsum("td,dhk->thk", u, p["wq"].astype(dt_))
    k = jnp.einsum("td,dhk->thk", u, p["wk"].astype(dt_))
    v = jnp.einsum("td,dhk->thk", u, p["wv"].astype(dt_))
    q = apply_rope(rms_norm(q, p["q_norm"], cfg.norm_eps)[None], sin, cos)[0]
    k = apply_rope(rms_norm(k, p["k_norm"], cfg.norm_eps)[None], sin, cos)[0]
    log_g = jax.nn.log_sigmoid(
        jnp.dot(u, p["w_g"].astype(dt_), preferred_element_type=f32)
        + p["b_g"].astype(f32))
    return q, k, v, log_g


def ragged_step(
    params: Params,
    tokens: jax.Array,       # [T] flat ragged token buffer
    tok_pos: jax.Array,      # [T] absolute positions
    row_slot: jax.Array,     # [R] slot of each packed row
    row_start: jax.Array,    # [R] tokens the row's sequence already holds
    row_len: jax.Array,      # [R] fresh tokens this step (0 = padding)
    row_off: jax.Array,      # [R] row's offset into the flat buffer
    block_tables: jax.Array,  # unused: no layer has pages
    cfg: BrumbyConfig,
    cache: Dict[str, jax.Array],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One unified serving step over a ragged batch of prompt chunks and
    decode rows.  Returns (logits [R, V] float32 at each row's last
    fresh token, new cache).  Padding rows return garbage logits and
    leave the cache as it was."""
    del block_tables
    T = tokens.shape[0]
    rows = (row_slot, row_start, row_len, row_off)
    with jax.named_scope("embed"):
        x = params["tok_embed"][tokens].astype(cfg.dtype)  # [T, D]
        sin, cos = rope_table(cfg, tok_pos[None])

    def body(carry, _):
        h, ret_s, ret_z, li = carry
        p = layer_slice(params["ret"], li)
        with jax.named_scope("ret_proj"):
            normed = rms_norm(h, params["ln_in"][li], cfg.norm_eps)
            q, k, v, log_g = _mixer_inputs(normed, p, cfg, sin, cos)
        with jax.named_scope("retention"):
            y, ret_s, ret_z = pr.retention(
                q, k, v, log_g, ret_s, ret_z, li, *rows, eps=cfg.ret_eps)
        with jax.named_scope("ret_proj"):
            h = h + jnp.einsum("thk,hkd->td", y.astype(cfg.dtype),
                               p["wo"].astype(cfg.dtype))
        with jax.named_scope("mlp"):
            layer = {"mlp": layer_slice(params["mlp"], li)}
            normed = rms_norm(h, params["ln_ff"][li], cfg.norm_eps)
            h = h + _mlp_block(normed[None], layer, cfg)[0]
        return (h, ret_s, ret_z, li + 1), None

    (x, ret_s, ret_z, _), _ = lax.scan(
        body, (x, cache["ret_s"], cache["ret_z"], jnp.int32(0)), None,
        length=cfg.n_layers)
    with jax.named_scope("lm_head"):
        last = jnp.clip(row_off + jnp.maximum(row_len, 1) - 1, 0, T - 1)
        head = (params["tok_embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        x = rms_norm(x[last], params["final_norm"], cfg.norm_eps)
        logits = _head_matmul(x, head, cfg)
    return logits.astype(jnp.float32), dict(cache, ret_s=ret_s, ret_z=ret_z)
