"""Xing4.0: multi-head latent attention over a latent page pool, routed
experts with no token dropped, and a four-stream hyper-connection
residual, for serving through the engine's ragged step.

The published model (XingChen-AGI/Xing4.0-29B-A4B) has DeepSeek-V3's key
set plus the hyper-connection's: 40 layers of hidden 3584, the first two
with a dense SwiGLU and the others with 64 routed experts (top 4 by
sigmoid score, a selection bias that does not enter the weights, the
weights renormalised and doubled) and one shared expert; 32 heads of
latent attention (queries through a rank of 768, keys and values through
ONE latent of 512 a token plus one rotary key of 64 all heads share,
YaRN frequencies); and in place of ``x + f(x)`` a residual of FOUR
streams a token, read and written through per-token mixes (mHC):

    x~ = rms_norm(vec(X));  h_pre = sigmoid(a (x~ P_pre) + b)
    h_post = 2 sigmoid(a (x~ P_post) + b)
    H_res = sinkhorn(exp(clip(a mat4x4(x~ P_res) + B)))     20 rounds
    u = h_pre . X;   y = F(rms_norm(u, ln));   X' = H_res X + h_post^T y

``ragged_step`` is the engine's unified step (see
``llama.ragged_step_paged`` for the contract).  The cache is ONE page
pool ``kv_c [L, 1, P + 1, page, 640]`` holding ``c | kr`` a token and
layer (576 lanes, padded to the chip's 128), read through
``ops/latent_attention`` with the up-projections absorbed into the query
and the output, and appended once after the layers (the pool is
read-only inside them).  The lanes past the 576 are the token's log
(``token_log``): which experts each routed layer chose for it, and in
the first layer's row its id and position, so that what a served
sequence was routed to can be read back from its pages and replayed
(the benchmark's served check runs its reference with them); queries
are zero there, so no score sees them.  Beside the pool two counters
the step adds to, ``moe_tokens [Lm, E]`` (pairs each expert has served)
and ``moe_distinct [Lm]`` (experts hit, summed over steps).  Layers are
unrolled with static indices into the stacked leaves, which XLA fuses
into the matmuls that read them; the experts are a leaf a layer, because
nothing fuses a slice into the experts' kernel and a slice of 1.4 GB in
front of it is a copy.

Float32: the residual streams, the mixes' coefficients and Sinkhorn, the
router's scores, softmax sums, norms' statistics; weights, activations
into matmuls and the pool are ``cfg.dtype``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models import latent_moe
from ray_tpu.models.latent_moe import (  # noqa: F401  (this file's names)
    LOG_ID,
    LOG_POS,
    _swiglu,
    absorbed_query,
    attention_out,
    logged,
    rope,
    route,
    token_log,
)
from ray_tpu.models.llama import _head_matmul, rms_norm
from ray_tpu.ops import latent_attention as la
from ray_tpu.ops import moe_experts as moe
from ray_tpu.ops import platform

Params = Dict[str, Any]

# the seeded residual mix: H~ = RES_DIAG on the diagonal plus noise, the
# learned scalars at HC_ALPHA, so H_res is near but not at the identity
# (its diagonal about 0.75) and twenty Sinkhorn rounds bring its sums
# within 1e-3 of 1 (at a diagonal of 3 they stop at 7e-3)
RES_DIAG, HC_ALPHA = 2.0, 0.1


@dataclasses.dataclass(frozen=True)
class XingConfig:
    vocab_size: int = 131072
    dim: int = 3584
    n_layers: int = 40
    n_heads: int = 32
    n_kv_heads: int = 32
    mlp_dim: int = 9216
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    first_dense: int = 2
    q_rank: int = 768
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    n_experts: int = 64
    top_k: int = 4
    n_shared: int = 1
    moe_dim: int = 1024
    route_scale: float = 2.0
    hc_mult: int = 4
    hc_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)
    # YaRN: (factor, original context, beta_fast, beta_slow, mscale_all_dim)
    yarn: Tuple[float, int, float, float, float] = (64.0, 4096, 32.0, 1.0, 1.0)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @classmethod
    def from_published(cls, c: Dict[str, Any], **over) -> "XingConfig":
        """The configuration from the published config.json's keys."""
        rs = c["rope_scaling"]
        return cls(**dict(dict(
            vocab_size=c["vocab_size"], dim=c["hidden_size"],
            n_layers=c["num_hidden_layers"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            mlp_dim=c["intermediate_size"],
            rope_theta=float(c["rope_theta"]),
            norm_eps=float(c["rms_norm_eps"]),
            tie_embeddings=bool(c["tie_word_embeddings"]),
            first_dense=c["first_k_dense_replace"],
            q_rank=c["q_lora_rank"], kv_rank=c["kv_lora_rank"],
            nope_dim=c["qk_nope_head_dim"], rope_dim=c["qk_rope_head_dim"],
            v_dim=c["v_head_dim"], n_experts=c["n_routed_experts"],
            top_k=c["num_experts_per_tok"], n_shared=c["n_shared_experts"],
            moe_dim=c["moe_intermediate_size"],
            route_scale=float(c["routed_scaling_factor"]),
            hc_mult=c["hc_mult"], hc_iters=c["hc_sinkhorn_iters"],
            hc_eps=float(c["hc_eps"]),
            hc_clamp=(float(c["mhc_h_res_clamp_min"]),
                      float(c["mhc_h_res_clamp_max"])),
            yarn=(float(rs["factor"]),
                  int(rs["original_max_position_embeddings"]),
                  float(rs["beta_fast"]), float(rs["beta_slow"]),
                  float(rs["mscale_all_dim"]))), **over))

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.first_dense

    @property
    def latent_dim(self) -> int:
        return self.kv_rank + self.rope_dim

    @property
    def pool_width(self) -> int:
        """Lanes of a token's row in the pool
        (``latent_moe.pool_width``)."""
        return latent_moe.pool_width(self.latent_dim, self.top_k)

    @property
    def softmax_scale(self) -> float:
        m = 0.1 * self.yarn[4] * math.log(self.yarn[0]) + 1.0
        return (self.nope_dim + self.rope_dim) ** -0.5 * m * m


def init_params(rng: jax.Array, cfg: XingConfig) -> Params:
    """Random weights, stacked per kind of layer, made leaf by leaf
    where the arrays live (the experts drawn in ``param_dtype`` itself: a
    float32 draw of one stacked leaf would be 7 GB).  The residual mix is
    drawn near the identity (``RES_DIAG``, ``HC_ALPHA``): a random H~
    mixes the streams to their mean within a few layers, and no check
    would then see the mechanism."""
    d, L, Ld, Lm = cfg.dim, cfg.n_layers, cfg.first_dense, cfg.n_moe
    H, E, F, m = cfg.n_heads, cfg.n_experts, cfg.moe_dim, cfg.hc_mult
    pd, f32 = cfg.param_dtype, jnp.float32
    keys = iter(jax.random.split(rng, 40))

    def normal(shape, fan_in, dtype=pd):
        return (jax.random.normal(next(keys), shape, dtype)
                * fan_in ** -0.5).astype(dtype)

    def hyper():
        cols = 2 * m + m * m
        b_res = (RES_DIAG * jnp.eye(m, dtype=f32).reshape(-1)[None]
                 + 0.5 * jax.random.normal(next(keys), (L, m * m), f32))
        b = jnp.concatenate(
            [jax.random.normal(next(keys), (L, 2 * m), f32), b_res], 1)
        return {"p": normal((L, m * d, cols), m * d, f32),
                "a": jnp.full((L, 3), HC_ALPHA, f32), "b": b}

    def swiglu(lead, width):
        return {"w_gate": normal(lead + (d, width), d),
                "w_up": normal(lead + (d, width), d),
                "w_down": normal(lead + (width, d), width)}

    params: Params = {
        "tok_embed": normal((cfg.vocab_size, d), d),
        "final_norm": jnp.ones((d,), pd),
        "lm_head": normal((d, cfg.vocab_size), d),
        "ln_attn": jnp.ones((L, d), pd),
        "ln_ff": jnp.ones((L, d), pd),
        "hc_attn": hyper(),
        "hc_ffn": hyper(),
        "attn": {
            "w_dq": normal((L, d, cfg.q_rank), d),
            "q_norm": jnp.ones((L, cfg.q_rank), pd),
            "w_uq": normal((L, cfg.q_rank, H, cfg.nope_dim + cfg.rope_dim),
                           cfg.q_rank),
            "w_dkv": normal((L, d, cfg.latent_dim), d),
            "kv_norm": jnp.ones((L, cfg.kv_rank), pd),
            "w_uk": normal((L, cfg.kv_rank, H, cfg.nope_dim), cfg.kv_rank),
            "w_uv": normal((L, cfg.kv_rank, H, cfg.v_dim), cfg.kv_rank),
            "w_o": normal((L, H, cfg.v_dim, d), H * cfg.v_dim),
        },
        "dense": swiglu((Ld,), cfg.mlp_dim),
        "moe": dict(
            # a leaf each layer: the kernel takes an expert stack
            # whole, and a slice in front of it is a copy of 1.4 GB
            experts=[swiglu((E,), F) for _ in range(Lm)],
            router=normal((Lm, d, E), d, f32),
            # e_score_correction_bias: selects, does not weigh
            bias=jnp.zeros((Lm, E), f32),
            shared=swiglu((Lm,), F * cfg.n_shared)),
    }
    return params


def init_cache(cfg: XingConfig, num_pages: int,
               page_size: int) -> Dict[str, jax.Array]:
    """The latent pool ``kv_c [L, 1, P + 1, page, pool_width]`` with a
    scratch page last (as ``llama.init_paged_cache``), and the experts'
    counters."""
    return {
        "kv_c": jnp.zeros((cfg.n_layers, 1, num_pages + 1, page_size,
                           cfg.pool_width), cfg.dtype),
        "moe_tokens": jnp.zeros((cfg.n_moe, cfg.n_experts), jnp.int32),
        "moe_distinct": jnp.zeros((cfg.n_moe,), jnp.int32),
    }


# ----------------------------------------------------------------- pieces

def yarn_inv_freq(cfg: XingConfig) -> np.ndarray:
    """Rotary frequencies of the ``rope_dim`` lanes under YaRN: as
    published where a wavelength turns over ``beta_fast`` times inside
    the original context, divided by ``factor`` where under
    ``beta_slow``, blended linearly between."""
    factor, orig, fast, slow, _ = cfg.yarn
    dim, base = cfg.rope_dim, cfg.rope_theta
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def lane(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    lo = max(math.floor(lane(fast)), 0)
    hi = min(math.ceil(lane(slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / factor * ramp


def rope_tables(cfg: XingConfig, pos: jax.Array):
    """positions [T] -> (sin, cos) [T, rope_dim / 2] float32."""
    return latent_moe.rope_tables(yarn_inv_freq(cfg), pos)


def sinkhorn(h, iters: int, eps: float):
    """``h[i][j]`` [T] each: rounds of (rows over their sum + eps, then
    columns).  Written over the sixteen entries so that XLA sees
    element-wise work and fuses the rounds, where a chain of reductions
    over axes of four would be forty small kernels.  On the chip the
    rounds are unrolled into that one chain; XLA's CPU compiler takes a
    minute and a half over the unrolled chain, so off the chip they stay
    a loop: the same arithmetic in the same order."""
    n = len(h)

    def one_round(_, h):
        rows = [sum(h[i]) + eps for i in range(n)]
        h = [[h[i][j] / rows[i] for j in range(n)] for i in range(n)]
        cols = [sum(h[i][j] for i in range(n)) + eps for j in range(n)]
        return [[h[i][j] / cols[j] for j in range(n)] for i in range(n)]

    return lax.fori_loop(0, iters, one_round, h,
                         unroll=1 if platform.interpret_mode() else iters)


def hc_coefficients(X, hp, cfg: XingConfig):
    """X [T, m, D] float32 -> (h_pre [m], h_post [m], H_res [m][m]) as
    lists of [T] vectors."""
    T, m = X.shape[0], cfg.hc_mult
    flat = X.reshape(T, -1)
    xn = flat * lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                          + cfg.hc_eps)
    z = jnp.dot(xn, hp["p"], precision=lax.Precision.HIGHEST)   # [T, 24]
    a, b = hp["a"], hp["b"]
    pre = jax.nn.sigmoid(a[0] * z[:, :m] + b[:m])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[:, m:2 * m] + b[m:2 * m])
    raw = jnp.exp(jnp.clip(a[2] * z[:, 2 * m:] + b[2 * m:], *cfg.hc_clamp))
    H = sinkhorn([[raw[:, i * m + j] for j in range(m)] for i in range(m)],
                 cfg.hc_iters, cfg.hc_eps)
    return ([pre[:, i] for i in range(m)], [post[:, i] for i in range(m)], H)


def hyper(X, hp, ln, cfg: XingConfig, F):
    """One wrapped sub-layer on the streams X [T, m, D] float32; ``F``
    maps the normed [T, D] in ``cfg.dtype`` to [T, D]."""
    m = cfg.hc_mult
    with jax.named_scope("hc_mix"):
        pre, post, H = hc_coefficients(X, hp, cfg)
        u = sum(pre[j][:, None] * X[:, j] for j in range(m))
        u = rms_norm(u, ln, cfg.norm_eps).astype(cfg.dtype)
    y = F(u).astype(jnp.float32)
    with jax.named_scope("hc_mix"):
        return jnp.stack(
            [sum(H[i][j][:, None] * X[:, j] for j in range(m))
             + post[i][:, None] * y for i in range(m)], axis=1)


def ragged_step(
    params: Params,
    tokens: jax.Array,       # [T] flat ragged token buffer
    tok_pos: jax.Array,      # [T] absolute positions
    row_slot: jax.Array,     # [R] slot of each packed row
    row_start: jax.Array,    # [R] tokens the row's sequence already holds
    row_len: jax.Array,      # [R] fresh tokens this step (0 = padding)
    row_off: jax.Array,      # [R] row's offset into the flat buffer
    block_tables: jax.Array,
    cfg: XingConfig,
    cache: Dict[str, jax.Array],
    *,
    with_routes: bool = False,
    route_dtype: Optional[Any] = None,
):
    """One unified serving step over a ragged batch of prompt chunks and
    decode rows.  Returns (logits [R, V] float32 at each row's last
    fresh token, new cache).  Padding rows return garbage logits and
    leave the pool as it was; padding tokens reach no expert.

    ``with_routes`` (the checks') also returns each routed layer's choice
    ``[Lm, T, k]``; ``route_dtype`` (the checks' control) computes the
    router's scores in that precision instead of float32."""
    T = tokens.shape[0]
    rows = (row_slot, row_start, row_len, row_off)
    pool = cache["kv_c"]
    with jax.named_scope("embed"):
        x = params["tok_embed"][tokens].astype(jnp.float32)
        X = jnp.broadcast_to(x[:, None, :], (T, cfg.hc_mult, cfg.dim))
        sin, cos = rope_tables(cfg, tok_pos)
        trel = jnp.arange(T)[:, None] - row_off[None, :]
        valid = jnp.any((trel >= 0) & (trel < row_len[None, :]), axis=1)

    a, fresh, routes, sizes = params["attn"], [], [], []
    for i in range(cfg.n_layers):
        def attn(un, i=i):
            with jax.named_scope("mla_proj"):
                q, new = absorbed_query(un, a, i, cfg, sin, cos)
            fresh.append(new)
            with jax.named_scope("latent_attn"):
                o_lat = la.ragged_latent_attention(
                    q, new, pool, i, *rows, block_tables,
                    scale=cfg.softmax_scale, rank=cfg.kv_rank)
            with jax.named_scope("mla_proj"):
                return attention_out(o_lat, a, i, cfg)

        X = hyper(X, jax.tree.map(lambda w: w[i], params["hc_attn"]),
                  params["ln_attn"][i], cfg, attn)

        def ffn(un, i=i):
            if i < cfg.first_dense:
                with jax.named_scope("mlp"):
                    return _swiglu(un, params["dense"], i)
            j, m = i - cfg.first_dense, params["moe"]
            with jax.named_scope("moe_route"):
                _s, choice, w = route(un, m["router"][j], m["bias"][j], cfg,
                                      route_dtype)
                routes.append(choice)
            with jax.named_scope("moe_experts"):
                y, gs = moe.routed_experts(un, choice, w, m["experts"][j],
                                           valid)
                sizes.append(gs)
            with jax.named_scope("moe_shared"):
                return y + _swiglu(un, m["shared"], j).astype(jnp.float32)

        X = hyper(X, jax.tree.map(lambda w: w[i], params["hc_ffn"]),
                  params["ln_ff"][i], cfg, ffn)

    with jax.named_scope("kv_append"):
        fresh = logged(fresh, routes, tokens, tok_pos, cfg)
        pool = la.ragged_latent_append(pool, jnp.stack(fresh), *rows,
                                       block_tables)
    new_cache = dict(cache, kv_c=pool)
    if sizes:
        with jax.named_scope("moe_route"):
            gs = jnp.stack(sizes)
            new_cache["moe_tokens"] = cache["moe_tokens"] + gs
            new_cache["moe_distinct"] = cache["moe_distinct"] + jnp.sum(
                gs > 0, axis=1, dtype=jnp.int32)
    with jax.named_scope("lm_head"):
        last = jnp.clip(row_off + jnp.maximum(row_len, 1) - 1, 0, T - 1)
        x = rms_norm(jnp.sum(X[last], axis=1), params["final_norm"],
                     cfg.norm_eps).astype(cfg.dtype)
        logits = _head_matmul(x, params["lm_head"], cfg).astype(jnp.float32)
    if with_routes:
        return logits, new_cache, (jnp.stack(routes) if routes else None)
    return logits, new_cache
