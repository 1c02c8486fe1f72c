"""GLM-5 (``glm_moe_dsa``): multi-head latent attention kept to the cached
positions a learned indexer selects, and routed experts of which this
chip holds a share, for serving through the engine's ragged step.

The published model (zai-org/GLM-5, 744B-A40B) has DeepSeek-V3.2's key
set: 78 layers of hidden 6144 with a plain residual; 64 heads of latent
attention (queries through a rank of 2048, keys and values through ONE
latent of 512 a token plus one rotary key of 64 all heads share, plain
rotary frequencies); on every layer a sparse-attention INDEXER, 32 light
heads of 128 that score each cached position for a query from the same
query latent, after which the attention's softmax runs over the 2048
positions of largest score only; three leading dense layers, then 256
routed experts (top 8 by sigmoid score, a selection bias that does not
enter the weights, the weights renormalised x 2.5) and one shared
expert.  With ``h`` the normed input of a position:

    cq = rms_norm(h W_dq);  q = cq W_uq;  c | kr = rms_norm(.) | rope(.)
    qI[t, j] = rope64(cq_t W_iq)_j           32 heads of 128
    kI[s]    = rope64(layer_norm(h_s W_ik))  ONE key of 128 a token
    w[t, j]  = (h_t W_iw)_j 32^-1/2 128^-1/2
    I[t, s]  = sum_j w[t, j] relu(qI[t, j] . kI[s])
    S_t      = the 2048 positions s <= t of largest I[t, s]

One routed layer is 19.3 GB in bfloat16, so a chip serves it as ONE OF
SIXTEEN that share each layer (attention replicated, experts split):
``cfg.n_experts`` are the experts held, ids ``expert_first`` and up of
the layer's ``n_routed``; the router scores all ``n_routed`` and picks
eight, and the layer's output here is the shared expert plus the sum
over the chosen experts THIS CHIP HOLDS.  What the others would add is
left out and that partial sum goes on to the next layer: nothing here
stands in for the other chips or their exchange.

``ragged_step`` is the engine's unified step (see
``llama.ragged_step_paged`` for the contract).  The cache is TWO page
pools under the same block tables, ``kv_c [L, 1, P + 1, page, 640]``
(``c | kr`` and the token's log, as ``models/xing.py``) and ``kv_i [L, 1,
P + 1, page, 128]`` (the index keys), both read-only inside the layers
and appended once after them, and the experts' counters ``moe_tokens
[Lm, held]``, ``moe_distinct [Lm]``.

Float32: the residual, the router's scores, the index scores and their
selection, softmax sums, norms' statistics; weights, activations into
matmuls and both pools are ``cfg.dtype``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import latent_moe
from ray_tpu.models.latent_moe import (
    _swiglu,
    absorbed_query,
    attention_out,
    logged,
    rope,
    route,
)
from ray_tpu.models.llama import _head_matmul, rms_norm
from ray_tpu.ops import dsa_index as dsa
from ray_tpu.ops import latent_attention as la
from ray_tpu.ops import moe_experts as moe

Params = Dict[str, Any]

# Seeded weights (the published ones are trained; what a seed has to give
# is a mechanism a check can see).  With every matrix at fan_in^-1/2 the
# attention's scores have a spread near 1 and a softmax over n positions
# leans on about n / e of them: attending to the wrong 2048, or to all,
# then moves little.  W_uq is drawn Q_GAIN times larger, which spreads a
# head's scores to about 2, so that a softmax over 6000 positions leans on
# about a hundred of them and attending to everything instead of the 2048
# selected moves the first layer's attention output well past its limit
# (PERF.md section 4).  At a gain of 3 a softmax leans on ONE to three
# positions: a selection that differs in one of them is another function,
# bfloat16's rounding of a logit near 10 moves a head's output by
# hundredths, and five layers deep the served check could not tell the
# program from a fault.  The indexer needs no such help: at fan_in^-1/2 a
# query's index scores spread over several units where bfloat16's rounding
# upstream moves one by hundredths, so the 2048th and 2049th are rarely a
# near-tie.
Q_GAIN = 2.0


@dataclasses.dataclass(frozen=True)
class Glm5Config:
    vocab_size: int = 154880
    dim: int = 6144
    n_layers: int = 78
    n_heads: int = 64
    n_kv_heads: int = 64
    mlp_dim: int = 12288
    rope_theta: float = 1.0e6
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    first_dense: int = 3
    q_rank: int = 2048
    kv_rank: int = 512
    nope_dim: int = 192
    rope_dim: int = 64
    v_dim: int = 256
    n_routed: int = 256         # experts of a layer: the router's outputs
    n_experts: int = 256        # experts this chip holds ...
    expert_first: int = 0       # ... ids expert_first and up
    top_k: int = 8
    n_shared: int = 1
    moe_dim: int = 2048
    route_scale: float = 2.5
    index_heads: int = 32
    index_dim: int = 128
    index_topk: int = 2048
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @classmethod
    def from_published(cls, c: Dict[str, Any], **over) -> "Glm5Config":
        """The configuration from the published config.json's keys and
        the deployment's share: ``n_routed_experts`` counts the experts
        HELD, rank ``expert_rank``'s of the ``router_experts`` the layer
        has (both default to the whole layer)."""
        held = c["n_routed_experts"]
        first = c.get("expert_rank", 0) * held
        return cls(**dict(dict(
            vocab_size=c["vocab_size"], dim=c["hidden_size"],
            n_layers=c["num_hidden_layers"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            mlp_dim=c["intermediate_size"],
            rope_theta=float(c["rope_parameters"]["rope_theta"]),
            norm_eps=float(c["rms_norm_eps"]),
            tie_embeddings=bool(c["tie_word_embeddings"]),
            first_dense=c["first_k_dense_replace"],
            q_rank=c["q_lora_rank"], kv_rank=c["kv_lora_rank"],
            nope_dim=c["qk_nope_head_dim"], rope_dim=c["qk_rope_head_dim"],
            v_dim=c["v_head_dim"], n_routed=c.get("router_experts", held),
            n_experts=held, expert_first=first,
            top_k=c["num_experts_per_tok"], n_shared=c["n_shared_experts"],
            moe_dim=c["moe_intermediate_size"],
            route_scale=float(c["routed_scaling_factor"]),
            index_heads=c["index_n_heads"], index_dim=c["index_head_dim"],
            index_topk=c["index_topk"]), **over))

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.first_dense

    @property
    def latent_dim(self) -> int:
        return self.kv_rank + self.rope_dim

    @property
    def pool_width(self) -> int:
        return latent_moe.pool_width(self.latent_dim, self.top_k)

    @property
    def softmax_scale(self) -> float:
        return (self.nope_dim + self.rope_dim) ** -0.5

    @property
    def index_scale(self) -> float:
        return self.index_heads ** -0.5 * self.index_dim ** -0.5


def init_params(rng: jax.Array, cfg: Glm5Config) -> Params:
    """Random weights, stacked per kind of layer, each leaf drawn where
    it lives in ``param_dtype``; only the experts HELD are made."""
    d, L, Ld, Lm = cfg.dim, cfg.n_layers, cfg.first_dense, cfg.n_moe
    H, E, F = cfg.n_heads, cfg.n_experts, cfg.moe_dim
    J, Di = cfg.index_heads, cfg.index_dim
    pd, f32 = cfg.param_dtype, jnp.float32
    keys = iter(jax.random.split(rng, 40 + 3 * Lm))

    def normal(shape, fan_in, dtype=pd, gain=1.0):
        return (jax.random.normal(next(keys), shape, dtype)
                * (gain * fan_in ** -0.5)).astype(dtype)

    def swiglu(lead, width):
        return {"w_gate": normal(lead + (d, width), d),
                "w_up": normal(lead + (d, width), d),
                "w_down": normal(lead + (width, d), width)}

    return {
        "tok_embed": normal((cfg.vocab_size, d), 1.0),
        "final_norm": jnp.ones((d,), pd),
        "lm_head": normal((d, cfg.vocab_size), d),
        "ln_attn": jnp.ones((L, d), pd),
        "ln_ff": jnp.ones((L, d), pd),
        "attn": {
            "w_dq": normal((L, d, cfg.q_rank), d),
            "q_norm": jnp.ones((L, cfg.q_rank), pd),
            "w_uq": normal((L, cfg.q_rank, H, cfg.nope_dim + cfg.rope_dim),
                           cfg.q_rank, gain=Q_GAIN),
            "w_dkv": normal((L, d, cfg.latent_dim), d),
            "kv_norm": jnp.ones((L, cfg.kv_rank), pd),
            "w_uk": normal((L, cfg.kv_rank, H, cfg.nope_dim), cfg.kv_rank),
            "w_uv": normal((L, cfg.kv_rank, H, cfg.v_dim), cfg.kv_rank),
            "w_o": normal((L, H, cfg.v_dim, d), H * cfg.v_dim),
        },
        "index": {
            "w_iq": normal((L, cfg.q_rank, J, Di), cfg.q_rank),
            "w_ik": normal((L, d, Di), d),
            "k_norm": jnp.ones((L, Di), pd),
            "k_bias": jnp.zeros((L, Di), pd),
            "w_iw": normal((L, d, J), d, f32),
        },
        "dense": swiglu((Ld,), cfg.mlp_dim),
        "moe": dict(
            # a leaf each layer: the kernel takes an expert stack whole
            experts=[swiglu((E,), F) for _ in range(Lm)],
            router=normal((Lm, d, cfg.n_routed), d, f32),
            # e_score_correction_bias: selects, does not weigh
            bias=jnp.zeros((Lm, cfg.n_routed), f32),
            shared=swiglu((Lm,), F * cfg.n_shared)),
    }


def init_cache(cfg: Glm5Config, num_pages: int,
               page_size: int) -> Dict[str, jax.Array]:
    """The latent pool, the index keys' pool (a scratch page last in
    both) and the counters of the experts held."""
    return {
        "kv_c": jnp.zeros((cfg.n_layers, 1, num_pages + 1, page_size,
                           cfg.pool_width), cfg.dtype),
        "kv_i": jnp.zeros((cfg.n_layers, 1, num_pages + 1, page_size,
                           cfg.index_dim), cfg.dtype),
        "moe_tokens": jnp.zeros((cfg.n_moe, cfg.n_experts), jnp.int32),
        "moe_distinct": jnp.zeros((cfg.n_moe,), jnp.int32),
    }


def inv_freq(cfg: Glm5Config) -> np.ndarray:
    dim = cfg.rope_dim
    return cfg.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)


def layer_norm(x, w, b, eps: float):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32))


def index_inputs(un, cq, ix, i, cfg: Glm5Config, sin, cos):
    """(qI [T, J, Di], wI [T, J] float32, kI [T, Di]) of normed inputs
    ``un`` and query latents ``cq``: rotary on the first ``rope_dim``
    lanes of queries and keys, interleaved."""
    dt, rd = cfg.dtype, cfg.rope_dim
    q = jnp.einsum("tc,cjd->tjd", cq.astype(dt), ix["w_iq"][i].astype(dt))
    q = jnp.concatenate([rope(q[..., :rd], sin, cos).astype(dt),
                         q[..., rd:]], -1)
    k = layer_norm(jnp.dot(un, ix["w_ik"][i].astype(dt)), ix["k_norm"][i],
                   ix["k_bias"][i], cfg.norm_eps)
    k = jnp.concatenate([rope(k[:, :rd], sin, cos), k[:, rd:]], -1).astype(dt)
    w = jnp.dot(un.astype(jnp.float32), ix["w_iw"][i],
                precision=jax.lax.Precision.HIGHEST) * cfg.index_scale
    return q, w, k


def ragged_step(
    params: Params,
    tokens: jax.Array,       # [T] flat ragged token buffer
    tok_pos: jax.Array,      # [T] absolute positions
    row_slot: jax.Array,     # [R] slot of each packed row
    row_start: jax.Array,    # [R] tokens the row's sequence already holds
    row_len: jax.Array,      # [R] fresh tokens this step (0 = padding)
    row_off: jax.Array,      # [R] row's offset into the flat buffer
    block_tables: jax.Array,
    cfg: Glm5Config,
    cache: Dict[str, jax.Array],
    *,
    with_routes: bool = False,
):
    """One unified serving step over a ragged batch of prompt chunks and
    decode rows.  Returns (logits [R, V] float32 at each row's last
    fresh token, new cache).  Padding rows return garbage logits and
    leave both pools as they were; padding tokens reach no expert.

    The checks' own: ``with_routes`` also returns ``{"routes" [Lm, T, k],
    "sel_pool" [L, T, C], "sel_self" [L, T, T], "sel_one" [L, R, C],
    "more" [R], "attn0" [T, D]}`` (each routed layer's choice, each
    layer's selection as ``dsa.select`` gave it, the first layer's
    attention output).  The checks plant their faults from outside
    (``benchmarks/runners/serve_glm5.planted``): nothing here or in
    ``ops/`` has a mode for them."""
    T = tokens.shape[0]
    rows = (row_slot, row_start, row_len, row_off)
    pool, pool_i = cache["kv_c"], cache["kv_i"]
    with jax.named_scope("embed"):
        x = params["tok_embed"][tokens].astype(jnp.float32)
        sin, cos = latent_moe.rope_tables(inv_freq(cfg), tok_pos)
        trel = jnp.arange(T)[:, None] - row_off[None, :]
        valid = jnp.any((trel >= 0) & (trel < row_len[None, :]), axis=1)

    a, ix = params["attn"], params["index"]
    fresh, fresh_i, routes, sizes, sels, attn0 = [], [], [], [], [], None
    for i in range(cfg.n_layers):
        un = rms_norm(x, params["ln_attn"][i], cfg.norm_eps).astype(cfg.dtype)
        with jax.named_scope("mla_proj"):
            q, new, cq = absorbed_query(un, a, i, cfg, sin, cos, with_cq=True)
        fresh.append(new)
        with jax.named_scope("dsa_index"):
            qI, wI, kI = index_inputs(un, cq, ix, i, cfg, sin, cos)
            fresh_i.append(kI)
            scores = dsa.index_scores(qI, wI, kI, pool_i, i, *rows,
                                      block_tables)
        with jax.named_scope("dsa_select"):
            sel = dsa.select(scores, cfg.index_topk)
            sels.append(sel)
        with jax.named_scope("latent_attn"):
            o_lat = la.ragged_sparse_latent_attention(
                q, new, pool, i, *rows, block_tables, sel,
                scale=cfg.softmax_scale, rank=cfg.kv_rank)
        with jax.named_scope("mla_proj"):
            y = attention_out(o_lat, a, i, cfg).astype(jnp.float32)
        if i == 0:
            attn0 = y
        x = x + y

        un = rms_norm(x, params["ln_ff"][i], cfg.norm_eps).astype(cfg.dtype)
        if i < cfg.first_dense:
            with jax.named_scope("mlp"):
                y = _swiglu(un, params["dense"], i)
        else:
            j, m = i - cfg.first_dense, params["moe"]
            with jax.named_scope("moe_route"):
                _s, choice, w = route(un, m["router"][j], m["bias"][j], cfg)
                routes.append(choice)
            with jax.named_scope("moe_experts"):
                y, gs = moe.routed_experts(un, choice, w, m["experts"][j],
                                           valid, first=cfg.expert_first)
                sizes.append(gs)
            with jax.named_scope("moe_shared"):
                y = y + _swiglu(un, m["shared"], j).astype(jnp.float32)
        x = x + y.astype(jnp.float32)

    with jax.named_scope("kv_append"):
        fresh = logged(fresh, routes, tokens, tok_pos, cfg)
        pool = la.ragged_latent_append(pool, jnp.stack(fresh), *rows,
                                       block_tables)
        pool_i = la.ragged_latent_append(pool_i, jnp.stack(fresh_i), *rows,
                                         block_tables)
    new_cache = dict(cache, kv_c=pool, kv_i=pool_i)
    if sizes:
        with jax.named_scope("moe_route"):
            gs = jnp.stack(sizes)
            new_cache["moe_tokens"] = cache["moe_tokens"] + gs
            new_cache["moe_distinct"] = cache["moe_distinct"] + jnp.sum(
                gs > 0, axis=1, dtype=jnp.int32)
    with jax.named_scope("lm_head"):
        last = jnp.clip(row_off + jnp.maximum(row_len, 1) - 1, 0, T - 1)
        xl = rms_norm(x[last], params["final_norm"],
                      cfg.norm_eps).astype(cfg.dtype)
        logits = _head_matmul(xl, params["lm_head"], cfg).astype(jnp.float32)
    if with_routes:
        seen = {"routes": jnp.stack(routes) if routes else None,
                "sel_pool": jnp.stack([s.pool for s in sels]),
                "sel_self": jnp.stack([s.self for s in sels]),
                "sel_one": jnp.stack([
                    dsa.one_mask(s, s.pool.shape[1]) for s in sels]),
                "more": sels[0].more, "attn0": attn0}
        return logits, new_cache, seen
    return logits, new_cache
