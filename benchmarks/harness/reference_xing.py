"""Plain reference for Xing4.0-29B-A4B: the forward pass of one sequence
in straightforward float32 ``jax.numpy``: no cache, no batching, no
absorbed attention, no sorted experts, no kernels.  Independent of
``ray_tpu/models/xing.py`` and the ops it calls.

Residual state ``X [n, 4, hidden]`` (``hc_mult`` streams), the token's
embedding copied into each stream.  Every sub-layer ``F`` (attention,
then feed-forward) is wrapped the same way (mHC, "Manifold-Constrained
Hyper-Connections"):

    x~     = rms_norm(vec(X))                 no weight, eps = hc_eps
    h_pre  = sigmoid(a_pre (x~ P_pre) + b_pre)                    [4]
    h_post = 2 sigmoid(a_post (x~ P_post) + b_post)               [4]
    H_res  = sinkhorn(exp(clip(a_res mat4x4(x~ P_res) + B_res)))  [4, 4]
    u = h_pre . X;   y = F(rms_norm(u, ln));   X' = H_res X + h_post^T y

and after the last layer ``x = sum of the streams``, the final norm and
the untied head.  ``sinkhorn`` is ``hc_sinkhorn_iters`` rounds of (each
row over its sum + hc_eps, then each column over its sum + hc_eps).

Attention (MLA), written EXPANDED: ``k_h = [c W_uk,h | kr]`` and
``v_h = c W_uv,h`` per head from the latent ``c = rms_norm(u W_dkv[:512])``
and the one rotary key ``kr`` all heads share; causal softmax over the
whole sequence in blocks of queries; scale ``192^-1/2 m^2`` with ``m``
YaRN's ``0.1 mscale_all_dim ln(factor) + 1``.  Rotary turns interleaved
pairs in place with YaRN's blended frequencies.

Feed-forward: a SwiGLU in the first ``first_k_dense_replace`` layers;
after them sigmoid scores over the routed experts in float32, top-k of
``scores + bias``, weights ``scores[chosen] / (their sum + 1e-20) *
routed_scaling_factor``, the experts as a Python loop over all of them
with a mask, plus one shared SwiGLU.  ``layer`` takes a ``choice`` to
run with in the reference's place: top-k is discontinuous, so a token
whose k-th and (k+1)-th scores lie closer than the rounding of the
program's activations is routed differently by the two and no tolerance
on logits can hold it.  The choice is taken only where it is such a
near-tie: every expert on which it differs from the reference's own has
its biased score within ``route_eps`` of the k-th largest (``swap_gap``,
returned for each token); anywhere else the reference keeps its own.

Weights may be given in any dtype; every use converts to float32, one
matrix (one EXPERT) at a time, so a layer needs no float32 copy of itself
beside a serving engine.  ``ASSUMED`` lists what the published
config.json has no key for.  The multi-token-prediction module is not
part of this forward pass.

Callers wrap calls in ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

ASSUMED = {
    "hyper_connection": "the formulas above: streams start as copies of "
                        "the embedding and end as their sum; hc_eps is "
                        "the Sinkhorn denominators' and the flattened "
                        "norm's epsilon; rows before columns; the clamp "
                        "on H~ before exp; h_post's factor 2",
    "rotary": "interleaved pairs turned in place, YaRN frequencies, no "
              "factor on the tables (mscale == mscale_all_dim)",
    "e_score_correction_bias": "zero: its trained values are not public",
    "prediction_module": "num_nextn_predict_layers' block is not loaded",
}
F32 = jnp.float32
QUERY_BLOCK = 512


def _f(w):
    return jnp.asarray(w).astype(F32)


# ---------------------------------------------------------------- weights

def hc_from_program_tree(tree, i: int) -> Dict[str, Any]:
    """The (P [4 hidden, 24], a [3], b [24]) of one wrapper: columns
    0:4 pre, 4:8 post, 8:24 res (row-major 4 x 4)."""
    return {"P": tree["p"][i], "a": tree["a"][i], "b": tree["b"][i]}


def layer_from_program_tree(tree: Dict[str, Any], c: Dict[str, Any],
                            i: int) -> Dict[str, Any]:
    """Layer ``i`` of the program's stacked tree under the published
    names, in the dtype it is stored in; a routed layer WITHOUT its
    experts, which ``expert_from_program_tree`` hands out one by one."""
    a = tree["attn"]
    d = c["hidden_size"]
    out = {
        "hc_attn": hc_from_program_tree(tree["hc_attn"], i),
        "hc_ffn": hc_from_program_tree(tree["hc_ffn"], i),
        "input_layernorm": tree["ln_attn"][i],
        "post_attention_layernorm": tree["ln_ff"][i],
        "q_a_proj": a["w_dq"][i], "q_a_layernorm": a["q_norm"][i],
        "q_b_proj": a["w_uq"][i].reshape(a["w_uq"].shape[1], -1),
        "kv_a_proj_with_mqa": a["w_dkv"][i],
        "kv_a_layernorm": a["kv_norm"][i],
        "kv_b_k": a["w_uk"][i], "kv_b_v": a["w_uv"][i],   # [512, H, 128]
        "o_proj": a["w_o"][i].reshape(-1, d),
    }
    k = c["first_k_dense_replace"]
    if i < k:
        m = tree["dense"]
        out.update(mlp_gate=m["w_gate"][i], mlp_up=m["w_up"][i],
                   mlp_down=m["w_down"][i])
    else:
        m = tree["moe"]
        out.update(router=m["router"][i - k], router_bias=m["bias"][i - k],
                   shared_gate=m["shared"]["w_gate"][i - k],
                   shared_up=m["shared"]["w_up"][i - k],
                   shared_down=m["shared"]["w_down"][i - k])
    return out


def expert_from_program_tree(tree: Dict[str, Any], c: Dict[str, Any],
                             i: int, e: int):
    """(gate, up, down) of expert ``e`` of routed layer ``i``."""
    m = tree["moe"]["experts"][i - c["first_k_dense_replace"]]
    return m["w_gate"][e], m["w_up"][e], m["w_down"][e]


def head_from_program_tree(tree: Dict[str, Any]) -> Dict[str, Any]:
    return {"embed_tokens": tree["tok_embed"], "norm": tree["final_norm"],
            "lm_head": tree["lm_head"]}


# ------------------------------------------------------------------ pieces

def rms_norm(x, w, eps):
    x = x.astype(F32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y if w is None else y * _f(w)


def yarn_inv_freq(c: Dict[str, Any]) -> np.ndarray:
    """The rotary frequencies of the ``qk_rope_head_dim`` lanes: the
    published ones where a wavelength turns over ``beta_fast`` times
    inside the original context, those over ``factor`` where it turns
    under ``beta_slow`` times, a linear blend between."""
    rs, dim, base = c["rope_scaling"], c["qk_rope_head_dim"], c["rope_theta"]
    orig = rs["original_max_position_embeddings"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def at(turns):
        return (dim * math.log(orig / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(at(rs["beta_fast"])), 0)
    high = min(math.ceil(at(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return extra / rs["factor"] * ramp + extra * (1 - ramp)


def softmax_scale(c: Dict[str, Any]) -> float:
    rs = c["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 * m * m


def rope(x, c: Dict[str, Any]):
    """x [n, heads, 64] at positions 0..n-1: pairs (2i, 2i + 1) turned
    by the position's angle, left where they were."""
    n = x.shape[0]
    ang = (np.arange(n, dtype=np.float64)[:, None]
           * yarn_inv_freq(c)[None, :])
    sin = jnp.asarray(np.sin(ang), F32)[:, None, :]
    cos = jnp.asarray(np.cos(ang), F32)[:, None, :]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     -1).reshape(x.shape)


def sinkhorn(m, iters: int, eps: float):
    for _ in range(iters):
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)
    return m


def hc_coefficients(X, hp: Dict[str, Any], c: Dict[str, Any]):
    """(h_pre [n, 4], h_post [n, 4], H_res [n, 4, 4]) for X [n, 4, D]."""
    n, m = X.shape[0], c["hc_mult"]
    z = rms_norm(X.reshape(n, -1), None, float(c["hc_eps"])) @ _f(hp["P"])
    a, b = _f(hp["a"]), _f(hp["b"])
    h_pre = jax.nn.sigmoid(a[0] * z[:, :m] + b[:m])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * z[:, m:2 * m] + b[m:2 * m])
    raw = jnp.clip(a[2] * z[:, 2 * m:] + b[2 * m:],
                   c["mhc_h_res_clamp_min"], c["mhc_h_res_clamp_max"])
    H = sinkhorn(jnp.exp(raw).reshape(n, m, m), c["hc_sinkhorn_iters"],
                 float(c["hc_eps"]))
    return h_pre, h_post, H


def hyper_open(X, hp, ln, c: Dict[str, Any]):
    """What a wrapped sub-layer reads: (its normed input u [n, D],
    h_post [n, 4], H_res [n, 4, 4])."""
    h_pre, h_post, H = hc_coefficients(X, hp, c)
    u = jnp.einsum("nm,nmd->nd", h_pre, X)
    return rms_norm(u, ln, float(c["rms_norm_eps"])), h_post, H


def hyper_close(X, h_post, H, y):
    """What it writes: ``H_res X + h_post^T y``."""
    return (jnp.einsum("nij,njd->nid", H, X)
            + h_post[:, :, None] * y[:, None, :])


def latent_of(u, lp: Dict[str, Any], c: Dict[str, Any]):
    """What a latent cache holds of each token: ``c | kr`` [n, 576]."""
    r = c["kv_lora_rank"]
    ckr = u @ _f(lp["kv_a_proj_with_mqa"])
    lat = rms_norm(ckr[:, :r], lp["kv_a_layernorm"], float(c["rms_norm_eps"]))
    return jnp.concatenate([lat, rope(ckr[:, None, r:], c)[:, 0]], -1)


def attention(u, lp: Dict[str, Any], c: Dict[str, Any],
              query_block: int = QUERY_BLOCK):
    """Expanded MLA on normed inputs u [n, D] -> [n, D]."""
    n = u.shape[0]
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    nope, eps = c["qk_nope_head_dim"], float(c["rms_norm_eps"])
    cq = rms_norm(u @ _f(lp["q_a_proj"]), lp["q_a_layernorm"], eps)
    q = (cq @ _f(lp["q_b_proj"])).reshape(n, H, -1)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], c)], -1)
    lat = latent_of(u, lp, c)
    k = jnp.concatenate(
        [jnp.einsum("nc,chd->nhd", lat[:, :r], _f(lp["kv_b_k"])),
         jnp.broadcast_to(lat[:, None, r:], (n, H, lat.shape[1] - r))], -1)
    v = jnp.einsum("nc,chd->nhd", lat[:, :r], _f(lp["kv_b_v"]))
    scale, pos, out = softmax_scale(c), jnp.arange(n), []
    for t0 in range(0, n, query_block):
        t1 = min(n, t0 + query_block)
        s = jnp.einsum("thd,shd->hts", q[t0:t1], k[:t1]) * scale
        s = jnp.where(pos[None, t0:t1, None] >= pos[None, None, :t1],
                      s, -jnp.inf)
        out.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v[:t1]))
    return jnp.concatenate(out, 0).reshape(n, -1) @ _f(lp["o_proj"])


def swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ _f(gate)) * (u @ _f(up))) @ _f(down)


def route(u, lp: Dict[str, Any], c: Dict[str, Any]):
    """(scores [n, E], biased scores [n, E], choice [n, k] sorted
    ascending by expert, the k-th largest biased score [n]) of the
    router on normed inputs u."""
    k = c["num_experts_per_tok"]
    s = jax.nn.sigmoid(u @ _f(lp["router"]))
    biased = s + _f(lp["router_bias"])
    top, idx = jax.lax.top_k(biased, k)
    return s, biased, jnp.sort(idx, -1), top[:, k - 1]


def swap_gap(biased, kth, own, choice):
    """[n]: how far from the k-th largest biased score the experts lie
    on which ``choice`` differs from ``own`` (the largest distance over
    the experts in one and not the other; 0 where the two agree)."""
    E = biased.shape[-1]
    member = [jnp.any(c[..., None] == jnp.arange(E), axis=-2)
              for c in (own, choice)]
    return jnp.max(jnp.where(member[0] != member[1],
                             jnp.abs(biased - kth[:, None]), 0.0), -1)


def route_weights(u, lp: Dict[str, Any], c: Dict[str, Any], choice,
                  route_eps):
    """(scores, the reference's own choice, ``swap_gap`` of ``choice``,
    the choice run with, its weights [n, k]).  ``choice`` [n, k] (sorted
    by expert; None = the reference's own) replaces the reference's on
    tokens where its gap is at most ``route_eps``."""
    s, biased, own, kth = route(u, lp, c)
    if choice is None:
        gap, used = jnp.zeros(kth.shape, F32), own
    else:
        gap = swap_gap(biased, kth, own, choice)
        used = jnp.where((gap <= route_eps)[:, None], choice, own)
    picked = jnp.take_along_axis(s, used, -1)
    w = (picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
         * c["routed_scaling_factor"])
    return s, own, gap, used, w


def expert_part(u, used, w, e, gate, up, down):
    """Expert ``e``'s addition: its SwiGLU of every token, weighted by
    the token's weight for it (0 = not chosen)."""
    we = jnp.sum(jnp.where(used == e, w, 0.0), -1)
    return we[:, None] * swiglu(u, gate, up, down)


_ATTN = ("hc_attn", "input_layernorm", "q_a_proj", "q_a_layernorm",
         "q_b_proj", "kv_a_proj_with_mqa", "kv_a_layernorm", "kv_b_k",
         "kv_b_v", "o_proj")


@functools.lru_cache(maxsize=4)
def _stages(key: str, query_block: int) -> Dict[str, Callable]:
    """The layer's pieces, each compiled once a configuration and
    sequence length: run operation by operation, a chip compiles a
    hundred small programs a sequence."""
    c = json.loads(key)

    def attn(X, lp):
        u, h_post, H = hyper_open(X, lp["hc_attn"], lp["input_layernorm"], c)
        return (hyper_close(X, h_post, H, attention(u, lp, c, query_block)),
                latent_of(u, lp, c))

    def ffn_open(X, lp):
        return hyper_open(X, lp["hc_ffn"], lp["post_attention_layernorm"], c)

    def dense(u, lp):
        return swiglu(u, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"])

    def routed(u, lp, choice, route_eps):
        return route_weights(u, lp, c, choice, route_eps) + (
            swiglu(u, lp["shared_gate"], lp["shared_up"], lp["shared_down"]),)

    return {"attn": jax.jit(attn), "ffn_open": jax.jit(ffn_open),
            "dense": jax.jit(dense), "routed": jax.jit(routed),
            "expert": jax.jit(expert_part), "close": jax.jit(hyper_close)}


def layer(X, lp: Dict[str, Any], c: Dict[str, Any], *, expert=None,
          choice=None, route_eps: float = math.inf,
          query_block: int = QUERY_BLOCK):
    """One layer on X [n, 4, D].  Returns (X, info): the first
    sub-layer's latent rows, and for a routed layer the router's inputs,
    its scores, the reference's choice and ``choice``'s gap.
    ``expert(e)`` gives expert e's (gate, up, down); see
    ``route_weights`` for ``choice``."""
    st = _stages(json.dumps(c, sort_keys=True), query_block)
    # the attention's own leaves: one compiled program for both kinds of
    # layer
    X, latent = st["attn"](X, {k: v for k, v in lp.items() if k in _ATTN})
    info: Dict[str, Any] = {"latent": latent}
    u, h_post, H = st["ffn_open"](X, {k: lp[k] for k in (
        "hc_ffn", "post_attention_layernorm")})
    if "router" not in lp:
        return st["close"](X, h_post, H, st["dense"](u, lp)), info
    s, own, gap, used, w, y = st["routed"](
        u, lp, None if choice is None else jnp.asarray(choice),
        jnp.float32(route_eps))
    for e in range(c["n_routed_experts"]):      # every expert, masked
        y = y + st["expert"](u, used, w, e, *expert(e))
    info.update(scores=s, choice=own, gap=gap, router_in=u)
    return st["close"](X, h_post, H, y), info


def embed(head: Dict[str, Any], tokens, c: Dict[str, Any]):
    x = _f(head["embed_tokens"][jnp.asarray(tokens)])
    return jnp.broadcast_to(x[:, None, :], (x.shape[0], c["hc_mult"],
                                            x.shape[1]))


def logits_of(X, head: Dict[str, Any], c: Dict[str, Any],
              block: int = 16384):
    """Logits [rows, V] of residual rows X [rows, 4, D], the head a block
    of the vocabulary at a time."""
    x = rms_norm(jnp.sum(X, 1), head["norm"], float(c["rms_norm_eps"]))
    w = head["lm_head"]
    V = w.shape[1]
    part = jax.jit(lambda x, w: x @ _f(w))
    return jnp.concatenate(
        [part(x, w[:, a:min(V, a + block)]) for a in range(0, V, block)], 1)


def forward(tree: Dict[str, Any], tokens, c: Dict[str, Any], *,
            choices: Optional[Dict[int, Any]] = None,
            route_eps: float = math.inf,
            query_block: int = QUERY_BLOCK):
    """tokens [n] -> (X [n, 4, D] before the final sum, [info of each
    layer]) over the program's tree, one layer (one expert) at a time.
    ``choices[i]`` [n, k] is the program's choice in routed layer ``i``:
    tokens where it is a near-tie of the reference's own (``swap_gap``
    at most ``route_eps``) run with it, the others with the
    reference's."""
    X = embed(head_from_program_tree(tree), tokens, c)
    infos = []
    for i in range(c["num_hidden_layers"]):
        X, info = layer(
            X, layer_from_program_tree(tree, c, i), c,
            choice=(choices or {}).get(i), route_eps=route_eps,
            query_block=query_block,
            expert=lambda e, i=i: expert_from_program_tree(tree, c, i, e))
        infos.append(info)
    return X, infos
