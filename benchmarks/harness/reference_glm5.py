"""Plain reference for GLM-5 (``glm_moe_dsa``): the forward pass of one
sequence in straightforward float32 ``jax.numpy``: no cache, no batching,
no absorbed attention, no sorted experts, no kernels, no bisection.
Independent of ``ray_tpu/models/glm5.py`` and the ops it calls.

A layer, with ``h`` the normed input of a position (plain residual ``x +
f(rms_norm(x))`` around attention and feed-forward):

Attention (MLA), written EXPANDED: ``cq = rms_norm(h W_dq)``, ``q = cq
W_uq`` split a head into ``nope`` 192 and ``rope`` 64; ``k_h = [c W_uk,h |
kr]`` and ``v_h = c W_uv,h`` from the latent ``c = rms_norm((h
W_dkv)[:512])`` and the one rotary key ``kr`` all heads share; softmax
scale ``256^-1/2``; rotary turns interleaved pairs in place, plain
frequencies of ``rope_theta``.

Indexer: ``qI[t, j] = (cq_t W_iq)_j`` (32 heads of 128, from the SAME
``cq``), ``kI[s] = layer_norm(h_s W_ik)`` (weight and bias), rotary on the
first 64 lanes of both; ``w[t, j] = (h_t W_iw)_j 32^-1/2 128^-1/2``;
``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``; ``S_t`` the
``index_topk`` positions ``s <= t`` of largest ``I[t, s]`` (by a sort; all
of them while there are no more), and the softmax runs over ``S_t`` only.
A top-2048 is discontinuous as a top-8 is, so ``layer`` takes the
program's selection to run with in the reference's place, on the terms
``reference_xing`` takes a routing: a query's selection is taken only
where every position on which it differs from the reference's own has its
score within ``sel_eps`` of the reference's k-th largest (``sel_gap``,
returned for each query); anywhere else the reference keeps its own.

Feed-forward: a SwiGLU in the first ``first_k_dense_replace`` layers;
after them sigmoid scores over ALL ``router_experts`` in float32, top-k of
``scores + bias``, weights ``scores[chosen] / (their sum + 1e-20) *
routed_scaling_factor``, plus one shared SwiGLU.  The configuration is ONE
CHIP'S SHARE of a layer: ``n_routed_experts`` experts are held, ids
``expert_rank * n_routed_experts`` and up; the sum runs over the chosen
experts held (a Python loop over them with a mask), what the others would
add is left out, and that partial sum goes on to the next layer, as in the
program.  The vocabulary is the slice the tree holds.

Weights may be given in any dtype; every use converts to float32, one
matrix (one EXPERT) at a time.  ``ASSUMED`` lists what the published
config.json has no key for.  Callers wrap calls in
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

# the sigmoid router with a selection bias, its near-tie rule and an
# expert's masked addition are the family's, written once
from benchmarks.harness.reference_xing import (  # noqa: F401
    expert_part,
    route,
    route_weights,
    swap_gap,
)

ASSUMED = {
    "indexer": "the formulas above, from the published DeepSeek-V3.2 "
               "inference code's Indexer (whose key set glm_moe_dsa "
               "carries); that code rotates qI and kI by a Hadamard "
               "matrix and scores in FP8: here no rotation (orthogonal: "
               "the same dot products), float32 scores",
    "indexer_rotary": "interleaved pairs on the FIRST qk_rope_head_dim "
                      "lanes of qI and kI (indexer_rope_interleave)",
    "index_key_norm": "LayerNorm with weight and bias, eps rms_norm_eps",
    "rotary": "interleaved pairs turned in place (rope_interleave), plain "
              "frequencies theta^(-2i/64), no scaling",
    "e_score_correction_bias": "zero: its trained values are not public",
    "prediction_module": "num_nextn_predict_layers' block is not loaded",
    "share": "the experts held and no other; nothing stands in for the "
             "other fifteen chips' parts or their exchange",
}
F32 = jnp.float32
QUERY_BLOCK = 256
HEAD_BLOCK = 8


def _f(w):
    return jnp.asarray(w).astype(F32)


def held_range(c: Dict[str, Any]):
    """(first id, count) of the experts this share holds."""
    n = c["n_routed_experts"]
    return c.get("expert_rank", 0) * n, n


# ---------------------------------------------------------------- weights

def layer_from_program_tree(tree: Dict[str, Any], c: Dict[str, Any],
                            i: int) -> Dict[str, Any]:
    """Layer ``i`` of the program's stacked tree under the published
    names, in the dtype it is stored in; a routed layer WITHOUT its
    experts, which ``expert_from_program_tree`` hands out one by one."""
    a, x = tree["attn"], tree["index"]
    d = c["hidden_size"]
    out = {
        "input_layernorm": tree["ln_attn"][i],
        "post_attention_layernorm": tree["ln_ff"][i],
        "q_a_proj": a["w_dq"][i], "q_a_layernorm": a["q_norm"][i],
        "q_b_proj": a["w_uq"][i].reshape(a["w_uq"].shape[1], -1),
        "kv_a_proj_with_mqa": a["w_dkv"][i],
        "kv_a_layernorm": a["kv_norm"][i],
        "kv_b_k": a["w_uk"][i], "kv_b_v": a["w_uv"][i],
        "o_proj": a["w_o"][i].reshape(-1, d),
        "indexer_wq_b": x["w_iq"][i].reshape(x["w_iq"].shape[1], -1),
        "indexer_wk": x["w_ik"][i],
        "indexer_k_norm": x["k_norm"][i],
        "indexer_k_norm_bias": x["k_bias"][i],
        "indexer_weights_proj": x["w_iw"][i],
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
    """(gate, up, down) of the ``e``-th expert HELD of routed layer
    ``i`` (the layer's id ``held_range(c)[0] + e``)."""
    m = tree["moe"]["experts"][i - c["first_k_dense_replace"]]
    return m["w_gate"][e], m["w_up"][e], m["w_down"][e]


def head_from_program_tree(tree: Dict[str, Any]) -> Dict[str, Any]:
    return {"embed_tokens": tree["tok_embed"], "norm": tree["final_norm"],
            "lm_head": tree["lm_head"]}


# ------------------------------------------------------------------ pieces

def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f(w)


def layer_norm(x, w, b, eps):
    x = x.astype(F32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f(w) + _f(b)


def rope_tables(n: int, dim: int, c: Dict[str, Any]):
    """(sin, cos) [n, dim / 2] of positions 0..n-1, angles in float64."""
    theta = float(c["rope_parameters"]["rope_theta"])
    freq = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.arange(n, dtype=np.float64)[:, None] * freq[None, :]
    return jnp.asarray(np.sin(ang), F32), jnp.asarray(np.cos(ang), F32)


def rope(x, sin, cos):
    """x [m, heads, 64] at the positions of ``sin``/``cos`` [m, 32]:
    pairs (2i, 2i + 1) turned by the position's angle, left in place."""
    sin, cos = sin[:, None, :], cos[:, None, :]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     -1).reshape(x.shape)


def latent_of(u, lp: Dict[str, Any], c: Dict[str, Any]):
    """What a latent cache holds of each token: ``c | kr`` [n, 576]."""
    r = c["kv_lora_rank"]
    ckr = u @ _f(lp["kv_a_proj_with_mqa"])
    lat = rms_norm(ckr[:, :r], lp["kv_a_layernorm"], float(c["rms_norm_eps"]))
    sin, cos = rope_tables(u.shape[0], c["qk_rope_head_dim"], c)
    return jnp.concatenate([lat, rope(ckr[:, None, r:], sin, cos)[:, 0]], -1)


def index_keys_of(u, lp: Dict[str, Any], c: Dict[str, Any]):
    """What the index-key cache holds of each token: ``kI`` [n, 128]."""
    rd = c["qk_rope_head_dim"]
    k = layer_norm(u @ _f(lp["indexer_wk"]), lp["indexer_k_norm"],
                   lp["indexer_k_norm_bias"], float(c["rms_norm_eps"]))
    sin, cos = rope_tables(u.shape[0], rd, c)
    return jnp.concatenate([rope(k[:, None, :rd], sin, cos)[:, 0],
                            k[:, rd:]], -1)


def index_scores(u, cq, keys, lp: Dict[str, Any], c: Dict[str, Any],
                 sin, cos):
    """``I[t, s]`` of the queries whose normed inputs ``u``, query latents
    ``cq`` and rotary rows ``sin``/``cos`` are given, over the index keys
    ``keys`` [n, 128]."""
    J, Di, rd = c["index_n_heads"], c["index_head_dim"], c["qk_rope_head_dim"]
    q = (cq @ _f(lp["indexer_wq_b"])).reshape(-1, J, Di)
    q = jnp.concatenate([rope(q[..., :rd], sin, cos), q[..., rd:]], -1)
    w = (u @ _f(lp["indexer_weights_proj"])) * (J ** -0.5 * Di ** -0.5)
    s = jnp.einsum("tjd,sd->tjs", q, keys)
    return jnp.sum(w[..., None] * jax.nn.relu(s), axis=1)


def own_selection(I, pos, k: int):
    """(mask [m, n], the k-th largest score [m], causal [m, n]) of the
    scores of queries at positions ``pos``: the ``min(k, t + 1)``
    positions ``s <= t`` of largest ``I[t, s]`` (bit-equal scores at the
    k-th are all in)."""
    n = I.shape[1]
    causal = jnp.arange(n)[None, :] <= pos[:, None]
    x = jnp.where(causal, I, -jnp.inf)
    count = jnp.minimum(pos + 1, k)
    kth = jnp.take_along_axis(jnp.sort(x, -1)[:, ::-1],
                              (count - 1)[:, None], -1)
    return causal & (x >= kth), kth[:, 0], causal


def attention(u, lp: Dict[str, Any], c: Dict[str, Any], sel, sel_eps,
              query_block: int = QUERY_BLOCK):
    """Expanded MLA over each query's selection, on normed inputs u
    [n, D] -> (out [n, D], sel_gap [n], positions selected otherwise
    [n], positions selected [n]).  ``sel`` [n, n] bool (row ``t``: the
    positions the program attended to for query ``t``; None = the
    reference's own) replaces the reference's selection on queries whose
    ``sel_gap`` is at most ``sel_eps``.  A block of queries and of heads
    at a time, each against every position under the causal mask, so
    that one block's arrays and weights are all that is live."""
    n = u.shape[0]
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rd = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    vd, eps = c["v_head_dim"], float(c["rms_norm_eps"])
    qb, hb = min(query_block, n), min(HEAD_BLOCK, H)
    assert H % hb == 0, (H, hb)
    pad = -n % qb
    if pad:
        u = jnp.pad(u, ((0, pad), (0, 0)))
        sel = None if sel is None else jnp.pad(sel, ((0, pad), (0, pad)))
    m = n + pad
    cq = rms_norm(u @ _f(lp["q_a_proj"]), lp["q_a_layernorm"], eps)
    lat = latent_of(u, lp, c)
    keys = index_keys_of(u, lp, c)
    sin, cos = rope_tables(m, rd, c)
    scale = (nope + rd) ** -0.5
    # the heads' weights a block of heads apart: [H / hb, ...]
    w_q = lp["q_b_proj"].reshape(-1, H // hb, hb * (nope + rd))
    w_k = lp["kv_b_k"].reshape(r, H // hb, hb, nope)
    w_v = lp["kv_b_v"].reshape(r, H // hb, hb, vd)
    w_o = lp["o_proj"].reshape(H // hb, hb * vd, -1)

    def query_block_out(t0):
        rows = lambda a: jax.lax.dynamic_slice_in_dim(a, t0, qb, 0)  # noqa: E731
        pos = t0 + jnp.arange(qb)
        I = index_scores(rows(u), rows(cq), keys, lp, c, rows(sin),
                         rows(cos))
        own, kth, causal = own_selection(I, pos, c["index_topk"])
        if sel is None:
            used, gap, diff = own, jnp.zeros((qb,), F32), own & ~own
        else:
            theirs = rows(sel) & causal
            diff = theirs != own
            gap = jnp.max(jnp.where(diff, jnp.abs(I - kth[:, None]), 0.0), -1)
            # (a query the program attended nowhere for, as the padding
            # behind a sequence, keeps the reference's own)
            take = (gap <= sel_eps) & jnp.any(theirs, -1)
            used = jnp.where(take[:, None], theirs, own)

        def head_block_out(j):
            q = (rows(cq) @ _f(w_q[:, j])).reshape(qb, hb, nope + rd)
            q = jnp.concatenate(
                [q[..., :nope], rope(q[..., nope:], rows(sin), rows(cos))],
                -1)
            k = jnp.concatenate(
                [jnp.einsum("nc,chd->nhd", lat[:, :r], _f(w_k[:, j])),
                 jnp.broadcast_to(lat[:, None, r:], (m, hb, rd))], -1)
            v = jnp.einsum("nc,chd->nhd", lat[:, :r], _f(w_v[:, j]))
            s = jnp.einsum("thd,shd->hts", q, k) * scale
            s = jnp.where(used[None], s, -jnp.inf)
            o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
            return o.reshape(qb, hb * vd) @ _f(w_o[j])

        o = jnp.sum(jax.lax.map(head_block_out, jnp.arange(H // hb)), 0)
        return o, gap, jnp.sum(diff, -1), jnp.sum(used, -1)

    o, gap, diff, size = jax.lax.map(query_block_out,
                                     jnp.arange(0, m, qb))
    return (o.reshape(m, -1)[:n], gap.reshape(-1)[:n],
            diff.reshape(-1)[:n], size.reshape(-1)[:n])


def swiglu(u, gate, up, down, block: int = 4096):
    """A block of the intermediate columns at a time (one block's
    float32 copies live, not a 12288-wide layer's)."""
    F = gate.shape[-1]
    return sum((jax.nn.silu(u @ _f(gate[:, a:a + block]))
                * (u @ _f(up[:, a:a + block]))) @ _f(down[a:a + block])
               for a in range(0, F, block))


_ATTN = ("input_layernorm", "q_a_proj", "q_a_layernorm", "q_b_proj",
         "kv_a_proj_with_mqa", "kv_a_layernorm", "kv_b_k", "kv_b_v",
         "o_proj", "indexer_wq_b", "indexer_wk", "indexer_k_norm",
         "indexer_k_norm_bias", "indexer_weights_proj")


_ROWS = ("attn_out", "latent", "index_keys", "attn_in", "cq")


@functools.lru_cache(maxsize=4)
def _stages(key: str, query_block: int, rows: bool) -> Dict[str, Callable]:
    """The layer's pieces, each compiled once a configuration and
    sequence length.  ``rows``: whether the attention also hands back
    its per-position arrays (``_ROWS``, five of ``[n, .]`` a layer)."""
    c = json.loads(key)
    eps = float(c["rms_norm_eps"])

    def attn(x, lp, sel, sel_eps):
        u = rms_norm(x, lp["input_layernorm"], eps)
        o, gap, diff, size = attention(u, lp, c, sel, sel_eps, query_block)
        info = {"sel_gap": gap, "sel_diff": diff, "sel_size": size}
        if rows:
            cq = rms_norm(u @ _f(lp["q_a_proj"]), lp["q_a_layernorm"], eps)
            info.update(attn_out=o, latent=latent_of(u, lp, c),
                        index_keys=index_keys_of(u, lp, c), attn_in=u, cq=cq)
        return x + o, info

    def ffn_open(x, lp):
        return rms_norm(x, lp["post_attention_layernorm"], eps)

    def dense(u, lp):
        return swiglu(u, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"])

    def routed(u, lp, choice, route_eps):
        return route_weights(u, lp, c, choice, route_eps) + (
            swiglu(u, lp["shared_gate"], lp["shared_up"], lp["shared_down"]),)

    return {"attn": jax.jit(attn), "ffn_open": jax.jit(ffn_open),
            "dense": jax.jit(dense), "routed": jax.jit(routed),
            "expert": jax.jit(expert_part)}


def layer(x, lp: Dict[str, Any], c: Dict[str, Any], *, expert=None,
          choice=None, route_eps: float = math.inf, sel=None,
          sel_eps: float = math.inf, query_block: int = QUERY_BLOCK,
          keep=None):
    """One layer on x [n, D].  Returns (x, info): the attention's output,
    latent rows and index keys, its selection's gap, differing positions
    and size a query, its normed input and query latent (what the
    indexer reads), and for a routed layer the router's inputs, scores,
    the reference's choice and ``choice``'s gap; only the entries
    ``keep`` names where it is given, and then the per-position arrays
    it does not name are never made.  ``expert(e)`` gives the e-th held
    expert's (gate, up, down).  Each expert's part is waited for before
    the next is asked: dispatched ahead, sixteen parts' ``[n, D]``
    results are all allocated at once (3.9 GiB beside a serving engine
    at 12k positions; my chip runs, PR 39)."""
    rows = keep is None or any(k in _ROWS for k in keep)
    st = _stages(json.dumps(c, sort_keys=True), query_block, rows)
    x, info = st["attn"](
        x, {k: v for k, v in lp.items() if k in _ATTN},
        None if sel is None else jnp.asarray(sel), jnp.float32(sel_eps))
    u = st["ffn_open"](x, {"post_attention_layernorm":
                           lp["post_attention_layernorm"]})
    if "router" not in lp:
        x = x + st["dense"](u, lp)
    else:
        s, own, rgap, used, w, y = st["routed"](
            u, lp, None if choice is None else jnp.asarray(choice),
            jnp.float32(route_eps))
        first, held = held_range(c)
        for e in range(held):       # the experts held, masked; no other
            y = jax.block_until_ready(
                y + st["expert"](u, used, w, first + e, *expert(e)))
        info.update(scores=s, choice=own, gap=rgap, router_in=u)
        x = x + y
    if keep is not None:
        info = {k: info[k] for k in keep if k in info}
    return x, info


def logits_of(x, head: Dict[str, Any], c: Dict[str, Any]):
    """Logits [rows, V] of residual rows x [rows, D] over the vocabulary
    slice the head holds."""
    xn = rms_norm(x, head["norm"], float(c["rms_norm_eps"]))
    w = head["lm_head"]
    part = jax.jit(lambda a, w: a @ _f(w))
    return jnp.concatenate([part(xn, w[:, a:a + 4096])
                            for a in range(0, w.shape[1], 4096)], 1)


def forward(tree: Dict[str, Any], tokens, c: Dict[str, Any], *,
            choices: Optional[Dict[int, Any]] = None,
            route_eps: float = math.inf,
            selections: Optional[Dict[int, Any]] = None,
            sel_eps: float = math.inf,
            query_block: int = QUERY_BLOCK, keep=None):
    """tokens [n] -> (x [n, D] before the final norm, [info of each
    layer]) over the program's tree, one layer (one expert) at a time.
    ``keep`` names the entries of a layer's info to hold on to (all by
    default: three of them are ``[n, D]`` a layer, which a caller beside
    a serving engine has no room for; see ``layer``).
    ``choices[i]`` [n, k] / ``selections[i]`` [n, n] are the program's
    routing / selection in layer ``i``, taken where they are near-ties
    of the reference's own (``route_eps`` / ``sel_eps``)."""
    x = _f(head_from_program_tree(tree)["embed_tokens"][jnp.asarray(tokens)])
    infos = []
    for i in range(c["num_hidden_layers"]):
        x, info = layer(
            x, layer_from_program_tree(tree, c, i), c,
            choice=(choices or {}).get(i), route_eps=route_eps,
            sel=(selections or {}).get(i), sel_eps=sel_eps,
            query_block=query_block, keep=keep,
            expert=lambda e, i=i: expert_from_program_tree(tree, c, i, e))
        infos.append(info)
    return x, infos
