"""Plain reference for openbmb/MiniCPM-SALA: the forward pass of one
sequence in straightforward ``jax.numpy`` (float32 unless told) with no
cache, no chunks, no kernels, no packed batch: the linear recurrence as
a ``lax.scan`` over time, the block selection from the whole sequence's
scores.  Written from the layer equations, independent of
``ray_tpu/models/minicpm_sala.py`` and ``ray_tpu/ops``.

    x_0 = scale_emb * E[id]
    x <- x + (scale_depth / sqrt(32)) * Mixer(n(x))
    x <- x + (scale_depth / sqrt(32)) * W_down(silu(W_gate u) * W_up u)
    logits = W_head (n(x) / (hidden_size / dim_model_base))

with 32 the PUBLISHED depth whatever ``num_hidden_layers`` is held, and
``n`` RMSNorm with a learned weight.

    lightning-attn (32 heads of 128): q, k, v by head; per-head RMSNorm
        on q and k; rotary (theta 10000, halves) on q and k; q / sqrt(128);
        S_t = lambda_h S_{t-1} + k_t^T v_t, o_t = q_t S_t (S float32,
        zero at the start); y = W_o (sigmoid(u W_g) * n_out(o)), n_out ONE
        RMSNorm over the 4096 concatenated lanes.  lambda_h = exp(-s_h
        f_l), s_h = 2^(-8 (h + 1) / 32), f_l = 1 - l / 31 + 1e-5, l the
        layer's PUBLISHED position.
    minicpm4 (32 query heads over 2 KV heads of 128, no rotary): per-head
        RMSNorm on q and k; softmax(q . k / sqrt(128)) over the keys query
        t of KV head g attends to; y = W_o (sigmoid(u W_g) * o).  Below
        dense_len (by the QUERY's position) every s <= t.  Else the
        tokens s <= t of the blocks B_g(t): compressed keys kc_g[j] =
        mean(k_g[16 j : 16 j + 32]), complete windows, visible when 16 j +
        31 <= t; p_h[t, :] = softmax_j(q_h[t] . kc_g[j] / sqrt(128)) over
        the visible j; r_g[t, j] the sum of p_h over the 16 heads of g;
        block m scores the max of r_g[t, j] over j in [4 m - 1, 4 m + 3];
        block 0 and the 32 blocks ending at t's own score +inf; B_g(t) is
        the 64 blocks of largest score among m <= t // 64, ties by lower m.

A top-64 is as discontinuous as a router's top-8: a bfloat16 program's
scores differ from these by rounding, and where two blocks stand within
that of the 64th score either choice is the function.  ``minicpm4``
therefore takes the selection the program made (``selection``) and
attends as the program did for every query whose differing blocks ALL
have this reference's own score within ``sel_eps`` (relative) of its
own 64th; any other query keeps the reference's own selection, and the
worst gap and the swap are reported (``info``), so that a check names
the swap instead of failing on logits alone.

``controls``: ``dense_control`` attends to every position whatever the
query's; ``recent_control`` to the forced blocks alone; ``decay_control``
gives every head head 0's decay.  Each is another function, which a
comparison has to refuse.

``ASSUMED`` lists what the published config.json has no key for.
Callers wrap calls in ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

ASSUMED = {
    "sparse_config": "kernel_size 32, kernel_stride 16, block_size 64, "
                     "topk 64, window_size 2048, init_blocks 1, dense_len "
                     "8192: MiniCPM4's published sparse_config (InfLLM v2), "
                     "the family the minicpm4 mixer is named after; the "
                     "catalog's described_as confirms 'block top-64'",
    "lightning_decay": "lambda_h = exp(-s_h f_l), s_h = 2^(-8 (h + 1) / "
                       "32), f_l = 1 - l / 31 + 1e-5 at the published "
                       "layer position l: the Lightning Attention-2 / "
                       "MiniMax-01 slopes; the config does not state them",
    "lightning_form": "no activation on q, k, v and no normaliser; the "
                      "output norm is one RMSNorm over the 4096 lanes",
    "minicpm4_qk_norm": "per-head RMSNorm on q and k of the sparse layers "
                        "too: qk_norm is one key for the model",
    "dense_switch": "the published code switches dense to sparse by the "
                    "length of the sequence at the call; served in chunks "
                    "there is no one call, so the switch is by the QUERY's "
                    "position (a stated departure)",
    "mup": "mup_denominator and rand_init take no part in the forward",
    "rotary": "rotate-half pairs (x[:64], x[64:]), theta 10000, on the "
              "lightning layers only (attn_use_rope false)",
    "torch_dtype": "bfloat16",
}

SPARSE_DEFAULTS = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                   "topk": 64, "window_size": 2048, "init_blocks": 1,
                   "dense_len": 8192}
CONTROLS = ("dense_control", "recent_control", "decay_control")


def sparse_config(c: Dict[str, Any]) -> Dict[str, int]:
    return dict(SPARSE_DEFAULTS, **c.get("sparse_config", {}))


def layer_kinds(c: Dict[str, Any]) -> List[str]:
    return list(c["mixer_types"])


def published_position(c: Dict[str, Any], i: int) -> int:
    """Where held layer ``i`` stands in the published list."""
    return int(c.get("first_layer", 0)) + i


def published_depth(c: Dict[str, Any]) -> int:
    return int(c.get("published", {}).get("num_hidden_layers", 32))


def _f(w, dtype=jnp.float32):
    return jnp.asarray(w).astype(dtype)


def layer_slice(tree: Dict[str, Any], kind: str, i, j,
                dtype=jnp.float32) -> Dict[str, Any]:
    """The weights of layer ``i``, the ``j``-th of its ``kind``, out of
    the program's stacked tree (``i`` and ``j`` may be traced: one
    compiled layer of a kind then serves every layer of it)."""
    src = tree["lin" if kind == "lightning-attn" else "attn"]
    lp = {"ln_in": _f(tree["ln_in"][i], dtype),
          "ln_ff": _f(tree["ln_ff"][i], dtype),
          "w_gate": _f(tree["mlp"]["w_gate"][i], dtype),
          "w_up": _f(tree["mlp"]["w_up"][i], dtype),
          "w_down": _f(tree["mlp"]["w_down"][i], dtype)}
    for name in ("wq", "wk", "wv", "wg", "wo"):
        lp[name] = _f(src[name][j], dtype)
    for name in ("q_norm", "k_norm", "o_norm"):
        if name in src:
            lp[name] = _f(src[name][j], dtype)
    return lp


def layer_from_program_tree(tree: Dict[str, Any], c: Dict[str, Any],
                            i: int, dtype=jnp.float32) -> Dict[str, Any]:
    """Layer ``i``'s weights out of the program's stacked tree."""
    kinds = layer_kinds(c)
    return layer_slice(tree, kinds[i], i, kinds[:i].count(kinds[i]), dtype)


def head_from_program_tree(tree: Dict[str, Any],
                           dtype=jnp.float32) -> Dict[str, Any]:
    return {"tok_embed": _f(tree["tok_embed"], dtype),
            "final_norm": _f(tree["final_norm"], dtype),
            "lm_head": _f(tree["lm_head"], dtype)}


def rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)).astype(x.dtype) * w


def rope(x, theta: float):
    """``x`` [n, heads, hd] at positions 0..n-1, rotate-half pairs."""
    n, _h, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * freqs[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = (x[..., :half].astype(jnp.float32),
              x[..., half:].astype(jnp.float32))
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def decay(c: Dict[str, Any], l_pub: int, control: Optional[str] = None):
    """``lambda_h`` [H] float32 of the lightning layer at published
    position ``l_pub``."""
    H = c["lightning_nh"]
    f = 1.0 - l_pub / (published_depth(c) - 1) + 1e-5
    s = jnp.exp2(-8.0 * (jnp.arange(H, dtype=jnp.float32) + 1.0) / H)
    lam = jnp.exp(-s * f)
    if control == "decay_control":
        lam = jnp.full_like(lam, lam[0])
    return lam


def lightning(u, lp: Dict[str, Any], c: Dict[str, Any], l_pub: int,
              control: Optional[str] = None):
    """One lightning mixer over a sequence ``u`` [n, D].  Returns (y
    [n, D], the state after the last token [H, hd, hd] in ``u``'s
    precision)."""
    n = u.shape[0]
    H, hd = c["lightning_nh"], c["lightning_head_dim"]
    eps = c["rms_norm_eps"]
    q = rms_norm((u @ lp["wq"]).reshape(n, H, hd), lp["q_norm"], eps)
    k = rms_norm((u @ lp["wk"]).reshape(n, H, hd), lp["k_norm"], eps)
    v = (u @ lp["wv"]).reshape(n, H, hd)
    q = rope(q, c["rope_theta"]) / hd ** 0.5
    k = rope(k, c["rope_theta"])
    lam = decay(c, l_pub, control).astype(u.dtype)[:, None, None]

    def step(s, qkv):
        qt, kt, vt = qkv
        s = lam * s + kt[:, :, None] * vt[:, None, :]
        return s, jnp.einsum("hk,hkd->hd", qt, s)

    s, o = lax.scan(step, jnp.zeros((H, hd, hd), u.dtype), (q, k, v))
    o = rms_norm(o.reshape(n, H * hd), lp["o_norm"], eps)
    return (jax.nn.sigmoid(u @ lp["wg"]) * o) @ lp["wo"], s


def _own_selection(b, t, sc):
    """(the selection bool[B, KVH, nb], the kth score [B, KVH, 1], the
    forced blocks, the blocks up to the query's own) from scores ``b``
    [B, KVH, nb] of queries at positions ``t`` [B]."""
    nb = b.shape[-1]
    m = jnp.arange(nb)[None, None, :]
    own = (t // sc["block_size"])[:, None, None]
    upto = m <= own
    forced = (m < sc["init_blocks"]) | (
        m > own - sc["window_size"] // sc["block_size"])
    cand = jnp.where(upto, jnp.where(forced, jnp.inf, b), -jnp.inf)
    order = jnp.argsort(-cand, axis=-1, stable=True)       # ties: lower m
    rank = jnp.argsort(order, axis=-1, stable=True)
    k = min(sc["topk"], nb)
    kth = jnp.take_along_axis(cand, order[..., k - 1:k], axis=-1)
    return (rank < k) & upto, kth, forced & upto, upto


def minicpm4(u, lp: Dict[str, Any], c: Dict[str, Any], *,
             selection=None, sel_eps: float = 0.0,
             control: Optional[str] = None, query_block: int = 256):
    """One minicpm4 mixer over a sequence ``u`` [n, D].  Returns (y
    [n, D], info): ``info["sel"]`` the selection attended to
    ``bool[n, KVH, nb]`` (every block up to the query's own where dense),
    and with ``selection`` given ``mismatch_share`` (selected blocks
    that differ, over those selected), ``gap_max`` (the largest relative
    distance of a differing block's own score from the 64th), ``kept``
    (queries that kept the reference's own selection) and ``swap`` (the
    worst: query, KV head, block, its score, the 64th)."""
    n = u.shape[0]
    H, KVH, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    G = H // KVH
    eps = c["rms_norm_eps"]
    sc = sparse_config(c)
    blk, ks, st = sc["block_size"], sc["kernel_size"], sc["kernel_stride"]
    q = rms_norm((u @ lp["wq"]).reshape(n, KVH, G, hd), lp["q_norm"], eps)
    k = rms_norm((u @ lp["wk"]).reshape(n, KVH, hd), lp["k_norm"], eps)
    v = (u @ lp["wv"]).reshape(n, KVH, hd)
    nb = -(-n // blk)
    nj = max((n - ks) // st + 1, 1)
    # the compressed keys: means of complete windows
    win = jnp.clip(jnp.arange(nj)[:, None] * st + jnp.arange(ks)[None, :],
                   0, n - 1)
    kc = jnp.mean(k[win].astype(jnp.float32), axis=1).astype(u.dtype)
    # the windows that touch block m: j in [E m - 1, E m + E - 1]
    E = blk // st
    touch = jnp.arange(nb)[:, None] * E - 1 + jnp.arange(E + 1)[None, :]
    touch_ok = (touch >= 0) & (touch < nj)
    touch = jnp.clip(touch, 0, nj - 1)
    B = query_block
    n_pad = -(-n // B) * B
    qp = jnp.pad(q, ((0, n_pad - n), (0, 0), (0, 0), (0, 0)))
    if selection is None:
        selection = jnp.zeros((n, KVH, nb), bool)
        given = False
    else:
        given = True
    selp = jnp.pad(selection, ((0, n_pad - n), (0, 0), (0, 0)))
    s_tok = jnp.arange(n)

    def block(args):
        qb, selb, t0 = args
        t = t0 + jnp.arange(B)
        s_c = jnp.einsum("tkgd,jkd->tkgj", qb, kc).astype(
            jnp.float32) / hd ** 0.5
        if u.dtype != jnp.float32:      # the lower-precision reading
            s_c = s_c.astype(u.dtype).astype(jnp.float32)
        vis = (jnp.arange(nj)[None, :] * st + ks - 1
               <= t[:, None])[:, None, None, :]
        p = jax.nn.softmax(jnp.where(vis, s_c, -jnp.inf), axis=-1)
        r = jnp.sum(jnp.where(vis, p, 0.0), axis=2)        # [B, KVH, nj]
        r = jnp.where(vis[:, :, 0], r, -jnp.inf)
        b = jnp.max(jnp.where(touch_ok[None, None], r[:, :, touch],
                              -jnp.inf), axis=-1)          # [B, KVH, nb]
        own, kth, forced, upto = _own_selection(b, t, sc)
        # the program's selection, where every differing block is a
        # near-tie of this reference's own scores
        differs = (selb != own) & upto
        gap = jnp.where(differs & jnp.isfinite(b),
                        jnp.abs(b - kth) / jnp.maximum(kth, 1e-30), 0.0)
        gap = jnp.where(differs & ~jnp.isfinite(b), jnp.inf, gap)
        takes = (jnp.max(gap, axis=-1, keepdims=True) <= sel_eps) & given
        sel = jnp.where(takes, selb & upto, own)
        if control == "recent_control":
            sel = jnp.broadcast_to(forced, own.shape)
        dense = (t < sc["dense_len"])[:, None, None]
        if control == "dense_control":
            dense = jnp.ones_like(dense)
        sel = jnp.where(dense, upto, sel)
        sparse_q = ~dense & upto[:, :, :1] & (t < n)[:, None, None]
        tok = (jnp.repeat(sel, blk, axis=-1)[:, :, :n]
               & (s_tok[None, None, :] <= t[:, None, None]))
        s = jnp.einsum("tkgd,skd->tkgs", qb, k).astype(
            jnp.float32) / hd ** 0.5
        a = jax.nn.softmax(jnp.where(tok[:, :, None, :], s, -jnp.inf),
                           axis=-1)
        a = jnp.where(tok[:, :, None, :], a, 0.0).astype(u.dtype)
        o = jnp.einsum("tkgs,skd->tkgd", a, v)
        worst = jnp.argmax(jnp.where(sparse_q, gap, -1.0).reshape(-1))
        return (o, sel,
                jnp.sum(differs & sparse_q), jnp.sum(own & sparse_q),
                jnp.max(jnp.where(sparse_q, gap, 0.0)),
                jnp.sum(~takes & sparse_q[:, :, :1]
                        & jnp.any(differs, -1, keepdims=True)),
                jnp.stack([worst.astype(jnp.float32),
                           b.reshape(-1)[worst], jnp.broadcast_to(
                               kth, b.shape).reshape(-1)[worst]]))

    o, sel, n_diff, n_sel, gap_max, kept, swaps = lax.map(
        block, (qp.reshape(n_pad // B, B, KVH, G, hd),
                selp.reshape(n_pad // B, B, KVH, nb),
                jnp.arange(n_pad // B) * B))
    o = o.reshape(n_pad, H * hd)[:n]
    wb = jnp.argmax(gap_max)
    flat = swaps[wb, 0].astype(jnp.int32)
    info = {"sel": sel.reshape(n_pad, KVH, nb)[:n],
            "mismatch_share": jnp.sum(n_diff) / jnp.maximum(jnp.sum(n_sel), 1),
            "gap_max": jnp.max(gap_max), "kept": jnp.sum(kept),
            "swap": {"query": wb * B + flat // (KVH * nb),
                     "kv_head": (flat // nb) % KVH, "block": flat % nb,
                     "score": swaps[wb, 1], "kth_score": swaps[wb, 2]}}
    return (jax.nn.sigmoid(u @ lp["wg"]) * o) @ lp["wo"], info


def swiglu(u, lp: Dict[str, Any], block: int = 2048):
    """The dense feed-forward in blocks of tokens (a 16k-token sequence's
    float32 intermediate is 1.1 GB whole)."""
    n, d = u.shape
    n_pad = -(-n // block) * block
    up = jnp.pad(u, ((0, n_pad - n), (0, 0))).reshape(-1, block, d)
    out = lax.map(lambda x: (jax.nn.silu(x @ lp["w_gate"])
                             * (x @ lp["w_up"])) @ lp["w_down"], up)
    return out.reshape(n_pad, d)[:n]


def layer(x, lp: Dict[str, Any], kind: str, c: Dict[str, Any], l_pub,
          *, selection=None, sel_eps: float = 0.0,
          control: Optional[str] = None, query_block: int = 256):
    """One layer over a sequence ``x`` [n, D].  Returns (x, extra): the
    lightning layer's final state, or the sparse layer's ``info``.
    ``query_block`` is how many queries a sparse layer scores at once (a
    matter of memory, not of the function)."""
    scale = jnp.asarray(c["scale_depth"] / published_depth(c) ** 0.5, x.dtype)
    eps = c["rms_norm_eps"]
    u = rms_norm(x, lp["ln_in"], eps)
    if kind == "lightning-attn":
        y, extra = lightning(u, lp, c, l_pub, control)
    else:
        y, extra = minicpm4(u, lp, c, selection=selection, sel_eps=sel_eps,
                            control=control, query_block=query_block)
    x = x + scale * y
    return x + scale * swiglu(rms_norm(x, lp["ln_ff"], eps), lp), extra


def embed(tokens, head: Dict[str, Any], c: Dict[str, Any], dtype=None):
    """``dtype`` casts the rows looked up, where the table itself is the
    program's (a float32 copy of the whole table is 1.2 GB)."""
    rows = head["tok_embed"][tokens]
    rows = rows if dtype is None else rows.astype(dtype)
    return rows * jnp.asarray(c["scale_emb"], rows.dtype)


def logits_of(x, head: Dict[str, Any], c: Dict[str, Any]):
    x = rms_norm(x, head["final_norm"], c["rms_norm_eps"])
    x = x / jnp.asarray(c["hidden_size"] / c["dim_model_base"], x.dtype)
    return (x @ head["lm_head"]).astype(jnp.float32)


def forward(tree: Dict[str, Any], tokens, c: Dict[str, Any], *,
            selections=None, sel_eps: float = 0.0,
            control: Optional[str] = None, logits_from: int = 0,
            dtype=jnp.float32):
    """The whole forward pass of one sequence from the program's weight
    tree, one layer's ``dtype`` copy at a time.  Returns a dict:
    ``logits`` [n - logits_from, V] float32 (after each token from
    ``logits_from``), ``states`` (each lightning layer's final state),
    ``sparse`` (each sparse layer's ``info``).  ``selections`` is the
    program's ``bool[La, n, KVH, nb]`` or None."""
    head = head_from_program_tree(tree, dtype)
    x = embed(jnp.asarray(tokens), head, c)
    states, sparse = [], []
    for i, kind in enumerate(layer_kinds(c)):
        lp = layer_from_program_tree(tree, c, i, dtype)
        sel = None
        if kind != "lightning-attn" and selections is not None:
            sel = selections[len(sparse)]
        x, extra = layer(x, lp, kind, c, published_position(c, i),
                         selection=sel, sel_eps=sel_eps, control=control)
        (states if kind == "lightning-attn" else sparse).append(extra)
    return {"logits": logits_of(x[logits_from:], head, c),
            "states": states, "sparse": sparse}
