"""Plain references: the llama-shaped decoder's forward pass, loss and
gradients in straightforward float32 ``jax.numpy`` — no kernels, no
cache, no batching tricks, no scan.  Written from the published
equations (pre-norm decoder, RMSNorm, rotary embedding on half-split
pairs as in the ``transformers`` Llama/Mistral/InternLM2 code, grouped
query attention with contiguous groups, SwiGLU), independent of
``ray_tpu/models/llama.py``.

Callers wrap calls in ``jax.default_matmul_precision("highest")``: on
a TPU a float32 matmul otherwise runs in bf16 passes.

``from_program_tree`` is the only part that knows the program: it
re-lays the program's parameter tree into the flat per-layer matrices
used here.  ``dequantize`` reads an int8 weight-only artifact by the
published convention (value = int8 x the scale of its output channel),
not through the program's own dequantizer.  A fused ``wqkv``/``w_gateup`` operand is
split back; the fusion is a storage layout, not another equation.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp


def dims(c: Dict[str, Any]) -> Dict[str, int]:
    h = c["num_attention_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // h
    return {"d": c["hidden_size"], "h": h, "kvh": c["num_key_value_heads"],
            "hd": hd, "m": c["intermediate_size"], "v": c["vocab_size"]}


def dequantize(tree: Any) -> Any:
    """Every ``{"q": int8, "scale": float32}`` leaf of a weight-only
    int8 tree becomes ``q * scale`` in float32.  The scale has one entry
    per output channel (the last axis) and size 1 on the axes it is
    shared over, so the product broadcasts; a stacked leaf keeps its
    layer axis in both."""
    if isinstance(tree, dict):
        if set(tree) == {"q", "scale"}:
            q, scale = tree["q"], tree["scale"]
            if scale.shape[-1] != q.shape[-1] or scale.ndim != q.ndim:
                raise ValueError(f"scale {scale.shape} is not one per "
                                 f"output channel of {q.shape}")
            return jnp.asarray(q, jnp.float32) * jnp.asarray(scale,
                                                             jnp.float32)
        return {k: dequantize(v) for k, v in tree.items()}
    return tree


def from_program_tree(tree: Dict[str, Any], c: Dict[str, Any]
                      ) -> Dict[str, Any]:
    """The program's stacked tree (float arrays) -> reference layout."""
    z = dims(c)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    lay = tree["layers"]
    n_layers = lay["ln_attn"].shape[0]
    layers: List[Dict[str, Any]] = []
    for i in range(n_layers):
        a, m = lay["attn"], lay["mlp"]
        if "wqkv" in a:
            w = f32(a["wqkv"][i])
            nq, nk = z["h"] * z["hd"], z["kvh"] * z["hd"]
            wq, wk, wv = w[:, :nq], w[:, nq:nq + nk], w[:, nq + nk:]
        else:
            wq = f32(a["wq"][i]).reshape(z["d"], -1)
            wk = f32(a["wk"][i]).reshape(z["d"], -1)
            wv = f32(a["wv"][i]).reshape(z["d"], -1)
        if "w_gateup" in m:
            gu = f32(m["w_gateup"][i])
            w_gate, w_up = gu[:, :z["m"]], gu[:, z["m"]:]
        else:
            w_gate, w_up = f32(m["w_gate"][i]), f32(m["w_up"][i])
        layers.append({
            "wq": wq, "wk": wk, "wv": wv,
            "wo": f32(a["wo"][i]).reshape(-1, z["d"]),
            "w_gate": w_gate, "w_up": w_up, "w_down": f32(m["w_down"][i]),
            "ln_attn": f32(lay["ln_attn"][i]),
            "ln_mlp": f32(lay["ln_mlp"][i])})
    out = {"tok_embed": f32(tree["tok_embed"]), "layers": layers,
           "final_norm": f32(tree["final_norm"])}
    out["lm_head"] = (f32(tree["lm_head"]) if "lm_head" in tree
                      else out["tok_embed"].T)
    return out


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x [S, H, hd]: rotate the pairs (x[i], x[i + hd/2])."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(params: Dict[str, Any], tokens, c: Dict[str, Any]):
    """One sequence: tokens [S] -> logits [S, V], float32, causal."""
    z = dims(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    causal = pos[:, None] >= pos[None, :]
    group = z["h"] // z["kvh"]
    x = params["tok_embed"][tokens]
    for lp in params["layers"]:
        y = rms_norm(x, lp["ln_attn"], eps)
        q = rope((y @ lp["wq"]).reshape(s, z["h"], z["hd"]), pos, theta)
        k = rope((y @ lp["wk"]).reshape(s, z["kvh"], z["hd"]), pos, theta)
        v = (y @ lp["wv"]).reshape(s, z["kvh"], z["hd"])
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        att = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(z["hd"]))
        att = jnp.where(causal[None], att, -jnp.inf)
        att = jax.nn.softmax(att, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", att, v).reshape(s, -1)
        x = x + o @ lp["wo"]
        y = rms_norm(x, lp["ln_mlp"], eps)
        x = x + (jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"])) \
            @ lp["w_down"]
    return rms_norm(x, params["final_norm"], eps) @ params["lm_head"]


def next_token_loss(params: Dict[str, Any], tokens, c: Dict[str, Any]):
    """tokens [B, S] -> mean cross-entropy of token t+1 given tokens
    up to t, over the B * (S - 1) predicted positions."""
    def one(seq):
        logits = forward(params, seq, c)[:-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, seq[1:, None], axis=-1)[:, 0]

    return jnp.mean(jnp.stack([one(seq) for seq in tokens]))


def loss_and_grad_norm(params: Dict[str, Any], tokens, c: Dict[str, Any]):
    loss, grads = jax.value_and_grad(next_token_loss)(params, tokens, c)
    sq = sum(jnp.sum(g * g) for g in jax.tree.leaves(grads))
    return loss, jnp.sqrt(sq)
