"""Plain reference for AI21-Jamba2-3B: the forward pass of one sequence
in straightforward float32 ``jax.numpy`` — no cache, no chunks, no
kernels, no packed batch.  Written from the equations of the
``transformers`` ``JambaForCausalLM`` (pre-norm residual blocks, a
Mamba-1 mixer or causal attention, then a dense SwiGLU), independent of
``ray_tpu/models/jamba.py``.

    layer i is attention iff i % attn_layer_period == attn_layer_offset
    h = h + mixer(rms_norm(h, input_layernorm))
    h = h + mlp(rms_norm(h, pre_ff_layernorm))
    mlp(x) = down(silu(gate(x)) * up(x))
    logits = final_layernorm(h) @ embed.T            (tied)

    attention: softmax(q k^T / sqrt(head_dim)) v, causal, the query
        heads share the KV heads in contiguous groups, no rotary or any
        other positional term, no bias
    mamba:  [x, z] = in_proj(u)
            x = silu(causal_depthwise_conv(x, width d_conv) + conv_bias)
            [dt, B, C] = x_proj(x), split dt_rank / d_state / d_state
            dt, B, C = rms_norm(dt), rms_norm(B), rms_norm(C)
            delta = softplus(dt_proj(dt) + dt_bias);  A = -exp(A_log)
            s_t = exp(delta_t (x) A) * s_{t-1} + (delta_t * x_t) (x) B_t
            y_t = s_t C_t + D * x_t
            out = out_proj(y * silu(z))

The scan over time is a ``lax.scan`` whose carry is one
``[d_inner, d_state]`` state: the recurrence as written, nothing
reassociated.

Departures from the published code, each one a layout and none an
equation: the weights are random (``from_program_tree`` re-lays the
program's tree: its ``A_log`` is stored ``[d_state, d_inner]``, its
``conv_w`` ``[d_conv, d_inner]``); ``num_experts`` is 1 in the
published config, so every feed-forward is the dense MLP and no router
exists; ``transformers`` folds ``dt_bias`` into ``dt_proj.bias``.

``ASSUMED`` lists the sizes and conventions the published config.json
has no key for; they follow the Jamba family's code.

Callers wrap calls in ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

ASSUMED = {
    "layer_order": "attention at i % attn_layer_period == "
                   "attn_layer_offset (7 and 21 of 28), Mamba elsewhere",
    "head_dim": "128 = hidden_size / num_attention_heads",
    "positional_encoding": "none: the Jamba family's attention layers "
                           "have no rotary or learned positions",
    "inner_norms": "RMSNorm on dt, B and C after x_proj, each with a "
                   "learned weight, eps = rms_norm_eps",
    "mamba_d_inner": "mamba_expand * hidden_size = 5120",
}


def layer_kinds(c: Dict[str, Any]) -> List[str]:
    return ["attention"
            if i % c["attn_layer_period"] == c["attn_layer_offset"]
            else "mamba" for i in range(c["num_hidden_layers"])]


def layer_from_program_tree(tree: Dict[str, Any], c: Dict[str, Any],
                            i: int) -> Dict[str, Any]:
    """Layer ``i`` of the program's per-kind stacked tree -> one dict,
    float32, in the published orientation."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    d = c["hidden_size"]
    kinds = layer_kinds(c)
    k = kinds[:i].count(kinds[i])            # its index among its kind
    lp = {"ln_in": f32(tree["ln_in"][i]),
          "ln_ff": f32(tree["ln_ff"][i]),
          "w_gate": f32(tree["mlp"]["w_gate"][i]),
          "w_up": f32(tree["mlp"]["w_up"][i]),
          "w_down": f32(tree["mlp"]["w_down"][i])}
    if kinds[i] == "attention":
        a = tree["attn"]
        lp.update(wq=f32(a["wq"][k]).reshape(d, -1),
                  wk=f32(a["wk"][k]).reshape(d, -1),
                  wv=f32(a["wv"][k]).reshape(d, -1),
                  wo=f32(a["wo"][k]).reshape(-1, d))
    else:
        m = tree["mamba"]
        lp.update(
            in_proj=f32(m["in_proj"][k]),
            conv_w=f32(m["conv_w"][k]).T,                # [d_inner, d_conv]
            conv_b=f32(m["conv_b"][k]),
            x_proj=f32(m["x_proj"][k]),
            dt_proj=f32(m["dt_proj"][k]),
            dt_bias=f32(m["dt_bias"][k]),
            A_log=f32(m["A_log"][k]).T,                  # [d_inner, d_state]
            D=f32(m["D"][k]),
            dt_norm=f32(m["dt_norm"][k]),
            b_norm=f32(m["b_norm"][k]),
            c_norm=f32(m["c_norm"][k]),
            out_proj=f32(m["out_proj"][k]))
    return lp


def head_from_program_tree(tree: Dict[str, Any]) -> Dict[str, Any]:
    """The embedding, the final norm and the (tied) output matrix."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    embed = f32(tree["tok_embed"])
    return {"tok_embed": embed, "final_norm": f32(tree["final_norm"]),
            "lm_head": (f32(tree["lm_head"]) if "lm_head" in tree
                        else embed.T)}


def from_program_tree(tree: Dict[str, Any], c: Dict[str, Any]
                      ) -> Dict[str, Any]:
    """The program's whole tree: the head and one dict a layer."""
    return dict(head_from_program_tree(tree),
                layers=[layer_from_program_tree(tree, c, i)
                        for i in range(c["num_hidden_layers"])])


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def attention(y, lp, c):
    s = y.shape[0]
    h, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // h
    q = (y @ lp["wq"]).reshape(s, h, hd)
    k = jnp.repeat((y @ lp["wk"]).reshape(s, kvh, hd), h // kvh, axis=1)
    v = jnp.repeat((y @ lp["wv"]).reshape(s, kvh, hd), h // kvh, axis=1)
    pos = jnp.arange(s)
    att = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(hd))
    att = jnp.where((pos[:, None] >= pos[None, :])[None], att, -jnp.inf)
    att = jax.nn.softmax(att, axis=-1)
    return jnp.einsum("hqk,khd->qhd", att, v).reshape(s, -1) @ lp["wo"]


def mamba(u, lp, c):
    eps = c["rms_norm_eps"]
    n, r, width = c["mamba_d_state"], c["mamba_dt_rank"], c["mamba_d_conv"]
    d_inner = lp["in_proj"].shape[1] // 2
    xz = u @ lp["in_proj"]
    x, z = xz[:, :d_inner], xz[:, d_inner:]
    s = x.shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, d_inner)), x])
    x = sum(padded[i:i + s] * lp["conv_w"][:, i] for i in range(width))
    x = jax.nn.silu(x + lp["conv_b"])
    dbc = x @ lp["x_proj"]
    dt = rms_norm(dbc[:, :r], lp["dt_norm"], eps)
    b = rms_norm(dbc[:, r:r + n], lp["b_norm"], eps)
    cm = rms_norm(dbc[:, r + n:], lp["c_norm"], eps)
    delta = jax.nn.softplus(dt @ lp["dt_proj"] + lp["dt_bias"])
    a = -jnp.exp(lp["A_log"])                            # [d_inner, n]

    def step(state, inp):
        delta_t, x_t, b_t, c_t = inp
        state = (jnp.exp(delta_t[:, None] * a) * state
                 + (delta_t * x_t)[:, None] * b_t[None, :])
        return state, state @ c_t

    state, y = jax.lax.scan(step, jnp.zeros((d_inner, n)),
                            (delta, x, b, cm))
    y = y + lp["D"] * x
    return (y * jax.nn.silu(z)) @ lp["out_proj"], state


def layer(x, lp: Dict[str, Any], kind: str, c: Dict[str, Any]):
    """One residual block on one sequence: x [S, D] -> (x, the Mamba
    layer's SSM state after the last token or None)."""
    eps = c["rms_norm_eps"]
    y = rms_norm(x, lp["ln_in"], eps)
    if kind == "attention":
        out, state = attention(y, lp, c), None
    else:
        out, state = mamba(y, lp, c)
    x = x + out
    y = rms_norm(x, lp["ln_ff"], eps)
    return x + (jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"])) \
        @ lp["w_down"], state


def logits_of(x, params: Dict[str, Any], c: Dict[str, Any]):
    return rms_norm(x, params["final_norm"], c["rms_norm_eps"]) \
        @ params["lm_head"]


def forward_with_states(params: Dict[str, Any], tokens, c: Dict[str, Any]):
    """One sequence: tokens [S] -> (logits [S, V], the SSM state
    [d_inner, d_state] of each Mamba layer after the last token)."""
    x = params["tok_embed"][tokens]
    states = []
    for lp, kind in zip(params["layers"], layer_kinds(c)):
        x, state = layer(x, lp, kind, c)
        if state is not None:
            states.append(state)
    return logits_of(x, params, c), states


def forward(params: Dict[str, Any], tokens, c: Dict[str, Any]):
    """One sequence: tokens [S] -> logits [S, V], float32, causal."""
    return forward_with_states(params, tokens, c)[0]
