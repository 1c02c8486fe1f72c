"""Plain reference for Brumby-14B-Base: the forward pass of one sequence
in straightforward float32 ``jax.numpy``, with the power retention
written as the QUADRATIC sum over the sequence: no feature map, no
state, no chunks, no kernels, no packed batch.  Independent of
``ray_tpu/models/brumby.py`` and ``ray_tpu/ops/power_retention.py``.

    h = h + o_proj(retention(rms_norm(h, input_layernorm)))
    h = h + mlp(rms_norm(h, post_attention_layernorm))
    mlp(x) = down(silu(gate(x)) * up(x))
    logits = lm_head(norm(h))                                    (untied)

    retention, per token t, KV head j, query head h in j's group:
        q = rope(rms_norm_head(q_proj(x)))   k = rope(rms_norm_head(k_proj(x)))
        v = v_proj(x)         log g = logsigmoid(gate_proj(x) + gate_bias)
        a_ts = (q_t,h . k_s,j)^2 * exp(sum_{r=s+1..t} log g_r,j)     s <= t
        y_t,h = sum_s a_ts v_s,j / (sum_s a_ts + eps)

Queries are taken in blocks so that 16k tokens fit; each block sees
every key at or before it, written out.

``final_state`` is the state the recurrent form of the same function
holds after the last token, by its definition as a direct sum,
``S_j = sum_s exp(sum_{r>s} log g_r) phi(k_s) v_s^T`` and ``z_j`` the
same without ``v``, with ``phi(u)`` the ``d`` squares followed by
``sqrt(2) u_i u_i'`` for ``i < i'`` in row-major order (``phi(a) .
phi(b) == (a . b)^2``): what the program's state is held to.

Weights may be given in any dtype; every use converts to float32, one
matrix at a time, so that a layer of the model needs no float32 copy of
itself beside a serving engine.  ``ASSUMED`` lists what the published
config.json has no key for.

Callers wrap calls in ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

ASSUMED = {
    "degree": "2: weights are squares of scores",
    "feature_map": "symmetric: u_i^2 and sqrt(2) u_i u_i' (i < i'), "
                   "D = 128 * 129 / 2 = 8256 a head",
    "gate": "one scalar per token and KV head, sigmoid(w_g . x + b_g), "
            "with a bias; its log summed in float32",
    "normaliser": "z = decayed sum of phi(k); y = S^T phi(q) / "
                  "(z . phi(q) + eps), eps = 1e-6",
    "qk_norm": "RMSNorm with a learned weight on each head's q and k "
               "(eps = rms_norm_eps), then rotary at rope_theta, halves "
               "rotated, before the scores",
    "head_dim": "128 = hidden_size / num_attention_heads",
    "state_from_token_0": "the published code keeps keys and values up "
                          "to a set length and then switches to the "
                          "state; the same function",
}
F32 = jnp.float32
QUERY_BLOCK = 512


def _f(w):
    return jnp.asarray(w).astype(F32)


def mixer_from_program_tree(tree: Dict[str, Any], c: Dict[str, Any],
                            i: int) -> Dict[str, Any]:
    """The retention block's leaves of layer ``i`` of the program's
    stacked tree under the published names, in the dtype they are
    stored in."""
    d = c["hidden_size"]
    ret = tree["ret"]
    return {
        "input_layernorm": tree["ln_in"][i],
        "q_proj": ret["wq"][i].reshape(d, -1),
        "k_proj": ret["wk"][i].reshape(d, -1),
        "v_proj": ret["wv"][i].reshape(d, -1),
        "o_proj": ret["wo"][i].reshape(-1, d),
        "q_norm": ret["q_norm"][i], "k_norm": ret["k_norm"][i],
        "gate_proj": ret["w_g"][i], "gate_bias": ret["b_g"][i],
    }


def mlp_columns_from_program_tree(tree: Dict[str, Any], i: int,
                                  c0: int = 0, c1: Optional[int] = None):
    """Layer ``i``'s (gate, up, down) over the intermediate columns
    ``c0:c1`` (``down``'s rows): ``mlp_part``'s last three operands."""
    mlp = tree["mlp"]
    return (mlp["w_gate"][i, :, c0:c1], mlp["w_up"][i, :, c0:c1],
            mlp["w_down"][i, c0:c1])


def layer_from_program_tree(tree: Dict[str, Any], c: Dict[str, Any],
                            i: int) -> Dict[str, Any]:
    """Layer ``i`` of the program's stacked tree under the published
    names, in the dtype it is stored in."""
    gate, up, down = mlp_columns_from_program_tree(tree, i)
    return dict(mixer_from_program_tree(tree, c, i),
                post_attention_layernorm=tree["ln_ff"][i],
                mlp_gate=gate, mlp_up=up, mlp_down=down)


def head_from_program_tree(tree: Dict[str, Any]) -> Dict[str, Any]:
    return {"embed_tokens": tree["tok_embed"], "norm": tree["final_norm"],
            "lm_head": tree["lm_head"]}


def from_program_tree(tree: Dict[str, Any], c: Dict[str, Any]
                      ) -> Dict[str, Any]:
    out = head_from_program_tree(tree)
    out["layers"] = [layer_from_program_tree(tree, c, i)
                     for i in range(c["num_hidden_layers"])]
    return out


def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f(w)


def rope(x, theta: float):
    """x [n, heads, d]: rotate the halves by the position's angles."""
    n, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d // 2, dtype=F32) / (d // 2))
    ang = jnp.arange(n, dtype=F32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mixer_inputs(u, lp, c):
    """q [n, H, d], k, v [n, KVH, d] and the gate's log [n, KVH]."""
    n = u.shape[0]
    H, KVH = c["num_attention_heads"], c["num_key_value_heads"]
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    q = (u @ _f(lp["q_proj"])).reshape(n, H, -1)
    k = (u @ _f(lp["k_proj"])).reshape(n, KVH, -1)
    v = (u @ _f(lp["v_proj"])).reshape(n, KVH, -1)
    q = rope(rms_norm(q, lp["q_norm"], eps), theta)
    k = rope(rms_norm(k, lp["k_norm"], eps), theta)
    log_g = jax.nn.log_sigmoid(u @ _f(lp["gate_proj"]) + _f(lp["gate_bias"]))
    return q, k, v, log_g


def retention(q, k, v, log_g, eps: float = 1e-6,
              query_block: int = QUERY_BLOCK):
    """The quadratic form, queries in blocks.  Returns y [n, H, d]."""
    n, H, d = q.shape
    KVH = k.shape[1]
    G = H // KVH
    cum = jnp.cumsum(log_g, axis=0)                        # [n, KVH]
    s_idx = jnp.arange(n)
    out = []
    for t0 in range(0, n, query_block):
        t1 = min(n, t0 + query_block)
        qb = q[t0:t1].reshape(t1 - t0, KVH, G, d)
        score = jnp.einsum("tjgd,sjd->jgts", qb, k[:t1])
        decay = cum[t0:t1].T[:, :, None] - cum[:t1].T[:, None, :]
        seen = s_idx[None, t0:t1, None] >= s_idx[None, None, :t1]
        a = score * score * jnp.where(seen, jnp.exp(
            jnp.where(seen, decay, 0.0)), 0.0)[:, None]    # [j, g, t, s]
        num = jnp.einsum("jgts,sjd->tjgd", a, v[:t1])
        den = jnp.sum(a, axis=-1).transpose(2, 0, 1)       # [t, j, g]
        out.append((num / (den[..., None] + eps)).reshape(t1 - t0, H, d))
    return jnp.concatenate(out, axis=0)


def phi(u):
    """[..., d] -> [..., d (d + 1) / 2]: squares, then sqrt(2) u_i u_i'."""
    d = u.shape[-1]
    i, j = np.triu_indices(d, 1)
    return jnp.concatenate(
        [u * u, np.sqrt(2.0) * u[..., i] * u[..., j]], axis=-1)


def final_state(k, v, log_g):
    """(S [KVH, D, d], z [KVH, D]) after the last token, by direct sum."""
    cum = jnp.cumsum(log_g, axis=0)
    w = jnp.exp(cum[-1][None, :] - cum)                    # [n, KVH]

    def head(args):
        kj, vj, wj = args                                  # [n, d] [n, d] [n]
        f = phi(kj) * wj[:, None]
        return f.T @ vj, jnp.sum(f, axis=0)

    return jax.lax.map(head, (k.transpose(1, 0, 2), v.transpose(1, 0, 2),
                              w.T))


def mixer(x, lp: Dict[str, Any], c: Dict[str, Any], with_state=False,
          query_block: int = QUERY_BLOCK):
    """The retention block's addition to [n, hidden], and the final
    state if asked."""
    u = rms_norm(x, lp["input_layernorm"], float(c["rms_norm_eps"]))
    q, k, v, log_g = mixer_inputs(u, lp, c)
    y = retention(q, k, v, log_g,
                  query_block=query_block).reshape(x.shape[0], -1)
    return (y @ _f(lp["o_proj"]),
            final_state(k, v, log_g) if with_state else None)


def mlp_part(u, gate, up, down):
    """The SwiGLU's sum over the intermediate columns given: the whole
    of it, or one block of columns (with ``down``'s rows) at a time."""
    return (jax.nn.silu(u @ _f(gate)) * (u @ _f(up))) @ _f(down)


def layer(x, lp: Dict[str, Any], c: Dict[str, Any], with_state=False):
    """One layer on [n, hidden].  Returns (x, state or None)."""
    add, state = mixer(x, lp, c, with_state)
    x = x + add
    u = rms_norm(x, lp["post_attention_layernorm"], float(c["rms_norm_eps"]))
    return x + mlp_part(u, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"]), state


def embed(params: Dict[str, Any], tokens):
    return _f(params["embed_tokens"][tokens])


def logits_of(x, params: Dict[str, Any], c: Dict[str, Any],
              block: int = 16384):
    """Logits [rows, V] of hidden rows ``x``, the head a block of the
    vocabulary at a time."""
    x = rms_norm(x, params["norm"], float(c["rms_norm_eps"]))
    head = params["lm_head"]
    V = head.shape[1]
    part = jax.jit(lambda x, w: x @ _f(w))
    return jnp.concatenate(
        [part(x, head[:, a:min(V, a + block)]) for a in range(0, V, block)],
        axis=1)


def forward_hidden(params: Dict[str, Any], tokens, c: Dict[str, Any]):
    """tokens [n] -> (hidden [n, hidden] before the final norm, the first
    layer's final (S, z))."""
    x = embed(params, tokens)
    first, *rest = params["layers"]
    x, state = layer(x, first, c, with_state=True)
    for lp in rest:
        x, _ = layer(x, lp, c)
    return x, state
