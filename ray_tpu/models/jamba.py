"""Jamba: Mamba-1 mixers with a few attention layers between them, for
serving through the engine's ragged step.

The published model (AI21-Jamba2-3B) has 28 layers; layer ``i`` is
attention where ``i % attn_layer_period == attn_layer_offset`` (7 and
21) and a Mamba-1 mixer otherwise, and every layer is followed by a
dense SwiGLU feed-forward (``num_experts`` 1):

    h = h + mixer(rms_norm(h, ln_in));  h = h + mlp(rms_norm(h, ln_ff))

Attention is causal softmax over 20 query heads that share ONE KV head
of 128, with no positional term at all.  The Mamba mixer is

    [x, z] = in_proj(u)
    x = silu(causal_depthwise_conv(x, width 4) + conv_bias)
    [dt, B, C] = x_proj(x);  dt, B, C = rms_norm(dt), rms_norm(B), rms_norm(C)
    delta = softplus(dt_proj(dt) + dt_bias);  A = -exp(A_log)
    s_t = exp(delta_t (x) A) * s_{t-1} + (delta_t * x_t) (x) B_t
    y_t = s_t C_t + D * x_t;  out = out_proj(y * silu(z))

so a sequence carries two kinds of state: K/V per token for the two
attention layers, and for each Mamba layer a fixed-size pair of the
convolution's last three inputs and the SSM state ``s``, whatever the
length.  ``init_cache`` returns both in one tree: ``k``/``v`` page pools
addressed through block tables as llama's are, and ``conv``/``ssm``
indexed by slot, which no page table addresses.

``ragged_step`` is the engine's unified step (see
``llama.ragged_step_paged`` for the contract).  The attention layers go
through ``ragged_paged_attention`` with one deferred append, as llama's
unfused route does.  The Mamba layers run their projections over the
whole packed buffer as matmuls; the convolution takes each token's
predecessors from its own row or, at the row's head, from the slot's
tail; the scan is ``ops/ssm_scan``.  A row with ``row_start == 0``
starts from zero state, so a slot is reset by the first chunk of
whoever takes it.  Float32: the state, the exponent, the softplus, the
norms, the softmax; weights and activations are ``cfg.dtype``.

Gathers and scatters between rows, slots and tokens are written as
products with 0/1 matrices built once a step: on the TPU a gather of a
few hundred rows inside each of 26 layers costs more than these small
matmuls, and a product with a 0/1 matrix is exact.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import _head_matmul, _mlp_block, rms_norm
from ray_tpu.ops.ragged_paged_attention import (
    layer_slice,
    live_attention_cells,
    ragged_paged_append,
    ragged_paged_attention,
)
from ray_tpu.ops.ssm_scan import ssm_scan, token_rows

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    dim: int = 2560
    n_layers: int = 28
    n_heads: int = 20
    n_kv_heads: int = 1
    head_dim: int = 128
    mlp_dim: int = 8192
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    expand: int = 2
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @property
    def d_inner(self) -> int:
        return self.expand * self.dim

    def layer_kinds(self) -> List[str]:
        return ["attention"
                if i % self.attn_layer_period == self.attn_layer_offset
                else "mamba" for i in range(self.n_layers)]

    def state_bytes_per_slot(self) -> int:
        """Recurrent state one sequence holds, whatever its length: per
        Mamba layer the convolution's tail in ``dtype`` and the SSM
        state in float32."""
        n_mamba = self.layer_kinds().count("mamba")
        conv = (self.d_conv - 1) * jnp.dtype(self.dtype).itemsize
        return n_mamba * self.d_inner * (conv + self.d_state * 4)


def init_params(rng: jax.Array, cfg: JambaConfig) -> Params:
    """Random weights, stacked per kind of layer so that the step scans
    over each run of Mamba layers.  Made leaf by leaf where the arrays
    live, so nothing larger than a leaf is ever a temporary.  The SSM's
    own parameters follow Mamba's initialisation (``A = -[1..d_state]``,
    ``dt`` log-uniform in [1e-3, 1e-1]), so that a random model still
    remembers across hundreds of tokens, which is what a check of the
    state has to exercise."""
    d, C, N, R = cfg.dim, cfg.d_inner, cfg.d_state, cfg.dt_rank
    H, KVH, hd, m = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.mlp_dim
    kinds = cfg.layer_kinds()
    L, Lm, La = len(kinds), kinds.count("mamba"), kinds.count("attention")
    pd = cfg.param_dtype
    keys = iter(jax.random.split(rng, 16))

    def normal(shape, fan_in):
        # drawn in ``pd`` itself: a float32 draw of the stacked MLP leaf
        # would be a 2.3 GB temporary beside a 6 GB model
        return (jax.random.normal(next(keys), shape, pd)
                * fan_in ** -0.5).astype(pd)

    def uniform(shape, bound):
        return jax.random.uniform(next(keys), shape, jnp.float32,
                                  -bound, bound).astype(pd)

    dt = jnp.exp(jax.random.uniform(next(keys), (Lm, C), jnp.float32)
                 * (jnp.log(1e-1) - jnp.log(1e-3)) + jnp.log(1e-3))
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
        "mamba": {
            "in_proj": normal((Lm, d, 2 * C), d),
            "conv_w": uniform((Lm, cfg.d_conv, C), cfg.d_conv ** -0.5),
            "conv_b": uniform((Lm, C), cfg.d_conv ** -0.5),
            "x_proj": normal((Lm, C, R + 2 * N), C),
            "dt_proj": uniform((Lm, R, C), R ** -0.5),
            # softplus(dt_bias) == dt
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1.0, N + 1))[None, :, None],
                (Lm, N, C)).astype(pd),
            "D": jnp.ones((Lm, C), pd),
            "dt_norm": jnp.ones((Lm, R), pd),
            "b_norm": jnp.ones((Lm, N), pd),
            "c_norm": jnp.ones((Lm, N), pd),
            "out_proj": normal((Lm, C, d), C),
        },
        "attn": {
            "wq": normal((La, d, H, hd), d),
            "wk": normal((La, d, KVH, hd), d),
            "wv": normal((La, d, KVH, hd), d),
            "wo": normal((La, H, hd, d), H * hd),
        },
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = normal((d, cfg.vocab_size), d)
    return params


def init_cache(cfg: JambaConfig, num_pages: int, page_size: int,
               max_slots: int) -> Dict[str, jax.Array]:
    """Both kinds of state in one tree.  ``k``/``v``: page pools of the
    attention layers, ``[La, KVH, P + 1, page, hd]`` with a scratch page
    last, as ``llama.init_paged_cache``.  ``conv`` ``[Lm, d_conv - 1,
    slots, C]`` in ``dtype`` and ``ssm`` ``[Lm, slots + 1, d_state, C]``
    in float32: per slot and Mamba layer, with a scratch slot last in
    ``ssm`` for the scan kernel's padding rows."""
    kinds = cfg.layer_kinds()
    La, Lm = kinds.count("attention"), kinds.count("mamba")
    kv = (La, cfg.n_kv_heads, num_pages + 1, page_size, cfg.head_dim)
    C = cfg.d_inner
    return {
        "k": jnp.zeros(kv, cfg.dtype),
        "v": jnp.zeros(kv, cfg.dtype),
        "conv": jnp.zeros((Lm, cfg.d_conv - 1, max_slots, C), cfg.dtype),
        "ssm": jnp.zeros((Lm, max_slots + 1, cfg.d_state, C), jnp.float32),
    }


def _exact_dot(a, b):
    """Product with a 0/1 matrix: a gather or a scatter, so every pass."""
    return jnp.dot(a, b, precision=lax.Precision.HIGHEST,
                   preferred_element_type=b.dtype)


def _row_maps(row_slot, row_start, row_len, row_off, T: int, S: int, K1: int):
    """The step's 0/1 matrices, shared by every Mamba layer.

    ``slot_of_row`` [R, S]: row r reads slot s (no slot where the row
    starts a sequence or is padding: its tail reads as zero).
    ``head`` [K1][T, R]: token t is the j-th of row r, j < K1: the
    positions whose convolution reaches back into the slot's tail.
    ``last`` [K1][R, T]: token t is the row's (K1 - i)-th from its end.
    ``back`` [K1][T, 1]: token t has k + 1 predecessors in its own row.
    """
    R = row_slot.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    tok_row, valid = token_rows(row_len, row_off, T)
    j = t - row_off[tok_row]                               # in-row index
    carries = (row_len > 0) & (row_start > 0)
    slot_of_row = (carries[:, None]
                   & (row_slot[:, None] == jnp.arange(S)[None, :]))
    rows = jnp.arange(R)[None, :]
    head = [valid[:, None] & (tok_row[:, None] == rows)
            & (j[:, None] == jj) for jj in range(K1)]
    last = [(t[None, :] == (row_off + row_len - K1 + i)[:, None])
            & (row_len + i >= K1)[:, None] for i in range(K1)]
    back = [(valid & (j > k))[:, None] for k in range(K1)]
    return slot_of_row, head, last, back


def _conv(x, tail, w, bias, head, back, K1: int):
    """Causal depthwise convolution of width K1 + 1 over the packed
    buffer.  ``x`` [T, C] is the fresh input, ``tail`` [K1, R, C] each
    row's last K1 inputs from before this step (oldest first, zeros for
    a new sequence).  Token t's k-th predecessor is x[t - k] where its
    row reaches that far back and the tail's entry otherwise."""
    f32 = jnp.float32
    acc = x.astype(f32) * w[K1] + bias
    for k in range(1, K1 + 1):                 # predecessor k, in-row part
        acc += jnp.where(back[k - 1], jnp.roll(x, k, axis=0), 0
                         ).astype(f32) * w[K1 - k]
    tail = tail.astype(f32)
    for jj in range(K1):                       # j-th token of a row
        # its predecessors k > jj lie in the tail at K1 + jj - k
        reach = sum(tail[K1 + jj - k] * w[K1 - k]
                    for k in range(jj + 1, K1 + 1))
        acc += _exact_dot(head[jj].astype(f32), reach)
    return acc


def _new_tail(x, tail, row_len, last, K1: int):
    """Each row's last K1 inputs after this step: entry i of
    ``tail ++ x_row`` shifted by the row's length."""
    out = []
    for i in range(K1):
        fresh = _exact_dot(last[i].astype(x.dtype), x)     # [R, C]
        kept = sum(jnp.where((row_len + i == m)[:, None], tail[m], 0)
                   for m in range(i, K1))
        out.append(fresh + kept.astype(x.dtype))
    return jnp.stack(out)                                  # [K1, R, C]


def _mamba_mixer(u, p, cfg: JambaConfig, conv_l, ssm, li_m, maps, rows):
    """One Mamba-1 mixer over the packed buffer ``u`` [T, D].  Returns
    (out [T, D], new conv tails [K1, R, C], ssm)."""
    slot_of_row, head, last, back = maps
    row_slot, row_start, row_len, row_off = rows
    C, N, R_dt, K1 = cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.d_conv - 1
    dt_, f32 = cfg.dtype, jnp.float32
    eps = cfg.norm_eps
    with jax.named_scope("ssm_proj"):
        xz = jnp.dot(u, p["in_proj"].astype(dt_))
        x, z = xz[:, :C], xz[:, C:]
    with jax.named_scope("ssm_conv"):
        tail = jnp.stack([_exact_dot(slot_of_row.astype(dt_), conv_l[m])
                          for m in range(K1)])             # [K1, R, C]
        xc = jax.nn.silu(_conv(
            x, tail, p["conv_w"].astype(f32), p["conv_b"].astype(f32),
            head, back, K1)).astype(dt_)
        new_tail = _new_tail(x, tail, row_len, last, K1)
    with jax.named_scope("ssm_proj"):
        dbc = jnp.dot(xc, p["x_proj"].astype(dt_),
                      preferred_element_type=f32)
        dt_low = rms_norm(dbc[:, :R_dt], p["dt_norm"].astype(f32), eps)
        b = rms_norm(dbc[:, R_dt:R_dt + N], p["b_norm"].astype(f32), eps)
        c = rms_norm(dbc[:, R_dt + N:], p["c_norm"].astype(f32), eps)
        delta = jax.nn.softplus(
            jnp.dot(dt_low.astype(dt_), p["dt_proj"].astype(dt_),
                    preferred_element_type=f32)
            + p["dt_bias"].astype(f32))
    with jax.named_scope("ssm_scan"):
        a = -jnp.exp(p["A_log"].astype(f32))               # [N, C]
        y, ssm = ssm_scan(delta, xc, b, c, a, ssm, li_m, row_slot,
                          row_start, row_len, row_off)
    with jax.named_scope("ssm_proj"):
        y = y + p["D"].astype(f32) * xc.astype(f32)
        gated = (y * jax.nn.silu(z.astype(f32))).astype(dt_)
        out = jnp.dot(gated, p["out_proj"].astype(dt_))
    return out, new_tail, ssm


def _attention_mixer(u, p, cfg: JambaConfig, cache, li_a, rows, block_tables,
                     live_cells):
    """Causal MQA over the paged pool plus the row's own fresh tokens;
    no positional term.  Returns (out [T, D], k [T, KVH, hd], v)."""
    row_slot, row_start, row_len, row_off = rows
    dt_ = cfg.dtype
    q = jnp.einsum("td,dhk->thk", u, p["wq"].astype(dt_))
    k = jnp.einsum("td,dhk->thk", u, p["wk"].astype(dt_))
    v = jnp.einsum("td,dhk->thk", u, p["wv"].astype(dt_))
    out = ragged_paged_attention(
        q, k, v, cache["k"], cache["v"], li_a, row_slot, row_start,
        row_len, row_off, block_tables,
        live_cells=live_cells)                             # [T, H, hd] f32
    out = jnp.einsum("thk,hkd->td", out.astype(dt_), p["wo"].astype(dt_))
    return out, k, v


def _segments(kinds: List[str]) -> List[Tuple[str, int, int]]:
    """Runs of equal kind: (kind, first layer, count)."""
    out: List[Tuple[str, int, int]] = []
    for i, kind in enumerate(kinds):
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1], out[-1][2] + 1)
        else:
            out.append((kind, i, 1))
    return out


def ragged_step(
    params: Params,
    tokens: jax.Array,       # [T] flat ragged token buffer
    tok_pos: jax.Array,      # [T] absolute positions (unused: no rotary)
    row_slot: jax.Array,     # [R] slot of each packed row
    row_start: jax.Array,    # [R] tokens the row's sequence already holds
    row_len: jax.Array,      # [R] fresh tokens this step (0 = padding)
    row_off: jax.Array,      # [R] row's offset into the flat buffer
    block_tables: jax.Array,
    cfg: JambaConfig,
    cache: Dict[str, jax.Array],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One unified serving step over a ragged batch of prompt chunks and
    decode rows.  Returns (logits [R, V] float32 at each row's last
    fresh token, new cache).  Padding rows return garbage logits and
    leave every part of the cache as it was."""
    del tok_pos
    T = tokens.shape[0]
    K1 = cfg.d_conv - 1
    S = cache["conv"].shape[2]
    rows = (row_slot, row_start, row_len, row_off)
    maps = _row_maps(row_slot, row_start, row_len, row_off, T, S, K1)
    with jax.named_scope("embed"):
        x = params["tok_embed"][tokens].astype(cfg.dtype)  # [T, D]

    def feed_forward(h, li):
        with jax.named_scope("mlp"):
            layer = {"mlp": layer_slice(params["mlp"], li)}
            normed = rms_norm(h, params["ln_ff"][li], cfg.norm_eps)
            return h + _mlp_block(normed[None], layer, cfg)[0]

    with jax.named_scope("attention"):
        # the cells of the page table that hold these rows' tokens are
        # the same in every attention layer: listed once, for all
        live_cells = live_attention_cells(
            row_start, row_len, row_off, T, block_tables.shape[1],
            cache["k"].shape[3])
    ssm = cache["ssm"]
    tails, k_news, v_news = [], [], []
    li_m = li_a = 0
    for kind, first, count in _segments(cfg.layer_kinds()):
        if kind == "mamba":
            def body(carry, _):
                h, ssm, li, lm = carry
                p = layer_slice(params["mamba"], lm)
                normed = rms_norm(h, params["ln_in"][li], cfg.norm_eps)
                out, tail, ssm = _mamba_mixer(
                    normed, p, cfg, cache["conv"][lm], ssm, lm, maps, rows)
                return (feed_forward(h + out, li), ssm, li + 1, lm + 1), tail

            (x, ssm, _, _), seg_tails = lax.scan(
                body, (x, ssm, jnp.int32(first), jnp.int32(li_m)), None,
                length=count)
            tails.append(seg_tails)
            li_m += count
            continue
        for li in range(first, first + count):
            with jax.named_scope("attention"):
                p = jax.tree.map(lambda w: w[li_a], params["attn"])
                normed = rms_norm(x, params["ln_in"][li], cfg.norm_eps)
                out, k1, v1 = _attention_mixer(
                    normed, p, cfg, cache, li_a, rows, block_tables,
                    live_cells)
            x = feed_forward(x + out, li)
            k_news.append(k1)
            v_news.append(v1)
            li_a += 1

    new_cache = dict(cache, ssm=ssm)
    if tails:
        with jax.named_scope("ssm_conv"):
            # one write of every layer's tails; padding rows drop out
            slots = jnp.where(row_len > 0, row_slot, S)
            new_cache["conv"] = cache["conv"].at[:, :, slots].set(
                jnp.concatenate(tails), mode="drop")
    if k_news:
        with jax.named_scope("kv_append"):
            new_cache["k"], new_cache["v"] = ragged_paged_append(
                cache["k"], cache["v"], jnp.stack(k_news),
                jnp.stack(v_news), row_slot, row_start, row_len, row_off,
                block_tables)
    with jax.named_scope("lm_head"):
        last = jnp.clip(row_off + jnp.maximum(row_len, 1) - 1, 0, T - 1)
        head = (params["tok_embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        x = rms_norm(x[last], params["final_norm"], cfg.norm_eps)
        logits = _head_matmul(x, head, cfg)
    return logits.astype(jnp.float32), new_cache
