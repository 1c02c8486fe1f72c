"""MiniCPM-SALA: a dense decoder whose mixers are ``lightning-attn``
(linear attention, a constant decay per head, a matrix state by slot)
and ``minicpm4`` (grouped-query attention that selects its keys by
blocks through a pool of compressed keys), for serving through the
engine's ragged step.

The published model (openbmb/MiniCPM-SALA) has 32 layers of hidden 4096,
``mixer_types`` naming each layer's mixer (24 lightning, 8 minicpm4, not
periodic), a dense SwiGLU of 16384 on every layer, and MiniCPM's depth
scaling, with 32 the PUBLISHED depth whatever is held:

    x_0 = scale_emb * E[id]
    x <- x + (scale_depth / sqrt(32)) * Mixer(n(x))
    x <- x + (scale_depth / sqrt(32)) * W_down(silu(W_gate u) * W_up u)
    logits = W_head (n(x) / (hidden / dim_model_base))

``lightning-attn`` (32 heads of 128, none shared): per-head RMSNorm on q
and k, rotary, ``q / sqrt(128)``, ``S_t = lambda_h S_{t-1} + k_t^T v_t``,
``o_t = q_t S_t`` (``ops/lightning_attention``), then ``W_o (sigmoid(u
W_g) * n_out(o))`` with one RMSNorm over the 4096 concatenated lanes.
``lambda_h = exp(-s_h f_l)``, ``s_h = 2^(-8 (h + 1) / 32)``, ``f_l = 1 -
l / 31 + 1e-5`` at the layer's PUBLISHED position ``l``: a buffer of the
weights (``lin.decay``), as the published code registers its slopes.

``minicpm4`` (32 query heads over 2 KV heads of 128, no rotary):
per-head RMSNorm on q and k, softmax attention over the keys the query
selects (``ops/block_sparse_attention``: every ``s <= t`` below
``dense_len`` by the QUERY's position, else the tokens of its top-64
blocks), ``W_o (sigmoid(u W_g) * o)``.

A sequence carries three kinds of state, in one tree under the engine's
one allocator: ``k``/``v`` page pools of the sparse layers, ``kh``, the
paged pool of their compressed keys (one entry a 16 tokens, float32),
and ``lin_s`` ``[Ll, slots + 1, 32, 128, 128]`` float32 by slot for the
lightning layers, which no page table addresses; ``sel_pages`` counts
on the device the pool pages the walk read (``[2]``: by rows of one
token, by rows of more; summed over rows, KV heads and layers) and
``walk_cells`` the pool cells it read them in (the same ``[2]``; a cell
holds up to ``ops/block_sparse_attention.CELL_PAGES`` pages).

``ragged_step`` is the engine's unified step (see
``llama.ragged_step_paged`` for the contract).  Pools are read-only in
the layer loop: one aliased append of k / v and one write of the
compressed keys at the step's end.  A row with ``row_start == 0`` starts
from zero state, so a slot is reset by the first chunk of whoever takes
it.  Float32: the state, the selection's scores and top-k, the norms,
the softmax; weights and activations are ``cfg.dtype``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.jamba import _segments
from ray_tpu.models.llama import (
    _head_matmul,
    _mlp_block,
    apply_rope,
    rms_norm,
    rope_table,
)
from ray_tpu.ops import block_sparse_attention as bsa
from ray_tpu.ops import lightning_attention as la
from ray_tpu.ops.ragged_paged_attention import layer_slice, ragged_paged_append

Params = Dict[str, Any]

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
# a sparse layer's seeded q-norm weight: the spread of its scores
Q_GAIN = 2.4
# the published list of mixers, by layer
PUBLISHED_MIXERS = tuple(
    SPARSE if i in (0, 9, 16, 17, 22, 29, 30, 31) else LIGHTNING
    for i in range(32))


@dataclasses.dataclass(frozen=True)
class SalaConfig:
    vocab_size: int = 73448
    dim: int = 4096
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    mlp_dim: int = 16384
    # the layers HELD, and where the first of them stands in the
    # published list of ``published_layers``
    mixer_types: Tuple[str, ...] = PUBLISHED_MIXERS
    first_layer: int = 0
    published_layers: int = 32
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    sparse: bsa.BlockSparse = bsa.BlockSparse()
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @property
    def n_layers(self) -> int:
        return len(self.mixer_types)

    def layer_kinds(self) -> List[str]:
        return list(self.mixer_types)

    @property
    def branch_scale(self) -> float:
        return self.scale_depth / self.published_layers ** 0.5

    def state_bytes_per_slot(self) -> int:
        """State one sequence holds whatever its length: per lightning
        layer a float32 matrix a head."""
        return (self.layer_kinds().count(LIGHTNING)
                * la.state_bytes(self.head_dim, self.n_heads))

    def pool_bytes_per_token(self) -> int:
        """Paged bytes one cached token holds: k and v of every sparse
        layer and its share of their compressed keys."""
        n = self.layer_kinds().count(SPARSE)
        kv = 2 * self.n_kv_heads * self.head_dim * jnp.dtype(
            self.dtype).itemsize
        return n * (kv + self.n_kv_heads * self.head_dim * 4
                    // self.sparse.stride)


def decay_rates(cfg: SalaConfig) -> jax.Array:
    """``lambda_h`` of every lightning layer held, ``[Ll, H]`` float32,
    from each layer's PUBLISHED position."""
    at = [cfg.first_layer + i for i, kind in enumerate(cfg.mixer_types)
          if kind == LIGHTNING]
    f = 1.0 - jnp.asarray(at, jnp.float32) / (cfg.published_layers - 1) + 1e-5
    s = jnp.exp2(-8.0 * (jnp.arange(cfg.n_heads, dtype=jnp.float32) + 1.0)
                 / cfg.n_heads)
    return jnp.exp(-f[:, None] * s[None, :])


def init_params(rng: jax.Array, cfg: SalaConfig) -> Params:
    """Random weights, stacked per kind of layer, made leaf by leaf where
    the arrays live.  Matrices at ``fan_in ** -0.5``; the embedding at
    ``1 / scale_emb`` so that ``x_0`` has unit scale; a sparse layer's
    q-norm weight at ``Q_GAIN`` so that a head's scores spread to about
    that and its softmax over ten thousand positions leans on tens of
    them: attending elsewhere than the selection then moves the output,
    which is what a check of the selection has to see."""
    d, m, V = cfg.dim, cfg.mlp_dim, cfg.vocab_size
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kinds = cfg.layer_kinds()
    L, Ll, La = len(kinds), kinds.count(LIGHTNING), kinds.count(SPARSE)
    pd = cfg.param_dtype
    keys = iter(jax.random.split(rng, 24))

    def normal(shape, fan_in):
        # drawn in ``pd`` itself: a float32 draw of the stacked MLP leaf
        # would be a 4.3 GB temporary beside a 10 GB model
        return (jax.random.normal(next(keys), shape, pd)
                * fan_in ** -0.5).astype(pd)

    return {
        "tok_embed": normal((V, d), cfg.scale_emb ** 2),
        "lm_head": normal((d, V), d),
        "final_norm": jnp.ones((d,), pd),
        "ln_in": jnp.ones((L, d), pd),
        "ln_ff": jnp.ones((L, d), pd),
        "mlp": {
            "w_gate": normal((L, d, m), d),
            "w_up": normal((L, d, m), d),
            "w_down": normal((L, m, d), m),
        },
        "lin": {
            # projections are stored as matrices (heads folded into the
            # lanes): with an axis a head XLA re-laid the three stacks of
            # twelve layers out in front of the scan, 1.2 GB a step
            "wq": normal((Ll, d, H * hd), d),
            "wk": normal((Ll, d, H * hd), d),
            "wv": normal((Ll, d, H * hd), d),
            "wg": normal((Ll, d, H * hd), d),
            "wo": normal((Ll, H * hd, d), H * hd),
            "q_norm": jnp.ones((Ll, hd), pd),
            "k_norm": jnp.ones((Ll, hd), pd),
            "o_norm": jnp.ones((Ll, H * hd), pd),
            "decay": decay_rates(cfg),
        },
        "attn": {
            "wq": normal((La, d, H * hd), d),
            "wk": normal((La, d, KVH * hd), d),
            "wv": normal((La, d, KVH * hd), d),
            "wg": normal((La, d, H * hd), d),
            "wo": normal((La, H * hd, d), H * hd),
            "q_norm": jnp.full((La, hd), Q_GAIN, pd),
            "k_norm": jnp.ones((La, hd), pd),
        },
    }


def init_cache(cfg: SalaConfig, num_pages: int, page_size: int,
               max_slots: int) -> Dict[str, jax.Array]:
    """Pages and state in one tree.  ``k``/``v`` ``[La, KVH, P + 1, page,
    hd]`` with a scratch page last, as ``llama.init_paged_cache``; ``kh``
    ``[La, (P + 1) * page / stride, KVH * hd]`` float32 under the same
    block tables (a row an entry: ``ops/block_sparse_attention``); ``lin_s`` ``[Ll, slots + 1, H, hd, hd]`` float32 with a
    scratch slot last; the counters ``sel_pages`` and ``walk_cells``, ``[2]``
    each."""
    assert page_size == cfg.sparse.block, (
        "a selection is a list of pages: the engine's page has to be the "
        f"model's block ({cfg.sparse.block}), not {page_size}")
    kinds = cfg.layer_kinds()
    La, Ll = kinds.count(SPARSE), kinds.count(LIGHTNING)
    kv = (La, cfg.n_kv_heads, num_pages + 1, page_size, cfg.head_dim)
    return {
        "k": jnp.zeros(kv, cfg.dtype),
        "v": jnp.zeros(kv, cfg.dtype),
        "kh": jnp.zeros((La, (num_pages + 1) * cfg.sparse.entries,
                         cfg.n_kv_heads * cfg.head_dim), jnp.float32),
        "lin_s": jnp.zeros((Ll, max_slots + 1, cfg.n_heads, cfg.head_dim,
                            cfg.head_dim), jnp.float32),
        "sel_pages": jnp.zeros((2,), jnp.int32),
        "walk_cells": jnp.zeros((2,), jnp.int32),
    }


def _gated_out(o, gate, wo, cfg: SalaConfig):
    """``W_o (sigmoid(gate) * o)``: ``o`` [T, H * hd], ``wo`` [H * hd, D]."""
    y = (jax.nn.sigmoid(gate.astype(jnp.float32))
         * o.astype(jnp.float32)).astype(cfg.dtype)
    return jnp.dot(y, wo.astype(cfg.dtype))


def _heads(u, w, cfg: SalaConfig):
    """``u W`` by head: ``[T, D] x [D, n * hd] -> [T, n, hd]``."""
    y = lax.optimization_barrier(jnp.dot(u, w.astype(cfg.dtype)))
    return y.reshape(u.shape[0], -1, cfg.head_dim)


def _lightning_mixer(u, p, cfg: SalaConfig, lin_s, lm, rows, sin, cos):
    """One lightning mixer over the packed buffer ``u`` [T, D].  Returns
    (out [T, D], lin_s)."""
    dt_ = cfg.dtype
    T = u.shape[0]
    with jax.named_scope("lin_proj"):
        q, k, v = (_heads(u, p[w], cfg) for w in ("wq", "wk", "wv"))
        gate = jnp.dot(u, p["wg"].astype(dt_))
        q = apply_rope(rms_norm(q, p["q_norm"], cfg.norm_eps)[None],
                       sin, cos)[0]
        k = apply_rope(rms_norm(k, p["k_norm"], cfg.norm_eps)[None],
                       sin, cos)[0]
        q = q.astype(jnp.float32) * cfg.head_dim ** -0.5
    with jax.named_scope("lin_attn"):
        o, lin_s = la.lightning_attention(q, k, v, p["decay"], lin_s, lm,
                                          *rows)
    with jax.named_scope("lin_proj"):
        o = rms_norm(o.reshape(T, -1), p["o_norm"].astype(jnp.float32),
                     cfg.norm_eps)
        return _gated_out(o, gate, p["wo"], cfg), lin_s


def _sparse_mixer(u, p, cfg: SalaConfig, cache, li_a, rows, block_tables,
                  groups):
    """One minicpm4 mixer.  Returns (out [T, D], k [T, KVH, hd], v, the
    step's group sums [NG, KVH, hd], pages read and cells walked [2, 2],
    the selection bool[T, KVH, maxp])."""
    dt_ = cfg.dtype
    T = u.shape[0]
    H, KVH, hd, sp = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.sparse
    with jax.named_scope("attention"):
        q, k, v = (_heads(u, p[w], cfg) for w in ("wq", "wk", "wv"))
        gate = jnp.dot(u, p["wg"].astype(dt_))
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    with jax.named_scope("bsa_compress"):
        sums = bsa.group_sums(k, groups, sp.stride)
    picked = bsa.select_pages(
        q.reshape(T, KVH, H // KVH, hd), cache["kh"], li_a, sums, groups,
        *rows, block_tables, sp)
    with jax.named_scope("sparse_attn"):
        o, pages, cells = bsa.block_sparse_attention(
            q, k, v, cache["k"], cache["v"], li_a, *rows, block_tables,
            picked)
    with jax.named_scope("attention"):
        out = _gated_out(o.reshape(T, -1), gate, p["wo"], cfg)
    return out, k, v, sums, jnp.stack([pages, cells]), picked


def ragged_step(
    params: Params,
    tokens: jax.Array,       # [T] flat ragged token buffer
    tok_pos: jax.Array,      # [T] absolute positions
    row_slot: jax.Array,     # [R] slot of each packed row
    row_start: jax.Array,    # [R] tokens the row's sequence already holds
    row_len: jax.Array,      # [R] fresh tokens this step (0 = padding)
    row_off: jax.Array,      # [R] row's offset into the flat buffer
    block_tables: jax.Array,
    cfg: SalaConfig,
    cache: Dict[str, jax.Array],
    *,
    probe: bool = False,
):
    """One unified serving step over a ragged batch of prompt chunks and
    decode rows.  Returns (logits [R, V] float32 at each row's last
    fresh token, new cache).  Padding rows return garbage logits and
    leave every part of the cache as it was.  ``probe`` (the checks'; the
    engine's step has none) returns a third value: every sparse layer's
    selection ``bool[La, T, KVH, maxp]``."""
    T = tokens.shape[0]
    i32 = jnp.int32
    rows = tuple(jnp.asarray(a, i32) for a in
                 (row_slot, row_start, row_len, row_off))
    tokens, tok_pos, block_tables = (
        jnp.asarray(a, i32) for a in (tokens, tok_pos, block_tables))
    scale = cfg.branch_scale
    with jax.named_scope("embed"):
        x = (params["tok_embed"][tokens].astype(jnp.float32)
             * cfg.scale_emb).astype(cfg.dtype)            # [T, D]
        sin, cos = rope_table(cfg, tok_pos[None])

    def feed_forward(h, li):
        with jax.named_scope("mlp"):
            layer = {"mlp": layer_slice(params["mlp"], li)}
            normed = rms_norm(h, params["ln_ff"][li], cfg.norm_eps)
            return h + (scale * _mlp_block(normed[None], layer, cfg)[0]
                        ).astype(h.dtype)

    with jax.named_scope("bsa_compress"):
        # which halves of 16 tokens the step's fresh tokens fall into is
        # the same in every sparse layer: listed once, for all
        groups = bsa.step_groups(*rows[1:], T, cfg.sparse.stride)
    lin_s = cache["lin_s"]
    k_news, v_news, sums, walked, picks = [], [], [], [], []
    li_m = li_a = 0
    for kind, first, count in _segments(cfg.layer_kinds()):
        if kind == LIGHTNING:
            def body(carry, _):
                h, lin_s, li, lm = carry
                p = layer_slice(params["lin"], lm)
                normed = rms_norm(h, params["ln_in"][li], cfg.norm_eps)
                out, lin_s = _lightning_mixer(normed, p, cfg, lin_s, lm,
                                              rows, sin, cos)
                h = h + (scale * out).astype(h.dtype)
                return (feed_forward(h, li), lin_s, li + 1, lm + 1), None

            (x, lin_s, _, _), _ = lax.scan(
                body, (x, lin_s, jnp.int32(first), jnp.int32(li_m)), None,
                length=count)
            li_m += count
            continue
        for li in range(first, first + count):
            p = jax.tree.map(lambda w: w[li_a], params["attn"])
            with jax.named_scope("attention"):
                normed = rms_norm(x, params["ln_in"][li], cfg.norm_eps)
            out, k1, v1, s1, n1, m1 = _sparse_mixer(
                normed, p, cfg, cache, li_a, rows, block_tables, groups)
            x = feed_forward(x + (scale * out).astype(x.dtype), li)
            k_news.append(k1)
            v_news.append(v1)
            sums.append(s1)
            walked.append(n1)
            picks.append(m1)
            li_a += 1

    new_cache = dict(cache, lin_s=lin_s)
    if k_news:
        with jax.named_scope("kv_append"):
            new_cache["k"], new_cache["v"] = ragged_paged_append(
                cache["k"], cache["v"], jnp.stack(k_news),
                jnp.stack(v_news), *rows, block_tables)
        with jax.named_scope("bsa_compress"):
            new_cache["kh"] = bsa.compressed_append(
                cache["kh"], jnp.stack(sums), groups, rows[0],
                block_tables, cfg.sparse)
        pages, cells = sum(walked)
        new_cache["sel_pages"] = cache["sel_pages"] + pages
        new_cache["walk_cells"] = cache["walk_cells"] + cells
    with jax.named_scope("lm_head"):
        last = jnp.clip(rows[3] + jnp.maximum(rows[2], 1) - 1, 0, T - 1)
        x = rms_norm(x[last], params["final_norm"], cfg.norm_eps)
        x = (x.astype(jnp.float32)
             * (cfg.dim_model_base / cfg.dim)).astype(cfg.dtype)
        logits = _head_matmul(x, params["lm_head"], cfg)
    if probe:
        return logits.astype(jnp.float32), new_cache, jnp.stack(picks)
    return logits.astype(jnp.float32), new_cache
