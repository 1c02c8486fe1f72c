"""Llama-3 family — pure-JAX functional implementation, sharding-aware.

The flagship model for the Train/Serve equivalents (BASELINE.json's
north-star config).  The reference has no model code of its own — models
arrive via user torch code (ray: python/ray/train/torch/train_loop_utils.py
wraps them in DDP/FSDP); here the model is TPU-first by construction:

  * params are a plain pytree with a parallel pytree of *logical axis
    names* (ray_tpu.parallel.sharding), so any mesh layout (dp/fsdp/tp/sp)
    is a rule-table choice;
  * layers are stacked and iterated with ``lax.scan`` (one trace,
    fast XLA compiles even at 80 layers);
  * compute in bfloat16 on the MXU, reductions/softmax in float32;
  * optional per-layer rematerialization for HBM headroom.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import dot_product_attention

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    mlp_dim: int = 14_336
    max_seq_len: int = 8192
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # "dots": save matmul outputs, recompute the rest (best tokens/sec when
    # HBM allows); "full": save nothing (max memory headroom, ~12% slower)
    remat_policy: str = "dots"
    # Head-projection chunk along S for the training loss (0 = off):
    # never materializes [B, S, V] logits — the dominant activation for
    # small-dim/big-vocab models (see chunked_next_token_loss).
    loss_chunk: int = 0
    logits_soft_cap: Optional[float] = None
    tie_embeddings: bool = False
    # Shard the sequence over the mesh "sp" axis: attention becomes ring
    # attention (ray_tpu.ops.ring_attention) over the ICI ring, or
    # Ulysses all-to-all head scattering (ray_tpu.ops.ulysses) when
    # sp_backend == "ulysses".
    sequence_parallel: bool = False
    sp_backend: str = "ring"
    # Serving-side tensor parallelism: decode's paged attention runs
    # per-shard inside shard_map over the ambient mesh's "tp" axis
    # (heads are embarrassingly parallel), and the engine shards
    # params/KV over the same axis — see serve/llm_engine.py mesh=.
    tensor_parallel: bool = False
    # Multi-host shard-group serving (ambient mesh carries a dcn_tp
    # axis > 1): the per-layer decode allreduce splits into an ICI
    # psum over "tp" plus a DCN leg over "dcn_tp".  True = int8
    # quantized DCN allreduce with per-chunk absmax scales
    # (parallel/collectives.quantized_allreduce, EQuARX-style);
    # False = exact psum (the bf16-wire fallback — byte-identical
    # greedy decode on the CPU test backend).
    dcn_quantized_allreduce: bool = True
    dcn_allreduce_chunk: int = 256
    # Llama-3.1-style RoPE frequency scaling, as a hashable tuple
    # (factor, low_freq_factor, high_freq_factor, original_max_pos) —
    # None for unscaled RoPE (Llama-3.0 and earlier).
    rope_scaling: Optional[Tuple[float, float, float, int]] = None
    # INT8 KV page pools with one f32 scale per physical page
    # (ops/paged_attention.py quantized kernels): halves live-page
    # decode reads and doubles slot capacity per GB of HBM.  Serving
    # only (paged cache paths).
    kv_int8: bool = False
    # Route decode_slots_paged through the per-layer fused megakernel
    # (ops/fused_decode.py): RMSNorm -> qkv -> RoPE -> paged attention
    # -> o-proj -> RMSNorm -> MLP in ONE Pallas program per layer,
    # eliminating the per-op dispatch latency that dominates decode at
    # small batches.  Falls back to the unfused path under
    # tensor_parallel (the fused kernel is single-shard).
    fused_decode: bool = False
    # Multi-tenant LoRA multiplexing: an ops.segmented_lora.LoRAConfig
    # enables the per-row segmented adapter path in ragged_step_paged
    # (serve/adapter_pool.py holds the paged factors).  None = base
    # model only — the serving programs are structurally unchanged.
    lora: Optional[Any] = None

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def num_params(self) -> int:
        d, h = self.dim, self.head_dim
        attn = d * self.n_heads * h + 2 * d * self.n_kv_heads * h + self.n_heads * h * d
        mlp = 3 * d * self.mlp_dim
        per_layer = attn + mlp + 2 * d
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb + d


# --- canonical configs ----------------------------------------------------

LLAMA3_8B = LlamaConfig()
LLAMA3_70B = LlamaConfig(dim=8192, n_layers=80, n_heads=64, n_kv_heads=8,
                         mlp_dim=28672)
LLAMA3_1B = LlamaConfig(dim=2048, n_layers=16, n_heads=32, n_kv_heads=8,
                        mlp_dim=8192, vocab_size=128_256)
LLAMA_TINY = LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                         n_kv_heads=2, mlp_dim=128, max_seq_len=128,
                         remat=False)

CONFIGS = {
    "llama3-8b": LLAMA3_8B,
    "llama3-70b": LLAMA3_70B,
    "llama3-1b": LLAMA3_1B,
    "tiny": LLAMA_TINY,
}


# --- params ---------------------------------------------------------------

def logical_axes(cfg: LlamaConfig) -> Params:
    """Pytree of per-dimension logical axis names, mirroring init_params."""
    layer = {
        "attn": {
            "wq": ("layers", "embed", "heads", "head_dim"),
            "wk": ("layers", "embed", "kv_heads", "head_dim"),
            "wv": ("layers", "embed", "kv_heads", "head_dim"),
            "wo": ("layers", "heads", "head_dim", "embed"),
        },
        "mlp": {
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "ln_attn": ("layers", "embed"),
        "ln_mlp": ("layers", "embed"),
    }
    out: Params = {
        "tok_embed": ("vocab", "embed"),
        "layers": layer,
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = ("embed", "vocab")
    return out


def init_params(rng: jax.Array, cfg: LlamaConfig) -> Params:
    d, h, kvh, hd, m = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.mlp_dim
    L = cfg.n_layers
    keys = jax.random.split(rng, 8)
    pd = cfg.param_dtype

    def norm_init(key, shape, fan_in):
        return (jax.random.normal(key, shape, pd) * (fan_in**-0.5)).astype(pd)

    params: Params = {
        "tok_embed": norm_init(keys[0], (cfg.vocab_size, d), d),
        "layers": {
            "attn": {
                "wq": norm_init(keys[1], (L, d, h, hd), d),
                "wk": norm_init(keys[2], (L, d, kvh, hd), d),
                "wv": norm_init(keys[3], (L, d, kvh, hd), d),
                "wo": norm_init(keys[4], (L, h, hd, d), h * hd),
            },
            "mlp": {
                "w_gate": norm_init(keys[5], (L, d, m), d),
                "w_up": norm_init(keys[6], (L, d, m), d),
                "w_down": norm_init(keys[7], (L, m, d), m),
            },
            "ln_attn": jnp.ones((L, d), pd),
            "ln_mlp": jnp.ones((L, d), pd),
        },
        "final_norm": jnp.ones((d,), pd),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = norm_init(jax.random.fold_in(keys[0], 1),
                                      (d, cfg.vocab_size), d)
    return params


# --- building blocks ------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)).astype(x.dtype) * weight.astype(x.dtype)


def rope_table(cfg: LlamaConfig, positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """positions [B, S] → (sin, cos) each [B, S, head_dim//2], float32."""
    half = cfg.head_dim // 2
    freqs = cfg.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    # getattr: sibling configs (Mixtral etc.) share this table without
    # carrying the Llama-3.1 scaling field.
    if getattr(cfg, "rope_scaling", None) is not None:
        # Llama-3.1 frequency scaling (the "llama3" rope_type):
        # long wavelengths divide by `factor`, short ones stay, the
        # band between interpolates — matching transformers'
        # ROPE_INIT_FUNCTIONS["llama3"].
        factor, low_ff, high_ff, orig_max = cfg.rope_scaling
        wavelen = 2 * jnp.pi / freqs
        low_wl = orig_max / low_ff
        high_wl = orig_max / high_ff
        smooth = (orig_max / wavelen - low_ff) / (high_ff - low_ff)
        scaled = jnp.where(
            wavelen > low_wl, freqs / factor,
            jnp.where(wavelen < high_wl, freqs,
                      (1 - smooth) * freqs / factor + smooth * freqs))
        freqs = scaled
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x [B, S, H, D]; rotate pairs (x1, x2) = (x[..., :half], x[..., half:])."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    sin, cos = sin[:, :, None, :], cos[:, :, None, :]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1
    )
    return out.astype(x.dtype)


def _rotate_half_matrix(dim: int, dtype) -> jax.Array:
    """[dim, dim], a signed permutation: ``x @ P == [-x2, x1]``."""
    eye = jnp.eye(dim // 2, dtype=dtype)
    zero = jnp.zeros_like(eye)
    return jnp.block([[zero, eye], [-eye, zero]])


def _rope_product(x, sin, cos):
    """``x * [cos, cos] + [-x2, x1] * [sin, sin]`` in float32, the
    swapped halves taken through the product."""
    rot = jnp.einsum("bshd,de->bshe", x,
                     _rotate_half_matrix(x.shape[-1], x.dtype))
    sin = jnp.concatenate([sin, sin], axis=-1)[:, :, None, :]
    cos = jnp.concatenate([cos, cos], axis=-1)[:, :, None, :]
    out = x.astype(jnp.float32) * cos + rot.astype(jnp.float32) * sin
    return out.astype(x.dtype)


@jax.custom_vjp
def _rope_bf16(x, sin, cos):
    return _rope_product(x, sin, cos)


def _rope_bf16_fwd(x, sin, cos):
    return _rope_product(x, sin, cos), (sin, cos)


def _rope_bf16_bwd(tables, g):
    # the rotation's transpose is the rotation by the opposite angle
    sin, cos = tables
    return (_rope_product(g, -sin, cos), jnp.zeros_like(sin),
            jnp.zeros_like(cos))


_rope_bf16.defvjp(_rope_bf16_fwd, _rope_bf16_bwd)


_ROPE_TRACES = None


def _rope_traces():
    """Counter of RoPE traces by form (re-registered on refetch: see
    serve/llm_engine._telemetry)."""
    global _ROPE_TRACES
    from ray_tpu.util import metrics

    if _ROPE_TRACES is None:
        _ROPE_TRACES = metrics.Counter(
            "raytpu_rope_traces_total",
            "Times _qkv's RoPE was traced into a program, by form: "
            "product (the rotation as one product's epilogue, a single "
            "pass over the tensor) or concat (apply_rope's slices and "
            "concatenate: another dtype than bfloat16, or one position "
            "a row).",
            tag_keys=("form",),
        )
    else:
        metrics.registry().register(_ROPE_TRACES)
    return _ROPE_TRACES


def rope_in_one_pass(x: jax.Array, sin: jax.Array, cos: jax.Array):
    """``apply_rope``'s arithmetic (the same two products and one sum
    an element, in float32) as ONE pass over ``x`` in the compiled
    program: the halves change places through a product with a signed
    permutation (exact in bfloat16: every sum has one term), so the
    compiler fuses the rotation into that product's epilogue, where
    ``apply_rope``'s slices and ``concatenate`` along the lanes cost it
    two passes over half-filled tiles forward and a float32 copy of the
    cotangent backward (PERF.md, PR 52).  The tables are functions of
    the positions and get no cotangent.  Another dtype's product would
    round on a TPU, and a decode step's one position a row has nothing
    to pass over: both take ``apply_rope``."""
    by_product = x.dtype == jnp.bfloat16 and x.shape[1] > 1
    _rope_traces().inc(tags={"form": "product" if by_product else "concat"})
    if by_product:
        return _rope_bf16(x, sin, cos)
    return apply_rope(x, sin, cos)


def _qkv(x, layer, cfg: LlamaConfig, sin, cos):
    """Shared q/k/v projection + RoPE (used by train, prefill and decode).

    A fused serving artifact (models/quant.py fuse_for_decode) carries
    one ``wqkv`` operand instead of wq/wk/wv — one matmul instead of
    three, for the per-op-latency-bound decode regime."""
    a = layer["attn"]
    dt = cfg.dtype
    if "wqkv" in a:
        H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        B, S = x.shape[0], x.shape[1]
        qkv = jnp.einsum("bsd,dc->bsc", x, a["wqkv"].astype(dt))
        q, k, v = jnp.split(qkv, [H * hd, (H + KVH) * hd], axis=-1)
        q = q.reshape(B, S, H, hd)
        k = k.reshape(B, S, KVH, hd)
        v = v.reshape(B, S, KVH, hd)
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, a["wq"].astype(dt))
        k = jnp.einsum("bsd,dhk->bshk", x, a["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", x, a["wv"].astype(dt))
    return rope_in_one_pass(q, sin, cos), rope_in_one_pass(k, sin, cos), v


def _attn_block(x, layer, cfg: LlamaConfig, sin, cos, segment_ids,
                use_ring: bool = False):
    """Returns (out, (k, v)) — k/v for cache population during prefill.

    ``use_ring`` is a training-time choice (forward sets it from
    cfg.sequence_parallel); prefill/decode always use the local path.
    """
    q, k, v = _qkv(x, layer, cfg, sin, cos)
    if use_ring:
        if segment_ids is not None or cfg.logits_soft_cap is not None:
            raise ValueError(
                "sequence_parallel does not support segment_ids or "
                "logits_soft_cap yet — ring attention would silently "
                "ignore them"
            )
        if cfg.sp_backend == "ulysses":
            from ray_tpu.ops.ulysses import ulysses_attention

            out = ulysses_attention(q, k, v)
        elif cfg.sp_backend == "ring":
            from ray_tpu.ops.ring_attention import ring_attention

            out = ring_attention(q, k, v)
        else:
            raise ValueError(
                f"unknown sp_backend {cfg.sp_backend!r} (want 'ring' or "
                "'ulysses')"
            )
    else:
        out = dot_product_attention(q, k, v, causal=True,
                                    segment_ids=segment_ids,
                                    logits_soft_cap=cfg.logits_soft_cap)
    out = jnp.einsum("bshk,hkd->bsd", out, layer["attn"]["wo"].astype(cfg.dtype))
    return out, (k, v)


def _mlp_block(x, layer, cfg: LlamaConfig):
    m = layer["mlp"]
    dt = cfg.dtype
    if "w_gateup" in m:  # fused serving artifact (quant.fuse_for_decode)
        gu = jnp.einsum("bsd,dm->bsm", x, m["w_gateup"].astype(dt))
        gate, up = jnp.split(gu, 2, axis=-1)
    else:
        gate = jnp.einsum("bsd,dm->bsm", x, m["w_gate"].astype(dt))
        up = jnp.einsum("bsd,dm->bsm", x, m["w_up"].astype(dt))
    return jnp.einsum("bsm,md->bsd", jax.nn.silu(gate) * up,
                      m["w_down"].astype(dt))


def _layer_fn(cfg: LlamaConfig, x, layer, sin, cos, segment_ids):
    with jax.named_scope("attention"):
        h = x + _attn_block(rms_norm(x, layer["ln_attn"], cfg.norm_eps),
                            layer, cfg, sin, cos, segment_ids,
                            use_ring=cfg.sequence_parallel)[0]
    with jax.named_scope("mlp"):
        return h + _mlp_block(rms_norm(h, layer["ln_mlp"], cfg.norm_eps),
                              layer, cfg)


# --- forward --------------------------------------------------------------

def forward_hidden(
    params: Params,
    tokens: jax.Array,
    cfg: LlamaConfig,
    *,
    positions: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Backbone only: tokens [B, S] → (hidden [B, S, D], head [D, V]).
    The head projection is left to the caller so the loss can run it
    CHUNKED — materializing full [B, S, V] float32 logits is the single
    biggest activation on small models (B8·S2048·V32k f32 = 2.1 GB)."""
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    with jax.named_scope("embed"):
        sin, cos = rope_table(cfg, positions)
        x = params["tok_embed"][tokens].astype(cfg.dtype)

    if cfg.remat_policy not in ("dots", "full"):
        raise ValueError(
            f"remat_policy must be 'dots' or 'full', got {cfg.remat_policy!r}"
        )
    policy = (
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        if cfg.remat_policy == "dots" else None
    )

    def body(carry, layer):
        fn = _layer_fn
        if cfg.remat:
            fn = jax.checkpoint(fn, static_argnums=(0,), policy=policy)
        return fn(cfg, carry, layer, sin, cos, segment_ids), None

    x, _ = lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = (params["tok_embed"].T if cfg.tie_embeddings else params["lm_head"])
    return x, head


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: LlamaConfig,
    *,
    positions: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """Training/prefill forward: tokens [B, S] → logits [B, S, V] (float32)."""
    x, head = forward_hidden(params, tokens, cfg, positions=positions,
                             segment_ids=segment_ids)
    return jnp.einsum("bsd,dv->bsv", x, head.astype(cfg.dtype)).astype(jnp.float32)


def next_token_loss(
    logits: jax.Array,
    tokens: jax.Array,
    loss_mask: Optional[jax.Array] = None,
    *,
    z_loss: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """Shifted next-token masked cross-entropy, shared by all model
    families.  logits [B, S, V], tokens [B, S] → (mean_nll, ntokens)."""
    logits = logits[:, :-1]
    targets = tokens[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt_logit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - tgt_logit
    if z_loss:
        nll = nll + z_loss * logz**2
    if loss_mask is None:
        mask = jnp.ones_like(nll)
    else:
        mask = loss_mask[:, 1:].astype(nll.dtype)
    total = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return total, jnp.sum(mask)


def chunked_next_token_loss(
    x: jax.Array,
    head: jax.Array,
    tokens: jax.Array,
    loss_mask: Optional[jax.Array] = None,
    *,
    chunk: int = 512,
    z_loss: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """Cross-entropy with the head projection chunked over the sequence
    axis: at no point do full [B, S, V] logits exist — each scan step
    materializes only [B, chunk, V] and the backward rematerializes it
    (jax.checkpoint).  Chunking along S (not a flatten over B·S) keeps
    the dp/fsdp batch sharding intact under pjit."""
    x = x[:, :-1]
    targets = tokens[:, 1:]
    B, S1, D = x.shape
    mask = (jnp.ones((B, S1), jnp.float32) if loss_mask is None
            else loss_mask[:, 1:].astype(jnp.float32))
    pad = (-S1) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n_chunks = (S1 + pad) // chunk
    # [C, B, chunk, ...] so scan walks sequence chunks.
    xs = x.reshape(B, n_chunks, chunk, D).swapaxes(0, 1)
    ts = targets.reshape(B, n_chunks, chunk).swapaxes(0, 1)
    ms = mask.reshape(B, n_chunks, chunk).swapaxes(0, 1)
    hd = head.astype(x.dtype)

    def body(carry, inp):
        xi, ti, mi = inp
        logits = jnp.einsum("bkd,dv->bkv", xi, hd).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, ti[..., None], axis=-1)[..., 0]
        nll = logz - tgt
        if z_loss:
            nll = nll + z_loss * logz**2
        tot, cnt = carry
        return (tot + jnp.sum(nll * mi), cnt + jnp.sum(mi)), None

    with jax.named_scope("lm_head"):
        (tot, cnt), _ = lax.scan(
            jax.checkpoint(body), (jnp.float32(0.0), jnp.float32(0.0)),
            (xs, ts, ms),
        )
    return tot / jnp.maximum(cnt, 1.0), cnt


def loss_fn(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: LlamaConfig,
    *,
    z_loss: float = 0.0,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross-entropy. batch: tokens [B,S], optional loss_mask [B,S]."""
    tokens = batch["tokens"]
    # Run the full sequence length (keeps S block-divisible for the flash
    # kernel) and shift logits instead of inputs.
    if cfg.loss_chunk:
        x, head = forward_hidden(params, tokens, cfg,
                                 segment_ids=batch.get("segment_ids"))
        total, ntokens = chunked_next_token_loss(
            x, head, tokens, batch.get("loss_mask"),
            chunk=cfg.loss_chunk, z_loss=z_loss,
        )
    else:
        logits = forward(params, tokens, cfg,
                         segment_ids=batch.get("segment_ids"))
        total, ntokens = next_token_loss(
            logits, tokens, batch.get("loss_mask"), z_loss=z_loss
        )
    return total, {"loss": total, "ntokens": ntokens}


# --- inference (paged KV cache) -------------------------------------------

def prefill_batch_paged(
    params: Params,
    tokens: jax.Array,
    true_lens: jax.Array,
    pages_rows: jax.Array,
    cfg: LlamaConfig,
    cache: Dict[str, jax.Array],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Batched prefill into the PAGE POOL: one [K, S] forward, then one
    scatter of all K rows' page chunks (pages_rows [K, S // page]).
    Rows own disjoint pages (padding duplicates write identical data)."""
    K, S = tokens.shape
    page = cache["k"].shape[3]
    positions = jnp.arange(S)[None, :]
    sin, cos = rope_table(cfg, positions)
    x = params["tok_embed"][tokens].astype(cfg.dtype)

    def body(carry, layer):
        x = carry
        layer = _deq_layer(layer, cfg.dtype)
        normed = rms_norm(x, layer["ln_attn"], cfg.norm_eps)
        out, (k, v) = _attn_block(normed, layer, cfg, sin, cos, None)
        h = x + out
        h = h + _mlp_block(rms_norm(h, layer["ln_mlp"], cfg.norm_eps), layer, cfg)
        return h, (k, v)

    x, (k_all, v_all) = lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    last = jnp.take_along_axis(
        x, (true_lens - 1)[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]
    head = (params["tok_embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    logits = _head_matmul(last, head, cfg)

    # [L, K, S, KVH, D] → [L, KVH, K * S/page, page, D]; one scatter.
    npg = S // page
    def to_pages(a):
        a = a.transpose(0, 3, 1, 2, 4)  # [L, KVH, K, S, D]
        L, KVH = a.shape[0], a.shape[1]
        return a.reshape(L, KVH, K * npg, page, a.shape[-1])

    page_ids = pages_rows[:, :npg].reshape(K * npg)
    cache = dict(cache)
    if "k_scale" in cache:
        qk, sk = _quant_pages(to_pages(k_all))
        qv, sv = _quant_pages(to_pages(v_all))
        cache["k"] = cache["k"].at[:, :, page_ids].set(qk)
        cache["v"] = cache["v"].at[:, :, page_ids].set(qv)
        # Scales are page-major [L, P, KVH, 1]; _quant_pages returns
        # [L, KVH, pages].
        cache["k_scale"] = cache["k_scale"].at[:, page_ids].set(
            sk.transpose(0, 2, 1)[..., None])
        cache["v_scale"] = cache["v_scale"].at[:, page_ids].set(
            sv.transpose(0, 2, 1)[..., None])
    else:
        cache["k"] = cache["k"].at[:, :, page_ids].set(to_pages(k_all))
        cache["v"] = cache["v"].at[:, :, page_ids].set(to_pages(v_all))
    return logits.astype(jnp.float32), cache


# --- quantized-weight support (w8a16 serving, models/quant.py) -------------

def _is_qdict(node) -> bool:
    return isinstance(node, dict) and set(node.keys()) == {"q", "scale"}


def _deq_layer(layer, dtype):
    """Dequantize one layer's int8 leaves INSIDE the scan body — per
    layer, so XLA cannot hoist a full-model bf16 materialization out of
    the loop (which would defeat the int8 memory win: an 8B model's
    dequantized tree is 16 GB).  Identity for unquantized layers."""
    def walk(node):
        if _is_qdict(node):
            return node["q"].astype(dtype) * node["scale"].astype(dtype)
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(layer)


def _head_matmul(x, head, cfg):
    """Logits projection x [..., d] @ head [d, V].

    For an int8 head the per-OUTPUT-channel scale [1, V] is applied to
    the matmul RESULT instead of the operand: the operand is then a
    bare int8→bf16 convert, which XLA always fuses into the dot's
    operand read — a scale-multiplied operand risks materializing the
    full bf16 head (≈1 GB at 8B vocab) as a per-step temp."""
    if not _is_qdict(head):
        return jnp.einsum("...d,dv->...v", x, head.astype(cfg.dtype))
    out = jnp.einsum("...d,dv->...v", x, head["q"].astype(cfg.dtype))
    return out.astype(jnp.float32) * head["scale"][0].astype(jnp.float32)


# --- serving tensor parallelism --------------------------------------------

_SERVING_RULES = {
    # Serving meshes have only a "tp" axis: heads/kv-heads/mlp/vocab
    # shard over it; everything else replicates (no fsdp/dp in the
    # decode program — batch is the slot dimension, tiny).
    "batch": None, "seq": None, "embed": None, "vocab": "tp",
    "heads": "tp", "kv_heads": "tp", "mlp": "tp", "layers": None,
    "head_dim": None,
}


def shard_params_for_serving(params: Params, cfg: LlamaConfig, mesh,
                             axis: str = "tp") -> Params:
    """Place a (possibly int8-quantized) serving param tree on a tp
    mesh: heads/kv-heads/mlp/vocab dims shard over ``axis``; for
    quantized leaves the scale tensor inherits the weight's spec on
    its non-reduced dims (size-1 dims stay replicated).  Parity target:
    SURVEY §7 phase 7 — serving a model too big for one chip."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel.sharding import spec_for

    rules = dict(_SERVING_RULES)
    if axis != "tp":
        rules = {k: (axis if v == "tp" else v) for k, v in rules.items()}
    # Multi-host shard groups: a serving mesh carrying a dcn_tp axis
    # shards the same rule table over (dcn_tp, tp) — the mechanical
    # _DCN_EXPANSION in parallel/sharding.spec_for, driven by the
    # mesh's axis names.
    mesh_axes = frozenset(mesh.axis_names) if axis == "tp" else None
    logical = logical_axes(cfg)

    def place(axes, leaf):
        spec = spec_for(axes, rules, mesh_axes=mesh_axes)
        entries = list(spec) + [None] * (len(axes) - len(spec))
        if _is_qdict(leaf):
            q = jax.device_put(leaf["q"], NamedSharding(mesh, P(*entries)))
            s_entries = [
                e if leaf["scale"].shape[i] != 1 else None
                for i, e in enumerate(entries[:leaf["scale"].ndim])
            ]
            scale = jax.device_put(
                leaf["scale"], NamedSharding(mesh, P(*s_entries)))
            return {"q": q, "scale": scale}
        return jax.device_put(leaf, NamedSharding(mesh, P(*entries)))

    return jax.tree.map(
        place, logical, params,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(e, (str, type(None))) for e in x),
    )


def paged_cache_shardings(mesh, axis: str = "tp",
                          kv_int8: bool = False):
    """Shardings for the paged cache: k/v page pools
    [L, KVH, P, page, D] shard on KVH over ``axis`` (scale pools
    [L, KVH, P] likewise).  The engine allocates the pool UNDER these
    (jit out_shardings) — a materialize-then-reshard would put the
    whole unsharded pool on one chip first, which is exactly what tp
    serving exists to avoid."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    if axis == "tp" and mesh.shape.get("dcn_tp", 1) > 1:
        # Shard-group replica: KV heads split across the whole group
        # (cross-daemon × in-host), matching the weight expansion.
        axis = ("dcn_tp", "tp")
    sh = NamedSharding(mesh, P(None, axis, None, None, None))
    out = {"k": sh, "v": sh}
    if kv_int8:
        ssh = NamedSharding(mesh, P(None, None, axis, None))
        out["k_scale"] = ssh
        out["v_scale"] = ssh
    return out


def _serving_hybrid_mesh():
    """The ambient mesh when it carries a populated ``dcn_tp`` axis —
    i.e. this decode program belongs to a multi-host shard-group
    replica — else None (flat single-host tp, or no mesh at all)."""
    from ray_tpu.ops.ring_attention import _ambient_mesh

    try:
        mesh = _ambient_mesh()
    except Exception:
        return None
    if mesh.shape.get("dcn_tp", 1) == 1:
        return None
    return mesh


def _dcn_row_matmul(eq: str, x, w, *, x_spec, w_spec, mesh,
                    cfg: "LlamaConfig"):
    """Row-parallel matmul with the per-layer collective split of a
    shard-group replica: each device contracts its shard, the partial
    sums psum over "tp" (ICI, exact) and then allreduce over "dcn_tp"
    — int8-quantized per cfg.dcn_quantized_allreduce (the DCN leg is
    the bandwidth roofline; EQuARX-style quantization buys back ~4x),
    exact psum under the bf16 fallback.  Under GSPMD alone both legs
    would fuse into one unquantized allreduce — taking the projection
    into shard_map is what makes the DCN leg controllable."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.collectives import dcn_allreduce
    from ray_tpu.parallel.mesh import shard_map_unchecked

    def body(xs, ws):
        part = jnp.einsum(eq, xs, ws)
        part = lax.psum(part, "tp")
        return dcn_allreduce(part, "dcn_tp",
                             quantized=cfg.dcn_quantized_allreduce,
                             chunk=cfg.dcn_allreduce_chunk)

    mapped = shard_map_unchecked(body, mesh=mesh,
                                 in_specs=(x_spec, w_spec), out_specs=P())
    return mapped(x, w)


def _mlp_block_dcn(x, layer, cfg: "LlamaConfig", mesh):
    """_mlp_block with the down projection's reduce split into
    ICI psum + (quantized) DCN allreduce — the gate/up column-parallel
    matmuls need no collective and stay under GSPMD."""
    from jax.sharding import PartitionSpec as P

    m = layer["mlp"]
    dt = cfg.dtype
    if "w_gateup" in m:
        gu = jnp.einsum("bsd,dm->bsm", x, m["w_gateup"].astype(dt))
        gate, up = jnp.split(gu, 2, axis=-1)
    else:
        gate = jnp.einsum("bsd,dm->bsm", x, m["w_gate"].astype(dt))
        up = jnp.einsum("bsd,dm->bsm", x, m["w_up"].astype(dt))
    act = jax.nn.silu(gate) * up
    return _dcn_row_matmul(
        "bsm,md->bsd", act, m["w_down"].astype(dt),
        x_spec=P(None, None, ("dcn_tp", "tp")),
        w_spec=P(("dcn_tp", "tp"), None), mesh=mesh, cfg=cfg)


def decode_collective_bytes(cfg: "LlamaConfig", mesh,
                            rows: int) -> Dict[str, int]:
    """Analytic bytes-on-wire ONE decode step of ``rows`` active slots
    puts on each link class, per device: 2 allreduces of [rows, dim]
    activations per layer (attention o-proj + MLP down-proj).  The ICI
    leg is an exact psum over "tp"; the DCN leg follows the engine's
    quantization mode.  Analytic by design so the CPU emulation, the
    multichip dryrun and real DCN all report the same accounting —
    this feeds raytpu_serve_collective_bytes_total and the
    MULTICHIP/bench records."""
    from ray_tpu.parallel.collectives import allreduce_wire_bytes

    tp = mesh.shape.get("tp", 1)
    dcn = mesh.shape.get("dcn_tp", 1)
    elems = int(rows) * cfg.dim
    itemsize = jnp.dtype(cfg.dtype).itemsize
    n_reduces = cfg.n_layers * 2
    return {
        "ici": n_reduces * allreduce_wire_bytes(
            elems, axis_size=tp, quantized=False, itemsize=itemsize),
        "dcn": n_reduces * allreduce_wire_bytes(
            elems, axis_size=dcn,
            quantized=cfg.dcn_quantized_allreduce, itemsize=itemsize,
            chunk=cfg.dcn_allreduce_chunk),
    }


def serving_collective_probes(cfg: "LlamaConfig", mesh):
    """Zero-arg jitted probes, one per populated link class, each
    running a single decode-shaped collective ([1, dim] activations) —
    the engine times these at startup to observe
    raytpu_serve_collective_seconds with measured wall time (the
    per-step collective cost inside the fused decode program is not
    separately observable from the host)."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.collectives import dcn_allreduce
    from ray_tpu.parallel.mesh import shard_map_unchecked

    x = jnp.zeros((1, cfg.dim), cfg.dtype)
    probes = {}
    if mesh.shape.get("tp", 1) > 1:
        ici = jax.jit(shard_map_unchecked(
            lambda v: lax.psum(v, "tp"), mesh=mesh,
            in_specs=P(), out_specs=P()))
        probes["ici"] = (lambda f=ici: jax.block_until_ready(f(x)))
    if mesh.shape.get("dcn_tp", 1) > 1:
        dcn = jax.jit(shard_map_unchecked(
            lambda v: dcn_allreduce(
                v, "dcn_tp", quantized=cfg.dcn_quantized_allreduce,
                chunk=cfg.dcn_allreduce_chunk),
            mesh=mesh, in_specs=P(), out_specs=P()))
        probes["dcn"] = (lambda f=dcn: jax.block_until_ready(f(x)))
    return probes


# --- paged inference (block-table KV cache) --------------------------------

def _quant_pages(pages: jax.Array):
    """[..., n_pages, page, D] values → (int8 pages, [..., n_pages] f32
    per-page absmax scales) — the int8 KV pool's write-side quant."""
    a = jnp.max(jnp.abs(pages.astype(jnp.float32)), axis=(-2, -1))
    scale = jnp.maximum(a / 127.0, 1e-8)
    q = jnp.clip(jnp.round(pages.astype(jnp.float32)
                           / scale[..., None, None]), -127, 127)
    return q.astype(jnp.int8), scale


def init_paged_cache(cfg: LlamaConfig, num_pages: int,
                     page_size: int) -> Dict[str, jax.Array]:
    """Page-pool cache: k/v [L, KVH, P+1, page, D] (kv-head-major per
    layer — the paged kernel's layout, ops/paged_attention.py).  The
    LAST physical page is a scratch page: OOB sentinel writes (inactive
    slots, chunk-ladder overshoot — sentinel value == num_pages) land
    there instead of clamping onto a live page, where an aliased
    append's copy-through could race another slot's append.

    With ``cfg.kv_int8`` the pools are int8 plus one f32 scale per
    physical page per kv head (``k_scale``/``v_scale``
    [L, P+1, KVH, 1] — page-major so the append kernel's write block
    is exactly one page's scale column, a layout Mosaic tiles):
    live-page decode reads halve and a 16 GB chip holds twice the
    slots."""
    shape = (cfg.n_layers, cfg.n_kv_heads, num_pages + 1, page_size,
             cfg.head_dim)
    if cfg.kv_int8:
        sshape = (cfg.n_layers, num_pages + 1, cfg.n_kv_heads, 1)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.float32),
                "v_scale": jnp.zeros(sshape, jnp.float32)}
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype)}


def copy_page_paged(cache: Dict[str, jax.Array], src: jax.Array,
                    dst: jax.Array) -> Dict[str, jax.Array]:
    """Duplicate ONE physical page src → dst across every layer: k/v
    (page axis 2) and, for int8 pools, the per-page scales (page axis
    1).  The prefix cache's copy-on-write split — the only KV write
    that may target a shared page (the last-token re-run of an exact
    full-prompt hit) goes to the copy, never the cached original."""
    out = dict(cache)
    for key in ("k", "v"):
        out[key] = cache[key].at[:, :, dst].set(cache[key][:, :, src])
    for key in ("k_scale", "v_scale"):
        if key in cache:
            out[key] = cache[key].at[:, dst].set(cache[key][:, src])
    return out


def prefill_slot_paged(
    params: Params,
    tokens: jax.Array,
    true_len: jax.Array,
    pages: jax.Array,
    cfg: LlamaConfig,
    cache: Dict[str, jax.Array],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Prefill ONE sequence, writing k/v into its assigned PAGES.

    tokens [S] (S a multiple of page_size), pages [S // page_size]
    physical page ids.  Returns (logits at true_len-1 [V], cache)."""
    S = tokens.shape[0]
    page = cache["k"].shape[3]
    positions = jnp.arange(S)[None, :]
    sin, cos = rope_table(cfg, positions)
    x = params["tok_embed"][tokens[None, :]].astype(cfg.dtype)

    def body(carry, layer):
        x = carry
        layer = _deq_layer(layer, cfg.dtype)
        normed = rms_norm(x, layer["ln_attn"], cfg.norm_eps)
        out, (k, v) = _attn_block(normed, layer, cfg, sin, cos, None)
        h = x + out
        h = h + _mlp_block(rms_norm(h, layer["ln_mlp"], cfg.norm_eps), layer, cfg)
        return h, (k[0], v[0])

    x, (k_all, v_all) = lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    last = lax.dynamic_index_in_dim(x[0], true_len - 1, axis=0, keepdims=False)
    head = (params["tok_embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    logits = _head_matmul(last, head, cfg)

    # k_all/v_all [L, S, KVH, D] → [L, KVH, S, D], then one
    # dynamic_update_slice per page chunk.
    k_all = k_all.swapaxes(1, 2)
    v_all = v_all.swapaxes(1, 2)
    quantized = "k_scale" in cache
    ck, cv = cache["k"], cache["v"]
    if quantized:
        cks, cvs = cache["k_scale"], cache["v_scale"]
    for j in range(S // page):
        chunk_k = lax.dynamic_slice_in_dim(k_all, j * page, page, axis=2)
        chunk_v = lax.dynamic_slice_in_dim(v_all, j * page, page, axis=2)
        if quantized:
            qk, sk = _quant_pages(chunk_k[:, :, None])
            qv, sv = _quant_pages(chunk_v[:, :, None])
            ck = lax.dynamic_update_slice(ck, qk, (0, 0, pages[j], 0, 0))
            cv = lax.dynamic_update_slice(cv, qv, (0, 0, pages[j], 0, 0))
            # [L, KVH, 1] → page-major [L, 1, KVH, 1].
            cks = lax.dynamic_update_slice(
                cks, sk.transpose(0, 2, 1)[..., None],
                (0, pages[j], 0, 0))
            cvs = lax.dynamic_update_slice(
                cvs, sv.transpose(0, 2, 1)[..., None],
                (0, pages[j], 0, 0))
        else:
            ck = lax.dynamic_update_slice(
                ck, chunk_k[:, :, None], (0, 0, pages[j], 0, 0))
            cv = lax.dynamic_update_slice(
                cv, chunk_v[:, :, None], (0, 0, pages[j], 0, 0))
    if quantized:
        return logits.astype(jnp.float32), {
            "k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
    return logits.astype(jnp.float32), {"k": ck, "v": cv}


def prefill_chunk_paged(
    params: Params,
    tokens: jax.Array,
    start: jax.Array,
    chunk_lens: jax.Array,
    pages_rows: jax.Array,
    cfg: LlamaConfig,
    cache: Dict[str, jax.Array],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One CHUNK of an incremental prefill (chunked prefill: long
    prompts process in segments interleaved with decode chunks, so
    admission never stalls running streams).

    tokens [K, C] — the next C prompt tokens of K sequences, occupying
    absolute positions start[k] .. start[k]+C-1 (right-pad short
    tails; ``chunk_lens`` [K] is each row's true count).  K/V write
    into the rows' pages; attention runs against ALL cached positions
    (prior chunks + this one, causal).  Returns (logits [K, V] at each
    row's last true position — only meaningful on the final chunk —
    and the cache)."""
    if "k_scale" in cache:
        raise NotImplementedError(
            "chunked prefill with kv_int8 pools: per-token scatters "
            "would need page-scale growth on the gather path; admit "
            "long prompts via batched prefill (raise "
            "prefill_chunk_tokens) or serve with bf16 KV")
    K, C = tokens.shape
    page = cache["k"].shape[3]
    maxp = pages_rows.shape[1]
    D = cfg.head_dim
    KVH = cfg.n_kv_heads
    positions = start[:, None] + jnp.arange(C)[None, :]
    sin, cos = rope_table(cfg, positions)
    x = params["tok_embed"][tokens].astype(cfg.dtype)
    ctx = maxp * page
    group = cfg.n_heads // KVH
    key_idx = jnp.arange(ctx)[None, None, :]          # [1, 1, S_ctx]
    q_pos = positions[:, :, None]                     # [K, C, 1]
    mask = key_idx <= q_pos                           # causal over cache

    # Scatter coordinates for this chunk's K/V (pad rows write OOB).
    pid = jnp.take_along_axis(
        pages_rows, jnp.minimum(positions // page, maxp - 1), axis=1)
    in_chunk = jnp.arange(C)[None, :] < chunk_lens[:, None]
    num_pages = cache["k"].shape[2]
    pid = jnp.where(in_chunk, pid, num_pages)         # drop pad writes
    off = positions % page

    def body(carry, inputs):
        x = carry
        layer, k_pages, v_pages = inputs
        layer = _deq_layer(layer, cfg.dtype)
        normed = rms_norm(x, layer["ln_attn"], cfg.norm_eps)
        q, k, v = _qkv(normed, layer, cfg, sin, cos)  # [K, C, H/KVH, D]
        k_pages = k_pages.at[:, pid, off].set(
            k.transpose(2, 0, 1, 3), mode="drop")
        v_pages = v_pages.at[:, pid, off].set(
            v.transpose(2, 0, 1, 3), mode="drop")
        # Gather the rows' full contexts and attend (prefill chunks are
        # compute-bound matmuls — the gather path is the right shape
        # for the MXU here; the Pallas kernel covers decode).
        kk = k_pages[:, pages_rows]                   # [KVH, K, maxp, pg, D]
        vv = v_pages[:, pages_rows]
        kk = kk.transpose(1, 2, 3, 0, 4).reshape(K, ctx, KVH, D)
        vv = vv.transpose(1, 2, 3, 0, 4).reshape(K, ctx, KVH, D)
        kk = jnp.repeat(kk, group, axis=2)
        vv = jnp.repeat(vv, group, axis=2)
        s = jnp.einsum("kchd,kshd->khcs", q.astype(jnp.float32),
                       kk.astype(jnp.float32)) * (D ** -0.5)
        if cfg.logits_soft_cap is not None:
            s = cfg.logits_soft_cap * jnp.tanh(s / cfg.logits_soft_cap)
        s = jnp.where(mask[:, None, :, :], s, -1e30)
        probs = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("khcs,kshd->kchd", probs,
                         vv.astype(jnp.float32)).astype(cfg.dtype)
        out = jnp.einsum("kchd,hdE->kcE", out,
                         layer["attn"]["wo"].astype(cfg.dtype))
        h = x + out
        h = h + _mlp_block(rms_norm(h, layer["ln_mlp"], cfg.norm_eps),
                           layer, cfg)
        return h, (k_pages, v_pages)

    x, (k_new, v_new) = lax.scan(body, x, (params["layers"], cache["k"],
                                           cache["v"]))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    last = jnp.take_along_axis(
        x, jnp.maximum(chunk_lens - 1, 0)[:, None, None].astype(jnp.int32),
        axis=1)[:, 0]
    head = (params["tok_embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    logits = _head_matmul(last, head, cfg)
    return logits.astype(jnp.float32), {"k": k_new, "v": v_new}


def decode_slots_paged(
    params: Params,
    tokens: jax.Array,
    active: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    cfg: LlamaConfig,
    cache: Dict[str, jax.Array],
) -> Tuple[jax.Array, Dict[str, jax.Array], jax.Array]:
    """One decode step over all slots against the page pool.

    tokens [slots], active [slots] bool, block_tables [slots, maxp],
    lengths [slots] → (logits [slots, V], cache, new_lengths).
    The new token's k/v is scattered into page
    block_tables[b, lengths[b] // page] at offset lengths[b] % page.

    Deferred-append design: inside the layer scan the page pools are
    STRICTLY READ-ONLY — the layer-indexed pallas kernel returns flash
    partials over past tokens and the current token's self-attention
    folds in outside the kernel (combine_with_self).  Each layer's new
    k/v rides out as tiny scan ys, and ONE scatter after the scan
    appends all layers at once.  Any in-loop pool mutation made XLA
    clone the multi-GB pools every layer/step (measured 10-30x off the
    weight-bandwidth roofline); read-only loop + single post-scan
    scatter is what lets the carried pools alias in place."""
    if cfg.fused_decode and not cfg.tensor_parallel:
        return decode_slots_paged_fused(
            params, tokens, active, block_tables, lengths, cfg, cache)
    from ray_tpu.ops.paged_attention import (
        combine_with_self,
        paged_append,
        paged_append_quantized,
        paged_append_quantized_tp,
        paged_append_tp,
        paged_decode_attention_partial,
        paged_decode_attention_partial_tp,
    )

    quantized = "k_scale" in cache
    # Multi-host shard group: heads/KV shard over (dcn_tp, tp) and the
    # per-layer reduces split into ICI psum + (quantized) DCN legs.
    hybrid = _serving_hybrid_mesh() if cfg.tensor_parallel else None
    tp_axis = ("dcn_tp", "tp") if hybrid is not None else "tp"
    attn_fn = (partial(paged_decode_attention_partial_tp, axis=tp_axis)
               if cfg.tensor_parallel else paged_decode_attention_partial)
    if quantized:
        attn_fn = partial(attn_fn, k_scales=cache["k_scale"],
                          v_scales=cache["v_scale"])
        append_fn = (partial(paged_append_quantized_tp, axis=tp_axis)
                     if cfg.tensor_parallel else paged_append_quantized)
    else:
        append_fn = (partial(paged_append_tp, axis=tp_axis)
                     if cfg.tensor_parallel else paged_append)

    page = cache["k"].shape[3]
    new_len = jnp.where(active, lengths + 1, lengths)
    positions = lengths[:, None]
    sin, cos = rope_table(cfg, positions)
    # Gather BEFORE convert: converting the whole embedding per step is
    # a vocab×dim materialization (1 GB at 8B) for an 8-row lookup.
    x = params["tok_embed"][tokens[:, None]].astype(cfg.dtype)
    maxp = block_tables.shape[1]
    scratch = cache["k"].shape[2] - 1  # physical scratch page
    pids = jnp.take_along_axis(
        block_tables, jnp.minimum(lengths // page, maxp - 1)[:, None],
        axis=1)[:, 0]  # [B]
    # Inactive slots must not write to live pages (theirs may already
    # belong to another request) — route them to the scratch page.
    # (Block-table OOB sentinels == logical num_pages == scratch too.)
    pids = jnp.where(active, pids, jnp.int32(scratch))
    offs = lengths % page

    def body(carry, layer):
        x, li = carry
        layer = _deq_layer(layer, cfg.dtype)
        normed = rms_norm(x, layer["ln_attn"], cfg.norm_eps)
        q, k, v = _qkv(normed, layer, cfg, sin, cos)
        k1, v1 = k[:, 0], v[:, 0]              # [B, KVH, D]
        acc, m, l = attn_fn(
            q[:, 0], cache["k"], cache["v"], li, block_tables, lengths,
            soft_cap=cfg.logits_soft_cap,
        )
        out = combine_with_self(q[:, 0], k1, v1, acc, m, l,
                                soft_cap=cfg.logits_soft_cap)
        if hybrid is not None:
            from jax.sharding import PartitionSpec as P

            out = _dcn_row_matmul(
                "bhk,hkd->bd", out,
                layer["attn"]["wo"].astype(cfg.dtype),
                x_spec=P(None, ("dcn_tp", "tp"), None),
                w_spec=P(("dcn_tp", "tp"), None, None),
                mesh=hybrid, cfg=cfg)[:, None]
            h = x + out
            h = h + _mlp_block_dcn(
                rms_norm(h, layer["ln_mlp"], cfg.norm_eps), layer, cfg,
                hybrid)
            return (h, li + 1), (k1, v1)
        out = jnp.einsum("bhk,hkd->bd", out,
                         layer["attn"]["wo"].astype(cfg.dtype))[:, None]
        h = x + out
        h = h + _mlp_block(rms_norm(h, layer["ln_mlp"], cfg.norm_eps), layer, cfg)
        return (h, li + 1), (k1, v1)

    (x, _), (k_news, v_news) = lax.scan(
        body, (x, jnp.int32(0)), params["layers"])
    # One append for every layer, in place via the aliased pallas
    # kernel (a jnp scatter here made XLA clone the pools per step).
    if quantized:
        k_pool, v_pool, k_sc, v_sc = append_fn(
            cache["k"], cache["v"], cache["k_scale"], cache["v_scale"],
            k_news, v_news, pids, offs)
        new_cache = {"k": k_pool, "v": v_pool, "k_scale": k_sc,
                     "v_scale": v_sc}
    else:
        k_pool, v_pool = append_fn(cache["k"], cache["v"], k_news,
                                   v_news, pids, offs)
        new_cache = {"k": k_pool, "v": v_pool}
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = (params["tok_embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    logits = _head_matmul(x[:, 0], head, cfg)
    return logits.astype(jnp.float32), new_cache, new_len


def decode_slots_paged_fused(
    params: Params,
    tokens: jax.Array,
    active: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    cfg: LlamaConfig,
    cache: Dict[str, jax.Array],
) -> Tuple[jax.Array, Dict[str, jax.Array], jax.Array]:
    """decode_slots_paged with the per-layer megakernel.

    Same contract and same deferred-append design: pools are read-only
    inside the scan, every layer's k/v rides out as scan ys, one
    aliased append after the scan.  The difference is the scan body —
    the whole per-layer op graph collapses into one
    ops/fused_decode.fused_decode_layer call, so XLA sees a scan of
    single kernels instead of ~15 small ops per layer."""
    from ray_tpu.ops.fused_decode import fused_decode_layer
    from ray_tpu.ops.paged_attention import (
        paged_append,
        paged_append_quantized,
    )

    quantized = "k_scale" in cache
    page = cache["k"].shape[3]
    new_len = jnp.where(active, lengths + 1, lengths)
    sin, cos = rope_table(cfg, lengths[:, None])
    sin, cos = sin[:, 0], cos[:, 0]                      # [B, hd//2]
    x = params["tok_embed"][tokens].astype(cfg.dtype)    # [B, D]
    maxp = block_tables.shape[1]
    scratch = cache["k"].shape[2] - 1
    pids = jnp.take_along_axis(
        block_tables, jnp.minimum(lengths // page, maxp - 1)[:, None],
        axis=1)[:, 0]
    pids = jnp.where(active, pids, jnp.int32(scratch))
    offs = lengths % page

    layer_fn = partial(
        fused_decode_layer,
        eps=cfg.norm_eps, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, soft_cap=cfg.logits_soft_cap,
        k_scales=cache.get("k_scale"), v_scales=cache.get("v_scale"))

    def body(carry, layer):
        x, li = carry
        x, k1, v1 = layer_fn(x, layer, cache["k"], cache["v"], li,
                             block_tables, lengths, sin, cos)
        return (x, li + 1), (k1, v1)

    (x, _), (k_news, v_news) = lax.scan(
        body, (x, jnp.int32(0)), params["layers"])
    if quantized:
        k_pool, v_pool, k_sc, v_sc = paged_append_quantized(
            cache["k"], cache["v"], cache["k_scale"], cache["v_scale"],
            k_news, v_news, pids, offs)
        new_cache = {"k": k_pool, "v": v_pool, "k_scale": k_sc,
                     "v_scale": v_sc}
    else:
        k_pool, v_pool = paged_append(cache["k"], cache["v"], k_news,
                                      v_news, pids, offs)
        new_cache = {"k": k_pool, "v": v_pool}
    x = rms_norm(x[:, None], params["final_norm"], cfg.norm_eps)
    head = (params["tok_embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    logits = _head_matmul(x[:, 0], head, cfg)
    return logits.astype(jnp.float32), new_cache, new_len


def ragged_weight_routes(params: Params, cfg: LlamaConfig
                         ) -> Optional[Dict[str, List[str]]]:
    """Which operands the fused layer kernel of ``ragged_step_paged``
    reads in place from the stacked weights and which it has XLA slice
    (copy) per layer first, for these parameters; None where the step
    does not run that kernel.  A fused artifact (``quant.
    fuse_for_decode``) reads every matrix in place; a tree with separate
    projections pays a copy of those every step, and this is where that
    shows without a trace."""
    if not cfg.fused_decode or cfg.tensor_parallel:
        return None
    from ray_tpu.ops.ragged_paged_attention import weight_routes

    return weight_routes(params["layers"])


def ragged_step_paged(
    params: Params,
    tokens: jax.Array,       # [T] flat ragged token buffer
    tok_pos: jax.Array,      # [T] absolute position of each token
    row_slot: jax.Array,     # [R] slot of each packed row
    row_start: jax.Array,    # [R] tokens already pooled for the row
    row_len: jax.Array,      # [R] fresh tokens this step (0 = padding)
    row_off: jax.Array,      # [R] row's offset into the flat buffer
    block_tables: jax.Array,
    cfg: LlamaConfig,
    cache: Dict[str, jax.Array],
    *,
    max_row_tokens: Optional[int] = None,
    lora=None,
    logit_idx: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One unified serving step over a ragged batch mixing prefill
    chunks (row_len > 1) and decode rows (row_len == 1).

    Replaces the separate prefill_chunk_paged + decode_slots_paged
    passes: every packed token attends to its slot's pooled past plus
    the causal prefix of its own row, and all fresh k/v lands in the
    pools through ONE aliased append after the layer scan (same
    deferred-append design as decode_slots_paged — pools strictly
    read-only inside the scan).  Unlike prefill_chunk_paged this path
    supports int8 KV pools: the ragged append kernel carries the
    grow-only scale policy per multi-token page.

    Returns (logits [R, V] float32 at each row's LAST fresh token,
    new_cache).  Padding rows (row_len == 0) return garbage logits —
    callers mask by row_len.  Length bookkeeping stays host-side.

    With ``cfg.fused_decode`` (and no ``lora``) each layer is one
    ``ops/ragged_paged_attention.fused_ragged_layer`` call.  It is given
    the stacked ``params["layers"]`` whole, with the layer's index, and
    reads that layer's weights where they are stored: a fused artifact
    (``quant.fuse_for_decode``) is read once a step and never copied;
    ``ragged_weight_routes`` says what a given tree gets.  The unfused
    and LoRA bodies take their layer's slice in XLA, which fuses it
    into the einsum that reads it.

    ``lora`` is an optional ``(stacks, tok_adapter, scale)`` triple
    (ops/segmented_lora): per-token segmented LoRA deltas are added at
    every targeted projection — qkv PRE-RoPE, where the base
    projections land.  Rows whose ``tok_adapter`` index gathers the
    pool's zero scratch page see exact-zero deltas, keeping base-model
    rows byte-identical to this function with ``lora=None``.  The
    segmented path always runs unfused (like tensor_parallel, the
    fused megakernel has no per-token weight gather)."""
    if cfg.tensor_parallel:
        raise NotImplementedError(
            "ragged_step_paged does not shard over tensor_parallel "
            "yet — use the prefill/decode pipeline for tp serving")
    from ray_tpu.ops.ragged_paged_attention import (
        fused_ragged_layer,
        layer_slice,
        live_attention_cells,
        live_page_cells,
        ragged_paged_append,
        ragged_paged_append_quantized,
        ragged_paged_attention,
    )

    quantized = "k_scale" in cache
    T = tokens.shape[0]
    # The scopes below (embed, weight_slice, fused_layer / attention /
    # mlp, kv_append, lm_head) change HLO metadata only; a profiler
    # trace's device operations carry them in their op_name, which is
    # how the benchmark attributes device time after a refactor.
    with jax.named_scope("embed"):
        sin, cos = rope_table(cfg, tok_pos[None])      # [1, T, hd//2]
        sin1, cos1 = sin[0], cos[0]                    # [T, hd//2]
        x = params["tok_embed"][tokens].astype(cfg.dtype)   # [T, D]

    # The scan runs over the layer index, not over the stacked tree.
    # The fused kernel takes the tree whole and reads its layer's
    # weights where they lie; the other two bodies take their layer's
    # slice themselves (what ``lax.scan`` over the tree does, spelled
    # out, so that the slice sits under the scope ``weight_slice``) and
    # XLA fuses it into the einsum that reads it.
    stacked = params["layers"]

    # The cells of the page table that hold these rows' tokens are the
    # same in every layer: listed once, here, for all of them (one list
    # for the fused kernel, one for each call of the unfused).
    fused = cfg.fused_decode and lora is None
    maxp, page = block_tables.shape[1], cache["k"].shape[3]
    with jax.named_scope("fused_layer" if fused else "attention"):
        live_cells = (
            live_page_cells(row_start, row_len, maxp, page) if fused
            else live_attention_cells(row_start, row_len, row_off, T, maxp,
                                      page))

    if fused:
        layer_fn = partial(
            fused_ragged_layer,
            eps=cfg.norm_eps, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, soft_cap=cfg.logits_soft_cap,
            k_scales=cache.get("k_scale"),
            v_scales=cache.get("v_scale"),
            max_row_tokens=max_row_tokens, live_cells=live_cells)

        def body(carry, _):
            x, li = carry
            with jax.named_scope("fused_layer"):
                x, k1, v1 = layer_fn(
                    x, stacked, cache["k"], cache["v"], li, row_slot,
                    row_start, row_len, row_off, block_tables, sin1, cos1)
            return (x, li + 1), (k1, v1)
    elif lora is None:
        def body(carry, _):
            x, li = carry
            layer = _deq_layer(layer_slice(stacked, li), cfg.dtype)
            with jax.named_scope("attention"):
                normed = rms_norm(x, layer["ln_attn"], cfg.norm_eps)
                q, k, v = _qkv(normed[None], layer, cfg, sin, cos)
                q, k1, v1 = q[0], k[0], v[0]           # [T, H/KVH, hd]
                out = ragged_paged_attention(
                    q, k1, v1, cache["k"], cache["v"], li,
                    row_slot, row_start, row_len, row_off, block_tables,
                    soft_cap=cfg.logits_soft_cap,
                    k_scales=cache.get("k_scale"),
                    v_scales=cache.get("v_scale"),
                    max_row_tokens=max_row_tokens,
                    live_cells=live_cells)             # [T, H, hd] f32
                # Round the f32 flash output to cfg.dtype BEFORE the
                # o-proj — the same cast point as the prefill/decode
                # paths, which is what keeps greedy argmax bit-identical
                # across the pipelines under bf16.
                out = jnp.einsum("thk,hkd->td", out.astype(cfg.dtype),
                                 layer["attn"]["wo"].astype(cfg.dtype))
                h = x + out.astype(x.dtype)
            with jax.named_scope("mlp"):
                h = h + _mlp_block(rms_norm(h, layer["ln_mlp"],
                                            cfg.norm_eps)[None],
                                   layer, cfg)[0]
            return (h, li + 1), (k1, v1)
    else:
        # Segmented LoRA body: the base body's exact op sequence (same
        # einsums, same cast points) with per-token adapter deltas
        # added at each targeted projection.  A delta that gathers the
        # scratch page is exactly 0.0, and x + 0.0 is exact in every
        # IEEE dtype — null rows stay bit-identical to the base body.
        from ray_tpu.ops.segmented_lora import segmented_lora_delta
        stacks, tok_adapter, lora_scale = lora
        H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

        def body(carry, _):
            x, li = carry
            layer, stk = layer_slice((stacked, stacks), li)
            layer = _deq_layer(layer, cfg.dtype)
            dt = cfg.dtype

            def delta(name, inp):
                if name not in stk:
                    return None
                return segmented_lora_delta(
                    inp, stk[name]["a"], stk[name]["b"], tok_adapter,
                    lora_scale, dt)

            normed = rms_norm(x, layer["ln_attn"], cfg.norm_eps)
            a = layer["attn"]
            x1 = normed[None]
            dqkv = delta("qkv", normed)            # joint pre-RoPE delta
            if "wqkv" in a:
                qkv = jnp.einsum("bsd,dc->bsc", x1, a["wqkv"].astype(dt))
                if dqkv is not None:
                    qkv = qkv + dqkv[None]
                q, k, v = jnp.split(qkv, [H * hd, (H + KVH) * hd],
                                    axis=-1)
                q = q.reshape(1, T, H, hd)
                k = k.reshape(1, T, KVH, hd)
                v = v.reshape(1, T, KVH, hd)
            else:
                q = jnp.einsum("bsd,dhk->bshk", x1, a["wq"].astype(dt))
                k = jnp.einsum("bsd,dhk->bshk", x1, a["wk"].astype(dt))
                v = jnp.einsum("bsd,dhk->bshk", x1, a["wv"].astype(dt))
                if dqkv is not None:
                    dq, dk, dv = jnp.split(dqkv, [H * hd, (H + KVH) * hd],
                                           axis=-1)
                    q = q + dq.reshape(1, T, H, hd)
                    k = k + dk.reshape(1, T, KVH, hd)
                    v = v + dv.reshape(1, T, KVH, hd)
            q = apply_rope(q, sin, cos)
            k = apply_rope(k, sin, cos)
            q, k1, v1 = q[0], k[0], v[0]           # [T, H/KVH, hd]
            out = ragged_paged_attention(
                q, k1, v1, cache["k"], cache["v"], li,
                row_slot, row_start, row_len, row_off, block_tables,
                soft_cap=cfg.logits_soft_cap,
                k_scales=cache.get("k_scale"),
                v_scales=cache.get("v_scale"),
                max_row_tokens=max_row_tokens,
                live_cells=live_cells)             # [T, H, hd] f32
            attn_f = out.astype(dt)                # base body's cast point
            o = jnp.einsum("thk,hkd->td", attn_f, a["wo"].astype(dt))
            do = delta("o", attn_f.reshape(T, H * hd))
            if do is not None:
                o = o + do
            h = x + o.astype(x.dtype)
            xm = rms_norm(h, layer["ln_mlp"], cfg.norm_eps)
            m = layer["mlp"]
            xm1 = xm[None]
            if "w_gateup" in m:
                gu = jnp.einsum("bsd,dm->bsm", xm1,
                                m["w_gateup"].astype(dt))
                gate, up = jnp.split(gu, 2, axis=-1)
            else:
                gate = jnp.einsum("bsd,dm->bsm", xm1,
                                  m["w_gate"].astype(dt))
                up = jnp.einsum("bsd,dm->bsm", xm1, m["w_up"].astype(dt))
            dg = delta("gate", xm)
            du = delta("up", xm)
            if dg is not None:
                gate = gate + dg[None]
            if du is not None:
                up = up + du[None]
            act = jax.nn.silu(gate) * up
            down = jnp.einsum("bsm,md->bsd", act, m["w_down"].astype(dt))
            dd = delta("down", act[0])
            if dd is not None:
                down = down + dd[None]
            h = h + down[0]
            return (h, li + 1), (k1, v1)

    (x, _), (k_news, v_news) = lax.scan(
        body, (x, jnp.int32(0)), None,
        length=jax.tree.leaves(stacked)[0].shape[0])
    # k_news/v_news [L, T, KVH, hd] — one in-place append, all layers.
    with jax.named_scope("kv_append"):
        if quantized:
            k_pool, v_pool, k_sc, v_sc = ragged_paged_append_quantized(
                cache["k"], cache["v"], cache["k_scale"],
                cache["v_scale"], k_news, v_news, row_slot, row_start,
                row_len, row_off, block_tables,
                max_row_tokens=max_row_tokens)
            new_cache = {"k": k_pool, "v": v_pool, "k_scale": k_sc,
                         "v_scale": v_sc}
        else:
            k_pool, v_pool = ragged_paged_append(
                cache["k"], cache["v"], k_news, v_news,
                row_slot, row_start, row_len, row_off, block_tables,
                max_row_tokens=max_row_tokens)
            new_cache = {"k": k_pool, "v": v_pool}
    with jax.named_scope("lm_head"):
        # logits at each row's last fresh token
        last = jnp.clip(row_off + jnp.maximum(row_len, 1) - 1, 0, T - 1)
        head = (params["tok_embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        if logit_idx is None:
            x = rms_norm(x[last], params["final_norm"], cfg.norm_eps)
            logits = _head_matmul(x, head, cfg)
            return logits.astype(jnp.float32), new_cache
        # Speculative verify: logits at extra flat-buffer positions, in
        # ONE gather + norm + head matmul with the row-wise logits so
        # the first R rows stay bit-identical to the logit_idx=None
        # path.
        R = row_slot.shape[0]
        sel = jnp.concatenate([last, jnp.clip(logit_idx, 0, T - 1)])
        x = rms_norm(x[sel], params["final_norm"], cfg.norm_eps)
        logits = _head_matmul(x, head, cfg).astype(jnp.float32)
        return logits[:R], logits[R:], new_cache
