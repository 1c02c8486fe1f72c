"""Mamba-2 (SSD) — selective state-space LM, TPU-first.

No reference counterpart (the reference ships no model code); BASELINE's
config matrix requires Mamba-2/Jamba.  The layer uses the **state-space
duality (SSD) chunked algorithm**: the sequence is split into chunks;
within a chunk the recurrence is materialized as masked matmuls (MXU
work, quadratic only in the small chunk length), and chunk-to-chunk
state is propagated with ``lax.associative_scan`` — O(log n_chunks)
depth, no Python loops, fully jittable.

Structure per layer (Mamba-2 style, scalar-per-head A):
  in_proj → [z gate | x | B | C | dt] → depthwise causal conv on (x,B,C)
  → SSD(x·dt, exp(A·dt), B, C) + D·x → ·silu(z) → out_proj

``attn_every=k`` interleaves a Llama attention block every k-th layer
(Jamba-style hybrid).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import rms_norm

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Mamba2Config:
    vocab_size: int = 50_288
    dim: int = 2560
    n_layers: int = 64
    d_state: int = 128
    expand: int = 2
    n_heads: int = 80          # head_dim = dim * expand / n_heads
    conv_kernel: int = 4
    chunk: int = 64            # SSD chunk length
    # Jamba-style hybrid: every k-th layer is attention (0 = pure SSM).
    attn_every: int = 0
    n_attn_heads: int = 20
    n_attn_kv_heads: int = 4
    rope_theta: float = 500_000.0
    max_seq_len: int = 8192
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    logits_soft_cap: Optional[float] = None
    sequence_parallel: bool = False  # not supported for SSM scan
    tie_embeddings: bool = True

    @property
    def d_inner(self) -> int:
        return self.dim * self.expand

    @property
    def head_dim(self) -> int:
        return self.d_inner // self.n_heads

    def num_params(self) -> int:
        d, di, N = self.dim, self.d_inner, self.d_state
        in_proj = d * (2 * di + 2 * N + self.n_heads)
        conv = (di + 2 * N) * self.conv_kernel
        per_layer = in_proj + conv + 3 * self.n_heads + di * d + 2 * d
        return self.n_layers * per_layer + self.vocab_size * d + d


MAMBA2_2_7B = Mamba2Config()
MAMBA2_TINY = Mamba2Config(
    vocab_size=256, dim=64, n_layers=2, d_state=16, n_heads=4,
    conv_kernel=4, chunk=8, max_seq_len=128, remat=False,
)
JAMBA_TINY = dataclasses.replace(
    MAMBA2_TINY, attn_every=2, n_attn_heads=4, n_attn_kv_heads=2,
)

CONFIGS = {"mamba2-2.7b": MAMBA2_2_7B, "tiny": MAMBA2_TINY,
           "jamba-tiny": JAMBA_TINY}


# --- params ---------------------------------------------------------------

def _mamba_layer_axes() -> Params:
    return {
        "w_in": ("layers", "embed", None),
        "conv_w": ("layers", None, None),
        "a_log": ("layers", "heads"),
        "dt_bias": ("layers", "heads"),
        "d_skip": ("layers", "heads"),
        "w_out": ("layers", None, "embed"),
        "ln": ("layers", "embed"),
        "ssm_norm": ("layers", None),
    }


def logical_axes(cfg: Mamba2Config) -> Params:
    out: Params = {
        "tok_embed": ("vocab", "embed"),
        "mamba": _mamba_layer_axes(),
        "final_norm": ("embed",),
    }
    if cfg.attn_every:
        out["attn"] = {
            "attn": {
                "wq": ("layers", "embed", "heads", "head_dim"),
                "wk": ("layers", "embed", "kv_heads", "head_dim"),
                "wv": ("layers", "embed", "kv_heads", "head_dim"),
                "wo": ("layers", "heads", "head_dim", "embed"),
            },
            "ln": ("layers", "embed"),
        }
    if not cfg.tie_embeddings:
        out["lm_head"] = ("embed", "vocab")
    return out


def _layer_kinds(cfg: Mamba2Config):
    """kinds[i] = "attn" every attn_every-th layer (1-indexed), else "ssm"."""
    return [
        "attn" if cfg.attn_every and (i + 1) % cfg.attn_every == 0 else "ssm"
        for i in range(cfg.n_layers)
    ]


def init_params(rng: jax.Array, cfg: Mamba2Config) -> Params:
    d, di, N, H = cfg.dim, cfg.d_inner, cfg.d_state, cfg.n_heads
    kinds = _layer_kinds(cfg)
    n_ssm = kinds.count("ssm")
    n_attn = kinds.count("attn")
    keys = jax.random.split(rng, 12)
    pd = cfg.param_dtype

    def norm_init(key, shape, fan_in):
        return (jax.random.normal(key, shape, pd) * (fan_in**-0.5)).astype(pd)

    proj_out = 2 * di + 2 * N + H
    params: Params = {
        "tok_embed": norm_init(keys[0], (cfg.vocab_size, d), d),
        "mamba": {
            "w_in": norm_init(keys[1], (n_ssm, d, proj_out), d),
            "conv_w": norm_init(
                keys[2], (n_ssm, di + 2 * N, cfg.conv_kernel), cfg.conv_kernel
            ),
            # A in (-1, 0): a_log ~ log-uniform; dt bias ~ softplus-inv range
            "a_log": jnp.log(
                jax.random.uniform(keys[3], (n_ssm, H), pd, 1.0, 8.0)
            ),
            "dt_bias": jnp.log(
                jnp.expm1(jax.random.uniform(keys[4], (n_ssm, H), pd,
                                             1e-3, 1e-1))
            ),
            "d_skip": jnp.ones((n_ssm, H), pd),
            "w_out": norm_init(keys[5], (n_ssm, di, d), di),
            "ln": jnp.ones((n_ssm, d), pd),
            "ssm_norm": jnp.ones((n_ssm, di), pd),
        },
        "final_norm": jnp.ones((d,), pd),
    }
    if n_attn:
        ah, akvh, hd = cfg.n_attn_heads, cfg.n_attn_kv_heads, d // cfg.n_attn_heads
        params["attn"] = {
            "attn": {
                "wq": norm_init(keys[6], (n_attn, d, ah, hd), d),
                "wk": norm_init(keys[7], (n_attn, d, akvh, hd), d),
                "wv": norm_init(keys[8], (n_attn, d, akvh, hd), d),
                "wo": norm_init(keys[9], (n_attn, ah, hd, d), ah * hd),
            },
            "ln": jnp.ones((n_attn, d), pd),
        }
    if not cfg.tie_embeddings:
        params["lm_head"] = norm_init(keys[10], (d, cfg.vocab_size), d)
    return params


# --- SSD core -------------------------------------------------------------

def _segsum(log_a: jax.Array) -> jax.Array:
    """log_a [..., T] → [..., T, T] lower-triangular cumulative log-decay:
    out[i, j] = sum_{k=j+1..i} log_a[k] for i >= j, -inf above diagonal."""
    T = log_a.shape[-1]
    cum = jnp.cumsum(log_a, axis=-1)
    diff = cum[..., :, None] - cum[..., None, :]
    i = jnp.arange(T)
    mask = i[:, None] >= i[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(
    x: jax.Array,       # [B, S, H, P]  (inputs, already scaled by dt)
    log_a: jax.Array,   # [B, S, H]     (per-step log decay = A*dt, <= 0)
    Bm: jax.Array,      # [B, S, N]     (input  projection, shared heads)
    Cm: jax.Array,      # [B, S, N]     (output projection, shared heads)
    chunk: int,
) -> jax.Array:
    """Chunked SSD: y[t] = C[t] · h[t], h[t] = a[t] h[t-1] + B[t] x[t].

    Returns y [B, S, H, P].  float32 state math, matmul-dominated.
    """
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, f"seq {S} not divisible by chunk {chunk}"
    nc = S // chunk
    f32 = jnp.float32

    xc = x.reshape(B, nc, chunk, H, P).astype(f32)
    la = log_a.reshape(B, nc, chunk, H).astype(f32)
    Bc = Bm.reshape(B, nc, chunk, N).astype(f32)
    Cc = Cm.reshape(B, nc, chunk, N).astype(f32)

    # 1) Intra-chunk (quadratic in chunk, all matmuls):
    L = jnp.exp(_segsum(la.transpose(0, 1, 3, 2)))        # [B,nc,H,c,c]
    scores = jnp.einsum("bzin,bzjn->bzij", Cc, Bc)        # [B,nc,c,c]
    y_intra = jnp.einsum("bzij,bzhij,bzjhp->bzihp",
                         scores, L, xc)                   # via masked decay

    # 2) Per-chunk final state: sum_j (decay j→end) B_j x_j^T
    total = jnp.cumsum(la, axis=2)                        # [B,nc,c,H]
    decay_to_end = jnp.exp(total[:, :, -1:, :] - total)   # [B,nc,c,H]
    states = jnp.einsum("bzjn,bzjh,bzjhp->bzhnp",
                        Bc, decay_to_end, xc)             # [B,nc,H,N,P]

    # 3) Inter-chunk recurrence over chunk states (associative scan):
    #    S_z = decay_z * S_{z-1} + states_z, decay_z = exp(sum la in chunk)
    chunk_decay = jnp.exp(total[:, :, -1, :])             # [B,nc,H]

    def combine(a, b):
        d_a, s_a = a
        d_b, s_b = b
        return d_a * d_b, s_b + d_b[..., None, None] * s_a

    _, carry = lax.associative_scan(
        combine, (chunk_decay, states), axis=1
    )                                                     # [B,nc,H,N,P]
    prev = jnp.concatenate(
        [jnp.zeros_like(carry[:, :1]), carry[:, :-1]], axis=1
    )

    # 4) Contribution of the carried-in state to each position:
    decay_in = jnp.exp(total)                             # decay start→i
    y_inter = jnp.einsum("bzin,bzih,bzhnp->bzihp", Cc, decay_in, prev)

    y = (y_intra + y_inter).reshape(B, S, H, P)
    return y


def _mamba_block(x: jax.Array, layer: Params, cfg: Mamba2Config) -> jax.Array:
    """x [B, S, D] → [B, S, D]."""
    Bsz, S, D = x.shape
    di, N, H, P = cfg.d_inner, cfg.d_state, cfg.n_heads, cfg.head_dim
    dt_f32 = jnp.float32

    proj = jnp.einsum("bsd,dk->bsk", x, layer["w_in"].astype(cfg.dtype))
    z, xin, Bm, Cm, dt = jnp.split(
        proj, [di, 2 * di, 2 * di + N, 2 * di + 2 * N], axis=-1
    )

    # Depthwise causal conv over (xin | B | C) — kernel K, silu activation.
    conv_in = jnp.concatenate([xin, Bm, Cm], axis=-1)     # [B,S,di+2N]
    K = cfg.conv_kernel
    padded = jnp.pad(conv_in, ((0, 0), (K - 1, 0), (0, 0)))
    w = layer["conv_w"].astype(cfg.dtype)                 # [di+2N, K]
    conv = sum(
        padded[:, k: k + S, :] * w[:, k] for k in range(K)
    )
    conv = jax.nn.silu(conv)
    xin, Bm, Cm = jnp.split(conv, [di, di + N], axis=-1)

    # Selective params: dt per head (softplus), A < 0 scalar per head.
    dt = jax.nn.softplus(
        dt.astype(dt_f32) + layer["dt_bias"].astype(dt_f32)
    )                                                     # [B,S,H]
    a = -jnp.exp(layer["a_log"].astype(dt_f32))           # [H]
    log_a = a * dt                                        # [B,S,H], <= 0

    xh = xin.reshape(Bsz, S, H, P)
    y = ssd_chunked(
        xh.astype(dt_f32) * dt[..., None], log_a, Bm, Cm, cfg.chunk
    )
    y = y + layer["d_skip"].astype(dt_f32)[None, None, :, None] \
        * xh.astype(dt_f32)
    y = y.reshape(Bsz, S, di).astype(cfg.dtype)
    y = rms_norm(y, layer["ssm_norm"], cfg.norm_eps)
    y = y * jax.nn.silu(z)
    return jnp.einsum("bsk,kd->bsd", y, layer["w_out"].astype(cfg.dtype))


# --- forward --------------------------------------------------------------

def _attn_layer(x, layer, cfg: Mamba2Config, sin, cos):
    from ray_tpu.models.llama import _attn_block

    acfg = dataclasses.replace(
        _ATTN_SHIM,
        dim=cfg.dim, n_heads=cfg.n_attn_heads, n_kv_heads=cfg.n_attn_kv_heads,
        dtype=cfg.dtype, logits_soft_cap=cfg.logits_soft_cap,
    )
    normed = rms_norm(x, layer["ln"], cfg.norm_eps)
    return x + _attn_block(normed, layer, acfg, sin, cos, None)[0]


def _ssm_layer(x, layer, cfg: Mamba2Config):
    return x + _mamba_block(
        rms_norm(x, layer["ln"], cfg.norm_eps), layer, cfg
    )


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: Mamba2Config,
    *,
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """tokens [B, S] → logits [B, S, V] (float32)."""
    from ray_tpu.models.llama import rope_table

    kinds = _layer_kinds(cfg)
    x = params["tok_embed"].astype(cfg.dtype)[tokens]
    sin = cos = None
    if cfg.attn_every:
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1]), tokens.shape
            )
        sin, cos = rope_table(
            dataclasses.replace(
                _ATTN_SHIM, dim=cfg.dim, n_heads=cfg.n_attn_heads,
                rope_theta=cfg.rope_theta,
            ),
            positions,
        )

    def ssm_body(carry, layer):
        fn = _ssm_layer
        if cfg.remat:
            fn = jax.checkpoint(fn, static_argnums=(2,))
        return fn(carry, layer, cfg), None

    if not cfg.attn_every:
        # Homogeneous stack: single-trace scan over stacked layer params.
        x, _ = lax.scan(ssm_body, x, params["mamba"])
    else:
        # Hybrid: unrolled loop indexing each stack (compile time grows
        # with n_layers; hybrid configs keep n_layers moderate).
        si = ai = 0
        for kind in kinds:
            if kind == "ssm":
                layer = jax.tree.map(lambda p: p[si], params["mamba"])
                x = _ssm_layer(x, layer, cfg)
                si += 1
            else:
                layer = jax.tree.map(lambda p: p[ai], params["attn"])
                x = _attn_layer(x, layer, cfg, sin, cos)
                ai += 1

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = (params["tok_embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(cfg.dtype))
    if cfg.logits_soft_cap:
        logits = cfg.logits_soft_cap * jnp.tanh(logits / cfg.logits_soft_cap)
    return logits.astype(jnp.float32)


def loss_fn(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: Mamba2Config,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    from ray_tpu.models.llama import next_token_loss

    tokens = batch["tokens"]
    logits = forward(params, tokens, cfg)
    total, ntokens = next_token_loss(logits, tokens, batch.get("loss_mask"))
    return total, {"loss": total, "ntokens": ntokens}


# Minimal config shim so llama attention blocks can be reused: only the
# fields _qkv/_attn_block/rope_table read.
from ray_tpu.models.llama import LlamaConfig as _LlamaConfig  # noqa: E402

_ATTN_SHIM = _LlamaConfig(
    vocab_size=1, dim=64, n_layers=1, n_heads=4, n_kv_heads=2, mlp_dim=1,
    max_seq_len=1, remat=False,
)
