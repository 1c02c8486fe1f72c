"""Bytes a step of Xing4.0 has to move, from the configuration's
published shapes and never from the program.

An expert is three matrices of ``hidden_size x moe_intermediate_size``;
a step that routes at least one token to it has to read it once, in the
weights' precision, and nothing less can compute its SwiGLU.  The latent
cache holds ``kv_lora_rank + qk_rope_head_dim`` values a token and layer
in the cache's precision; a decode row has to read every pooled token of
its sequence once a layer.  A layout that pads either (the program's
pool rounds 576 lanes up to 640) moves more bytes than are counted here,
so a share of the roofline computed from this cannot pass 100% by the
layout.
"""

from __future__ import annotations

from typing import Any, Dict

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def _itemsize(config: Dict[str, Any]) -> int:
    return _ITEMSIZE[config.get("torch_dtype", "bfloat16")]


def expert_bytes(config: Dict[str, Any]) -> int:
    """One routed expert's gate, up and down matrices."""
    return (3 * config["hidden_size"] * config["moe_intermediate_size"]
            * _itemsize(config))


def routed_layers(config: Dict[str, Any]) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def latent_bytes_per_token_layer(config: Dict[str, Any]) -> int:
    """``c | kr`` of one token in one layer."""
    return ((config["kv_lora_rank"] + config["qk_rope_head_dim"])
            * _itemsize(config))


def latent_read_bytes(config: Dict[str, Any], ctx_tokens: int) -> int:
    """One decode step whose rows hold ``ctx_tokens`` pooled tokens in
    all: every layer reads each once."""
    return (ctx_tokens * config["num_hidden_layers"]
            * latent_bytes_per_token_layer(config))
