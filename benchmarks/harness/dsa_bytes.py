"""Bytes a step of a sparse-attention (DSA) model has to move for its
indexer and for the attention over the selected positions, from the
configuration's published shapes and never from the program.

The index-key cache holds ``index_head_dim`` values a token and layer in
the cache's precision; a query has to read every cached key of its
sequence once a layer to score it, and the indexer's own weights
(``W_iq``, ``W_ik``, the heads' weights, the key norm) once a step.  The
attention then reads ``kv_lora_rank + qk_rope_head_dim`` values of each
SELECTED position (``min(position + 1, index_topk)`` a query) and no
other.  A layout that pads a row (the program's latent pool stores 576
lanes as 640) or keeps a weight in float32 moves more bytes than are
counted here, so a share of the roofline computed from this cannot pass
100% by the layout.
"""

from __future__ import annotations

from typing import Any, Dict

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def _itemsize(config: Dict[str, Any]) -> int:
    return _ITEMSIZE[config.get("torch_dtype", "bfloat16")]


def indexer_weight_bytes(config: Dict[str, Any]) -> int:
    """The indexer's weights of every layer: what a step reads once."""
    heads, dim = config["index_n_heads"], config["index_head_dim"]
    per_layer = (config["q_lora_rank"] * heads * dim
                 + config["hidden_size"] * dim
                 + config["hidden_size"] * heads + 2 * dim)
    return per_layer * config["num_hidden_layers"] * _itemsize(config)


def index_read_bytes(config: Dict[str, Any], ctx_tokens: int) -> int:
    """One decode step whose rows hold ``ctx_tokens`` cached tokens in
    all: every layer reads each one's index key once, and the indexer's
    weights once."""
    return (ctx_tokens * config["num_hidden_layers"]
            * config["index_head_dim"] * _itemsize(config)
            + indexer_weight_bytes(config))


def selected_latent_bytes(config: Dict[str, Any], sel_tokens: int) -> int:
    """One step whose queries select ``sel_tokens`` cached positions in
    all: every layer reads each one's ``c | kr`` once."""
    return (sel_tokens * config["num_hidden_layers"]
            * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            * _itemsize(config))
