"""Bytes and operations a power-retention layer (degree 2) has to move
and do, from the configuration's published shapes and never from the
program.

One sequence in one layer holds, per KV head, the state ``S [D, d]`` and
the normaliser ``z [D]`` in float32, with ``D = d (d + 1) / 2`` the
deduplicated degree-2 features of a head of ``d``: a layout that pads
``D`` moves more bytes than are counted here, so a share of the
roofline computed from this cannot pass 100% by the layout.  A decode
token reads and writes both once; nothing less can do the update, the
state being the sequence's whole past.

The chunk form, for ``n`` prompt tokens in rows of at most ``c``: every
token's ``phi(q)`` against the carried ``S`` and ``z`` (``H`` query
heads), every token's ``phi(k)`` into them (``KVH`` heads), and inside a
row the scores and their products with ``v`` over the causal half of
``c`` keys.  Multiply-adds count two operations.
"""

from __future__ import annotations

from typing import Any, Dict


def _shapes(config: Dict[str, Any]):
    d = config.get("head_dim") or (config["hidden_size"]
                                   // config["num_attention_heads"])
    return (config["num_hidden_layers"], config["num_attention_heads"],
            config["num_key_value_heads"], d, d * (d + 1) // 2)


def state_bytes_per_row_layer(config: Dict[str, Any]) -> int:
    """``S`` and ``z`` of one sequence in one layer, float32."""
    _L, _H, KVH, d, D = _shapes(config)
    return KVH * (D * d + D) * 4


def state_update_bytes(config: Dict[str, Any], rows: int) -> int:
    """One decode step over ``rows`` sequences: every layer reads and
    writes each row's state once."""
    L = _shapes(config)[0]
    return rows * L * 2 * state_bytes_per_row_layer(config)


def chunk_ops(config: Dict[str, Any], n_prefill: int, chunk_len: int) -> int:
    """Operations of the chunk form for ``n_prefill`` prompt tokens in
    rows of at most ``chunk_len``, all layers."""
    L, H, KVH, d, D = _shapes(config)
    carried = 2 * (H + KVH) * D * (d + 1)
    inside = 2 * H * chunk_len * d          # scores and a @ v, causal half
    return L * n_prefill * (carried + inside)
