"""Operations the algorithm needs, computed from a configuration's
published shapes (never from the program's own counters)."""

from __future__ import annotations


def matmul_params(c: dict) -> int:
    """Parameters that take part in a matrix multiplication for every
    token: the layers' projections and the output head.  The embedding
    table is a lookup, and the norms are vectors."""
    d = c["hidden_size"]
    hd = c.get("head_dim") or d // c["num_attention_heads"]
    q = d * c["num_attention_heads"] * hd
    kv = 2 * d * c["num_key_value_heads"] * hd
    o = c["num_attention_heads"] * hd * d
    mlp = 3 * d * c["intermediate_size"]
    head = d * c["vocab_size"]
    return c["num_hidden_layers"] * (q + kv + o + mlp) + head


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward of one token of a sequence of ``seq_len``:
    6 per matmul parameter, plus causal attention (QK^T and AV are
    2*S*d each forward, half of it under the causal mask, times three
    for forward and backward).  Recomputation is not counted."""
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    d_attn = c["num_attention_heads"] * hd
    attn = 6.0 * seq_len * d_attn * c["num_hidden_layers"]
    return 6.0 * matmul_params(c) + attn
