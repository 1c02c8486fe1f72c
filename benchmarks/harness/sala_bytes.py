"""Bytes and operations a step of a lightning / block-sparse hybrid has
to move and do, from the configuration's published shapes and never from
the program.

**Lightning.**  One sequence in one lightning layer holds a float32
matrix ``[d, d]`` a head (``lightning_nh`` heads of ``lightning_head_
dim``).  A decode token reads and writes it once; nothing less can do
the update, the state being the sequence's whole past.  The chunk form,
for ``n`` prompt tokens in ``r`` rows of at most ``c``: per token and
head ``q S`` (2 d^2 operations) and ``k^T v`` into the state (2 d^2),
and inside a row the scores and their products with ``v`` over the
causal half of ``c`` keys (2 c d); it moves each row's state once in
and once out and each token's q, k, v in the configuration's precision
and its output in float32.  Multiply-adds count two operations.

**Block-sparse.**  A page of one KV head is ``block_size`` tokens of k
and of v in the cache's precision (32,768 B at the published sizes); a
decode row reads the pages it selected and, to score them, one
compressed key a ``kernel_stride`` cached tokens and KV head (counted in
the cache's precision: the program keeps them in float32 and so moves
more than is counted).  A layout that pads or a kernel that reads a page
twice moves more bytes than are counted here, so a share of the roofline
computed from this cannot pass 100% by the layout.
"""

from __future__ import annotations

from typing import Any, Dict

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
_SPARSE = {"kernel_stride": 16, "block_size": 64}


def _itemsize(config: Dict[str, Any]) -> int:
    return _ITEMSIZE[config.get("torch_dtype", "bfloat16")]


def layers(config: Dict[str, Any], kind: str) -> int:
    """Layers of ``kind`` held (``lightning-attn`` or ``minicpm4``)."""
    return list(config["mixer_types"]).count(kind)


def lin_state_bytes_per_row_layer(config: Dict[str, Any]) -> int:
    d = config["lightning_head_dim"]
    return config["lightning_nh"] * d * d * 4


def lin_state_update_bytes(config: Dict[str, Any], rows: int) -> int:
    """One decode step over ``rows`` sequences: every lightning layer
    reads and writes each row's state once."""
    return (rows * layers(config, "lightning-attn") * 2
            * lin_state_bytes_per_row_layer(config))


def lin_chunk_ops(config: Dict[str, Any], n_prefill: int,
                  chunk_len: int) -> int:
    """Operations of the chunk form for ``n_prefill`` prompt tokens in
    rows of at most ``chunk_len``, all lightning layers."""
    H, d = config["lightning_nh"], config["lightning_head_dim"]
    return (layers(config, "lightning-attn") * H * n_prefill
            * (4 * d * d + 2 * chunk_len * d))


def lin_chunk_bytes(config: Dict[str, Any], n_prefill: int,
                    chunk_rows: int) -> int:
    """Bytes of the chunk form: ``chunk_rows`` states in and out, and
    each token's q, k, v in and output (float32) out."""
    H, d = config["lightning_nh"], config["lightning_head_dim"]
    per_layer = (chunk_rows * 2 * lin_state_bytes_per_row_layer(config)
                 + n_prefill * H * d * (3 * _itemsize(config) + 4))
    return layers(config, "lightning-attn") * per_layer


def page_bytes(config: Dict[str, Any]) -> int:
    """k and v of one page of one KV head."""
    sc = dict(_SPARSE, **config.get("sparse_config", {}))
    return sc["block_size"] * config["head_dim"] * 2 * _itemsize(config)


def walk_read_bytes(config: Dict[str, Any], pages: int,
                    ctx_tokens: int) -> int:
    """One decode step whose rows read ``pages`` (page, KV head) pairs in
    ONE sparse layer and hold ``ctx_tokens`` cached tokens in all: every
    sparse layer reads those pages and the rows' compressed keys."""
    sc = dict(_SPARSE, **config.get("sparse_config", {}))
    compressed = (ctx_tokens // sc["kernel_stride"]
                  * config["num_key_value_heads"] * config["head_dim"]
                  * _itemsize(config))
    return layers(config, "minicpm4") * (pages * page_bytes(config)
                                         + compressed)
