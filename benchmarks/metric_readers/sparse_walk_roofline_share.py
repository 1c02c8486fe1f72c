"""Over the joined steps that carried no prompt token: the least time
the chip's memory could take to read the pages the rows selected and
their compressed keys (``sala_bytes.walk_read_bytes`` of the published
shapes; the pages are ``llm.pack``'s ``grid_cells`` less a self cell a
row and KV head, exact for rows of one token and equal to the device's
own counter ``sel_pages`` there, ``tests/test_minicpm_sala.py``;
``ctx_tokens`` from ``llm.pack``) over the device time under
``sparse_attn`` and its kernel ``block_sparse_walk``.  Memory bounds it."""
from benchmarks.harness import sala_bytes, sala_spans


def _least(config, pack, chip):
    pages = (int(pack["grid_cells"])
             - config["num_key_value_heads"] * int(pack["rows"]))
    return sala_bytes.walk_read_bytes(
        config, pages, int(pack["ctx_tokens"])) / chip["hbm_bytes_per_s"]


def read(run):
    return sala_spans.roofline_share(run, sala_spans.WALK, _least,
                                     prefill=False)
