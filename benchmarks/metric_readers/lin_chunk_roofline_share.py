"""Over the joined steps that carried prompt tokens: the least time the
chip could take for the lightning layers' chunk form over the device
time of the kernel ``lightning_chunk``.  The least time is the LARGER of
its operations at the chip's bf16 peak and its bytes at the chip's
memory rate (``sala_bytes.lin_chunk_ops`` / ``lin_chunk_bytes`` of the
published shapes; ``n_prefill``, the longest row ``scan_len`` and the
rows that are no decode row from ``llm.pack``): at a chunk of 512 the
bytes bound it (each token's q, k, v and float32 output against 4 d^2 +
2 c d operations a head)."""
from benchmarks.harness import sala_bytes, sala_spans


def _least(config, pack, chip):
    n = int(pack["n_prefill"])
    chunk_rows = max(int(pack["rows"]) - int(pack["n_decode"]), 1)
    return max(
        sala_bytes.lin_chunk_ops(config, n, int(pack["scan_len"]))
        / chip["bf16_flops"],
        sala_bytes.lin_chunk_bytes(config, n, chunk_rows)
        / chip["hbm_bytes_per_s"])


def read(run):
    return sala_spans.roofline_share(
        run, (sala_spans.CHUNK_KERNEL,), _least, prefill=True)
