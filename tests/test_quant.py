"""Weight-only int8 quantization (w8a16 serving path).

Parity note: no reference counterpart (serve runs user torch code
there); this is the TPU-native big-model-fits-HBM play the 8B serving
artifact rides (ray_tpu/models/quant.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, quant

# Under the Pallas interpreter an eager call of a model step compiles its
# kernels anew; the steps of one shape share these (``cfg`` is static).
_prefill_slot = jax.jit(llama.prefill_slot_paged, static_argnames=("cfg",))
_decode_slots = jax.jit(llama.decode_slots_paged, static_argnames=("cfg",))


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig(
        vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        mlp_dim=64, max_seq_len=64,
    )
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_quantize_roundtrip_error_small(tiny):
    cfg, params = tiny
    q = quant.quantize_params(params)
    deq = quant.dequantize_params(q, jnp.float32)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(deq)):
        if a.ndim >= 2:
            rel = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                        - b.astype(jnp.float32)))
                        / (jnp.max(jnp.abs(a)) + 1e-9))
            assert rel < 0.02, rel


def test_norms_and_embeddings_stay_full_precision(tiny):
    cfg, params = tiny
    q = quant.quantize_params(params)
    assert q["tok_embed"].dtype == params["tok_embed"].dtype
    assert q["final_norm"].dtype == params["final_norm"].dtype
    attn = q["layers"]["attn"]
    assert attn["wq"]["q"].dtype == jnp.int8
    assert attn["wq"]["scale"].dtype == jnp.float32
    assert q["layers"]["ln_attn"].dtype == params["layers"]["ln_attn"].dtype


def test_quantized_forward_close(tiny):
    cfg, params = tiny
    deq = quant.dequantize_params(quant.quantize_params(params), cfg.dtype)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 16), np.int64).astype(np.int32))
    o1 = llama.forward(params, toks, cfg)
    o2 = llama.forward(deq, toks, cfg)
    rel = float(jnp.mean(jnp.abs(o1 - o2))
                / (jnp.mean(jnp.abs(o1)) + 1e-9))
    assert rel < 0.15, rel


def test_quantized_engine_generates(tiny):
    cfg, params = tiny
    from ray_tpu.serve.llm_engine import EngineConfig, LLMEngine

    q = quant.quantize_params(params)
    eng = LLMEngine(
        q, quant.llama_paged_adapter_quant(cfg),
        EngineConfig(max_slots=2, max_seq_len=64, decode_chunk=4,
                     max_new_tokens_default=4, min_prefill_bucket=16,
                     page_size=16),
    )
    try:
        out = eng.generate([1, 2, 3, 4, 5])
        assert len(out) == 4
        assert all(0 <= t < cfg.vocab_size for t in out)
    finally:
        eng.shutdown()


def test_quantized_bytes_counts_int8(tiny):
    cfg, params = tiny
    q = quant.quantize_params(params)
    qb = quant.quantized_bytes(q)
    fb = sum(l.size * l.dtype.itemsize
             for l in jax.tree_util.tree_leaves(params))
    # Weight matrices dominate; int8 tree must be far below the f32 one.
    assert qb < 0.45 * fb


def test_fused_decode_matches_unfused():
    """fuse_for_decode (wqkv + w_gateup) tracks the unfused quantized
    model through the serving path: same prefill logits (tight) and
    same greedy decode tokens on a tiny config."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama, quant

    cfg = llama.LlamaConfig(
        vocab_size=199, dim=128, n_layers=2, n_heads=2, n_kv_heads=1,
        mlp_dim=256, max_seq_len=256, dtype=jnp.float32,
        param_dtype=jnp.float32,
    )
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    q = quant.quantize_params(params, cast_rest=jnp.float32)
    fused = quant.fuse_for_decode(q, cfg)
    assert "wqkv" in fused["layers"]["attn"]
    assert "w_gateup" in fused["layers"]["mlp"]

    page, slots, maxp = 64, 1, 4
    rng = np.random.default_rng(1)
    toks = np.zeros((64,), np.int32)
    toks[:40] = rng.integers(0, cfg.vocab_size, 40)
    bt = np.arange(slots * maxp, dtype=np.int32).reshape(slots, maxp)

    outs = {}
    for name, p in (("unfused", q), ("fused", fused)):
        cache = llama.init_paged_cache(cfg, slots * maxp, page)
        lg, cache = _prefill_slot(
            p, jnp.asarray(toks), jnp.int32(40),
            jnp.asarray(bt[0][:1]), cfg, cache)
        lengths = np.asarray([40], np.int32)
        cur = np.asarray([int(np.argmax(np.asarray(lg)))], np.int32)
        seq = [int(cur[0])]
        for _ in range(5):
            lg, cache, nl = _decode_slots(
                p, jnp.asarray(cur), jnp.ones((slots,), bool),
                jnp.asarray(bt), jnp.asarray(lengths), cfg, cache)
            cur = np.argmax(np.asarray(lg), -1).astype(np.int32)
            seq.append(int(cur[0]))
            lengths = np.asarray(nl)
        outs[name] = (np.asarray(lg), seq)
    np.testing.assert_allclose(outs["fused"][0], outs["unfused"][0],
                               atol=0.15, rtol=0.15)
    assert outs["fused"][1] == outs["unfused"][1]
