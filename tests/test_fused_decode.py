"""Fused per-layer decode megakernel vs the unfused paged path.

The fused kernel (ops/fused_decode.py) replaces the entire per-layer
decode op graph; these tests pin its numerics against the op-by-op
path (decode_slots_paged) in Pallas interpret mode on CPU — fp32
weights tight-tolerance, int8 weights + int8 KV pools
quantization-tolerance — and check that the deferred int8 page append
behaves identically through the fused route (same scale pools, same
rows)."""

import dataclasses

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
def tiny_cfg():
    return llama.LlamaConfig(
        vocab_size=211, dim=128, n_layers=2, n_heads=2, n_kv_heads=1,
        mlp_dim=256, max_seq_len=256, dtype=jnp.float32,
        param_dtype=jnp.float32,
    )


def _prefilled(cfg, params, prompt_lens, *, page=64, maxp=4, rng_seed=2):
    """Prefill each slot's prompt into a fresh paged cache via the
    unfused path; returns (cache, bt, lengths, cur_tokens)."""
    slots = len(prompt_lens)
    rng = np.random.default_rng(rng_seed)
    cache = llama.init_paged_cache(cfg, num_pages=slots * maxp,
                                   page_size=page)
    bt = np.arange(slots * maxp, dtype=np.int32).reshape(slots, maxp)
    lengths = np.zeros((slots,), np.int32)
    cur = np.zeros((slots,), np.int32)
    for s, plen in enumerate(prompt_lens):
        bucket = -(-plen // page) * page
        toks = np.zeros((bucket,), np.int32)
        toks[:plen] = rng.integers(0, cfg.vocab_size, plen)
        lg, cache = _prefill_slot(
            params, jnp.asarray(toks), jnp.int32(plen),
            jnp.asarray(bt[s][: bucket // page]), cfg, cache)
        lengths[s] = plen
        cur[s] = int(np.argmax(np.asarray(lg)))
    return cache, jnp.asarray(bt), lengths, cur


def test_fused_matches_unfused_fp32(tiny_cfg):
    """fp32 weights, fp32 KV: logits, greedy tokens, appended pools and
    new lengths all match the unfused path step by step."""
    cfg_u = tiny_cfg
    cfg_f = dataclasses.replace(tiny_cfg, fused_decode=True)
    params = llama.init_params(jax.random.PRNGKey(0), cfg_u)
    cache, bt, lengths, cur = _prefilled(cfg_u, params, [37, 64])
    cache_u = cache_f = cache
    active = jnp.ones((2,), bool)
    for step in range(4):
        lg_u, cache_u, nl_u = _decode_slots(
            params, jnp.asarray(cur), active, bt,
            jnp.asarray(lengths), cfg_u, cache_u)
        lg_f, cache_f, nl_f = _decode_slots(
            params, jnp.asarray(cur), active, bt,
            jnp.asarray(lengths), cfg_f, cache_f)
        np.testing.assert_allclose(np.asarray(lg_f), np.asarray(lg_u),
                                   atol=2e-3, rtol=2e-3,
                                   err_msg=f"step {step}")
        tu = np.argmax(np.asarray(lg_u), -1)
        tf = np.argmax(np.asarray(lg_f), -1)
        assert (tu == tf).all(), f"step {step} diverged"
        np.testing.assert_array_equal(np.asarray(nl_u), np.asarray(nl_f))
        # The appended rows must agree too (same deferred-append
        # contract, new k/v computed inside the kernel).
        np.testing.assert_allclose(np.asarray(cache_f["k"]),
                                   np.asarray(cache_u["k"]),
                                   atol=2e-3, rtol=2e-3)
        np.testing.assert_allclose(np.asarray(cache_f["v"]),
                                   np.asarray(cache_u["v"]),
                                   atol=2e-3, rtol=2e-3)
        cur = tf.astype(np.int32)
        lengths = np.asarray(nl_f)


def test_fused_inactive_slot_isolated(tiny_cfg):
    """Inactive slots must not write into live pages through the fused
    route (their k/v is routed to the scratch page) and their lengths
    stay frozen."""
    cfg_f = dataclasses.replace(tiny_cfg, fused_decode=True)
    params = llama.init_params(jax.random.PRNGKey(0), tiny_cfg)
    cache, bt, lengths, cur = _prefilled(tiny_cfg, params, [40, 20])
    before = np.asarray(cache["k"])
    active = jnp.asarray([False, True])
    _, cache, new_len = _decode_slots(
        params, jnp.asarray(cur), active, bt, jnp.asarray(lengths),
        cfg_f, cache)
    after = np.asarray(cache["k"])
    # Slot 0 owns pages 0..3 — untouched; its length frozen.
    np.testing.assert_array_equal(before[:, :, 0:4], after[:, :, 0:4])
    assert np.asarray(new_len).tolist() == [40, 21]


@pytest.mark.slow
def test_fused_matches_unfused_int8_weights(tiny_cfg):
    """int8 weights (fused wqkv/w_gateup serving artifacts) with fp32
    KV: both paths dequantize the same integers — the fused kernel
    applies per-output-channel scales to matmul results instead of
    dequantizing weights, which is the same map — so logits stay
    tight."""
    cfg_u = tiny_cfg
    cfg_f = dataclasses.replace(tiny_cfg, fused_decode=True)
    qparams = quant.init_quantized_llama(jax.random.PRNGKey(1), cfg_u)
    fparams = quant.fuse_for_decode(qparams, cfg_u)
    cache, bt, lengths, cur = _prefilled(cfg_u, fparams, [33, 64])
    cache_u = cache_f = cache
    active = jnp.ones((2,), bool)
    for step in range(4):
        lg_u, cache_u, nl = _decode_slots(
            fparams, jnp.asarray(cur), active, bt,
            jnp.asarray(lengths), cfg_u, cache_u)
        lg_f, cache_f, _ = _decode_slots(
            fparams, jnp.asarray(cur), active, bt,
            jnp.asarray(lengths), cfg_f, cache_f)
        np.testing.assert_allclose(np.asarray(lg_f), np.asarray(lg_u),
                                   atol=5e-3, rtol=5e-3,
                                   err_msg=f"step {step}")
        cur = np.argmax(np.asarray(lg_f), -1).astype(np.int32)
        lengths = np.asarray(nl)


@pytest.mark.slow
def test_fused_int8_kv_append_invariants(tiny_cfg):
    """int8 KV pools through the fused route: the deferred append
    produces the same quantized rows and the same per-page scale pools
    as the unfused path (both feed paged_append_quantized with the
    per-layer k/v the kernels emit), and page scales are actually
    populated (> 0) where tokens landed."""
    cfg_u = dataclasses.replace(tiny_cfg, kv_int8=True)
    cfg_f = dataclasses.replace(tiny_cfg, kv_int8=True,
                                fused_decode=True)
    params = llama.init_params(jax.random.PRNGKey(0), tiny_cfg)
    cache, bt, lengths, cur = _prefilled(cfg_u, params, [37, 64])
    cache_u = cache_f = cache
    active = jnp.ones((2,), bool)
    agree = 0
    for step in range(6):
        lg_u, cache_u, nl = _decode_slots(
            params, jnp.asarray(cur), active, bt,
            jnp.asarray(lengths), cfg_u, cache_u)
        lg_f, cache_f, _ = _decode_slots(
            params, jnp.asarray(cur), active, bt,
            jnp.asarray(lengths), cfg_f, cache_f)
        agree += int((np.argmax(np.asarray(lg_u), -1)
                      == np.argmax(np.asarray(lg_f), -1)).all())
        # Scale pools evolve identically (append sees ~equal rows; the
        # running max only moves on growth, so tiny numeric differences
        # in the new rows stay within a relative tolerance).
        np.testing.assert_allclose(np.asarray(cache_f["k_scale"]),
                                   np.asarray(cache_u["k_scale"]),
                                   rtol=2e-2, atol=1e-6)
        np.testing.assert_allclose(np.asarray(cache_f["v_scale"]),
                                   np.asarray(cache_u["v_scale"]),
                                   rtol=2e-2, atol=1e-6)
        cur = np.argmax(np.asarray(lg_f), -1).astype(np.int32)
        lengths = np.asarray(nl)
    assert agree >= 4, agree
    # Slot 0 decoded past position 37 into page 0 (offsets 37+): its
    # page scale must be live in every layer.
    ks = np.asarray(cache_f["k_scale"])
    assert (ks[:, 0, :, 0] > 0).all()


def test_engine_paged_fused_matches_unfused(tiny_cfg):
    """The serving path end-to-end with the fused kernel enabled: the
    paged engine (continuous batching, real dispatch pipeline)
    generates the same greedy tokens with fused_decode on and off —
    the adapter picks the megakernel up purely through the config
    flag, no engine changes."""
    from ray_tpu.serve.llm_engine import (
        EngineConfig,
        LLMEngine,
        llama_paged_adapter,
    )

    params = llama.init_params(jax.random.PRNGKey(0), tiny_cfg)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, tiny_cfg.vocab_size, n).tolist()
               for n in (20, 33)]
    ec = EngineConfig(max_slots=2, max_seq_len=128, decode_chunk=4,
                      max_new_tokens_default=6, min_prefill_bucket=64,
                      page_size=64)
    eng_u = LLMEngine(params, llama_paged_adapter(tiny_cfg), ec)
    outs_u = [eng_u.generate(p) for p in prompts]
    eng_u.shutdown()
    cfg_f = dataclasses.replace(tiny_cfg, fused_decode=True)
    eng_f = LLMEngine(params, llama_paged_adapter(cfg_f), ec)
    outs_f = [eng_f.generate(p) for p in prompts]
    eng_f.shutdown()
    assert outs_u == outs_f


@pytest.mark.slow
def test_fused_quantized_end_to_end(tiny_cfg):
    """The bench configuration shape: int8 weights AND int8 KV through
    the fused kernel, greedy agreement with the unfused path on a
    clear majority of steps (int8 KV noise on random tiny models)."""
    cfg_u = dataclasses.replace(tiny_cfg, kv_int8=True)
    cfg_f = dataclasses.replace(tiny_cfg, kv_int8=True,
                                fused_decode=True)
    qparams = quant.init_quantized_llama(jax.random.PRNGKey(3), cfg_u)
    fparams = quant.fuse_for_decode(qparams, cfg_u)
    cache, bt, lengths, cur = _prefilled(cfg_u, fparams, [21, 50])
    cache_u = cache_f = cache
    active = jnp.ones((2,), bool)
    agree = 0
    for step in range(6):
        lg_u, cache_u, nl = _decode_slots(
            fparams, jnp.asarray(cur), active, bt,
            jnp.asarray(lengths), cfg_u, cache_u)
        lg_f, cache_f, _ = _decode_slots(
            fparams, jnp.asarray(cur), active, bt,
            jnp.asarray(lengths), cfg_f, cache_f)
        agree += int((np.argmax(np.asarray(lg_u), -1)
                      == np.argmax(np.asarray(lg_f), -1)).all())
        cur = np.argmax(np.asarray(lg_f), -1).astype(np.int32)
        lengths = np.asarray(nl)
    assert agree >= 4, agree
