"""Paged decode attention kernel + llama block-table inference.

Parity targets: vLLM-style PagedAttention re-designed for TPU (no
reference counterpart — the reference's serve layer runs user torch
code; PAPERS.md ragged paged attention is the pattern source).  Kernel
checked against a dense gather reference; the llama paged pipeline
(prefill into pages → scattered decode writes → paged attention) is
checked step-by-step against the cache-free oracle (tests/oracle.py).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.ops import paged_attention as pa
from tests import oracle

# Under the Pallas interpreter an eager call of a model step compiles its
# kernels anew; the steps of one shape share these (``cfg`` is static).
_prefill_slot = jax.jit(llama.prefill_slot_paged, static_argnames=("cfg",))
_decode_slots = jax.jit(llama.decode_slots_paged, static_argnames=("cfg",))


def test_kernel_matches_reference_ragged():
    rng = np.random.default_rng(0)
    B, H, KVH, D, page, maxp = 4, 8, 4, 128, 64, 6
    P = B * maxp
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((KVH, P, page, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((KVH, P, page, D)), jnp.float32)
    # Shuffled physical pages: the table indirection must be honored.
    bt = jnp.asarray(rng.permutation(P)[: B * maxp].reshape(B, maxp),
                     jnp.int32)
    lengths = jnp.asarray([5, 64, 130, 384], jnp.int32)
    out_k = pa.paged_decode_attention(q, k, v, bt, lengths)
    out_r = pa.paged_decode_attention_reference(q, k, v, bt, lengths)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)


def test_kernel_soft_cap():
    rng = np.random.default_rng(1)
    B, H, KVH, D, page, maxp = 2, 4, 2, 128, 64, 2
    P = B * maxp
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((KVH, P, page, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((KVH, P, page, D)), jnp.float32)
    bt = jnp.asarray(np.arange(P).reshape(B, maxp), jnp.int32)
    lengths = jnp.asarray([70, 128], jnp.int32)
    out_k = pa.paged_decode_attention(q, k, v, bt, lengths, soft_cap=20.0)
    out_r = pa.paged_decode_attention_reference(q, k, v, bt, lengths,
                                                soft_cap=20.0)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)


@pytest.fixture(scope="module")
def tiny_cfg():
    return llama.LlamaConfig(
        vocab_size=211, dim=128, n_layers=2, n_heads=2, n_kv_heads=1,
        mlp_dim=256, max_seq_len=256, dtype=jnp.float32,
        param_dtype=jnp.float32,
    )


def test_llama_paged_matches_dense(tiny_cfg):
    """Prefill into pages, then scattered decode writes, logits against
    ``llama.forward`` over each slot's whole sequence."""
    cfg = tiny_cfg
    page, slots, maxp = 64, 2, 4
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    prompt_lens = [37, 64]
    bucket = 64

    paged = llama.init_paged_cache(cfg, num_pages=slots * maxp,
                                   page_size=page)
    # Slot s owns pages [s*maxp, (s+1)*maxp).
    bt = np.arange(slots * maxp, dtype=np.int32).reshape(slots, maxp)
    lengths = np.zeros((slots,), np.int32)

    seqs = []
    for s, plen in enumerate(prompt_lens):
        toks = np.zeros((bucket,), np.int32)
        toks[:plen] = rng.integers(0, cfg.vocab_size, plen)
        seqs.append(toks[:plen].tolist())
        lg_p, paged = _prefill_slot(
            params, jnp.asarray(toks), jnp.int32(plen),
            jnp.asarray(bt[s][: bucket // page]), cfg, paged)
        np.testing.assert_allclose(
            oracle.next_token_logits(params, cfg, seqs[s]),
            np.asarray(lg_p), atol=1e-4, rtol=1e-4)
        seqs[s].append(int(np.argmax(np.asarray(lg_p))))
        lengths[s] = plen

    active = jnp.ones((slots,), bool)
    for step in range(6):
        cur = np.array([seq[-1] for seq in seqs], np.int32)
        lg_p, paged, new_len = _decode_slots(
            params, jnp.asarray(cur), active, jnp.asarray(bt),
            jnp.asarray(lengths), cfg, paged)
        lg_o = np.stack([oracle.next_token_logits(params, cfg, seq)
                         for seq in seqs])
        np.testing.assert_allclose(lg_o, np.asarray(lg_p),
                                   atol=1e-3, rtol=1e-3)
        toks_o = np.argmax(lg_o, -1)
        toks_p = np.argmax(np.asarray(lg_p), -1)
        assert (toks_o == toks_p).all(), f"step {step} diverged"
        for seq, tok in zip(seqs, toks_p):
            seq.append(int(tok))
        lengths = np.asarray(new_len)


def test_llama_paged_inactive_slot_isolated(tiny_cfg):
    """An inactive slot's scatter must not corrupt pages (they may
    already belong to another request)."""
    cfg = tiny_cfg
    page, slots, maxp = 64, 2, 2
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    paged = llama.init_paged_cache(cfg, num_pages=slots * maxp,
                                   page_size=page)
    bt = np.arange(slots * maxp, dtype=np.int32).reshape(slots, maxp)
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, 64), jnp.int32)
    _, paged = _prefill_slot(
        params, toks, jnp.int32(40), jnp.asarray(bt[0][:1]), cfg, paged)
    before = np.asarray(paged["k"])
    active = jnp.asarray([False, True])
    cur = jnp.asarray([5, 7], jnp.int32)
    _, paged, new_len = _decode_slots(
        params, cur, active, jnp.asarray(bt),
        jnp.asarray([40, 0], np.int32), cfg, paged)
    after = np.asarray(paged["k"])
    # Slot 0 inactive: its pages (0..1) untouched; its length frozen.
    np.testing.assert_array_equal(before[:, :, 0:2], after[:, :, 0:2])
    assert np.asarray(new_len).tolist() == [40, 1]


def test_engine_paged_matches_dense(tiny_cfg):
    """End-to-end: the paged engine generates the oracle's greedy
    tokens."""
    from ray_tpu.serve.llm_engine import (
        EngineConfig,
        LLMEngine,
        llama_paged_adapter,
    )

    cfg = tiny_cfg
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (20, 33, 40)]
    ec = EngineConfig(max_slots=2, max_seq_len=128, decode_chunk=4,
                      max_new_tokens_default=6, min_prefill_bucket=64,
                      page_size=64)
    paged = LLMEngine(params, llama_paged_adapter(cfg), ec)
    outs_p = [paged.generate(p) for p in prompts]
    paged.shutdown()
    assert outs_p == [oracle.greedy_tokens(params, cfg, p, 6)
                      for p in prompts]


def test_engine_paged_under_page_pressure(tiny_cfg):
    """A pool smaller than full occupancy: requests wait for page frees
    and all still complete."""
    from ray_tpu.serve.llm_engine import (
        EngineConfig,
        LLMEngine,
        llama_paged_adapter,
    )

    cfg = tiny_cfg
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(6)
    # Each request needs 1 page (64-token bucket covers prompt+gen);
    # 2 pages total with 4 slots → at most 2 in flight, rest queue.
    ec = EngineConfig(max_slots=4, max_seq_len=128, decode_chunk=4,
                      max_new_tokens_default=4, min_prefill_bucket=64,
                      page_size=64, num_pages=2)
    eng = LLMEngine(params, llama_paged_adapter(cfg), ec)
    prompts = [rng.integers(0, cfg.vocab_size, 30).tolist()
               for _ in range(6)]
    streams = [eng.submit(p) for p in prompts]
    outs = [s.result(timeout_s=120) for s in streams]
    eng.shutdown()
    assert all(len(o) == 4 for o in outs)


def test_engine_paged_short_prompt(tiny_cfg):
    """Prompts smaller than a page must still write their KV (the
    prefill bucket rounds UP to a page multiple)."""
    from ray_tpu.serve.llm_engine import (
        EngineConfig,
        LLMEngine,
        llama_paged_adapter,
    )

    cfg = tiny_cfg
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, 9).tolist()  # << page 64
    ec = EngineConfig(max_slots=2, max_seq_len=128, decode_chunk=4,
                      max_new_tokens_default=6, min_prefill_bucket=16,
                      page_size=64)
    paged = LLMEngine(params, llama_paged_adapter(cfg), ec)
    got = paged.generate(prompt)
    paged.shutdown()
    assert got == oracle.greedy_tokens(params, cfg, prompt, 6)


def test_engine_paged_backlog_drains_without_new_submits(tiny_cfg):
    """A request parked for pages must be admitted when actives finish
    — even if nothing else is ever submitted."""
    from ray_tpu.serve.llm_engine import (
        EngineConfig,
        LLMEngine,
        llama_paged_adapter,
    )

    cfg = tiny_cfg
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(8)
    ec = EngineConfig(max_slots=2, max_seq_len=128, decode_chunk=4,
                      max_new_tokens_default=4, min_prefill_bucket=64,
                      page_size=64, num_pages=1)  # ONE page: strict serial
    eng = LLMEngine(params, llama_paged_adapter(cfg), ec)
    prompts = [rng.integers(0, cfg.vocab_size, 20).tolist()
               for _ in range(3)]
    streams = [eng.submit(p) for p in prompts]  # 2nd+3rd must backlog
    outs = [s.result(timeout_s=120) for s in streams]
    eng.shutdown()
    assert all(len(o) == 4 for o in outs)


def test_engine_paged_rejects_infeasible(tiny_cfg):
    from ray_tpu.serve.llm_engine import (
        EngineConfig,
        LLMEngine,
        llama_paged_adapter,
    )

    cfg = tiny_cfg
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    ec = EngineConfig(max_slots=2, max_seq_len=256, decode_chunk=4,
                      max_new_tokens_default=100, min_prefill_bucket=64,
                      page_size=64, num_pages=1)
    eng = LLMEngine(params, llama_paged_adapter(cfg), ec)
    with pytest.raises(ValueError, match="pages"):
        eng.submit(list(range(1, 100)), max_new_tokens=100)
    eng.shutdown()


def test_chunked_prefill_matches_oneshot(tiny_cfg):
    """A long prompt admitted through the incremental-prefill track
    (EngineConfig.prefill_chunk) generates the same greedy tokens as
    one-shot admission (chunked prefill à la Sarathi/vLLM)."""
    from ray_tpu.serve.llm_engine import (
        EngineConfig,
        LLMEngine,
        llama_paged_adapter,
    )

    cfg = tiny_cfg
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(9)
    long_prompt = rng.integers(0, cfg.vocab_size, 90).tolist()
    base = EngineConfig(max_slots=2, max_seq_len=128, decode_chunk=4,
                        max_new_tokens_default=6, min_prefill_bucket=32,
                        page_size=32)
    one = LLMEngine(params, llama_paged_adapter(cfg), base)
    want = one.generate(long_prompt)
    one.shutdown()
    chunked = LLMEngine(
        params, llama_paged_adapter(cfg),
        EngineConfig(max_slots=2, max_seq_len=128, decode_chunk=4,
                     max_new_tokens_default=6, min_prefill_bucket=32,
                     page_size=32, prefill_chunk=32),
    )
    got = chunked.generate(long_prompt)
    # A long and a short prompt concurrently: the long one's prefill
    # chunks interleave with the short one's decode.
    s_long = chunked.submit(long_prompt, max_new_tokens=6)
    s_short = chunked.submit(long_prompt[:8], max_new_tokens=6)
    out_long = s_long.result(timeout_s=120)
    out_short = s_short.result(timeout_s=120)
    chunked.shutdown()
    assert got == want
    assert out_long == want
    assert len(out_short) == 6


# --- int8 KV pools (per-page scales) ---------------------------------------


def test_quantized_partial_kernel_close_to_fp(tiny_cfg):
    """The int8 partial kernel's combined attention output tracks the
    full-precision kernel within int8 quantization tolerance."""
    rng = np.random.default_rng(7)
    L, B, H, KVH, D, page, maxp = 2, 3, 2, 1, 128, 64, 4
    P = B * maxp
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((L, KVH, P + 1, page, D)),
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((L, KVH, P + 1, page, D)),
                    jnp.float32)
    bt = jnp.asarray(np.arange(P, dtype=np.int32).reshape(B, maxp))
    lengths = jnp.asarray([5, 100, 256], jnp.int32)
    qk, sk = llama._quant_pages(k)
    qv, sv = llama._quant_pages(v)
    # Scale pools are page-major [L, P, KVH, 1].
    sk = sk.transpose(0, 2, 1)[..., None]
    sv = sv.transpose(0, 2, 1)[..., None]
    for layer in range(L):
        acc_f, m_f, l_f = pa.paged_decode_attention_partial(
            q, k, v, jnp.int32(layer), bt, lengths)
        acc_q, m_q, l_q = pa.paged_decode_attention_partial(
            q, qk, qv, jnp.int32(layer), bt, lengths,
            k_scales=sk, v_scales=sv)
        out_f = np.asarray(acc_f / np.asarray(l_f))
        out_q = np.asarray(acc_q / np.asarray(l_q))
        np.testing.assert_allclose(out_q, out_f, atol=0.08, rtol=0.08)


def test_quantized_append_grows_scale_and_preserves_rows():
    """Appends that exceed the page scale grow it and requantize; rows
    written under a stable scale are untouched bit-for-bit; a write at
    page offset 0 RESETS the scale (recycled pages must not inherit
    the previous occupant's)."""
    L, KVH, P, page, D, B = 1, 1, 3, 8, 128, 1
    k = jnp.zeros((L, KVH, P + 1, page, D), jnp.int8)
    v = jnp.zeros_like(k)
    ks = jnp.zeros((L, P + 1, KVH, 1), jnp.float32)
    vs = jnp.zeros_like(ks)
    rng = np.random.default_rng(11)
    r0 = jnp.asarray(rng.standard_normal((L, B, KVH, D)), jnp.float32)
    k, v, ks, vs = pa.paged_append_quantized(
        k, v, ks, vs, r0, r0, jnp.asarray([0]), jnp.asarray([0]))
    s0 = float(np.asarray(ks)[0, 0, 0, 0])
    assert s0 > 0
    row0 = np.asarray(k)[0, 0, 0, 0].copy()
    # Second row, smaller magnitude: scale must not change, row 0 must
    # be preserved exactly.
    r1 = r0 * 0.5
    k, v, ks, vs = pa.paged_append_quantized(
        k, v, ks, vs, r1, r1, jnp.asarray([0]), jnp.asarray([1]))
    assert float(np.asarray(ks)[0, 0, 0, 0]) == s0
    np.testing.assert_array_equal(np.asarray(k)[0, 0, 0, 0], row0)
    # Third row, larger: scale grows, old rows requantize consistently.
    r2 = r0 * 3.0
    k, v, ks, vs = pa.paged_append_quantized(
        k, v, ks, vs, r2, r2, jnp.asarray([0]), jnp.asarray([2]))
    s2 = float(np.asarray(ks)[0, 0, 0, 0])
    assert s2 > s0
    deq0 = np.asarray(k)[0, 0, 0, 0].astype(np.float32) * s2
    np.testing.assert_allclose(deq0, np.asarray(r0)[0, 0, 0],
                               atol=2.5 * s2)
    # Recycle: a small row written at offset 0 resets the scale DOWN
    # instead of quantizing against the stale larger one.
    tiny = r0 * 0.01
    k, v, ks, vs = pa.paged_append_quantized(
        k, v, ks, vs, tiny, tiny, jnp.asarray([0]), jnp.asarray([0]))
    s_new = float(np.asarray(ks)[0, 0, 0, 0])
    assert s_new < s2 * 0.1, (s_new, s2)
    deq = np.asarray(k)[0, 0, 0, 0].astype(np.float32) * s_new
    np.testing.assert_allclose(deq, np.asarray(tiny)[0, 0, 0],
                               atol=2.0 * s_new)


def test_llama_paged_int8_tracks_fp(tiny_cfg):
    """End-to-end int8-KV decode: greedy tokens match the fp paged path
    over several steps (tiny model, moderate lengths)."""
    cfg = dataclasses.replace(tiny_cfg, kv_int8=True)
    page, slots, maxp = 64, 2, 4
    params = llama.init_params(jax.random.PRNGKey(0), tiny_cfg)
    rng = np.random.default_rng(5)
    bt = np.arange(slots * maxp, dtype=np.int32).reshape(slots, maxp)

    fp = llama.init_paged_cache(tiny_cfg, num_pages=slots * maxp,
                                page_size=page)
    qd = llama.init_paged_cache(cfg, num_pages=slots * maxp,
                                page_size=page)
    assert qd["k"].dtype == jnp.int8 and "k_scale" in qd
    lengths = np.zeros((slots,), np.int32)
    for s, plen in enumerate([37, 64]):
        toks = np.zeros((64,), np.int32)
        toks[:plen] = rng.integers(0, cfg.vocab_size, plen)
        jt = jnp.asarray(toks)
        lg_f, fp = _prefill_slot(
            params, jt, jnp.int32(plen), jnp.asarray(bt[s][:1]),
            tiny_cfg, fp)
        lg_q, qd = _prefill_slot(
            params, jt, jnp.int32(plen), jnp.asarray(bt[s][:1]), cfg, qd)
        np.testing.assert_allclose(np.asarray(lg_q), np.asarray(lg_f),
                                   atol=1e-4, rtol=1e-4)
        lengths[s] = plen

    cur = np.asarray([3, 9], np.int32)
    active = jnp.ones((slots,), bool)
    agree = 0
    for step in range(6):
        lg_f, fp, nl_f = _decode_slots(
            params, jnp.asarray(cur), active, jnp.asarray(bt),
            jnp.asarray(lengths), tiny_cfg, fp)
        lg_q, qd, nl_q = _decode_slots(
            params, jnp.asarray(cur), active, jnp.asarray(bt),
            jnp.asarray(lengths), cfg, qd)
        tf = np.argmax(np.asarray(lg_f), -1)
        tq = np.argmax(np.asarray(lg_q), -1)
        agree += int((tf == tq).all())
        cur = tq.astype(np.int32)
        lengths = np.asarray(nl_q)
    # int8 KV is an approximation: demand agreement on the clear
    # majority of steps (tiny random models amplify quant noise far
    # beyond trained-model behavior).
    assert agree >= 4, agree
