"""LLM engine tests: KV-cache correctness vs full recompute, continuous
batching, streaming, and the serve deployment wrapper.

The reference has no inference-engine counterpart (serving is user code
inside replicas); the correctness oracle here is the model's own
training ``forward`` — greedy decoding with the slot cache must match
greedy decoding by full-prefix recompute, token for token.
"""

import threading
import time

import jax
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.serve.llm_engine import (
    CompletionStream,
    EngineConfig,
    LLMEngine,
    llama_paged_adapter,
)
from tests import oracle

CFG = llama.LlamaConfig(
    vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, max_seq_len=128, remat=False,
)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.key(0), CFG)


def greedy_reference(params, prompt, n_tokens):
    """Oracle: argmax decoding by recomputing the full prefix each step."""
    return oracle.greedy_tokens(params, CFG, prompt, n_tokens)


@pytest.fixture(scope="module")
def engine(params):
    eng = LLMEngine(
        params, llama_paged_adapter(CFG),
        EngineConfig(max_slots=4, max_seq_len=128, min_prefill_bucket=16),
    )
    yield eng
    eng.shutdown()


def test_greedy_matches_full_recompute(engine, params):
    prompt = [1, 5, 9, 2, 7]
    want = greedy_reference(params, prompt, 10)
    got = engine.generate(prompt, max_new_tokens=10, temperature=0.0)
    assert got == want


def test_bucketing_handles_long_prompts(engine, params):
    # Longer than one bucket (16) — forces the 32-bucket compile.
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 127, size=23).tolist()
    want = greedy_reference(params, prompt, 6)
    got = engine.generate(prompt, max_new_tokens=6, temperature=0.0)
    assert got == want


def test_concurrent_requests_continuous_batching(engine, params):
    prompts = [[i + 1, i + 2, i + 3] for i in range(6)]  # > max_slots
    wants = [greedy_reference(params, p, 8) for p in prompts]
    streams = [
        engine.submit(p, max_new_tokens=8, temperature=0.0) for p in prompts
    ]
    results = [s.result(timeout_s=120) for s in streams]
    assert results == wants
    for s in streams:
        m = s.metrics
        assert m["ttft_s"] is not None and m["ttft_s"] >= 0
        assert m["num_tokens"] == 8


def test_streaming_tokens_arrive_incrementally(engine):
    stream = engine.submit([3, 1, 4], max_new_tokens=5, temperature=0.0)
    seen = list(stream)
    assert len(seen) == 5
    assert stream.result(timeout_s=5) == seen


def test_sampling_respects_temperature(engine):
    # Greedy must be deterministic; temperature > 0 should eventually differ.
    a = engine.generate([2, 7, 1], max_new_tokens=8, temperature=0.0)
    b = engine.generate([2, 7, 1], max_new_tokens=8, temperature=0.0)
    assert a == b
    sampled = {
        tuple(engine.generate([2, 7, 1], max_new_tokens=8, temperature=5.0))
        for _ in range(5)
    }
    assert len(sampled) > 1


def test_max_seq_len_stops_generation(params):
    eng = LLMEngine(
        params, llama_paged_adapter(CFG),
        EngineConfig(max_slots=2, max_seq_len=32, min_prefill_bucket=16),
    )
    try:
        out = eng.generate([1] * 20, max_new_tokens=1000, temperature=0.0)
        assert len(out) == 32 - 20
    finally:
        eng.shutdown()


def test_prompt_too_long_rejected(engine):
    with pytest.raises(ValueError):
        engine.submit(list(range(1, 200)))


def test_serve_llm_deployment(params):
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm_engine import LLMServer

    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    serve.start()
    try:
        app = serve.deployment(max_ongoing_requests=8)(LLMServer).bind(
            CFG, EngineConfig(max_slots=4, max_seq_len=128,
                              min_prefill_bucket=16),
            lambda: params,
        )
        handle = serve.run(app, name="llm", route_prefix=None)
        want = greedy_reference(params, [1, 2, 3], 5)
        out = handle.remote(
            {"tokens": [1, 2, 3], "max_new_tokens": 5}
        ).result(timeout_s=120)
        assert out["tokens"] == want
        assert out["metrics"]["ttft_s"] >= 0
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_llm_server_default_adapter_is_paged(params):
    """``LLMServer`` with no ``adapter_factory`` serves through pages:
    the engine reports its page pool, and the tokens are the cache-free
    oracle's."""
    from ray_tpu.serve.llm_engine import LLMServer

    server = LLMServer(
        CFG, EngineConfig(max_slots=2, max_seq_len=128, page_size=16,
                          ragged_batching=True),
        lambda: params)
    try:
        assert server.engine.adapter.ragged_step is not None
        assert server.stats()["kv_pages_free"] == 2 * (128 // 16)
        out = server({"tokens": [1, 5, 9, 2, 7], "max_new_tokens": 6})
        assert out["tokens"] == greedy_reference(params, [1, 5, 9, 2, 7], 6)
    finally:
        server.engine.shutdown()


def test_drain_preempts_with_resumable_continuation(params):
    """drain(): short grace, then eviction with a PreemptedError whose
    continuation (prompt + generated prefix) resumes on a second engine
    to the exact uninterrupted token sequence, and new submissions are
    bounced while draining."""
    import dataclasses as _dc
    import time as _time

    from ray_tpu.core.exceptions import PreemptedError

    base = llama_paged_adapter(CFG)

    def slow_decode(*a, **k):
        # decode_slots is traced under jit: the sleep must ride a
        # callback to fire per step at run time, not once at trace time.
        jax.debug.callback(lambda: _time.sleep(0.01), ordered=True)
        return base.decode_slots(*a, **k)

    slow = _dc.replace(base, decode_slots=slow_decode)
    # decode_chunk=1 keeps the delivered prefix small at eviction, so
    # the resume re-prefill stays inside the 16-token bucket; 12 new
    # tokens bounds the uninterrupted run the same way.
    ecfg = EngineConfig(max_slots=2, max_seq_len=128, min_prefill_bucket=16,
                        decode_chunk=1)
    eng = LLMEngine(params, slow, ecfg)
    eng2 = LLMEngine(params, llama_paged_adapter(CFG), ecfg)
    try:
        want = eng2.generate([1, 2, 3], max_new_tokens=12, temperature=0.0)
        stream = eng.submit([1, 2, 3], max_new_tokens=12, temperature=0.0)
        it = iter(stream)
        got = [next(it)]  # decoding is underway
        n = eng.drain(grace_s=0.05)
        assert eng.draining
        assert n >= 1
        cont = None
        try:
            for tok in it:
                got.append(tok)
        except PreemptedError as e:
            cont = e.continuation
        assert cont is not None
        # Delivered prefix == generated prefix: nothing in flight lost.
        assert cont["tokens"] == got
        assert cont["prompt"] == [1, 2, 3]
        # Draining engines bounce new work with an empty continuation.
        with pytest.raises(PreemptedError):
            eng.submit([4, 5], max_new_tokens=4)
        # One re-prefill of prompt+prefix on a fresh engine continues
        # the exact greedy sequence.
        rest = eng2.generate(
            cont["prompt"] + cont["tokens"],
            max_new_tokens=12 - len(got), temperature=0.0,
        )
        assert got + rest == want
    finally:
        eng.shutdown()
        eng2.shutdown()


def test_drain_idle_engine_is_immediate(params):
    eng = LLMEngine(
        params, llama_paged_adapter(CFG),
        EngineConfig(max_slots=2, max_seq_len=128, min_prefill_bucket=16),
    )
    try:
        t0 = time.monotonic()
        assert eng.drain(grace_s=30.0) == 0
        assert time.monotonic() - t0 < 5.0  # no grace wait when idle
    finally:
        eng.shutdown()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_engine_crash_fails_clients_fast(params):
    """An engine whose device loop raises must FAIL waiting clients
    (and reject new submits) — never hang them (the loop-crash path in
    LLMEngine._loop; the loop deliberately re-raises after failing
    clients so the crash is visible in logs — hence the filtered
    thread-exception warning)."""
    from ray_tpu.serve.llm_engine import (
        LLMEngine,
        PagedEngineAdapter,
        llama_paged_adapter,
    )

    cfg = CFG
    good = llama_paged_adapter(cfg)

    def boom(*a, **k):
        raise RuntimeError("injected device failure")

    bad = PagedEngineAdapter(
        init_cache=good.init_cache,
        prefill_slot=boom,
        decode_slots=boom,
        prefill_batch=boom,
    )
    eng = LLMEngine(params, bad, EngineConfig(
        max_slots=2, max_seq_len=64, decode_chunk=4,
        max_new_tokens_default=4, min_prefill_bucket=16, page_size=16))
    try:
        with pytest.raises(RuntimeError, match="engine loop crashed"):
            eng.generate([1, 2, 3])
        # The engine is dead: new submissions fail fast, not hang.
        with pytest.raises(RuntimeError, match="stopped"):
            eng.submit([4, 5])
    finally:
        eng.shutdown()
