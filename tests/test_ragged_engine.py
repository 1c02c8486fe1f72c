"""Ragged-batching engine mode: one unified device step packs decode
rows and prefill chunks into a single token-budgeted ragged batch
(EngineConfig.ragged_batching; ops/ragged_paged_attention.py).

Correctness oracle is the model's own ``forward`` (full-prefix
recompute), in fp32 so greedy argmax is exact across program
boundaries — bf16 greedy equality between DIFFERENT jitted programs is
not a contract (XLA keeps excess precision under fusion, and tiny-model
bf16 logit ties then round differently; both roundings are valid).

The no-stall test is the PR's acceptance teeth: a long prompt admitted
through prefill_chunk rides the same ragged steps as in-flight decode
rows (decode packs FIRST, so prompt tokens can never displace it), and
the PR-2 stall telemetry watermark must stay clean.
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.serve.llm_engine import (
    EngineConfig,
    LLMEngine,
    llama_paged_adapter,
    ragged_step_shapes,
)
from tests import oracle

pytestmark = pytest.mark.long_file(71)

CFG = llama.LlamaConfig(
    vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, max_seq_len=128, remat=False, dtype=jnp.float32,
    param_dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.key(0), CFG)


def greedy_reference(params, prompt, n_tokens):
    return oracle.greedy_tokens(params, CFG, prompt, n_tokens)


def _engine(params, **kw):
    cfg = dict(max_slots=4, max_seq_len=128, min_prefill_bucket=16,
               page_size=16, ragged_batching=True, token_budget=36)
    cfg.update(kw)
    return LLMEngine(params, llama_paged_adapter(CFG), EngineConfig(**cfg))


def _phase_totals():
    from ray_tpu.serve.llm_engine import _telemetry

    out = {}
    for _name, tags, value, _kind in _telemetry()["step_tokens"]._samples():
        out[dict(tags).get("phase")] = value
    return out


def test_ragged_greedy_matches_oracle(params):
    eng = _engine(params)
    try:
        prompts = [[i + 1, i + 2, i + 3] for i in range(6)]  # > max_slots
        wants = [greedy_reference(params, p, 6) for p in prompts]
        streams = [eng.submit(p, max_new_tokens=6, temperature=0.0)
                   for p in prompts]
        assert [s.result(timeout_s=120) for s in streams] == wants
        for s in streams:
            assert s.metrics["ttft_s"] is not None
            assert s.metrics["num_tokens"] == 6
    finally:
        eng.shutdown()


def test_ragged_chunked_prefill_matches_oracle(params):
    """Prompts longer than the chunk arrive over several ragged steps
    (mid-prompt chunks produce no token) and must still decode exactly."""
    eng = _engine(params, prefill_chunk=16)
    try:
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 127, size=n).tolist()
                   for n in (40, 3, 23)]
        wants = [greedy_reference(params, p, 6) for p in prompts]
        streams = [eng.submit(p, max_new_tokens=6, temperature=0.0)
                   for p in prompts]
        assert [s.result(timeout_s=120) for s in streams] == wants
    finally:
        eng.shutdown()


def test_long_prefill_never_stalls_decode(params):
    """The acceptance criterion: while a 96-token prompt trickles in
    via prefill_chunk, an in-flight decode stream keeps emitting every
    step — decode rows pack FIRST, so the prompt's chunks ride the
    decode steps instead of displacing them."""
    rng = np.random.default_rng(1)
    eng = _engine(params, prefill_chunk=16)
    try:
        # Both shapes of the step compile before anything is timed (a
        # chunk takes the budget's, the lone decode tail the small one):
        # a compile beside a live stream is no stall of the scheduler's.
        # Two steps at the budget: the program's first call is the only
        # one whose cache is not yet a result of its own, and jit's fast
        # path keys on that (a dozen ms of host work at the second).
        # A decode stream rides beside those chunks, as the timed one
        # will: the unfused step's attention is two calls, and the CPU
        # backend pays a dozen ms the first time a call's body runs at a
        # shape (the one-token call's, at the budget, only so).
        beside = eng.submit([2, 4], max_new_tokens=12, temperature=0.0)
        next(iter(beside))
        eng.generate(list(range(1, 81)), max_new_tokens=3)
        beside.result(timeout_s=120)
        steps0 = eng.stats()["steps"]
        short = eng.submit([1, 5, 9], max_new_tokens=24, temperature=0.0)
        # Let the short stream reach steady-state decode first.
        it = iter(short)
        next(it)
        long_prompt = rng.integers(1, 127, size=96).tolist()
        longs = eng.submit(long_prompt, max_new_tokens=4, temperature=0.0)
        got_short = short.result(timeout_s=120)
        got_long = longs.result(timeout_s=120)
        assert got_short == greedy_reference(params, [1, 5, 9], 24)
        assert got_long == greedy_reference(params, long_prompt, 4)
        # The runs genuinely overlapped on the device…
        assert longs._req.first_token_at < short._req.finished_at
        # …and the long prompt's 6 chunks consumed (almost) no steps of
        # their own: the short stream alone needs 24 (prefill + 23
        # decode rows).  A scheduler that parked decode behind the
        # prefill would serialize all 6 chunk steps on top (≥ 33).
        assert eng.stats()["steps"] - steps0 <= 28
        # The decode stream never gapped by more than one step: its
        # worst inter-token latency stays at step scale, nowhere near a
        # monolithic 96-token prefill program.
        assert short._req.max_itl_s < 1.0
        # PR-2 stall telemetry: no ragged step ballooned past the
        # stall factor — chunking bounds every step by token_budget.
        assert eng.stats()["stall_events"] == 0
    finally:
        eng.shutdown()


# -- the step's two compiled shapes -----------------------------------------

def _record_packs(eng, monkeypatch):
    """(program name, counts, length of the step's token array) of every
    step the engine packs from here on."""
    pack, packs = eng._pack_ragged_step, []

    def recording():
        step = pack()
        if step is not None:
            name, _fn, args, _parts, _finishing, counts = step
            packs.append((name, counts, len(args[2])))     # host_toks
        return step

    monkeypatch.setattr(eng, "_pack_ragged_step", recording)
    return packs


@pytest.mark.parametrize("budget,slots,shapes", [
    (80, 16, (16, 80)), (320, 64, (64, 320)),
    (524, 12, (16, 524)),       # 12 slots round up to 16 positions
    (36, 4, (8, 36)), (40, 33, (40,)), (13, 12, (13,))])
def test_ragged_step_shapes(budget, slots, shapes):
    assert ragged_step_shapes(budget, slots) == shapes


def test_two_shapes_serve_one_run(params):
    """A run that mixes chunk steps and decode steps goes through both
    compiled shapes of the one program, says so, and decodes exactly."""
    eng = _engine(params, prefill_chunk=16)
    try:
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, 127, size=n).tolist()
                   for n in (40, 3, 23, 9)]
        wants = [greedy_reference(params, p, 8) for p in prompts]
        streams = [eng.submit(p, max_new_tokens=8, temperature=0.0)
                   for p in prompts]
        assert [s.result(timeout_s=120) for s in streams] == wants
        stats = eng.stats()
    finally:
        eng.shutdown()
    by_shape = stats["steps_by_shape"]
    assert set(by_shape) == {8, 36}
    assert by_shape[8] > 0 and by_shape[36] > 0
    assert sum(by_shape.values()) == stats["steps"]
    from ray_tpu.util import metrics

    text = metrics.export_prometheus()
    for shape in (8, 36):
        assert f'raytpu_serve_steps_total{{shape="{shape}"' in text


def test_step_takes_the_smallest_shape_that_holds_its_tokens(
        params, monkeypatch):
    """Decode rows beside a prompt tail that fits ``round8(max_slots)``
    positions take the small shape; one token over takes the budget's."""
    eng = _engine(params)
    try:
        packs = _record_packs(eng, monkeypatch)
        long = eng.submit([1, 5, 9], max_new_tokens=100, temperature=0.0)
        next(iter(long))            # decoding from here on
        for n in (7, 8):
            prompt = list(range(2, 2 + n))
            assert eng.generate(prompt, max_new_tokens=2) == \
                greedy_reference(params, prompt, 2)
        long.cancel()
    finally:
        eng.shutdown()
    shape_of = {(c["n_decode"], c["n_prefill"]): (c["shape"], T)
                for _name, c, T in packs}
    assert shape_of[(0, 3)] == (8, 8)       # the first prompt alone
    assert shape_of[(1, 0)] == (8, 8)       # a lone decode row
    assert shape_of[(1, 7)] == (8, 8)       # 1 + 7 tokens fit 8 positions
    assert shape_of[(1, 8)] == (36, 36)     # one over
    for name, c, T in packs:
        assert name == "serve.ragged" and c["budget"] == 36
        assert c["shape"] == T == (
            8 if c["n_decode"] + c["n_prefill"] <= 8 else 36)


def test_verify_rows_keep_their_program_at_the_budget(params, monkeypatch):
    """A step with a speculative verify row runs ``serve.ragged_spec``,
    compiled for the budget alone, as before; the plain decode steps of
    the same engine take the small shape."""
    eng = _engine(params, spec_decode=True)
    try:
        packs = _record_packs(eng, monkeypatch)
        assert eng.generate([1, 5, 9], max_new_tokens=24) == \
            greedy_reference(params, [1, 5, 9], 24)
        assert eng.stats()["spec"]["rounds"] > 0
    finally:
        eng.shutdown()
    spec = [(name, c, T) for name, c, T in packs if c["n_spec"]]
    assert spec and len(spec) < len(packs)
    for name, c, T in packs:
        if c["n_spec"]:
            assert (name, c["shape"], T) == ("serve.ragged_spec", 36, 36)
        else:
            assert (name, c["shape"], T) == ("serve.ragged", 8, 8)


def test_ragged_step_token_phase_attribution(params):
    """Per-phase token accounting: each ragged step attributes its
    packed tokens to prefill vs decode, so goodput regressions are
    attributable.  Prefill counts every prompt token exactly once;
    decode counts every post-first generated token."""
    before = _phase_totals()
    eng = _engine(params, prefill_chunk=16)
    try:
        prompts = [[1, 5, 9, 2, 7], list(range(1, 41))]
        streams = [eng.submit(p, max_new_tokens=5, temperature=0.0)
                   for p in prompts]
        for s in streams:
            assert len(s.result(timeout_s=120)) == 5
    finally:
        eng.shutdown()
    after = _phase_totals()
    d_prefill = after.get("prefill", 0) - before.get("prefill", 0)
    d_decode = after.get("decode", 0) - before.get("decode", 0)
    assert d_prefill == sum(len(p) for p in prompts)
    # first token of each request comes off its final prefill chunk
    assert d_decode == sum(5 - 1 for _ in prompts)

    # The family is pinned in the exposition contract.
    import importlib.util
    import pathlib

    from ray_tpu.util import metrics

    path = (pathlib.Path(__file__).resolve().parent.parent
            / "scripts" / "check_metrics.py")
    spec = importlib.util.spec_from_file_location("check_metrics", path)
    cm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cm)
    assert cm.check_exposition(
        metrics.export_prometheus(),
        require=["raytpu_serve_step_tokens_total"]) == []


def test_ragged_unlocks_int8_kv_with_chunked_prefill(params):
    """kv_int8 + prefill_chunk is rejected on the legacy path (chunk
    boundaries re-quantize mid-prompt) but supported ragged: the append
    kernel's grow-only per-page scales make chunk boundaries bit-stable."""
    cfg = llama.LlamaConfig(
        vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        mlp_dim=64, max_seq_len=128, remat=False, dtype=jnp.float32,
        param_dtype=jnp.float32, kv_int8=True)
    with pytest.raises(ValueError, match="ragged_batching"):
        LLMEngine(params, llama_paged_adapter(cfg), EngineConfig(
            max_slots=2, max_seq_len=128, page_size=16, prefill_chunk=16))
    eng = LLMEngine(params, llama_paged_adapter(cfg), EngineConfig(
        max_slots=2, max_seq_len=128, page_size=16, prefill_chunk=16,
        ragged_batching=True))
    try:
        rng = np.random.default_rng(2)
        prompt = rng.integers(1, 127, size=40).tolist()
        out = eng.generate(prompt, max_new_tokens=5, temperature=0.0)
        assert len(out) == 5
    finally:
        eng.shutdown()


def test_ragged_requires_ragged_step_and_sane_budget(params):
    with pytest.raises(ValueError, match="ragged"):
        LLMEngine(params, dataclasses.replace(
            llama_paged_adapter(CFG), ragged_step=None), EngineConfig(
            max_slots=2, max_seq_len=128, ragged_batching=True))
    with pytest.raises(ValueError, match="token_budget"):
        LLMEngine(params, llama_paged_adapter(CFG), EngineConfig(
            max_slots=4, max_seq_len=128, page_size=16,
            ragged_batching=True, token_budget=4))


def test_ragged_streaming_and_temperature(params):
    """Sampling still flows through the same ragged step (temps ride
    the dispatch), and streamed tokens arrive incrementally."""
    eng = _engine(params)
    try:
        stream = eng.submit([3, 1, 4], max_new_tokens=5, temperature=0.0)
        seen = []
        t0 = time.monotonic()
        for tok in stream:
            seen.append(tok)
            assert time.monotonic() - t0 < 120
        assert seen == greedy_reference(params, [3, 1, 4], 5)
        hot = eng.generate([3, 1, 4], max_new_tokens=16, temperature=1.5)
        assert len(hot) == 16
    finally:
        eng.shutdown()


@pytest.mark.parametrize("artifact,sliced", [
    ("fused_int8", ["ln_attn", "ln_mlp"]),
    ("separate", ["ln_attn", "ln_mlp", "w_gateup", "wqkv"]),
    ("unfused_step", None)])
def test_engine_says_how_the_layer_kernel_reads_its_weights(
        params, artifact, sliced):
    """An artifact that makes the fused layer kernel fall back to
    copying a layer's weights out of the stack every step shows in
    ``stats()`` and in the flight recorder, without a trace."""
    import dataclasses

    from ray_tpu.models import quant
    from ray_tpu.util import flight_recorder

    cfg = dataclasses.replace(CFG, fused_decode=artifact != "unfused_step")
    p = params
    if artifact == "fused_int8":
        p = quant.fuse_for_decode(
            quant.init_quantized_llama(jax.random.key(0), cfg), cfg)
    flight_recorder.clear()
    eng = LLMEngine(p, llama_paged_adapter(cfg), EngineConfig(
        max_slots=4, max_seq_len=128, min_prefill_bucket=16, page_size=16,
        ragged_batching=True, token_budget=36))
    try:
        routes = eng.stats().get("weight_routes")
        events = [e for e in flight_recorder.snapshot()["driver"]
                  if e["kind"] == "ragged_weight_routes"]
        if sliced is None:
            assert routes is None and events == []
            return
        assert routes["sliced"] == sliced
        assert {"wo", "w_down"} <= set(routes["in_place"])
        assert len(events) == 1 and events[0]["sliced"] == sliced
        assert events[0]["in_place"] == routes["in_place"]
        assert len(eng.submit([1, 2, 3], max_new_tokens=3,
                              temperature=0.0).result(timeout_s=120)) == 3
    finally:
        eng.shutdown()


def test_fetch_thread_hands_back_a_step_when_it_has_run(monkeypatch):
    """The fetch thread takes the oldest queued step and, with it, only
    the later ones whose arrays are ready: a step's tokens leave when
    the step has run, not when the newest step queued behind it has
    (tokens in bursts of the pipeline's depth), and a fetcher that fell
    behind still catches up in one ``device_get``."""
    import queue
    import threading
    import types

    class Toks:
        def __init__(self, seq, ready):
            self.seq, self.ready = seq, ready

        def is_ready(self):
            return self.ready

    batches = []

    def device_get(payloads):
        batches.append([jax.tree.leaves(p)[0].seq for p in payloads])
        return jax.tree.map(lambda a: np.asarray([a.seq]), payloads)

    monkeypatch.setattr(jax, "device_get", device_get)
    eng = types.SimpleNamespace(_fetchq=queue.Queue(), _fetched=queue.Queue(),
                                _stopped=threading.Event())
    ready = {1: True, 2: True, 3: False, 4: False, 5: True, 6: True}
    for seq in sorted(ready):
        payload = Toks(seq, ready[seq])
        eng._fetchq.put(("ragged", (payload, payload) if seq == 2
                         else payload, 1, [], seq))
    fetcher = threading.Thread(target=LLMEngine._fetch_loop, args=(eng,))
    fetcher.start()
    got = [eng._fetched.get(timeout=30) for _ in ready]
    eng._fetchq.put(None)
    fetcher.join(30)
    assert not fetcher.is_alive()
    # 3 is not ready: it is waited for as the oldest, alone with what is
    # ready behind it; 4 likewise, with 5 and 6
    assert batches == [[1, 2], [3], [4, 5, 6]]
    assert [entry[4] for entry, _toks in got] == sorted(ready)
    assert isinstance(got[1][1], tuple)     # a tuple payload stays one
