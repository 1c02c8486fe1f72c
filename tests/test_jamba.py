"""Jamba through the ragged step at toy widths, float32, on the CPU:
the program against the plain reference on logits, the recurrent state's
life in the cache (chunks, packed rows, a reused slot, padding rows),
and the engine's continuous batching and refusals with
``jamba_paged_adapter``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import reference_jamba
from ray_tpu.models import jamba
from ray_tpu.ops import ssm_scan as ss
from ray_tpu.ops.ragged_paged_attention import pack_ragged_batch
from ray_tpu.serve.llm_engine import (
    EngineConfig,
    LLMEngine,
    jamba_paged_adapter,
)

CFG = jamba.JambaConfig(
    vocab_size=97, dim=64, n_layers=4, n_heads=4, n_kv_heads=1, head_dim=16,
    mlp_dim=96, attn_layer_period=3, attn_layer_offset=1, dt_rank=8,
    dtype=jnp.float32, param_dtype=jnp.float32)
PAGE, SLOTS, MAXP, BUDGET = 8, 4, 8, 48
TABLE = np.arange(SLOTS * MAXP, dtype=np.int32).reshape(SLOTS, MAXP)


def _hf(cfg):
    return dict(hidden_size=cfg.dim, num_hidden_layers=cfg.n_layers,
                num_attention_heads=cfg.n_heads,
                num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                attn_layer_period=cfg.attn_layer_period,
                attn_layer_offset=cfg.attn_layer_offset,
                mamba_d_state=cfg.d_state, mamba_dt_rank=cfg.dt_rank,
                mamba_d_conv=cfg.d_conv, rms_norm_eps=cfg.norm_eps)


def _params(cfg, seed=0):
    """Random weights with the norms, D and A_log moved off their
    initial ones, so that a dropped weight shows."""
    params = jamba.init_params(jax.random.key(seed), cfg)
    keys = iter(jax.random.split(jax.random.key(seed + 1), 16))

    def jitter(a):
        return a * (1 + 0.2 * jax.random.normal(next(keys), a.shape))

    for name in ("ln_in", "ln_ff", "final_norm"):
        params[name] = jitter(params[name])
    for name in ("D", "dt_norm", "b_norm", "c_norm", "A_log"):
        params["mamba"][name] = jitter(params["mamba"][name])
    return params


def _reference(cfg, params, toks):
    with jax.default_matmul_precision("highest"):
        hf = _hf(cfg)
        return np.asarray(reference_jamba.forward(
            reference_jamba.from_program_tree(params, hf),
            jnp.asarray(toks, jnp.int32), hf))


def _stepper(cfg):
    return jax.jit(lambda p, ht, pos, rs, r0, rl, ro, cache:
                   jamba.ragged_step(p, ht, pos, rs, r0, rl, ro, TABLE,
                                     cfg, cache))


def _run(step, params, cache, rows):
    """One step over ``rows`` [{slot, start, tokens}]: (logits of each
    row, cache)."""
    ht, _m, _s, pos, rs, r0, rl, ro = pack_ragged_batch(rows, BUDGET, SLOTS)
    with jax.default_matmul_precision("highest"):
        logits, cache = step(params, ht, pos, rs, r0, rl, ro, cache)
    return np.asarray(logits[:len(rows)]), cache


def _feed(step, params, cache, toks, chunks, slot):
    """A sequence through one slot in ``chunks``: logits at each
    chunk's last token, by position."""
    got, pos = {}, 0
    for n in chunks:
        logits, cache = _run(step, params, cache, [
            {"slot": slot, "start": pos, "tokens": toks[pos:pos + n]}])
        pos += n
        got[pos - 1] = logits[0]
    return got, cache


@pytest.fixture(scope="module")
def model():
    params = _params(CFG)
    toks = np.random.default_rng(0).integers(1, 97, 40).tolist()
    return params, toks, _reference(CFG, params, toks), _stepper(CFG)


def _fresh():
    return jamba.init_cache(CFG, SLOTS * MAXP, PAGE, SLOTS)


@pytest.mark.parametrize("chunks", [[40], [13, 14, 13], [1] * 40],
                         ids=["whole", "three_chunks", "token_by_token"])
def test_ragged_step_matches_reference(model, chunks):
    params, toks, want, step = model
    got, _ = _feed(step, params, _fresh(), toks, chunks, slot=2)
    scale = np.max(np.abs(want))
    for pos, logits in got.items():
        assert np.max(np.abs(logits - want[pos])) / scale < 5e-6
    # and the three ways of cutting agree with each other at the end
    whole, _ = _feed(step, params, _fresh(), toks, [40], slot=2)
    assert np.max(np.abs(got[39] - whole[39])) / scale < 5e-6


def test_two_rows_packed_match_each_alone(model):
    params, toks, want, step = model
    other = np.random.default_rng(1).integers(1, 97, 25).tolist()
    want_other = _reference(CFG, params, other)
    cache = _fresh()
    # step 1: both prompts' first chunks; step 2: the rest of one beside
    # a decode row of the other
    l1, cache = _run(step, params, cache, [
        {"slot": 3, "start": 0, "tokens": toks[:17]},
        {"slot": 0, "start": 0, "tokens": other[:24]}])
    l2, cache = _run(step, params, cache, [
        {"slot": 0, "start": 24, "tokens": other[24:25]},
        {"slot": 3, "start": 17, "tokens": toks[17:40]}])
    for got, ref in ((l1[0], want[16]), (l1[1], want_other[23]),
                     (l2[0], want_other[24]), (l2[1], want[39])):
        assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 5e-6


def test_released_slot_taken_by_new_request_matches_fresh_cache(model):
    params, toks, want, step = model
    first = np.random.default_rng(2).integers(1, 97, 30).tolist()
    _, used = _feed(step, params, _fresh(), first, [16, 14], slot=1)
    assert float(jnp.max(jnp.abs(used["ssm"][:, 1]))) > 0
    reused, _ = _feed(step, params, used, toks, [20, 20], slot=1)
    fresh, _ = _feed(step, params, _fresh(), toks, [20, 20], slot=1)
    for pos in fresh:
        np.testing.assert_array_equal(reused[pos], fresh[pos])
    assert np.max(np.abs(reused[39] - want[39])) / np.max(np.abs(want)) < 5e-6


def test_padding_rows_leave_state_bit_identical(model):
    params, toks, _want, step = model
    _, cache = _feed(step, params, _fresh(), toks, [20], slot=0)
    # a step whose only live row is slot 2: slot 0 (where every padding
    # row points) and slots 1, 3 keep their conv and ssm bit for bit
    before = {k: np.asarray(cache[k]) for k in ("conv", "ssm")}
    _, cache = _run(step, params, cache, [
        {"slot": 2, "start": 0, "tokens": toks[:5]}])
    for s in (0, 1, 3):
        np.testing.assert_array_equal(before["conv"][:, :, s],
                                      np.asarray(cache["conv"][:, :, s]))
        np.testing.assert_array_equal(before["ssm"][:, s],
                                      np.asarray(cache["ssm"][:, s]))
    assert not np.array_equal(before["ssm"][:, 2],
                              np.asarray(cache["ssm"][:, 2]))


def test_mqa_attention_layers_match_reference():
    cfg = dataclasses.replace(CFG, n_layers=2, attn_layer_period=1,
                              attn_layer_offset=0)
    assert cfg.layer_kinds() == ["attention"] * 2 and cfg.n_kv_heads == 1
    params = _params(cfg)
    toks = np.random.default_rng(3).integers(1, 97, 30).tolist()
    want = _reference(cfg, params, toks)
    step = _stepper(cfg)
    cache = jamba.init_cache(cfg, SLOTS * MAXP, PAGE, SLOTS)
    got, _ = _feed(step, params, cache, toks, [11, 12, 1, 1, 1, 1, 1, 1, 1],
                   slot=1)
    for pos, logits in got.items():
        assert np.max(np.abs(logits - want[pos])) / np.max(np.abs(want)) \
            < 5e-6


def test_ssm_scan_kernel_matches_its_plain_form():
    rng = np.random.default_rng(0)
    T, C, N, R, L, S = 24, 256, 16, 6, 3, 6
    rows = [{"slot": 4, "start": 7, "tokens": None},
            {"slot": 1, "start": 0, "tokens": [1] * 9},
            {"slot": 2, "start": 5, "tokens": [2] * 5},
            {"slot": 0, "start": 0, "tokens": [3]}]
    _, _, _, _, rs, r0, rl, ro = pack_ragged_batch(rows, T, R)
    def f(*s):
        return jnp.asarray(rng.standard_normal(s), jnp.float32)

    delta, x, b, c = jax.nn.softplus(f(T, C)), f(T, C), f(T, N), f(T, N)
    a, ssm = -jnp.exp(0.3 * f(N, C)), f(L, S + 1, N, C)
    y0, s0 = ss.ssm_scan_reference(delta, x, b, c, a, ssm, 1, rs, r0, rl, ro)
    y1, s1 = jax.jit(ss.ssm_scan)(delta, x, b, c, a, ssm, 1, rs, r0, rl, ro)
    np.testing.assert_allclose(y1, y0, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(s1[:, :S], s0[:, :S], rtol=1e-6, atol=1e-6)
    # other layers and the slots of no row: bit for bit
    np.testing.assert_array_equal(s1[0], ssm[0])
    np.testing.assert_array_equal(s1[1, [3, 5]], ssm[1, [3, 5]])
    # a row that starts a sequence ignores what its slot held
    assert not np.array_equal(s1[1, 1], ssm[1, 1])
    y2, _ = jax.jit(ss.ssm_scan)(delta, x, b, c, a, ssm.at[1, 1].set(7.0),
                                 1, rs, r0, rl, ro)
    np.testing.assert_array_equal(y2, y1)


def _engine_config(**kw):
    return EngineConfig(max_slots=4, max_seq_len=64, page_size=PAGE,
                        num_pages=32, ragged_batching=True, **kw)


def test_engine_continuous_batching_gives_each_request_its_own_tokens():
    params = _params(CFG)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 97, int(n)).tolist()
               for n in rng.integers(3, 30, 8)]
    eng = LLMEngine(params, jamba_paged_adapter(CFG),
                    _engine_config(prefill_chunk=8, token_budget=16))
    try:
        streams = [eng.submit(p, max_new_tokens=6, temperature=0.0)
                   for p in prompts]
        batched = [s.result(timeout_s=300) for s in streams]
        state = eng.stats()["state_cache"]
        assert state["slots"] == 4 and state["live"] == 0
        assert state["resets"] == 8
        assert state["bytes_per_slot"] == CFG.state_bytes_per_slot()
        alone = [eng.generate(p, max_new_tokens=6, temperature=0.0)
                 for p in prompts]
    finally:
        eng.shutdown()
    assert batched == alone
    # the logits-argmax continuation of the plain reference
    toks = prompts[0] + alone[0]
    want = _reference(CFG, params, toks)
    n = len(prompts[0])
    assert alone[0] == [int(np.argmax(want[n - 1 + i])) for i in range(6)]


@pytest.mark.parametrize("kw,word", [
    ({"prefix_cache": True}, "prefix"),
    ({"spec_decode": True}, "rewound"),
    ({"ragged_batching": False}, "ragged"),
])
def test_engine_refuses_what_recurrent_state_cannot_do(kw, word):
    cfg = dict(max_slots=4, max_seq_len=64, page_size=PAGE, num_pages=32,
               ragged_batching=True)
    cfg.update(kw)
    with pytest.raises(ValueError, match="recurrent state") as e:
        LLMEngine(_params(CFG), jamba_paged_adapter(CFG),
                  EngineConfig(**cfg))
    assert word in str(e.value)


def test_engine_refuses_a_migration_call():
    eng = LLMEngine(_params(CFG), jamba_paged_adapter(CFG), _engine_config())
    try:
        with pytest.raises(ValueError, match="recurrent state"):
            eng.migration_lease([1, 2, 3])
        with pytest.raises(ValueError, match="recurrent state"):
            eng.export_hot_prefixes()
    finally:
        eng.shutdown()
