"""Brumby through the ragged step at toy widths on the CPU: the feature
map, the recurrence against the quadratic reference, each kernel in
interpret mode against its ``jnp`` twin, the state's life in the cache
(chunks beside decode rows, a padding row, a reused slot), and the
engine with ``brumby_paged_adapter``: no page anywhere."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import reference_brumby as ref
from ray_tpu.models import brumby
from ray_tpu.ops import power_retention as pr
from ray_tpu.ops.ragged_paged_attention import pack_ragged_batch
from ray_tpu.serve.llm_engine import (
    EngineConfig,
    LLMEngine,
    brumby_paged_adapter,
)

pytestmark = pytest.mark.long_file(121)

CFG = brumby.BrumbyConfig(
    vocab_size=97, dim=32, n_layers=3, n_heads=4, n_kv_heads=2, head_dim=16,
    mlp_dim=64, rope_theta=1e4, dtype=jnp.float32, param_dtype=jnp.float32)
HF = dict(hidden_size=32, num_hidden_layers=3, num_attention_heads=4,
          num_key_value_heads=2, rms_norm_eps=1e-6, rope_theta=1e4)
SLOTS, BUDGET = 4, 24
NO_TABLE = np.zeros((SLOTS, 0), np.int32)
D, DP = 16, pr.feature_dim(16)
# the kernels' matmul operands are bfloat16, the reference's float32
TOL = 2e-2


def _params(seed=0):
    """Random weights with the norms moved off one and gates that
    forget over tens of tokens, so that the decay shows at this length."""
    params = brumby.init_params(jax.random.key(seed), CFG)
    keys = iter(jax.random.split(jax.random.key(seed + 1), 8))

    def jitter(a):
        return a * (1 + 0.2 * jax.random.normal(next(keys), a.shape))

    for name in ("ln_in", "ln_ff", "final_norm"):
        params[name] = jitter(params[name])
    for name in ("q_norm", "k_norm"):
        params["ret"][name] = jitter(params["ret"][name])
    params["ret"]["b_g"] = jax.random.uniform(
        next(keys), params["ret"]["b_g"].shape, minval=1.0, maxval=4.0)
    return params


def _reference_logits(params, toks):
    with jax.default_matmul_precision("highest"):
        p = ref.from_program_tree(params, HF)
        x, state = ref.forward_hidden(p, jnp.asarray(toks, jnp.int32), HF)
        return np.asarray(ref.logits_of(x, p, HF)), state


def _rows(rows, budget=BUDGET, slots=SLOTS):
    return pack_ragged_batch(rows, budget, slots)


def _operands(rng, T=BUDGET, L=2, heads=4, kv_heads=2, d=D, keys=0):
    """``keys`` > 0: each normaliser is the sum of ``phi`` of that many
    random keys, as a served one is, and each token's key leans to its
    queries, so that ``z . phi(q)`` and ``(q . k)^2`` are sums of
    squares that stand clear of zero (a fresh row's whole denominator
    is the second)."""
    f32 = jnp.float32
    dp = pr.feature_dim(d)
    q = jnp.asarray(rng.standard_normal((T, heads, d)), f32)
    k = jnp.asarray(rng.standard_normal((T, kv_heads, d)), f32)
    v = jnp.asarray(rng.standard_normal((T, kv_heads, d)), f32)
    log_g = jnp.asarray(np.log(rng.uniform(0.8, 0.999, (T, kv_heads))), f32)
    s0 = jnp.asarray(
        rng.standard_normal((L, SLOTS + 1, kv_heads, dp, d)), f32)
    z0 = jnp.asarray(
        np.abs(rng.standard_normal((L, SLOTS + 1, kv_heads, dp))), f32)
    if keys:
        k = k + jnp.sum(q.reshape(T, kv_heads, -1, d), axis=2)
        z0 = jnp.sum(pr.features(jnp.asarray(rng.standard_normal(
            (keys, L, SLOTS + 1, kv_heads, d)), f32)), axis=0)
    return q, k, v, log_g, s0, z0


def test_feature_map_squares_the_score():
    rng = np.random.default_rng(0)
    for d in (16, 128):
        a = rng.standard_normal((5, d)).astype(np.float32)
        b = rng.standard_normal((5, d)).astype(np.float32)
        got = np.sum(np.asarray(pr.features(a)) * np.asarray(pr.features(b)),
                     -1)
        np.testing.assert_allclose(got, np.sum(a * b, -1) ** 2, rtol=2e-5)
        assert pr.features(a).shape[-1] == pr.feature_dim(d)
        # the layout folds to the reference's deduplicated one
        np.testing.assert_allclose(
            pr.to_canonical(np.asarray(pr.features(a[0])), d),
            np.asarray(ref.phi(jnp.asarray(a[0]))), rtol=1e-5, atol=1e-6)
    # 9216 features in the kernels' layout hold the 8256 distinct ones
    assert pr.feature_dim(128) == 9216
    assert ref.phi(jnp.zeros((128,))).shape == (8256,)


@pytest.mark.parametrize("d", [16, 128])
def test_pair_features_t_is_the_feature_map(d):
    """What the chunk kernel forms of a tile on the vector unit, pair of
    blocks by pair, features on sublanes: ``features(u).T`` bit for bit,
    in float32 and as the kernel casts it for the MXU."""
    rng = np.random.default_rng(4)
    u = jnp.asarray(rng.standard_normal((8, d)), jnp.bfloat16)
    ut = u.astype(jnp.float32).T                           # [d, tokens]
    bs = pr.feature_block(d)
    pairs = zip(*pr._block_pairs(d))
    got = jnp.concatenate([
        pr.pair_features_t(ut[a * bs:(a + 1) * bs], ut[b * bs:(b + 1) * bs],
                           np.float32(1.0 if a == b else np.sqrt(2.0)))
        for a, b in pairs], axis=0)
    want = pr.features(u).T
    assert got.shape == want.shape == (pr.feature_dim(d), 8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.bfloat16).astype(jnp.float32)),
        np.asarray(want.astype(jnp.bfloat16).astype(jnp.float32)))


@pytest.mark.parametrize("d", [16, 128])
def test_decode_features_are_the_feature_map_bit_for_bit(d):
    """``phi`` of the decode tokens, formed in one pass with bfloat16
    operands and a float32 sum, is ``features`` of the same operands bit
    for bit: every selected value is one bfloat16 times 1.0 plus zeros.
    Negative values, zeros, a whole zero row (the operand's padding) and
    the largest and smallest magnitudes among them."""
    rng = np.random.default_rng(5)
    u = rng.standard_normal((3, 2, pr.FEAT_ROWS, d)).astype(np.float32)
    u[rng.random(u.shape) < 0.1] = 0.0
    u[0, 0, -2:] = 0.0
    u[1, 1, 0, :4] = [3.0e38, -3.0e38, 1e-38, -0.0]
    u[2, 0, 3] *= 1e-12
    u = jnp.asarray(u, jnp.bfloat16)
    assert bool(jnp.any(u < 0)) and bool(jnp.any(u == 0))
    got, want = jax.jit(pr.decode_features)(u), jax.jit(pr.features)(u)
    assert got.dtype == want.dtype == jnp.float32
    assert got.shape == want.shape == u.shape[:-1] + (pr.feature_dim(d),)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("through,limits", [
    (pr.retention_chunk_reference, lambda want: dict(rtol=1e-4, atol=1e-4)),
    # bfloat16 operands on the MXU: the limit the kernels are held to
    # against their twins, of the largest entry
    (pr.retention_chunk, lambda want: dict(
        rtol=TOL, atol=TOL * float(np.max(np.abs(want))))),
], ids=["twin", "kernel"])
def test_recurrence_is_the_quadratic_form(through, limits):
    """One row whole through the token-by-token twin, and through the
    chunk kernel (three tiles of a tile's tokens and five feature
    blocks): outputs and the final (S, z) are the reference's quadratic
    sum and direct sum."""
    rng = np.random.default_rng(1)
    q, k, v, log_g, s0, z0 = _operands(rng)
    (_t, _m, _s, _p, rs, r0, rl, ro) = _rows(
        [{"slot": 1, "start": 0, "tokens": list(range(BUDGET))}])
    y, s1, z1 = through(
        q.astype(jnp.bfloat16).astype(jnp.float32), k, v, log_g, s0, z0, 0,
        rs, r0, rl, ro)
    bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = ref.retention(bf(q), bf(k), bf(v), log_g)
        want_s, want_z = ref.final_state(bf(k), bf(v), log_g)

    def close(got, want):
        np.testing.assert_allclose(got, want, **limits(want))

    close(y, want)
    for j in range(2):
        close(pr.to_canonical(s1[0, 1, j], D), want_s[j])
        close(pr.to_canonical(z1[0, 1, j], D), want_z[j])


MIXED = [{"slot": 2, "start": 5, "tokens": None},
         {"slot": 0, "start": 0, "tokens": list(range(11))},
         {"slot": 3, "start": 7, "tokens": list(range(9))},
         {"slot": 1, "start": 3, "tokens": None}]
# a chunk that starts at position 1 of the buffer and ends inside its
# fourth tile of 8, a second one from there to inside the fifth
RAGGED = [{"slot": 2, "start": 5, "tokens": None},
          {"slot": 0, "start": 0, "tokens": list(range(26))},
          {"slot": 3, "start": 7, "tokens": list(range(10))},
          {"slot": 1, "start": 3, "tokens": None}]
# at the published head size a tile is 128 tokens: the first chunk ends
# one short of the tile's end, the second lies across it
WIDE = [{"slot": 2, "start": 5, "tokens": None},
        {"slot": 0, "start": 0, "tokens": list(range(126))},
        {"slot": 3, "start": 7, "tokens": list(range(9))},
        {"slot": 1, "start": 3, "tokens": None}]
# rows of one token only: two that carry a state and one that opens its
# sequence (a one-token prompt), whose slot holds what the last left
FRESH = [{"slot": 2, "start": 5, "tokens": None},
         {"slot": 0, "start": 0, "tokens": [0]},
         {"slot": 1, "start": 3, "tokens": None}]


@pytest.mark.parametrize("kernel,twin,rows,shape", [
    # D' 160: five state blocks of 32
    (pr.retention_decode, pr.retention_decode_reference, MIXED, {}),
    # D' 9216, the size the cell runs: nine state blocks of 1024
    (pr.retention_decode, pr.retention_decode_reference, FRESH,
     dict(heads=2, kv_heads=1, d=128, keys=6)),
    (pr.retention_chunk, pr.retention_chunk_reference, MIXED, {}),
    (pr.retention_chunk, pr.retention_chunk_reference, RAGGED,
     dict(T=40)),
    # blocks of 16, D' 9216, nine state blocks of four whole pairs
    (pr.retention_chunk, pr.retention_chunk_reference, WIDE,
     dict(T=144, heads=2, kv_heads=1, d=128)),
], ids=["retention_decode", "retention_decode_d128", "retention_chunk",
        "retention_chunk_ragged", "retention_chunk_d128"])
def test_kernel_matches_its_twin(kernel, twin, rows, shape):
    rng = np.random.default_rng(2)
    q, k, v, log_g, s0, z0 = _operands(rng, **shape)
    (_t, _m, _s, _p, rs, r0, rl, ro) = _rows(rows, shape.get("T", BUDGET))
    # a padding row between live ones
    rl = np.array(rl)
    rs, r0, rl, ro = (np.insert(a, 1, 0) for a in (rs, r0, rl, ro))
    got = kernel(q, k, v, log_g, s0, z0, 1, rs, r0, rl, ro)
    want = twin(q, k, v, log_g, s0, z0, 1, rs, r0, rl, ro)
    for g, w in zip(got, want):
        scale = float(jnp.max(jnp.abs(w)))
        assert float(jnp.max(jnp.abs(g - w))) / scale < TOL
    y, s1, z1 = got
    # the other layer, the slots of the other kind of row and scratch
    # are as they were, bit for bit
    assert bool(jnp.all(s1[0] == s0[0])) and bool(jnp.all(z1[0] == z0[0]))
    mine = [int(slot) for slot, n in zip(rs, rl)
            if (n == 1 if kernel is pr.retention_decode else n > 1)]
    assert len(mine) >= 2
    for slot in set(range(SLOTS + 1)) - set(mine):
        assert bool(jnp.all(s1[1, slot] == s0[1, slot])), slot
        assert bool(jnp.all(z1[1, slot] == z0[1, slot])), slot
    for slot in mine:
        assert not bool(jnp.all(s1[1, slot] == s0[1, slot]))
    # a row that starts a sequence ignores what its slot held
    if 0 in mine:
        y2, s2, _ = kernel(q, k, v, log_g, s0.at[1, 0].set(7.0), z0, 1,
                           rs, r0, rl, ro)
        np.testing.assert_array_equal(s2[1, 0], s1[1, 0])


def test_a_step_with_no_live_row_leaves_the_state_bit_equal():
    rng = np.random.default_rng(3)
    q, k, v, log_g, s0, z0 = _operands(rng)
    (_t, _m, _s, _p, rs, r0, rl, ro) = _rows(MIXED)
    y, s1, z1 = pr.retention(q, k, v, log_g, s0, z0, 1, rs, r0,
                             np.zeros_like(rl), ro)
    assert bool(jnp.all(s1 == s0)) and bool(jnp.all(z1 == z0))
    assert float(jnp.max(jnp.abs(y))) == 0.0


# -- the model's step -------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    params = _params()
    rng = np.random.default_rng(0)
    a = rng.integers(1, 97, 40).tolist()
    b = rng.integers(1, 97, 25).tolist()
    c = rng.integers(1, 97, 12).tolist()
    step = jax.jit(lambda p, ht, pos, rs, r0, rl, ro, cache:
                   brumby.ragged_step(p, ht, pos, rs, r0, rl, ro, NO_TABLE,
                                      CFG, cache))
    want = {name: _reference_logits(params, toks)[0]
            for name, toks in (("a", a), ("b", b), ("c", c))}
    return params, {"a": a, "b": b, "c": c}, want, step


def _run(step, params, cache, rows, pad_at=None):
    ht, _m, _s, pos, rs, r0, rl, ro = _rows(rows)
    if pad_at is not None:
        # a padding row between live ones: the packer never makes one,
        # a finished request inside the engine's row order does
        rs, r0, rl, ro = (np.insert(np.asarray(x), pad_at, 0)[:SLOTS]
                          for x in (rs, r0, rl, ro))
    logits, cache = step(params, ht, pos, rs, r0, rl, ro, cache)
    return np.asarray(logits), cache


def _close(got, want):
    return np.max(np.abs(got - want)) / np.max(np.abs(want)) < TOL


def test_init_cache_holds_state_and_no_page():
    cache = brumby.init_cache(CFG, 0, 8, SLOTS)
    assert set(cache) == {"ret_s", "ret_z"}
    assert cache["ret_s"].shape == (3, SLOTS + 1, 2, DP, D)
    assert cache["ret_z"].shape == (3, SLOTS + 1, 2, DP)
    assert cache["ret_s"].dtype == cache["ret_z"].dtype == jnp.float32
    per_slot = sum(int(v.size * 4) for v in cache.values()) // (SLOTS + 1)
    assert per_slot == CFG.state_bytes_per_slot()
    full = brumby.BrumbyConfig()
    assert full.state_bytes_per_slot() == 40 * 8 * (9216 * 128 + 9216) * 4


def test_ragged_step_chunks_beside_decode_rows_and_a_reused_slot(model):
    params, toks, want, step = model
    a, b, c = toks["a"], toks["b"], toks["c"]
    cache = brumby.init_cache(CFG, 0, 8, SLOTS)
    # b whole in slot 1, then a in chunks of 13 beside b's decode rows
    l, cache = _run(step, params, cache, [
        {"slot": 1, "start": 0, "tokens": b[:20]}])
    assert _close(l[0], want["b"][19])
    pos_b = 20
    for lo in (0, 13, 26):
        hi = min(40, lo + 13)
        l, cache = _run(step, params, cache, [
            {"slot": 1, "start": pos_b, "tokens": b[pos_b:pos_b + 1]},
            {"slot": 3, "start": lo, "tokens": a[lo:hi]}], pad_at=1)
        assert _close(l[0], want["b"][pos_b]), lo
        assert _close(l[2], want["a"][hi - 1]), lo
        pos_b += 1
    # slot 1 reused by c (row_start 0 resets it) beside b's... b is done:
    # c whole in b's slot beside a decode row of a
    l, cache = _run(step, params, cache, [
        {"slot": 1, "start": 0, "tokens": c[:11]},
        {"slot": 3, "start": 39, "tokens": a[39:40]}])
    assert _close(l[0], want["c"][10])
    l, cache = _run(step, params, cache, [
        {"slot": 1, "start": 11, "tokens": c[11:12]}])
    assert _close(l[0], want["c"][11])


def test_first_layer_state_is_the_reference_direct_sum(model):
    params, toks, _want, step = model
    a = toks["a"]
    cache = brumby.init_cache(CFG, 0, 8, SLOTS)
    for lo, hi in ((0, 17), (17, 39), (39, 40)):
        _l, cache = _run(step, params, cache, [
            {"slot": 2, "start": lo, "tokens": a[lo:hi]}])
    _logits, (want_s, want_z) = _reference_logits(params, a)
    for j in range(2):
        got = pr.to_canonical(np.asarray(cache["ret_s"][0, 2, j]), D)
        err = np.linalg.norm(got - want_s[j]) / np.linalg.norm(want_s[j])
        assert err < TOL, (j, err)
        got = pr.to_canonical(np.asarray(cache["ret_z"][0, 2, j]), D)
        err = np.linalg.norm(got - want_z[j]) / np.linalg.norm(want_z[j])
        assert err < TOL, (j, err)


# -- the engine -------------------------------------------------------------

def _engine_config(**kw):
    cfg = dict(max_slots=3, max_seq_len=64, page_size=8,
               ragged_batching=True, prefill_chunk=8, token_budget=16)
    cfg.update(kw)
    return EngineConfig(**cfg)


def test_engine_serves_without_a_page(model):
    params, toks, want, _step = model
    rng = np.random.default_rng(5)
    prompts = [toks["a"][:30], toks["b"][:19]] + [
        rng.integers(1, 97, int(n)).tolist() for n in rng.integers(3, 30, 5)]
    eng = LLMEngine(params, brumby_paged_adapter(CFG), _engine_config())
    try:
        assert set(eng._cache) == {"ret_s", "ret_z"}
        streams = [eng.submit(p, max_new_tokens=6, temperature=0.0)
                   for p in prompts]
        # seven requests, three slots and no page to wait for: admission
        # stops at max_slots
        busy = []
        while not all(s._req.finished_at for s in streams):
            busy.append(eng.stats()["active_slots"])
            time.sleep(0.002)
        batched = [s.result(timeout_s=300) for s in streams]
        stats = eng.stats()
        assert max(busy) <= 3
        assert stats["kv_pages_free"] == 0 and eng._num_pages == 0
        assert eng._bt.shape == (3, 0) and not eng._slot_pages
        state = stats["state_cache"]
        assert state["slots"] == 3 and state["live"] == 0
        assert state["resets"] == 7
        assert state["bytes_per_slot"] == CFG.state_bytes_per_slot()
        assert state["bytes"] == sum(
            int(v.size * v.dtype.itemsize) for v in eng._cache.values())
        alone = [eng.generate(p, max_new_tokens=6, temperature=0.0)
                 for p in prompts[:2]]
    finally:
        eng.shutdown()
    assert batched[:2] == alone
    # the served tokens are the reference's argmax, or as good as
    for name, n, answer in (("a", 30, alone[0]), ("b", 19, alone[1])):
        ref_logits, _ = _reference_logits(params, toks[name][:n] + answer)
        for i, tok in enumerate(answer):
            row = ref_logits[n - 1 + i]
            assert row.max() - row[tok] <= TOL * np.abs(row).max(), (name, i)


def test_engine_pack_counts_no_page_cells(model):
    """``llm.pack``'s page counters read 0 for a model without pages,
    and the start-up event says the cache has no paged part."""
    from ray_tpu.util import flight_recorder

    eng = LLMEngine(model[0], brumby_paged_adapter(CFG), _engine_config())
    seen = []
    pack = eng._pack_ragged_step

    def spy():
        step = pack()
        if step is not None:
            seen.append(step[-1])
        return step

    eng._pack_ragged_step = spy
    try:
        eng.generate(list(range(1, 20)), max_new_tokens=3, temperature=0.0)
        parts = [e for e in flight_recorder.snapshot()["driver"]
                 if e.get("kind") == "serve_cache_parts"
                 and e.get("engine") == eng._engine_id]
    finally:
        eng.shutdown()
    assert seen and all(
        (c["live_cells"], c["grid_cells"], c["append_cells"]) == (0, 0, 0)
        for c in seen)
    assert sum(c["n_state_reset"] for c in seen) == 1
    assert max(c["scan_len"] for c in seen) == 16     # the token budget
    assert parts and parts[-1]["paged_kv_bytes"] == 0
    assert parts[-1]["recurrent_state_bytes"] == sum(
        int(v.size * 4) for v in brumby.init_cache(CFG, 0, 8, 3).values())


@pytest.mark.parametrize("kw,word", [
    ({"prefix_cache": True}, "prefix"),
    ({"spec_decode": True}, "rewound"),
    ({"ragged_batching": False}, "ragged"),
])
def test_engine_refuses_what_recurrent_state_cannot_do(kw, word):
    with pytest.raises(ValueError, match="recurrent state") as e:
        LLMEngine(_params(), brumby_paged_adapter(CFG),
                  _engine_config(**kw))
    assert word in str(e.value)


def test_engine_refuses_state_whose_leaves_are_not_named(model):
    adapter = dataclasses.replace(brumby_paged_adapter(CFG), state_leaves=())
    with pytest.raises(ValueError, match="state_leaves"):
        LLMEngine(model[0], adapter, _engine_config())


def test_engine_refuses_a_migration_call(model):
    eng = LLMEngine(model[0], brumby_paged_adapter(CFG), _engine_config())
    try:
        with pytest.raises(ValueError, match="recurrent state"):
            eng.migration_lease([1, 2, 3])
    finally:
        eng.shutdown()


@pytest.fixture(scope="module")
def served():
    """Four finished requests whose answers are the reference's own
    greedy ones (what a faultless engine serves), and the reference's
    logits after each one's last-but-one token."""
    params = _params()
    with jax.default_matmul_precision("highest"):
        p = ref.from_program_tree(params, HF)
        logits = jax.jit(lambda toks: ref.logits_of(
            ref.forward_hidden(p, toks, HF)[0], p, HF))
    rng = np.random.default_rng(11)
    out = []
    for n_prompt in (9, 12, 40, 33):
        toks = np.zeros((44,), np.int32)    # causal: the padding is unseen
        toks[:n_prompt] = rng.integers(1, 97, n_prompt)
        for n in range(n_prompt, n_prompt + 4):
            row = np.asarray(logits(jnp.asarray(toks)))[n - 1]
            toks[n] = int(np.argmax(row))
        out.append((toks[:n_prompt].tolist(), toks[n_prompt:n].tolist()
                    + [int(toks[n])], int(np.argmin(row))))
    return params, out


@pytest.mark.parametrize("fault", [None, "short", "long"])
def test_served_check_compares_a_long_request_too(monkeypatch, served,
                                                  fault):
    """The benchmark runner's check of what the engine served: a few
    short finished requests and the longest one under its cap, through
    the reference in smaller blocks; a wrong token late in either kind
    turns it not ok, and so does traffic whose long requests were all
    over the cap."""
    from benchmarks.runners import serve_brumby as runner

    for name, value in (("SERVED_LEN", 16), ("SERVED_LONG_LEN", 48),
                        ("LONG_QUERY_BLOCK", 8), ("LONG_MLP_BLOCK", 16),
                        ("MLP_BLOCK", 32)):
        monkeypatch.setattr(runner, name, value)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    params, finished = served
    at = {"short": 0, "long": 2}.get(fault)
    log = [(p, a[:-1] + [worst] if i == at else a)
           for i, (p, a, worst) in enumerate(finished)]
    config = dict(HF, intermediate_size=64)
    out = runner.served_check(config, params, log)
    assert out["requests"] == 3 and out["longest"] == 44, out
    assert out["tokens"] == 12 and out["ok"] == (fault is None), out
    if fault is None:
        # long requests in the traffic, none short enough to compare
        monkeypatch.setattr(runner, "SERVED_LONG_LEN", 32)
        assert not runner.served_check(config, params, log)["ok"]
