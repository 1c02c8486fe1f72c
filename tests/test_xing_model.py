"""Xing4.0 on the CPU at a small size (hidden 64, 4 heads, ranks 16/8,
rope 8, 8 experts top-2, four streams, 2 dense + 2 routed layers): the
served path (``ragged_step`` directly, and through ``LLMEngine`` with
``xing_paged_adapter``) against the plain reference
(``benchmarks/harness/reference_xing.py``) on seeded random weights."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import reference_xing as ref
from ray_tpu.models import xing
from ray_tpu.ops import latent_attention as la
from ray_tpu.ops.ragged_paged_attention import pack_ragged_batch
from ray_tpu.serve.llm_engine import (
    EngineConfig,
    LLMEngine,
    PagedEngineAdapter,
    llama_paged_adapter,
    xing_paged_adapter,
)

pytestmark = pytest.mark.long_file(110)

HF = dict(
    vocab_size=128, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=4, intermediate_size=128,
    rope_theta=10000, rms_norm_eps=1e-6, tie_word_embeddings=False,
    first_k_dense_replace=2, q_lora_rank=16, kv_lora_rank=8,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
    moe_intermediate_size=32, routed_scaling_factor=2, hc_mult=4,
    hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                      mscale_all_dim=1,
                      original_max_position_embeddings=4096, type="yarn"))
CFG = xing.XingConfig.from_published(HF, dtype=jnp.float32,
                                     param_dtype=jnp.float32)
PAGE, SLOTS, MAXP, BUDGET = 8, 4, 8, 24


@pytest.fixture(scope="module")
def params():
    return xing.init_params(jax.random.key(0), CFG)


@pytest.fixture(autouse=True)
def every_pass():
    with jax.default_matmul_precision("highest"):
        yield


def _reference_logits(params, toks, **kw):
    X, infos = ref.forward(params, np.asarray(toks), HF, **kw)
    return np.asarray(ref.logits_of(
        X, ref.head_from_program_tree(params), HF)), infos


_STEPS = {}     # one compiled step a configuration, for every test


class _Program:
    """The adapter's step over a cache of its own, a row at a time."""

    def __init__(self, params, cfg=CFG):
        if cfg not in _STEPS:
            adapter = xing_paged_adapter(cfg)
            _STEPS[cfg] = (adapter, jax.jit(adapter.ragged_step))
        self.adapter, self.step = _STEPS[cfg]
        self.params = params
        self.cache = self.adapter.init_cache(SLOTS * MAXP, PAGE)

    def run(self, rows, table):
        (toks, _m, _s, pos, r_slot, r_start, r_len, r_off) = \
            pack_ragged_batch(rows, BUDGET, SLOTS)
        logits, self.cache = self.step(
            self.params, toks, pos, r_slot, r_start, r_len, r_off, table,
            self.cache)
        return np.asarray(logits)


def _serve(prog, toks, slot, table, pieces):
    """[(position, logits)] of one sequence served in ``pieces``."""
    got = []
    for start, n in pieces:
        logits = prog.run([{"slot": slot, "start": start,
                            "tokens": list(toks[start:start + n])}], table)
        got.append((start + n - 1, logits[0]))
    return got


def _close(got, want, tol=2e-5):
    scale = np.abs(want).max()
    for at, g in got:
        assert np.abs(g - want[at]).max() / scale < tol, at


def test_chunks_then_decode_equal_the_reference(params):
    toks = np.random.default_rng(0).integers(1, 128, 40)
    want, infos = _reference_logits(params, toks)
    assert [("gap" in i) for i in infos] == [False, False, True, True]
    table = np.arange(SLOTS * MAXP, dtype=np.int32).reshape(SLOTS, MAXP)
    prog = _Program(params)
    pieces = [(0, 16), (16, 8)] + [(i, 1) for i in range(24, 40)]
    _close(_serve(prog, toks, 2, table, pieces), want)
    # the counters: every token's two pairs, in both routed layers
    counted = np.asarray(prog.cache["moe_tokens"])
    np.testing.assert_array_equal(counted.sum(1), [80, 80])
    for j, info in enumerate(infos[2:]):
        np.testing.assert_array_equal(
            counted[j], np.bincount(np.asarray(info["choice"]).reshape(-1),
                                    minlength=8))
    # and the first layer's pages hold the reference's c | kr
    pool = np.asarray(prog.cache["kv_c"])[0, 0, table[2]].reshape(
        MAXP * PAGE, -1)
    np.testing.assert_allclose(pool[:40, :CFG.latent_dim],
                               np.asarray(infos[0]["latent"]),
                               rtol=1e-4, atol=1e-5)
    # past them the token's log, and zeros past that
    log_end = CFG.latent_dim + CFG.top_k + xing.LOG_ID + xing.LOG_POS
    np.testing.assert_array_equal(pool[:40, CFG.latent_dim + CFG.top_k],
                                  toks)
    np.testing.assert_array_equal(pool[:40, log_end:], 0.0)


def test_a_slot_reused_under_a_new_block_table(params):
    rng = np.random.default_rng(1)
    first, second = rng.integers(1, 128, 30), rng.integers(1, 128, 21)
    table = np.arange(SLOTS * MAXP, dtype=np.int32).reshape(SLOTS, MAXP)
    prog = _Program(params)
    _serve(prog, first, 1, table, [(0, 20)] + [(i, 1) for i in range(20, 30)])
    # the same slot, other pages, some of them the first request's
    table2 = table.copy()
    table2[1] = table[1][::-1]
    want, _ = _reference_logits(params, second)
    _close(_serve(prog, second, 1, table2,
                  [(0, 9)] + [(i, 1) for i in range(9, 21)]), want)


def test_two_rows_in_one_step_each_read_their_own_pages(params):
    rng = np.random.default_rng(2)
    a, b = rng.integers(1, 128, 18), rng.integers(1, 128, 12)
    table = np.arange(SLOTS * MAXP, dtype=np.int32).reshape(SLOTS, MAXP)
    prog = _Program(params)
    prog.run([{"slot": 0, "start": 0, "tokens": list(a[:17])},
              {"slot": 3, "start": 0, "tokens": list(b[:5])}], table)
    logits = prog.run([{"slot": 0, "start": 17, "tokens": [int(a[17])]},
                       {"slot": 3, "start": 5, "tokens": list(b[5:])}], table)
    for row, toks in ((0, a), (1, b)):
        want, _ = _reference_logits(params, toks)
        _close([(len(toks) - 1, logits[row])], want)


def test_every_token_to_one_expert_is_still_the_reference(params):
    """A selection bias that sends EVERY token to experts 2 and 6: a
    capacity form drops most of them, this one none."""
    bias = jnp.zeros_like(params["moe"]["bias"]).at[:, jnp.asarray(
        [2, 6])].set(10.0)
    skewed = dict(params, moe=dict(params["moe"], bias=bias))
    toks = np.random.default_rng(3).integers(1, 128, 24)
    want, infos = _reference_logits(skewed, toks)
    for info in infos[2:]:
        np.testing.assert_array_equal(np.asarray(info["choice"]),
                                      np.tile([2, 6], (24, 1)))
    table = np.arange(SLOTS * MAXP, dtype=np.int32).reshape(SLOTS, MAXP)
    prog = _Program(skewed)
    _close(_serve(prog, toks, 0, table, [(0, 24)]), want)
    counted = np.asarray(prog.cache["moe_tokens"])
    np.testing.assert_array_equal(counted[:, [2, 6]], 24)
    assert counted.sum() == 2 * 2 * 24
    np.testing.assert_array_equal(np.asarray(prog.cache["moe_distinct"]), 2)


def test_the_pages_hold_each_tokens_log(params):
    """Beside its latent row a token's page keeps its id, its position
    and the experts each routed layer chose for it (``xing.token_log``):
    what the benchmark's served check reads a sequence's routing from,
    and stops reading once another sequence owns the page."""
    from benchmarks.runners import serve_xing

    toks = np.random.default_rng(4).integers(1, 128, 20)
    _, infos = _reference_logits(params, toks)
    table = np.arange(SLOTS * MAXP, dtype=np.int32).reshape(SLOTS, MAXP)
    prog = _Program(params)
    _serve(prog, toks, 1, table, [(0, 12)] + [(i, 1) for i in range(12, 20)])
    log = jax.device_get(jax.jit(lambda c: xing.token_log(c, CFG))(prog.cache))
    assert log["routes"].shape == (2, SLOTS * MAXP + 1, 2, PAGE)
    mine = table[1, :3]
    np.testing.assert_array_equal(log["tokens"][mine].reshape(-1)[:20], toks)
    np.testing.assert_array_equal(log["pos"][mine].reshape(-1)[:20],
                                  np.arange(20))
    routes = serve_xing.logged_routes(log, toks.tolist())
    for j in range(2):      # float32 on both sides: the reference's own
        np.testing.assert_array_equal(routes[j],
                                      np.asarray(infos[2 + j]["choice"]))
    # the attention does not see the log: its queries are zero there
    assert float(jnp.abs(prog.cache["kv_c"][..., CFG.latent_dim:]).max()) > 0
    # another sequence takes the first page: the first is held no more
    other = np.random.default_rng(5).integers(1, 128, 5)
    _serve(prog, other, 2, np.roll(table, 1, axis=0), [(0, 5)])
    log = jax.device_get(xing.token_log(prog.cache, CFG))
    assert serve_xing.logged_routes(log, toks.tolist()) is None
    assert serve_xing.logged_routes(log, other.tolist()).shape == (2, 5, 2)
    assert serve_xing.logged_routes(log, toks.tolist()[:3]) is None


def test_sinkhorn_gives_a_doubly_stochastic_mix(params):
    X = jax.random.normal(jax.random.key(5), (9, 4, 64))
    hp = jax.tree.map(lambda w: w[1], params["hc_ffn"])
    _pre, _post, H = xing.hc_coefficients(X, hp, CFG)
    H = np.asarray(jnp.stack([jnp.stack(row, -1) for row in H], -2))
    np.testing.assert_allclose(H.sum(-1), 1.0, atol=1e-3)
    np.testing.assert_allclose(H.sum(-2), 1.0, atol=1e-3)
    assert (H > 0).all() and H.max() < 0.999    # a mix, not the identity
    want = ref.hc_coefficients(X, {"P": hp["p"], "a": hp["a"], "b": hp["b"]},
                               HF)[2]
    np.testing.assert_allclose(H, np.asarray(want), rtol=1e-5, atol=1e-6)


def test_absorbed_attention_equals_expanded(params):
    """The program's form (W_uk absorbed into the query, W_uv applied to
    the latent's weighted sum) against the reference's per-head keys and
    values, one layer, no pool: everything in the self cell."""
    n = 19
    u = jax.random.normal(jax.random.key(6), (n, 64))
    lp = ref.layer_from_program_tree(params, HF, 1)
    want = ref.attention(u, lp, HF)
    sin, cos = xing.rope_tables(CFG, jnp.arange(n))
    q, new = xing.absorbed_query(u, params["attn"], 1, CFG, sin, cos)
    zero = jnp.zeros((1,), jnp.int32)
    o_lat = la.ragged_latent_attention_reference(
        q, new, jnp.zeros((1, PAGE, CFG.pool_width)), zero, zero,
        jnp.full((1,), n, jnp.int32), zero, jnp.zeros((1, 1), jnp.int32),
        scale=CFG.softmax_scale, rank=CFG.kv_rank)
    got = xing.attention_out(o_lat, params["attn"], 1, CFG)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(CFG.softmax_scale, ref.softmax_scale(HF))
    np.testing.assert_allclose(xing.yarn_inv_freq(CFG), ref.yarn_inv_freq(HF))


def test_the_reference_runs_with_the_programs_choice_only_at_near_ties(
        params):
    toks = np.random.default_rng(7).integers(1, 128, 16)
    plain, own = _reference_logits(params, toks)
    theirs = {2: np.tile([0, 1], (16, 1)), 3: np.tile([4, 5], (16, 1))}
    # an eps under 0 takes no choice; an infinite one takes every token's
    same, infos = _reference_logits(params, toks, choices=theirs,
                                    route_eps=-1.0)
    forced, _ = _reference_logits(params, toks, choices=theirs)
    np.testing.assert_array_equal(same, plain)
    assert np.abs(forced - same).max() > 1e-3
    # what it reports stays its own, beside how far the choice lies
    np.testing.assert_array_equal(np.asarray(infos[2]["choice"]),
                                  np.asarray(own[2]["choice"]))
    s = np.asarray(own[2]["scores"])
    kth = np.sort(s, -1)[:, -2]
    for t in range(16):
        swapped = set(np.asarray(own[2]["choice"])[t].tolist()) ^ {0, 1}
        want = max([abs(s[t, e] - kth[t]) for e in swapped], default=0.0)
        np.testing.assert_allclose(np.asarray(infos[2]["gap"])[t], want,
                                   atol=1e-6)
    # between the two: a token runs with the choice where ITS gap allows
    gaps = np.sort(np.asarray(infos[2]["gap"]))
    some, _ = _reference_logits(params, toks, choices={2: theirs[2]},
                                route_eps=float(gaps[7]))
    assert np.abs(some - same).max() > 1e-3
    assert np.abs(some - _reference_logits(
        params, toks, choices={2: theirs[2]})[0]).max() > 1e-3


# ------------------------------------------------------------ the engine

@pytest.fixture(scope="module")
def engine(params):
    eng = LLMEngine(params, xing_paged_adapter(CFG), EngineConfig(
        max_slots=SLOTS, max_seq_len=MAXP * PAGE, page_size=PAGE,
        ragged_batching=True, prefill_chunk=16))
    yield eng
    eng.shutdown()


def test_the_engine_serves_the_references_tokens(params, engine):
    rng = np.random.default_rng(8)
    prompts = [rng.integers(1, 128, n).tolist() for n in (21, 5, 34)]
    streams = [engine.submit(p, max_new_tokens=10) for p in prompts]
    for prompt, stream in zip(prompts, streams):
        answer = stream.result(timeout_s=300)
        assert len(answer) == 10
        want, _ = _reference_logits(params, prompt + answer)
        at = np.arange(len(prompt) - 1, len(prompt) + 9)
        short = want[at].max(-1) - want[at, np.asarray(answer)]
        assert short.max() <= 1e-4 * np.abs(want).max()


def test_the_engine_counts_pages_and_reads_the_counters(engine):
    engine.generate([3, 1, 4, 1, 5], max_new_tokens=3)
    stats = engine.stats()
    assert "state_cache" not in stats            # pages, nothing by slot
    pool = SLOTS * MAXP + 1
    assert engine._paged_kv_bytes == (
        CFG.n_layers * pool * PAGE * CFG.pool_width * 4)
    counters = stats["model_counters"]
    # handed out by the loop thread with the step whose end they show
    assert counters["step"] == stats["steps"] > 0
    assert np.asarray(counters["moe_tokens"]).shape == (2, 8)
    served = np.asarray(counters["moe_tokens"]).sum(1)
    assert served[0] == served[1] and served[0] >= 2 * 7
    assert min(counters["moe_distinct"]) >= 2
    from ray_tpu.util import metrics

    text = metrics.export_prometheus()
    assert 'raytpu_serve_moe_expert_tokens_total{' in text
    assert 'layer="1"' in text


def test_the_loop_thread_hands_out_the_cache_between_dispatches(engine):
    """``read_cache`` while the loop donates the tree to step after
    step: every read is one step's end (both routed layers have served
    the same pairs), the steps do not go back, and what ``fn`` raises
    reaches the caller."""
    streams = [engine.submit([7, 8, 9, 10 + i], max_new_tokens=24)
               for i in range(3)]
    seen = []
    for _ in range(40):
        counters = engine.stats()["model_counters"]
        by_layer = np.asarray(counters["moe_tokens"]).sum(1)
        assert by_layer[0] == by_layer[1]
        seen.append((counters["step"], int(by_layer[0])))
    assert all(len(s.result(timeout_s=300)) == 24 for s in streams)
    assert seen == sorted(seen) and seen[-1] > seen[0]
    step, pool = engine.read_cache(lambda c: c["kv_c"][0, 0, :2, :, :4])
    assert step == engine.stats()["steps"] and pool.shape == (2, PAGE, 4)
    with pytest.raises(KeyError, match="no_such_leaf"):
        engine.read_cache(lambda c: c["no_such_leaf"])
    assert engine.doctor(deep=False)["violations"] == 0     # same queue


@pytest.mark.parametrize("config, match", [
    (dict(prefix_cache=True), "copy_page"),
    (dict(spec_decode=True), "logit_idx"),
    (dict(ragged_batching=False), "PagedEngineAdapter.prefill_slot"),
])
def test_the_engine_refuses_what_a_latent_pool_cannot_do(params, config,
                                                         match):
    kw = dict(dict(max_slots=2, max_seq_len=32, page_size=PAGE,
                   ragged_batching=True), **config)
    with pytest.raises(ValueError, match=match):
        LLMEngine(params, xing_paged_adapter(CFG), EngineConfig(**kw))


def test_the_engine_refuses_a_mesh_and_migration(params, engine):
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("tp",))
    with pytest.raises(ValueError, match="mesh"):
        LLMEngine(params, xing_paged_adapter(CFG), EngineConfig(
            max_slots=2, max_seq_len=32, page_size=PAGE,
            ragged_batching=True), mesh=mesh)
    with pytest.raises(RuntimeError, match="prefix_cache"):
        engine.migration_lease([1, 2, 3, 4, 5, 6, 7, 8, 9])
    with pytest.raises(ValueError, match="adapter_id"):
        engine.submit([1, 2, 3], adapter_id="tenant-a")


def test_migration_programs_name_the_pool_leaf_they_cannot_ship():
    """The migration programs read ``cache["k"]``/``cache["v"]``: an
    adapter with a copy_page and a pool under another name is refused at
    construction, not by a KeyError at the first migration."""
    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=64, dim=32, n_layers=1, n_heads=2, n_kv_heads=1,
        mlp_dim=64, max_seq_len=32, remat=False, dtype=jnp.float32,
        param_dtype=jnp.float32)
    base = llama_paged_adapter(cfg)

    def init_cache(num_pages, page):
        cache = base.init_cache(num_pages, page)
        return {"kv_c": cache["k"], "v": cache["v"]}

    odd = dataclasses.replace(base, init_cache=init_cache)
    assert isinstance(odd, PagedEngineAdapter)
    with pytest.raises(ValueError, match="'kv_c'"):
        LLMEngine(llama.init_params(jax.random.key(0), cfg), odd,
                  EngineConfig(max_slots=2, max_seq_len=32, page_size=PAGE,
                               ragged_batching=True, prefix_cache=True))
