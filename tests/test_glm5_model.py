"""GLM-5 on the CPU at a small size (hidden 64, 4 heads, ranks 16/8, rope
8, an indexer of 8 heads of 16 selecting 12 positions, 8 experts top-2 of
which ranks hold 4, 1 dense + 2 routed layers): the served path
(``ragged_step`` directly, and through ``LLMEngine`` with
``glm5_paged_adapter``) against the plain reference
(``benchmarks/harness/reference_glm5.py``) on seeded random weights, with
contexts that pass ``index_topk`` so the selection is live."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import reference_glm5 as ref
from benchmarks.runners.serve_glm5 import planted
from ray_tpu.models import glm5
from ray_tpu.ops.ragged_paged_attention import pack_ragged_batch
from ray_tpu.serve.llm_engine import (
    EngineConfig,
    LLMEngine,
    glm5_paged_adapter,
)

pytestmark = pytest.mark.long_file(94)

HF = dict(
    vocab_size=128, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=4, intermediate_size=128,
    rope_parameters=dict(rope_theta=10000, rope_type="default"),
    rms_norm_eps=1e-5, tie_word_embeddings=False,
    first_k_dense_replace=1, q_lora_rank=16, kv_lora_rank=8,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=4, router_experts=8, expert_rank=1,
    num_experts_per_tok=2, n_shared_experts=1,
    moe_intermediate_size=32, routed_scaling_factor=2.5,
    index_n_heads=8, index_head_dim=16, index_topk=12)
CFG = glm5.Glm5Config.from_published(HF, dtype=jnp.float32,
                                     param_dtype=jnp.float32)
PAGE, SLOTS, MAXP, BUDGET = 8, 4, 8, 24


@pytest.fixture(scope="module")
def params():
    return glm5.init_params(jax.random.key(0), CFG)


@pytest.fixture(autouse=True)
def every_pass():
    with jax.default_matmul_precision("highest"):
        yield


def _reference_logits(params, toks, hf=HF, **kw):
    x, infos = ref.forward(params, np.asarray(toks), hf, query_block=16,
                           **kw)
    return np.asarray(ref.logits_of(
        x, ref.head_from_program_tree(params), hf)), infos


_STEPS = {}


class _Program:
    """The model's step over a cache of its own, a row at a time;
    ``plant``: with that fault of the benchmark's runner in effect while
    it is traced (the program itself has no mode for one)."""

    def __init__(self, params, cfg=CFG, plant=None):
        key = (cfg, plant)
        if key not in _STEPS:
            step = jax.jit(
                lambda p, *a: glm5.ragged_step(p, *a[:-1], cfg, a[-1],
                                               with_routes=True))

            def traced_planted(*a):
                with planted(plant):
                    return step(*a)

            _STEPS[key] = traced_planted
        self.step, self.params = _STEPS[key], params
        self.cache = glm5.init_cache(cfg, SLOTS * MAXP, PAGE)
        self.seen = []

    def run(self, rows, table):
        (toks, _m, _s, pos, r_slot, r_start, r_len, r_off) = \
            pack_ragged_batch(rows, BUDGET, SLOTS)
        logits, self.cache, seen = self.step(
            self.params, toks, pos, r_slot, r_start, r_len, r_off, table,
            self.cache)
        self.seen.append(seen)
        return np.asarray(logits)


def _serve(prog, toks, slot, table, pieces):
    """[(position, logits)] of one sequence served in ``pieces``."""
    got = []
    for start, n in pieces:
        logits = prog.run([{"slot": slot, "start": start,
                            "tokens": list(toks[start:start + n])}], table)
        got.append((start + n - 1, logits[0]))
    return got


def _close(got, want, tol=3e-5):
    scale = np.abs(want).max()
    for at, g in got:
        assert np.abs(g - want[at]).max() / scale < tol, at


def _worst(got, want):
    scale = np.abs(want).max()
    return max(np.abs(g - want[at]).max() / scale for at, g in got)


TABLE = np.arange(SLOTS * MAXP, dtype=np.int32).reshape(SLOTS, MAXP)
PIECES = [(0, 16), (16, 8)] + [(i, 1) for i in range(24, 40)]


def test_chunks_then_decode_equal_the_reference(params):
    toks = np.random.default_rng(0).integers(1, 128, 40)
    want, infos = _reference_logits(params, toks)
    # contexts pass index_topk: queries attend to 12 of their past (and
    # to what ties with the twelfth: relu makes exact zeros)
    sizes = np.asarray(infos[0]["sel_size"])
    assert np.all(sizes >= np.minimum(np.arange(40) + 1, 12))
    assert np.mean(sizes == np.minimum(np.arange(40) + 1, 12)) > 0.9
    prog = _Program(params)
    got = _serve(prog, toks, 2, TABLE, PIECES)
    # where the twelfth score ties, a decode row's list keeps the first
    # twelve: the reference attends as the program did there (a gap of 0)
    sel = _selection(prog.seen, PIECES, 40)
    want, _ = _reference_logits(
        params, toks, selections={i: sel[i] for i in range(3)}, sel_eps=0.0)
    _close(got, want)
    # the counters: the pairs of the four experts held (ids 4 to 7)
    counted = np.asarray(prog.cache["moe_tokens"])
    for j, info in enumerate(infos[1:]):
        every = np.bincount(np.asarray(info["choice"]).reshape(-1),
                            minlength=8)
        np.testing.assert_array_equal(counted[j], every[4:])
    # both pools hold the reference's rows of the first layer
    for leaf, name, width in (("kv_c", "latent", CFG.latent_dim),
                              ("kv_i", "index_keys", CFG.index_dim)):
        pool = np.asarray(prog.cache[leaf])[0, 0, TABLE[2]].reshape(
            MAXP * PAGE, -1)
        np.testing.assert_allclose(pool[:40, :width],
                                   np.asarray(infos[0][name]),
                                   rtol=1e-4, atol=1e-5)
    pool = np.asarray(prog.cache["kv_c"])[0, 0, TABLE[2]].reshape(
        MAXP * PAGE, -1)
    np.testing.assert_array_equal(pool[:40, CFG.latent_dim + CFG.top_k],
                                  toks)


def test_the_selection_equals_the_references(params):
    """Every query's selected positions, chunk rows and decode rows, in
    every layer, against the reference's own."""
    toks = np.random.default_rng(3).integers(1, 128, 40)
    prog = _Program(params)
    _serve(prog, toks, 1, TABLE, PIECES)
    sel = _selection(prog.seen, PIECES, 40)
    _want, infos = _reference_logits(
        params, toks, selections={i: sel[i] for i in range(3)}, sel_eps=0.0)
    for info in infos:
        # a position selected otherwise is an exact tie with the k-th
        # (a list of k positions keeps the first of them)
        np.testing.assert_array_equal(np.asarray(info["sel_gap"]), 0.0)
        assert int(np.sum(np.asarray(info["sel_diff"]))) <= 2


def _selection(seen, pieces, n):
    """[L, n, n] bool of what the steps attended to, in position space."""
    L = seen[0]["sel_pool"].shape[0]
    out = np.zeros((L, n, n), bool)
    for s, (start, m) in zip(seen, pieces):
        if bool(np.asarray(s["more"])[0]):
            pool = np.asarray(s["sel_pool"])[:, :m, :start]
            own = np.asarray(s["sel_self"])[:, :m, :m]
            out[:, start:start + m, :start] = pool
            out[:, start:start + m, start:start + m] = own
        else:
            out[:, start, :start + 1] = np.asarray(
                s["sel_one"])[:, 0, :start + 1]
    return out


def test_a_row_under_topk_is_dense_attention(params):
    """A sequence no longer than index_topk selects everything: the
    sparse step and the step that attends to every cached position give
    the same logits."""
    toks = np.random.default_rng(4).integers(1, 128, 12)
    pieces = [(0, 8)] + [(i, 1) for i in range(8, 12)]
    sparse = _serve(_Program(params), toks, 0, TABLE, pieces)
    dense = _serve(_Program(params, plant="dense"), toks, 0, TABLE,
                   pieces)
    for (_a, g), (_b, w) in zip(sparse, dense):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
    want, _ = _reference_logits(params, toks)
    _close(sparse, want)


@pytest.mark.parametrize("mode", ["dense", "recent"])
def test_a_planted_selection_fault_is_seen(params, mode):
    """``dense_control`` / ``recent_control`` at this size: the logits
    leave the reference's, and the reference refuses the selection."""
    toks = np.random.default_rng(0).integers(1, 128, 40)
    want, _ = _reference_logits(params, toks)
    prog = _Program(params, plant=mode)
    got = _serve(prog, toks, 2, TABLE, PIECES)
    assert _worst(got, want) > 1e-3
    # the dense fault attends to everything whatever the step lists
    sel = (np.broadcast_to(np.tril(np.ones((40, 40), bool)), (3, 40, 40))
           if mode == "dense" else _selection(prog.seen, PIECES, 40))
    _w, infos = _reference_logits(
        params, toks, selections={i: sel[i] for i in range(3)}, sel_eps=0.0)
    assert float(np.max(np.asarray(infos[0]["sel_gap"]))) > 1e-3
    assert int(np.sum(np.asarray(infos[0]["sel_diff"]))) > 40


def test_a_slot_reused_under_a_new_block_table(params):
    rng = np.random.default_rng(1)
    first, second = rng.integers(1, 128, 30), rng.integers(1, 128, 29)
    prog = _Program(params)
    _serve(prog, first, 1, TABLE, [(0, 20)] + [(i, 1) for i in range(20, 30)])
    table2 = TABLE.copy()
    table2[1] = TABLE[1][::-1]
    want, _ = _reference_logits(params, second)
    _close(_serve(prog, second, 1, table2,
                  [(0, 17)] + [(i, 1) for i in range(17, 29)]), want)


def test_two_rows_in_one_step_and_padding_rows(params):
    """A chunk beside a decode row, each over its own pages; a step of
    padding rows leaves both pools bit-equal."""
    rng = np.random.default_rng(2)
    a, b = rng.integers(1, 128, 30), rng.integers(1, 128, 26)
    wa, _ = _reference_logits(params, a)
    wb, _ = _reference_logits(params, b)
    prog = _Program(params)
    _serve(prog, a, 0, TABLE, [(0, 20)])
    got_a, got_b = [], []
    for step in range(6):
        rows = [{"slot": 0, "start": 20 + step, "tokens": [a[20 + step]]}]
        if step < 2:
            rows.append({"slot": 3, "start": 10 * step,
                         "tokens": list(b[10 * step:10 * step + 10])})
        else:
            rows.append({"slot": 3, "start": 18 + step,
                         "tokens": [b[18 + step]]})
        logits = prog.run(rows, TABLE)
        got_a.append((20 + step, logits[0]))
        got_b.append((rows[1]["start"] + len(rows[1]["tokens"]) - 1,
                      logits[1]))
    _close(got_a, wa)
    _close(got_b, wb)
    before = {k: np.asarray(v) for k, v in prog.cache.items()}
    prog.run([], TABLE)
    for k in ("kv_c", "kv_i"):
        np.testing.assert_array_equal(np.asarray(prog.cache[k]), before[k])


def test_the_share_is_the_held_experts_part(params):
    """The reference given another rank's share differs, and the
    program given it follows: the share is in both alike."""
    toks = np.random.default_rng(5).integers(1, 128, 24)
    hf0 = dict(HF, expert_rank=0)
    cfg0 = dataclasses.replace(CFG, expert_first=0)
    want0, _ = _reference_logits(params, toks, hf0)
    want1, _ = _reference_logits(params, toks)
    assert np.abs(want0 - want1).max() / np.abs(want1).max() > 1e-2
    pieces = [(0, 16)] + [(i, 1) for i in range(16, 24)]
    _close(_serve(_Program(params, cfg0), toks, 0, TABLE, pieces), want0)


def test_served_through_the_engine(params):
    """Greedy decoding through ``LLMEngine`` equals the reference's
    argmax continuation, and ``llm.pack`` counts the selected tokens."""
    adapter = glm5_paged_adapter(CFG)
    assert adapter.ragged_sel_tokens([0, 20], [16, 1]) == sum(
        min(p + 1, 12) for p in range(16)) + 12
    eng = LLMEngine(params, adapter, EngineConfig(
        max_slots=2, max_seq_len=64, page_size=8, ragged_batching=True,
        prefix_cache=False, prefill_chunk=16, max_new_tokens_default=8))
    try:
        prompt = np.random.default_rng(6).integers(1, 128, 20).tolist()
        out = eng.generate(prompt, max_new_tokens=6)
        toks = list(prompt)
        for _ in range(6):
            want, _ = _reference_logits(params, toks)
            toks.append(int(np.argmax(want[-1])))
        assert list(out) == toks[len(prompt):]
        counters = eng.stats()["model_counters"]
        assert np.asarray(counters["moe_tokens"]).shape == (2, 4)
    finally:
        eng.shutdown()
