"""MiniCPM-SALA at a toy size on the CPU: the served path against the
plain reference (logits, lightning states, selected pages), each kernel
against its plain twin, the three controls refused, the dense-to-sparse
switch by the query's position, page lists that differ by KV head, and
the engine over pages and a matrix state side by side."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import reference_sala
from benchmarks.runners import serve_sala
from ray_tpu.models import minicpm_sala as sala
from ray_tpu.ops import block_sparse_attention as bsa
from ray_tpu.ops import lightning_attention as la
from ray_tpu.serve.llm_engine import EngineConfig, LLMEngine, sala_paged_adapter

pytestmark = pytest.mark.long_file(236)

L, S = sala.LIGHTNING, sala.SPARSE
PAGE = 8
# 2 sparse + 6 lightning layers of hidden 64; blocks of 8, top 4, a
# window of 16, dense below 32
HF = {"vocab_size": 97, "hidden_size": 64, "intermediate_size": 128,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
      "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
      "rope_theta": 10000, "rms_norm_eps": 1e-6, "scale_emb": 12,
      "scale_depth": 1.4, "dim_model_base": 32, "num_hidden_layers": 8,
      "first_layer": 9, "mixer_types": [S, L, L, L, S, L, L, L],
      "torch_dtype": "float32",
      "sparse_config": {"kernel_size": 4, "kernel_stride": 2,
                        "block_size": 8, "topk": 4, "window_size": 16,
                        "init_blocks": 1, "dense_len": 32},
      "engine": {"page_size": PAGE}}
CFG = serve_sala.model_config(HF)
SP = CFG.sparse
PLAN = {"chunk": 8, "slots": 4,
        "rows": {"beside": (2, 37, 6), "long": (0, 100, 8),
                 "reused_slot": (2, 11, 3)}}


@pytest.fixture(scope="module")
def side():
    """The check's program side at the toy size: chunks then decode, two
    rows interleaved, a slot taken again, through the ragged step."""
    return serve_sala.program_side(CFG, HF, 3, plan=PLAN, check_hf={})


@pytest.fixture(scope="module")
def check(side):
    """The runner's comparison of it with the reference."""
    return serve_sala.compare(*side[:-1], controls=reference_sala.CONTROLS)


@pytest.mark.parametrize("row", sorted(PLAN["rows"]))
def test_served_logits_match_the_reference(check, row):
    assert check[row]["ok"], check[row]
    assert check[row]["rel_err_prefill"] < 1e-4
    assert check[row]["rel_err_decode"] < 1e-4


def test_lightning_state_matches_the_reference(check):
    assert check["lin_state"]["ok"]
    assert max(check["lin_state"]["rel_err"].values()) < 1e-5


def test_selected_pages_match_the_reference(check):
    sel = check["selection"]
    assert sel["ok"]
    for row in PLAN["rows"]:
        assert sel[row]["kept"] == 0 and sel[row]["mismatch_share"] < 0.02


@pytest.mark.parametrize("control", reference_sala.CONTROLS)
def test_control_is_refused(check, control):
    assert check[control]["refused"], check[control]
    assert check["ok"]


def test_a_precision_below_fails_the_check(side):
    """The reference computed in bfloat16 (state and selection scores
    too) against itself in float32: past the logits' limit."""
    out = serve_sala.compare(*side[:-1], dtype=jnp.bfloat16)
    assert not out["ok"]
    assert max(out[r]["rel_err_decode"] for r in PLAN["rows"]) > 1e-3


def _rows(rows):
    return tuple(jnp.asarray(x, jnp.int32) for x in zip(*rows))


ROWS = [(0, 37, 1, 0), (2, 50, 17, 3), (1, 9, 1, 20), (3, 0, 12, 22),
        (0, 0, 0, 0)]


@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_lightning_kernel_matches_its_plain_form(kind):
    T, H, d = 40, 4, 16
    ks = jax.random.split(jax.random.key(0), 4)
    q, k, v = (jax.random.normal(ks[i], (T, H, d)) for i in range(3))
    lam = jnp.exp(-jnp.exp2(-8.0 * (jnp.arange(H) + 1) / H) * 0.5)
    s0 = jax.random.normal(ks[3], (2, 5, H, d, d))
    rows = _rows(ROWS)
    o, s = jax.jit(getattr(la, f"lightning_{kind}"))(
        q, k, v, lam, s0, 1, *rows)
    o2, s2 = jax.jit(getattr(la, f"lightning_{kind}_reference"))(
        q, k, v, lam, s0, 1, *rows)
    np.testing.assert_allclose(o, o2, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s, s2, rtol=1e-4, atol=1e-4)
    # other layers, other slots and the scratch slot's neighbours stay
    np.testing.assert_array_equal(s[0], s0[0])
    touched = {0, 1} if kind == "decode" else {2, 3}
    for slot in set(range(4)) - touched:
        np.testing.assert_array_equal(s[1, slot], s0[1, slot])
    # a row that starts a sequence ignores what its slot held
    if kind == "chunk":
        o3, _ = jax.jit(la.lightning_chunk)(
            q, k, v, lam, s0.at[1, 3].set(7.0), 1, *rows)
        np.testing.assert_array_equal(o3, o)


@pytest.mark.parametrize("layer", [0, 1])
def test_walk_takes_a_page_list_by_kv_head(layer):
    """The selection differs between the two KV heads; a chunk's rows
    (the context under the selection as a mask) and the rows of one token
    match the dense gather, in either layer of the pools."""
    T, H, KVH, hd, maxp, slots = 40, 8, 2, 16, 12, 4
    ks = jax.random.split(jax.random.key(1), 6)
    q = jax.random.normal(ks[0], (T, H, hd))
    kn, vn = (jax.random.normal(ks[i], (T, KVH, hd)) for i in (1, 2))
    P = slots * maxp
    kp, vp = (jax.random.normal(ks[i], (2, KVH, P + 1, PAGE, hd))
              for i in (3, 4))
    bt = jnp.asarray(np.random.default_rng(0).permutation(P).reshape(
        slots, maxp), jnp.int32)
    rows = _rows(ROWS)
    mask = jax.random.bernoulli(ks[5], 0.5, (T, KVH, maxp)).at[:, :, 0].set(
        True)
    assert bool(jnp.any(mask[:, 0] != mask[:, 1]))
    o, pages, _cells = jax.jit(bsa.block_sparse_attention)(
        q, kn, vn, kp, vp, layer, *rows, bt, mask)
    want = jax.jit(bsa.block_sparse_attention_reference)(
        q, kn, vn, kp[layer], vp[layer], *rows, bt, mask)
    np.testing.assert_allclose(o, want, rtol=1e-4, atol=1e-5)
    # a row of ONE token read the pages it selected and no other
    m = np.asarray(mask)
    read = sum(int(m[t, g, :-(-start // PAGE)].sum())
               for t, start in ((0, 37), (20, 9)) for g in range(KVH))
    assert int(pages[0]) == read


@functools.partial(jax.jit, static_argnames=("cell_pages", "lists"))
def _walk(q, kn, vn, kp, vp, slot, start, nlen, off, bt, mask, *, cell_pages,
          lists=None):
    """``block_sparse_attention`` in layer 1 of the pools, a pool cell
    ``cell_pages`` pages (and ``lists`` in ``walk_lists``' place) while it is
    traced; traced as the model's step traces it."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(bsa, "CELL_PAGES", cell_pages)
        if lists is not None:
            # past the op's own jit, which holds the real lists' trace
            patch.setattr(bsa, "walk_lists", lists)
            patch.setattr(bsa, "_attention", bsa._attention.__wrapped__)
        return bsa.block_sparse_attention(q, kn, vn, kp, vp, 1, slot, start,
                                          nlen, off, bt, mask)


# rows of one token and chunks side by side; in "long" the block tables
# are wider than one 128-page block of the mask and two rows reach past it
WALK_ROWS = {
    "short": (12, ROWS),
    "long": (136, [(0, 1050, 1, 0), (2, 1043, 17, 3), (1, 9, 1, 20),
                   (3, 0, 12, 22), (0, 0, 0, 0)]),
}


@functools.cache
def _walk_case(tables):
    """Inputs whose selection differs between the two KV heads, and the
    plain reference's answer."""
    maxp, rows = WALK_ROWS[tables]
    T, H, KVH, hd, slots = 40, 8, 2, 16, 4
    ks = jax.random.split(jax.random.key(1), 6)
    q = jax.random.normal(ks[0], (T, H, hd))
    kn, vn = (jax.random.normal(ks[i], (T, KVH, hd)) for i in (1, 2))
    P = slots * maxp
    kp, vp = (jax.random.normal(ks[i], (2, KVH, P + 1, PAGE, hd))
              for i in (3, 4))
    bt = jnp.asarray(np.random.default_rng(0).permutation(P).reshape(
        slots, maxp), jnp.int32)
    # a token of a chunk picks few pages, so that their union has gaps
    few = np.zeros(T, bool)
    for _slot, _start, n, off in rows:
        few[off:off + n] = n > 1
    mask = (jax.random.uniform(ks[5], (T, KVH, maxp))
            < jnp.where(few, 0.12, 0.5)[:, None, None]).at[:, :, 0].set(True)
    args = (q, kn, vn, kp, vp, *_rows(rows), bt, mask)
    want = jax.jit(bsa.block_sparse_attention_reference)(
        q, kn, vn, kp[1], vp[1], *args[5:])
    return args, np.asarray(want)


def _unit_pages(tables):
    """The pooled pages each (row, KV head) lists, on the host: ``{(row,
    KV head): pages}`` for the rows of one token and for the others."""
    maxp, rows = WALK_ROWS[tables]
    mask = np.asarray(_walk_case(tables)[0][-1])
    one, more = {}, {}
    for r, (_slot, start, n, off) in enumerate(rows):
        if n == 0:
            continue
        pooled = np.arange(maxp) * PAGE < start
        for g in range(mask.shape[1]):
            picked = mask[off:off + n, g].any(axis=0) & pooled
            (one if n == 1 else more)[r, g] = np.flatnonzero(picked)
    return one, more


@pytest.mark.parametrize("tables,pages_a_cell", [
    ("short", 1), ("short", 2), ("short", 4), ("short", 8), ("long", 4),
    ("long", 8)])
def test_walk_cells_of_several_pages_match_the_reference(tables,
                                                         pages_a_cell):
    """Both calls against the dense gather where a (row, KV head)'s list
    is no multiple of the cell, where it is shorter than one cell, where
    the two KV heads of a row selected different pages and where a
    chunk's cell takes pages from both sides of a 128-page mask block;
    the pages counted are the lists', the cells ``ceil(pages / G)`` a
    (row, KV head)."""
    G = pages_a_cell
    args, want = _walk_case(tables)
    one, more = _unit_pages(tables)
    lists = list(one.values()) + list(more.values())
    if G > 1:
        assert any(len(pages) % G for pages in lists)
    if G >= 4:
        assert any(0 < len(pages) < G for pages in lists)
    assert any(set(one[r, 0]) != set(one[r, 1]) for r, _ in one)
    assert any(set(more[r, 0]) != set(more[r, 1]) for r, _ in more)
    if tables == "long":
        # a cell of a chunk's list holds pages below 128 and past it
        assert any((pages < 128).sum() % G and (pages >= 128).any()
                   for pages in more.values())
    o, pages, cells = _walk(*args, cell_pages=G)
    np.testing.assert_allclose(o, want, rtol=1e-4, atol=1e-5)
    assert pages.tolist() == [sum(map(len, one.values())),
                              sum(map(len, more.values()))]
    assert cells.tolist() == [sum(-(-len(p) // G) for p in side.values())
                              for side in (one, more)]


def test_a_tail_entry_left_unmasked_is_caught():
    """The planted fault: a list's tail short of a cell, which repeats the
    page a cell before it, counted as entries.  The page is attended
    twice and the comparison above refuses it."""
    G = 4
    real = bsa.walk_lists

    def unmasked_tail(*a):
        ent, cnt, *rest = real(*a)
        maxp = ent.shape[0] // cnt.shape[0]
        e = jnp.arange(maxp)[None, :]
        src = jnp.where(e < cnt[:, None], e, jnp.maximum(e - G, 0))
        ent = jnp.take_along_axis(ent.reshape(-1, maxp), src, axis=1)
        return (ent.reshape(-1), -(-cnt // G) * G, *rest)

    args, want = _walk_case("short")
    o, _, _ = _walk(*args, cell_pages=G, lists=unmasked_tail)
    rel = np.abs(np.asarray(o) - want).max() / np.abs(want).max()
    assert rel > 1e-2, rel


def test_the_switch_at_dense_len_is_by_the_querys_position():
    maxp = 16
    b = jax.random.uniform(jax.random.key(2), (2, 2, maxp))
    t = jnp.asarray([SP.dense_len - 1, SP.dense_len], jnp.int32)
    sel = np.asarray(bsa.select_blocks(b, t, SP))
    own = SP.dense_len // SP.block
    assert sel[0, :, :own].all() and not sel[0, :, own:].any()
    assert (sel[1].sum(-1) == SP.topk).all()
    # block 0 and the window's blocks ending at its own are among them
    assert sel[1, :, 0].all() and sel[1, :, own - 1:own + 1].all()
    assert not sel[1, :, own + 1:].any()


def test_ties_go_to_the_lower_block():
    b = jnp.zeros((1, 1, 16))
    sel = np.asarray(bsa.select_blocks(b, jnp.asarray([100]), SP))[0, 0]
    # forced: block 0 and blocks 11, 12 (own 12); one free pick: block 1
    assert sel.nonzero()[0].tolist() == [0, 1, 11, 12]


def test_compressed_keys_come_from_their_pool():
    """Chunks of odd lengths, then single tokens: the halves a query sees
    (pool + the step's own) are the means of the sequence's keys, and
    the pool after the append holds them."""
    KVH, hd, maxp, slots = 2, 16, 8, 2
    n = 45
    keys = jax.random.normal(jax.random.key(3), (n, KVH, hd))
    pool = jnp.full((1, (slots * maxp + 1) * SP.entries, KVH * hd), 9.0)
    bt = jnp.asarray(np.arange(slots * maxp)[::-1].reshape(slots, maxp),
                     jnp.int32)
    start = 0
    for length in (7, 13, 1, 1, 16, 1, 6):
        T = 24
        rows = _rows([(1, start, length, 2), (0, 0, 0, 0)])
        k_new = jnp.zeros((T, KVH, hd)).at[2:2 + length].set(
            keys[start:start + length])
        groups = bsa.step_groups(*rows[1:], T, SP.stride)
        sums = bsa.group_sums(k_new, groups, SP.stride)
        seen = bsa.half_keys(pool, 0, bt[rows[0][:1]], rows[1][:1],
                             jnp.asarray([0]), sums, groups, SP)[0].reshape(
                                 -1, KVH, hd)
        start += length
        whole = start // SP.stride
        want = keys[:whole * SP.stride].reshape(whole, SP.stride, KVH, hd)
        np.testing.assert_allclose(seen[:whole], want.mean(1),
                                   rtol=1e-5, atol=1e-6)
        pool = bsa.compressed_append(pool, sums[None], groups, rows[0], bt,
                                     SP)
    by_page = pool[0].reshape(-1, SP.entries, KVH, hd)
    held = by_page[bt[1]].reshape(maxp * SP.entries, KVH, hd)
    np.testing.assert_allclose(held[:whole], want.mean(1),
                               rtol=1e-5, atol=1e-6)
    # the other slot's pages were never written
    assert bool(jnp.all(by_page[bt[0]] == 9.0))


def test_host_counts_of_a_step():
    sp = bsa.BlockSparse()
    # below dense_len every key; past it 63 whole blocks and the own
    assert bsa.sel_token_count([100], [1], sp) == 101
    assert bsa.sel_token_count([20000], [1], sp) == 63 * 64 + 20000 % 64 + 1
    assert bsa.sel_token_count([8190], [4], sp) == (
        8191 + 8192 + 2 * (63 * 64) + 1 + 2)
    assert bsa.walk_page_count([100, 20000, 20032, 0], [1, 1, 1, 0], 2, sp,
                               64) == 2 * (2 + 64 + 63)
    assert bsa.walk_page_count([16384], [512], 2, sp, 64) == 2 * 256


def _params(seed=0):
    return sala.init_params(jax.random.key(seed), CFG)


def test_device_counter_is_the_host_count_for_rows_of_one_token():
    cfg = dataclasses.replace(CFG, mixer_types=(L, S))
    params = sala.init_params(jax.random.key(0), cfg)
    maxp, slots = 16, 4
    cache = sala.init_cache(cfg, slots * maxp, PAGE, slots)
    table = np.arange(slots * maxp, dtype=np.int32).reshape(slots, maxp)
    step = jax.jit(lambda *a: sala.ragged_step(*a[:8], cfg, a[8]))
    from ray_tpu.ops.ragged_paged_attention import pack_ragged_batch

    toks = np.random.default_rng(0).integers(1, 97, 120).tolist()
    for start in range(0, 96, 8):
        packed = pack_ragged_batch(
            [{"slot": 1, "start": start, "tokens": toks[start:start + 8]}],
            16, slots)
        _, cache = step(params, packed[0], *packed[3:], table, cache)
    before = np.asarray(cache["sel_pages"]).copy()
    assert before[0] == 0 and before[1] > 0
    want = 0
    for start in range(96, 104):
        packed = pack_ragged_batch(
            [{"slot": 1, "start": start, "tokens": toks[start:start + 1]}],
            8, slots)
        _, cache = step(params, packed[0], *packed[3:], table, cache)
        want += bsa.walk_page_count([start], [1], cfg.n_kv_heads, SP, PAGE)
        # at most topk pages a KV head and layer, whatever the context
        assert bsa.walk_page_count([start], [1], 1, SP, PAGE) <= SP.topk
    after = np.asarray(cache["sel_pages"])
    assert after[0] - before[0] == want and after[1] == before[1]


def test_device_counts_the_cells_of_rows_of_one_token():
    """``walk_cells[0]`` is ``ceil(pages / G)`` a (row, KV head) and layer
    where ``sel_pages[0]`` is the pages: three pages in cells of two are
    two cells."""
    G = 2
    cfg = dataclasses.replace(CFG, mixer_types=(L, S))
    params = sala.init_params(jax.random.key(0), cfg)
    maxp, slots = 16, 4
    cache = sala.init_cache(cfg, slots * maxp, PAGE, slots)
    table = np.arange(slots * maxp, dtype=np.int32).reshape(slots, maxp)

    @jax.jit
    def step(*a):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(bsa, "CELL_PAGES", G)
            return sala.ragged_step(*a[:8], cfg, a[8])

    from ray_tpu.ops.ragged_paged_attention import pack_ragged_batch

    toks = np.random.default_rng(0).integers(1, 97, 50).tolist()
    for start in range(0, 40, 8):
        packed = pack_ragged_batch(
            [{"slot": 1, "start": start, "tokens": toks[start:start + 8]}],
            16, slots)
        _, cache = step(params, packed[0], *packed[3:], table, cache)
    assert cache["walk_cells"][0] == 0 and cache["walk_cells"][1] > 0
    pages = cells = 0
    for start in range(40, 44):
        packed = pack_ragged_batch(
            [{"slot": 1, "start": start, "tokens": toks[start:start + 1]}],
            8, slots)
        _, cache = step(params, packed[0], *packed[3:], table, cache)
        n = bsa.walk_page_count([start], [1], 1, SP, PAGE)
        pages += cfg.n_kv_heads * n
        cells += cfg.n_kv_heads * -(-n // G)
    # position 40 opens a block: its three pages are two cells
    assert bsa.walk_page_count([40], [1], 1, SP, PAGE) == 3
    assert int(cache["sel_pages"][0]) == pages
    assert int(cache["walk_cells"][0]) == cells == 16


def _engine_config(**kw):
    return EngineConfig(max_slots=4, max_seq_len=128, page_size=PAGE,
                        num_pages=64, ragged_batching=True, **kw)


SERVED_PLAN = {"past": 96, "length": 128, "answer": 8}


@pytest.fixture(scope="module")
def engine_run():
    """Five requests through ``LLMEngine`` at the toy size's eight layers
    (two sparse layers, so the second one's pools; two lightning scans):
    chunks then decode, rows interleaved, four slots for five requests."""
    params = _params()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 97, n).tolist()
               for n in (100, 40, 12, 5, 60)]
    eng = LLMEngine(params, sala_paged_adapter(CFG),
                    _engine_config(prefill_chunk=8, token_budget=9))
    try:
        streams = [eng.submit(p, max_new_tokens=5, temperature=0.0)
                   for p in prompts]
        batched = [s.result(timeout_s=600) for s in streams]
        stats = eng.stats()
        alone = eng.generate(prompts[0], max_new_tokens=5, temperature=0.0)
    finally:
        eng.shutdown()
    return params, prompts, batched, stats, alone


def test_engine_serves_pages_and_state_side_by_side(engine_run):
    params, prompts, batched, stats, alone = engine_run
    state = stats["state_cache"]
    assert state["slots"] == 4 and state["live"] == 0
    assert state["resets"] == 5
    assert state["bytes_per_slot"] == CFG.state_bytes_per_slot() == (
        6 * 4 * 16 * 16 * 4)
    assert np.asarray(stats["model_counters"]["sel_pages"]).sum() > 0
    assert batched[0] == alone
    # the logits-argmax continuation of the plain reference
    with jax.default_matmul_precision("highest"):
        want = reference_sala.forward(
            params, jnp.asarray(prompts[0] + alone), HF,
            logits_from=len(prompts[0]) - 1)["logits"]
    assert alone == [int(np.argmax(want[i])) for i in range(5)]


def test_served_check_replays_what_the_engine_served(engine_run):
    """The runner's check of the served tokens, at full depth: the one
    request past the mark and the last other to finish."""
    params, prompts, batched, _stats, _alone = engine_run
    out = serve_sala.served_check(HF, params, list(zip(prompts, batched)),
                                  SERVED_PLAN)
    assert out["ok"] and out["layers"] == 8, out
    assert out["positions"] == [105, 65] and out["tokens"] == 10, out
    assert out["rel_short_max"] < 1e-4 < out["rel_short_swapped_median"], out


def test_served_check_refuses_another_requests_answer(engine_run):
    params, prompts, batched, _stats, _alone = engine_run
    served = list(zip(prompts, batched[-1:] + batched[1:-1] + batched[:1]))
    out = serve_sala.served_check(HF, params, served, SERVED_PLAN)
    assert out["requests"] == 2 and not out["ok"], out
    assert out["rel_short_max"] > serve_sala.SERVED_MARGIN, out


def test_served_check_wants_a_request_past_the_mark(engine_run):
    params, prompts, batched, _stats, _alone = engine_run
    out = serve_sala.served_check(HF, params, list(zip(prompts, batched)),
                                  dict(SERVED_PLAN, past=105))
    assert not out["ok"] and out["tokens"] == 0, out


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
        LLMEngine(_params(), sala_paged_adapter(CFG), EngineConfig(**cfg))
    assert word in str(e.value)


@pytest.mark.parametrize("kw", [{"prefill_chunk": 8},
                                {"prefill_chunk": 4, "token_budget": 10}])
def test_engine_refuses_a_row_past_the_forced_window(kw):
    """The walk's self cell is plain causal attention, which is the
    function only while a row's fresh tokens lie inside the forced window
    of its last (window - block + 1 = 9 here): a row may take the whole
    token budget (4 slots + a chunk of 8 = 12, or the 10 given)."""
    assert SP.max_row_tokens == 9
    with pytest.raises(ValueError, match="max_row_tokens"):
        LLMEngine(_params(), sala_paged_adapter(CFG), _engine_config(**kw))


def test_the_page_has_to_be_the_block():
    with pytest.raises(AssertionError, match="block"):
        sala.init_cache(CFG, 8, 16, 2)


def test_published_config_file_builds_the_held_layers():
    import json
    import pathlib

    path = (pathlib.Path(__file__).resolve().parent.parent / "benchmarks"
            / "configs" / "minicpm_sala_pp2.json")
    hf = json.loads(path.read_text())
    cfg = serve_sala.model_config(hf)
    kinds = cfg.layer_kinds()
    assert (kinds.count(S), kinds.count(L)) == (4, 12)
    assert tuple(kinds) == sala.PUBLISHED_MIXERS[9:25]
    assert tuple(hf["published"]["mixer_types"]) == sala.PUBLISHED_MIXERS
    assert hf["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert cfg.state_bytes_per_slot() == 12 * 2_097_152
    assert cfg.pool_bytes_per_token() == 4_352
    assert dataclasses.asdict(cfg.sparse) == dataclasses.asdict(
        bsa.BlockSparse())
    # the decays follow the PUBLISHED positions, 10 the first lightning
    lam = np.asarray(sala.decay_rates(cfg))
    f = 1 - 10 / 31 + 1e-5
    np.testing.assert_allclose(lam[0, 0], np.exp(-2 ** -0.25 * f), rtol=1e-6)
    assert set(reference_sala.ASSUMED) >= {"sparse_config",
                                           "lightning_decay", "dense_switch"}
