"""Radix-tree prefix cache: COW KV pages, cache-aware routing, and
prefix-resumed failover.

Correctness contract: a cache-enabled engine (greedy, temperature=0)
is byte-identical to a cache-disabled engine AND to the full-prefix
recompute oracle, across shared-prefix hits, the exact-full-prompt COW
split, and eviction pressure — a cache that changes even one token is
worse than no cache.

Accounting contract (the refcount model prefix_index.py documents):
after every terminal path — finish, cancel, drain/PREEMPTED — every
physical page is in exactly one of free list / prefix index /
slot-owned, borrowed pages are a subset of cached, and nothing leaks
or double-frees.

Failover: replicas are in-process thread actors, so the test maps
replica actor -> engine directly, kills the replica actually serving
the stream (SIGKILL semantics), and asserts the continuation replay
resumed from the survivor's cached prefix instead of re-prefilling
from token 0.
"""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.serve.llm_engine import (
    EngineConfig,
    LLMEngine,
    LLMServer,
    llama_paged_adapter,
)
from ray_tpu.serve.prefix_index import (
    PrefixIndex,
    match_depth,
    prefix_hashes,
)
from tests import oracle

pytestmark = pytest.mark.long_file(82)

CFG = llama.LlamaConfig(
    vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, max_seq_len=128, remat=False, dtype=jnp.float32,
    param_dtype=jnp.float32,
)

PAGE = 16


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.key(0), CFG)


def greedy_reference(params, prompt, n_tokens):
    return oracle.greedy_tokens(params, CFG, prompt, n_tokens)


def _engine(params, **kw):
    cfg = dict(max_slots=4, max_seq_len=128, min_prefill_bucket=16,
               page_size=PAGE, ragged_batching=True, token_budget=36,
               prefix_cache=True)
    cfg.update(kw)
    return LLMEngine(params, llama_paged_adapter(CFG), EngineConfig(**cfg))


def _assert_pool_consistent(eng):
    """Every physical page in exactly one of free / cached / slot-owned;
    borrowed = cached pages a slot additionally maps; no duplicates."""
    free = list(eng._free_pages)
    assert len(free) == len(set(free)), "duplicate pages on free list"
    free = set(free)
    cached = eng._prefix.pages() if eng._prefix is not None else set()
    owned, borrowed = set(), set()
    for slot, pages in eng._slot_pages.items():
        b = eng._slot_borrowed.get(slot, []) if eng._prefix else []
        assert pages[:len(b)] == b
        for p in pages[:len(b)]:
            borrowed.add(p)
        tail = pages[len(b):]
        assert not owned & set(tail), "page owned by two slots"
        owned |= set(tail)
    assert borrowed <= cached, "borrowed page not owned by the index"
    assert not free & cached, "page both free and cached"
    assert not free & owned, "page both free and slot-owned"
    assert not cached & owned, "page both cached and slot-owned"
    assert len(free) + len(cached) + len(owned) == eng._num_pages, (
        f"pool leak: {len(free)} free + {len(cached)} cached + "
        f"{len(owned)} owned != {eng._num_pages}")


def _settle(eng, timeout_s=30.0):
    """Wait for the engine loop to go quiescent (all slots free, no
    queued work) so the pool invariant can be read without racing it."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if (len(eng._free_slots) == eng.config.max_slots
                and eng._waiting.empty() and not eng._prefilling
                and not eng._backlog):
            return
        time.sleep(0.005)
    raise TimeoutError("engine never went quiescent")


# -- index unit tests --------------------------------------------------------

def test_prefix_index_acquire_release_insert_evict():
    idx = PrefixIndex(4)
    a = list(range(1, 13))                      # 3 full pages
    assert idx.acquire(a) == []                 # cold: no match
    assert idx.insert(a, [10, 11, 12]) == {10, 11, 12}
    assert idx.cached_pages == 3
    # Borrow the shared 2-page prefix; divergent third page no match.
    got = idx.acquire(a[:8] + [99, 99, 99, 99])
    assert got == [10, 11]
    assert idx.refcount(10) == 1 and idx.refcount(12) == 0
    # Borrowed path is pinned: only the unborrowed leaf can go.
    assert idx.evict(3) == [12]
    idx.release(got)
    # Cascading LRU after release: leaf 11 then its parent 10.
    assert idx.evict(3) == [11, 10]
    assert idx.cached_pages == 0 and idx.evicted_total == 3
    # Double-free is a bug, not a silent no-op.
    with pytest.raises(RuntimeError, match="underflow"):
        idx.release([10])
    # Existing nodes never adopt a second page for the same chunk.
    assert idx.insert(a, [20, 21]) == {20, 21}
    assert idx.insert(a, [30, 31, 32]) == {32}


def test_prefix_summary_match_depth_roundtrip():
    idx = PrefixIndex(4)
    shared = [7, 1, 5, 3, 2, 2, 4, 9]
    idx.insert(shared + [8, 8, 8, 8], [1, 2, 3])
    s = idx.summary()
    assert s["page"] == 4 and len(s["hashes"]) == 3
    # The router-side chain matches what the index published.
    assert match_depth(shared + [50, 60], s) == 8
    assert match_depth(shared + [8, 8, 8, 8, 1], s) == 12
    assert match_depth([9, 9, 9, 9], s) == 0
    assert match_depth(shared, None) == 0
    assert match_depth(shared, {"page": 0, "hashes": [1]}) == 0
    # Chained hashes identify the PATH: same chunk at depth 2 under a
    # different depth-1 chunk must not collide.
    h1 = prefix_hashes([1, 2, 3, 4, 9, 9, 9, 9], 4)
    h2 = prefix_hashes([5, 6, 7, 8, 9, 9, 9, 9], 4)
    assert h1[1] != h2[1]


# -- engine e2e correctness --------------------------------------------------

def test_shared_prefix_hit_byte_identical(params):
    """Second request sharing a 2-page prefix hits the cache, resumes
    prefill at the boundary, and still emits exactly the oracle (and
    the cache-off engine's) tokens."""
    rng = np.random.default_rng(3)
    shared = rng.integers(1, 127, size=2 * PAGE).tolist()
    prompts = [shared + rng.integers(1, 127, size=7).tolist()
               for _ in range(3)]
    wants = [greedy_reference(params, p, 6) for p in prompts]

    cold = _engine(params, prefix_cache=False)
    try:
        got_cold = [cold.generate(p, max_new_tokens=6, temperature=0.0)
                    for p in prompts]
    finally:
        cold.shutdown()
    assert got_cold == wants

    eng = _engine(params)
    try:
        streams = []
        for p in prompts:  # sequential so each can hit the last's pages
            s = eng.submit(p, max_new_tokens=6, temperature=0.0)
            assert s.result(timeout_s=120) is not None
            streams.append(s)
        assert [s.result(timeout_s=120) for s in streams] == wants
        assert streams[0]._req.prefix_hit == 0
        for s in streams[1:]:
            assert s._req.prefix_hit == 2 * PAGE
        st = eng.stats()
        assert st["prefix"]["hit_tokens"] == 2 * 2 * PAGE
        assert st["kv_pages_cached"] == st["prefix"]["cached_pages"] > 0
        _settle(eng)
        _assert_pool_consistent(eng)
    finally:
        eng.shutdown()


def test_exact_full_prompt_hit_cow_split(params):
    """Resubmitting an identical prompt is a full-prompt hit: the
    mandatory last-token re-run would write inside the deepest shared
    page, so the engine COW-splits it — outputs stay byte-identical
    and the shared page is never mutated for a later third borrower."""
    rng = np.random.default_rng(4)
    prompt = rng.integers(1, 127, size=2 * PAGE).tolist()  # page-aligned
    want = greedy_reference(params, prompt, 6)
    sibling = prompt[:PAGE] + rng.integers(1, 127, size=5).tolist()
    want_sib = greedy_reference(params, sibling, 6)
    eng = _engine(params)
    try:
        s1 = eng.submit(prompt, max_new_tokens=6, temperature=0.0)
        assert s1.result(timeout_s=120) == want
        s2 = eng.submit(prompt, max_new_tokens=6, temperature=0.0)
        assert s2.result(timeout_s=120) == want
        # Full-prompt hit: everything but the re-run token came cached.
        assert s2._req.prefix_hit == len(prompt) - 1
        # The COW split kept the shared depth-2 page intact: a request
        # that borrows it again still decodes exactly.
        s3 = eng.submit(prompt + [9, 9, 9], max_new_tokens=6,
                        temperature=0.0)
        assert s3.result(timeout_s=120) == \
            greedy_reference(params, prompt + [9, 9, 9], 6)
        assert s3._req.prefix_hit == 2 * PAGE
        # Divergence after a shared first page rides the same tree.
        s4 = eng.submit(sibling, max_new_tokens=6, temperature=0.0)
        assert s4.result(timeout_s=120) == want_sib
        assert s4._req.prefix_hit == PAGE
        _settle(eng)
        _assert_pool_consistent(eng)
    finally:
        eng.shutdown()


def test_eviction_pressure_byte_identical(params):
    """A pool too small to cache every distinct prompt must evict
    (refcount-0 LRU) instead of failing admission, and evicted-then-
    recomputed prefixes still produce exact tokens."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 127, size=2 * PAGE + 3).tolist()
               for _ in range(6)]
    wants = [greedy_reference(params, p, 4) for p in prompts]
    eng = _engine(params, max_slots=2, num_pages=10)
    try:
        for _round in range(2):  # second pass re-prefills evicted ones
            for p, w in zip(prompts, wants):
                assert eng.generate(p, max_new_tokens=4,
                                    temperature=0.0) == w
        st = eng.stats()["prefix"]
        assert st["evicted_pages"] > 0
        assert st["inserted_pages"] > st["cached_pages"]
        _settle(eng)
        _assert_pool_consistent(eng)
        assert len(eng._free_pages) + eng._prefix.cached_pages \
            == eng._num_pages
    finally:
        eng.shutdown()


# -- refcount accounting across terminal paths -------------------------------

def test_cancel_returns_refcount_consistent_state(params):
    rng = np.random.default_rng(6)
    shared = rng.integers(1, 127, size=2 * PAGE).tolist()
    eng = _engine(params)
    try:
        eng.generate(shared + [5, 6, 7], max_new_tokens=4,
                     temperature=0.0)  # populate the cache
        held = eng._prefix.cached_pages
        s = eng.submit(shared + [8, 9], max_new_tokens=400,
                       temperature=0.0)
        for _tok in s:  # first token proves the borrow happened
            break
        assert s._req.prefix_hit == 2 * PAGE
        s.cancel()
        s.result(timeout_s=120)
        _settle(eng)
        _assert_pool_consistent(eng)
        # Cancel released the borrow but donated nothing (its tail
        # pages may be partially written).
        assert eng._prefix.stats()["borrowed_refs"] == 0
        assert eng._prefix.cached_pages == held
    finally:
        eng.shutdown()


def test_drain_preempts_with_refcount_consistent_state(params):
    from ray_tpu.core.exceptions import PreemptedError

    rng = np.random.default_rng(7)
    shared = rng.integers(1, 127, size=2 * PAGE).tolist()
    eng = _engine(params)
    try:
        eng.generate(shared + [1, 2], max_new_tokens=4, temperature=0.0)
        s = eng.submit(shared + [3, 4], max_new_tokens=400,
                       temperature=0.0)
        got = []
        err = []

        def consume():
            try:
                for tok in s:
                    got.append(tok)
            except PreemptedError as e:
                err.append(e)

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        deadline = time.monotonic() + 60
        while not got and time.monotonic() < deadline:
            time.sleep(0.005)
        assert got, "stream never started decoding"
        assert eng.drain(0.0) >= 1
        t.join(timeout=60)
        assert err, "drain did not preempt the long stream"
        cont = err[0].continuation
        assert cont["prompt"] == shared + [3, 4]
        assert cont["tokens"] == got  # delivered prefix, exactly
        _assert_pool_consistent(eng)
        assert eng._prefix.stats()["borrowed_refs"] == 0
    finally:
        eng.shutdown()


# -- metrics + state surfaces ------------------------------------------------

def test_prefix_metric_families_pinned(params):
    """The new families are present, well-formed, and named per the
    conventions check_metrics enforces."""
    import importlib.util
    import pathlib

    from ray_tpu.util import metrics

    rng = np.random.default_rng(8)
    shared = rng.integers(1, 127, size=2 * PAGE).tolist()
    eng = _engine(params)
    try:
        for tail in ([1, 2], [3, 4]):
            eng.generate(shared + tail, max_new_tokens=4, temperature=0.0)
    finally:
        eng.shutdown()
    path = (pathlib.Path(__file__).resolve().parent.parent
            / "scripts" / "check_metrics.py")
    spec = importlib.util.spec_from_file_location("check_metrics", path)
    cm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cm)
    assert cm.check_exposition(metrics.export_prometheus(), require=[
        "raytpu_serve_kv_pages_free",
        "raytpu_serve_kv_pages_cached",
        "raytpu_serve_prefix_requests_total",
        "raytpu_serve_prefix_hit_ratio",
        "raytpu_serve_prefix_hit_depth_tokens",
        "raytpu_serve_prefix_cached_pages",
        "raytpu_serve_prefix_evicted_pages_total",
    ]) == []


def test_prefix_hit_in_request_rows_and_cli(params):
    """prefix_hit rides the request-plane rows end to end: ring ->
    state.list_requests keep-tuple -> `raytpu list requests` column,
    deterministic across repeated snapshots."""
    from ray_tpu.scripts import cli
    from ray_tpu.util import state

    assert "prefix_hit" in cli._LIST_ROUTES["requests"][1]
    cols = cli._LIST_ROUTES["requests"][1]
    assert cols.index("prefix_hit") == cols.index("attempt") + 1

    rng = np.random.default_rng(9)
    shared = rng.integers(1, 127, size=2 * PAGE).tolist()
    eng = _engine(params)
    try:
        s1 = eng.submit(shared + [1], max_new_tokens=4, temperature=0.0)
        s1.result(timeout_s=120)
        s2 = eng.submit(shared + [2], max_new_tokens=4, temperature=0.0)
        s2.result(timeout_s=120)
        for _snap in range(2):  # deterministic across snapshots
            rows = {r["request_id"]: r for r in state.list_requests(
                filters=[("engine", "=", eng.engine_id)], limit=10)}
            assert rows[s1.request_id]["prefix_hit"] == 0
            assert rows[s2.request_id]["prefix_hit"] == 2 * PAGE
    finally:
        eng.shutdown()


# -- failover: resume from the survivor's cached prefix ----------------------

def _slow_paged_adapter_factory(cfg):
    """Paged adapter with a throttled ragged step so a 12-token stream
    spans an observable window and the kill reliably lands mid-decode.
    The sleep rides jax.debug.callback: ragged_step is traced under
    jit, so a bare time.sleep would only fire at trace time."""
    import dataclasses

    base = llama_paged_adapter(cfg)

    def slow_step(*args, **kwargs):
        jax.debug.callback(lambda: time.sleep(0.03), ordered=True)
        return base.ragged_step(*args, **kwargs)

    return dataclasses.replace(base, ragged_step=slow_step)


def test_midstream_kill_resumes_from_cached_prefix(params):
    """SIGKILL the replica serving a stream whose prompt prefix BOTH
    replicas hold cached: the continuation replay must finish with the
    exact oracle tokens AND the survivor must have admitted the resumed
    attempt from its cached prefix (prefix_hit == the shared full
    pages), not re-prefilled from token 0.  Replicas are process-mode
    actors, so warming and inspection go through their actor handles."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core import api
    from ray_tpu.serve import request_events
    from ray_tpu.utils.test_utils import ReplicaKiller

    rng = np.random.default_rng(10)
    shared = rng.integers(1, 127, size=2 * PAGE).tolist()
    prompt = shared + rng.integers(1, 127, size=8).tolist()
    n_new = 12
    want = greedy_reference(params, prompt, n_new)

    ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
    serve.start()
    try:
        app = serve.deployment(num_replicas=2, max_ongoing_requests=8)(
            LLMServer
        ).bind(
            CFG,
            EngineConfig(max_slots=8, max_seq_len=128,
                         min_prefill_bucket=16, page_size=PAGE,
                         ragged_batching=True, token_budget=64,
                         prefix_cache=True),
            lambda: params,
            adapter_factory=_slow_paged_adapter_factory,
        )
        handle = serve.run(app, name="llmpfx", route_prefix=None)
        # Prime the router's long-poll table.
        handle.remote({"tokens": [1, 2, 3], "max_new_tokens": 1,
                       "temperature": 0.0}).result(timeout_s=300)
        from ray_tpu.serve.handle import _routers
        router = _routers[("llmpfx", "LLMServer")]
        with router._lock:
            replicas = {rid: info.handle
                        for rid, info in router._replicas.items()}
        assert len(replicas) == 2
        # Warm BOTH replica caches with the shared prefix, bypassing
        # the router (cache-aware routing would pin every shared-prefix
        # request to whichever replica cached it first): cached depth =
        # the 2 full pages of `shared`; the warm tail diverges past the
        # page boundary.
        for h in replicas.values():
            out = api.get(h.handle_request.remote(
                "__call__", ({"tokens": shared + [1, 2, 3],
                              "max_new_tokens": 4,
                              "temperature": 0.0},), {}), timeout=300)
            assert len(out["tokens"]) == 4
            st = api.get(h.handle_request.remote("stats", (), {}))
            assert st["prefix"]["cached_pages"] >= 2
        # The routing summaries propagate replica push loop ->
        # controller -> router broadcast; wait until the router holds
        # a non-empty summary for both replicas.
        deadline = time.monotonic() + 120
        summaries = []
        while time.monotonic() < deadline:
            with router._lock:
                summaries = [r.prefix_summary
                             for r in router._replicas.values()]
            if len(summaries) == 2 and all(
                    isinstance(s, dict) and s.get("hashes")
                    for s in summaries):
                break
            time.sleep(0.01)
        else:
            raise TimeoutError(
                f"summaries never reached the router: {summaries}")

        gen = handle.options(stream=True).remote(
            {"tokens": prompt, "max_new_tokens": n_new,
             "temperature": 0.0})
        outs, errs = [], []

        def consume():
            try:
                for tok in gen:
                    outs.append(tok)
            except BaseException as e:
                errs.append(e)

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        deadline = time.monotonic() + 300
        while len(outs) < 2 and time.monotonic() < deadline:
            time.sleep(0.002)
        assert len(outs) >= 2, "stream never reached decode"

        # Kill the replica actually serving the stream (targeted — a
        # random victim would be a coin flip on failover happening).
        victim_rid = None
        for rid, h in replicas.items():
            if api.get(h.num_ongoing_requests.remote(), timeout=60) > 0:
                victim_rid = rid
        assert victim_rid is not None, "no replica owns the stream"
        killer = ReplicaKiller(api.runtime(), seed=0)
        assert killer.kill_one(
            actor_id=replicas[victim_rid]._actor_id) is not None

        t.join(timeout=300)
        assert not t.is_alive(), f"stream hung after kill ({len(outs)})"
        assert errs == [], f"stream failed: {errs}"
        assert outs == want  # exact continuation: no loss/dup/change

        # The replay re-entered through the survivor's cache: the
        # spliced prompt (prompt + delivered prefix) matched the shared
        # pages, so only the cold tail was re-prefilled.  The
        # survivor's engine ring rows piggyback on its task replies.
        (survivor_rid,) = [r for r in replicas if r != victim_rid]
        st = api.get(replicas[survivor_rid].handle_request.remote(
            "stats", (), {}), timeout=60)
        assert st["prefix"]["hit_tokens"] >= 2 * PAGE
        # Worker rows ship on a ~1 s throttle riding task replies: nudge
        # with cheap stats calls until the resumed row lands.  The
        # victim's stale attempt-0 row (also prefix_hit > 0 — both
        # replicas were warmed) can arrive first, so poll specifically
        # for the survivor's FINISHED resumed row, not just any hit.
        deadline = time.monotonic() + 120
        rows, done = [], []
        while time.monotonic() < deadline:
            api.get(replicas[survivor_rid].handle_request.remote(
                "stats", (), {}), timeout=60)
            rows = [r for r in request_events.snapshot_rows()
                    if r["request_id"] == gen.request_id
                    and r.get("prefix_hit", 0) > 0]
            done = [r for r in rows if r["state"] == "FINISHED"
                    and r["prefix_hit"] == 2 * PAGE]
            if done:
                break
            time.sleep(0.25)
        assert done, f"no FINISHED prefix-resumed row shipped: {rows}"
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_prefix_cache_requires_ragged_paged(params):
    with pytest.raises(ValueError, match="ragged"):
        LLMEngine(params, llama_paged_adapter(CFG), EngineConfig(
            max_slots=2, max_seq_len=128, page_size=PAGE,
            prefix_cache=True))
    with pytest.raises(ValueError, match="copy_page"):
        LLMEngine(params, dataclasses.replace(
            llama_paged_adapter(CFG), copy_page=None), EngineConfig(
            max_slots=2, max_seq_len=128, page_size=PAGE,
            ragged_batching=True, prefix_cache=True))
