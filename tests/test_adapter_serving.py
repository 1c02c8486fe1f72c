"""Multi-tenant LoRA multiplexing: paged adapter pool + segmented
batched LoRA matmul (serve/adapter_pool.py, ops/segmented_lora.py).

Correctness contract: one ragged step batching rows with DIFFERENT
adapter ids is byte-identical per request to serving each request
alone (the gathered-einsum delta is row-independent), and a row with
``adapter_id == ""`` is byte-identical to adapter-off serving (the
null adapter gathers the pool's never-written scratch page — exact
zeros, and adding 0.0 is exact in IEEE).

Allocator contract (the PrefixIndex refcount discipline): eviction
only ever claims refcount-0 page sets, release of an unborrowed id
raises, and content-identical ids dedup onto one upload.

Failover: the continuation replay re-resolves the adapter on a
survivor (the default loader derives factors deterministically from
the id, so every replica loads byte-identical weights) and the stream
finishes exactly — same tokens, RETRYING recorded.
"""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.ops import segmented_lora as _sl
from ray_tpu.serve.adapter_pool import AdapterPool, AdapterPoolPressure
from ray_tpu.serve.llm_engine import (
    EngineConfig,
    LLMEngine,
    LLMServer,
    llama_paged_adapter,
)

pytestmark = pytest.mark.long_file(60)

PAGE = 16

CFG = llama.LlamaConfig(
    vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, max_seq_len=128, remat=False, dtype=jnp.float32,
    param_dtype=jnp.float32,
)
LORA = _sl.LoRAConfig(rank=4, alpha=8.0)
LORA_CFG = dataclasses.replace(CFG, lora=LORA)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.key(0), CFG)


def _engine(params, cfg, **kw):
    ecfg = dict(max_slots=4, max_seq_len=128, min_prefill_bucket=16,
                page_size=PAGE, ragged_batching=True, token_budget=36)
    ecfg.update(kw)
    return LLMEngine(params, llama_paged_adapter(cfg),
                     EngineConfig(**ecfg))


# -- acceptance test 1: segmented batch == sequential oracle -----------------


def test_mixed_adapter_batch_matches_sequential_oracle(params):
    """Greedy output of a ragged batch mixing three adapter ids (and a
    base-model row) is byte-identical PER REQUEST to running each
    request alone on the same engine — the segmented gathered-einsum
    only ever reads a row's own gathered factors."""
    eng = _engine(params, LORA_CFG)
    reqs = [([1, 2, 3], "tenant-a"), ([4, 5, 6, 7], "tenant-b"),
            ([9, 3, 1], ""), ([2, 8, 5], "tenant-a"),
            ([7, 7, 2, 9], "tenant-c")]
    try:
        oracle = [eng.submit(p, max_new_tokens=8, temperature=0.0,
                             adapter_id=aid).result(timeout_s=120)
                  for p, aid in reqs]
        streams = [eng.submit(p, max_new_tokens=8, temperature=0.0,
                              adapter_id=aid) for p, aid in reqs]
        batched = [s.result(timeout_s=120) for s in streams]
        assert batched == oracle
        # Distinct adapters actually produce distinct continuations —
        # otherwise the parity above proves nothing.
        assert oracle[0] != eng.submit(
            reqs[0][0], max_new_tokens=8, temperature=0.0,
            adapter_id="tenant-b").result(timeout_s=120)
        st = eng.stats()["adapters"]
        assert st["borrowed_refs"] == 0  # borrows drain with the slots
        assert st["misses"] >= 3 and st["hits"] >= 1
    finally:
        eng.shutdown()


# -- acceptance test 2: "" rows == adapter-off serving -----------------------


def test_null_adapter_byte_identical_to_adapter_off(params):
    """A LoRA-enabled engine serving ``adapter_id == ""`` emits the
    same bytes as an engine with no adapter plumbing at all: base
    steps still dispatch the unmodified base program, and "" rows in a
    mixed step add the scratch page's exact zeros."""
    prompts = [[1, 2, 3], [4, 5, 6, 7], [9, 3, 1]]
    eng_off = _engine(params, CFG)
    try:
        want = [eng_off.submit(p, max_new_tokens=8,
                               temperature=0.0).result(timeout_s=120)
                for p in prompts]
    finally:
        eng_off.shutdown()
    eng = _engine(params, LORA_CFG)
    try:
        streams = [eng.submit(p, max_new_tokens=8, temperature=0.0,
                              adapter_id="") for p in prompts]
        assert [s.result(timeout_s=120) for s in streams] == want
        # And "" rows INSIDE a mixed batch stay identical too.
        mixed = [eng.submit(p, max_new_tokens=8, temperature=0.0,
                            adapter_id=aid)
                 for p, aid in zip(prompts, ("", "tenant-a", ""))]
        got = [s.result(timeout_s=120) for s in mixed]
        assert got[0] == want[0] and got[2] == want[2]
        assert got[1] != want[1]  # the adapter row DID change
    finally:
        eng.shutdown()


def test_adapter_requires_lora_engine(params):
    eng = _engine(params, CFG)
    try:
        with pytest.raises(ValueError, match="adapter"):
            eng.submit([1, 2, 3], max_new_tokens=2, temperature=0.0,
                       adapter_id="tenant-a")
    finally:
        eng.shutdown()


# -- acceptance test 3: pool allocator rules ---------------------------------


def test_eviction_never_evicts_borrowed_and_dedups(params):
    """Refcount-0 LRU under pressure: with every resident adapter
    borrowed the pool raises AdapterPoolPressure instead of evicting;
    once a borrow drains, eviction claims exactly the refcount-0 set.
    Content-identical ids dedup onto one upload, and re-loading an
    evicted id is a fresh miss that works."""
    pool = AdapterPool(CFG, LORA, page_elems=1024, num_pages=0)
    pp = pool.pages_per_adapter
    # Re-build sized for exactly two resident adapters.
    pool = AdapterPool(CFG, LORA, page_elems=1024, num_pages=2 * pp)
    pool.acquire("a")
    pool.acquire("b")
    assert pool.stats()["pages_free"] == 0
    with pytest.raises(AdapterPoolPressure):
        pool.acquire("c")  # both resident sets borrowed: nothing to evict
    assert pool.resident_ids() == ["a", "b"]  # pressure evicted nothing
    assert pool.refcount("a") == 1 and pool.refcount("b") == 1

    # A second borrow of a resident id is a hit, not a re-upload.
    pool.acquire("a")
    st = pool.stats()
    assert pool.refcount("a") == 2 and st["hits"] == 1
    pool.release("a")

    pool.release("b")
    pool.acquire("c")  # evicts b (refcount 0), never borrowed a
    st = pool.stats()
    assert st["evictions"] == 1
    assert pool.resident_ids() == ["a", "c"]
    assert pool.refcount("a") == 1  # untouched through the eviction

    pool.release("c")
    pool.release("a")
    with pytest.raises(RuntimeError, match="underflow"):
        pool.release("a")  # double-free surfaces, never masks

    # Re-load of the evicted id: known hash, pages gone -> fresh miss.
    misses = pool.stats()["misses"]
    pool.acquire("b")
    assert pool.stats()["misses"] == misses + 1
    assert "b" in pool.resident_ids()
    pool.release("b")


def test_content_hash_dedup_shares_one_upload(params):
    """Two ids whose loaders produce byte-identical factors share one
    page set: the second acquire is a HIT (no upload), both ids appear
    resident, and the shared block is one eviction unit."""
    content = _sl.init_adapter_params(jax.random.key(5), CFG, LORA)

    def loader(adapter_id):
        return content  # every id -> identical bytes

    pool = AdapterPool(CFG, LORA, page_elems=1024, loader=loader)
    pool.acquire("x")
    free_after_first = pool.stats()["pages_free"]
    pool.acquire("y")
    st = pool.stats()
    assert st["pages_free"] == free_after_first  # no second upload
    assert st["hits"] == 1 and st["misses"] == 1
    assert st["resident"] == 2 and st["resident_ids"] == ["x", "y"]
    pool.release("x")
    pool.release("y")


def test_segmented_gather_roundtrip_bit_exact(params):
    """Pool pages -> gather_adapter_flat -> gather_adapter_stacks is
    bit-exact against the flattened source factors, and the null row
    (page table row 0 = scratch) gathers exact zeros."""
    pool = AdapterPool(CFG, LORA, page_elems=1024)
    pool.acquire("tenant-a")
    table = jnp.asarray(pool.page_table(["tenant-a"]))
    flat = _sl.gather_adapter_flat(pool.device_pool, table)
    want = _sl.flatten_adapter(
        _sl.default_adapter_loader(CFG, LORA)("tenant-a"), CFG, LORA)
    got = np.asarray(flat)[1, :pool.elems]
    assert np.array_equal(got, want)
    assert not np.asarray(flat)[0].any()   # null row: exact zeros
    assert not np.asarray(flat)[2:].any()  # unused rows: exact zeros
    pool.release("tenant-a")


# -- satellite: adapter_id on the request plane ------------------------------


def test_adapter_id_in_request_rows_and_cli(params):
    """adapter_id rides the request-plane rows end to end: ring ->
    state.list_requests keep-tuple -> `raytpu list requests` column
    (right after prefix_hit), deterministic across snapshots."""
    from ray_tpu.scripts import cli
    from ray_tpu.util import state

    cols = cli._LIST_ROUTES["requests"][1]
    assert "adapter_id" in cols
    assert cols.index("adapter_id") == cols.index("prefix_hit") + 1

    eng = _engine(params, LORA_CFG)
    try:
        s1 = eng.submit([1, 2, 3], max_new_tokens=4, temperature=0.0,
                        adapter_id="tenant-a")
        s1.result(timeout_s=120)
        s2 = eng.submit([4, 5, 6], max_new_tokens=4, temperature=0.0)
        s2.result(timeout_s=120)
        for _snap in range(2):  # deterministic across snapshots
            rows = {r["request_id"]: r for r in state.list_requests(
                filters=[("engine", "=", eng.engine_id)], limit=10)}
            assert rows[s1.request_id]["adapter_id"] == "tenant-a"
            assert rows[s2.request_id]["adapter_id"] == ""
    finally:
        eng.shutdown()


# -- acceptance test 4: failover re-resolves the adapter ---------------------


def _slow_lora_adapter_factory(cfg):
    """Paged LoRA adapter with a throttled step (with and without
    ``lora=``) so a 12-token stream spans an observable window and the
    kill reliably lands mid-decode.  The sleep rides jax.debug.callback:
    the step is traced under jit, so a bare time.sleep would only fire
    at trace time."""
    base = llama_paged_adapter(cfg)

    def slow_step(*args, **kwargs):
        jax.debug.callback(lambda: time.sleep(0.03), ordered=True)
        return base.ragged_step(*args, **kwargs)

    return dataclasses.replace(base, ragged_step=slow_step)


def test_midstream_kill_reresolves_adapter_on_survivor(params):
    """SIGKILL the replica serving an adapter stream mid-decode: the
    continuation replay re-loads the adapter on the survivor (the
    deterministic loader gives it byte-identical factors — no weight
    shipping) and the stream finishes with the exact single-engine
    token sequence, RETRYING recorded on the router ring."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core import api
    from ray_tpu.serve import request_events
    from ray_tpu.utils.test_utils import ReplicaKiller

    prompt, n_new, aid = [3, 1, 4, 1, 5, 9], 12, "tenant-x"
    oracle = _engine(params, LORA_CFG)
    try:
        want = oracle.submit(prompt, max_new_tokens=n_new,
                             temperature=0.0,
                             adapter_id=aid).result(timeout_s=120)
    finally:
        oracle.shutdown()

    ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
    serve.start()
    try:
        app = serve.deployment(num_replicas=2, max_ongoing_requests=8)(
            LLMServer
        ).bind(
            LORA_CFG,
            EngineConfig(max_slots=8, max_seq_len=128,
                         min_prefill_bucket=16, page_size=PAGE,
                         ragged_batching=True, token_budget=64),
            lambda: params,
            adapter_factory=_slow_lora_adapter_factory,
        )
        handle = serve.run(app, name="llmlora", route_prefix=None)
        # Prime the router's long-poll table.
        handle.remote({"tokens": [1, 2, 3], "max_new_tokens": 1,
                       "temperature": 0.0}).result(timeout_s=300)
        from ray_tpu.serve.handle import _routers
        router = _routers[("llmlora", "LLMServer")]
        with router._lock:
            replicas = {rid: info.handle
                        for rid, info in router._replicas.items()}
        assert len(replicas) == 2

        gen = handle.options(stream=True).remote(
            {"tokens": prompt, "max_new_tokens": n_new,
             "temperature": 0.0, "adapter_id": aid})
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

        # The survivor re-resolved the adapter: its pool holds the id.
        (survivor_rid,) = [r for r in replicas if r != victim_rid]
        st = api.get(replicas[survivor_rid].handle_request.remote(
            "stats", (), {}), timeout=60)
        assert aid in st["adapters"]["resident_ids"]
        assert st["adapters"]["borrowed_refs"] == 0

        # RETRYING recorded on the router's failover ring.
        rows = [r for r in request_events.snapshot_rows()
                if r["engine"] == "router:llmlora/LLMServer"
                and r["request_id"] == gen.request_id]
        assert rows and rows[0]["state"] == "FINISHED"
        assert "RETRYING" in rows[0]["state_ts"]
        assert rows[0]["attempt"] >= 1
        assert rows[0]["adapter_id"] == aid
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
