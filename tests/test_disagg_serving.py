"""Disaggregated prefill/decode serving + the KV page-migration plane.

Correctness contract: disaggregation is an OPTIMIZATION, never a
semantics change — greedy (temperature=0) streams served across a
prefill→decode handoff are byte-identical to the unified
single-replica oracle, and every failure mode (no decode target, dead
prefill replica, aborted transfer) degrades to the PR-5 continuation
replay — local recompute, never a stall and never a different token.

Accounting contract: pages pinned under a migration lease are
eviction-proof but stay owned by the prefix index, so the pool
invariant extends to free ∪ cached ∪ slot-owned with
borrowed ⊆ cached and leased ⊆ cached — across finish, cancel
mid-migration, and lease release.

Prefix migration: a cold engine ingests a warm engine's exported hot
prefixes and then admits a matching prompt entirely from the migrated
pages (prefix_hit == transferred pages), with no recompute of the
migrated tokens.
"""

import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.serve import kv_transfer
from ray_tpu.serve.config import DeploymentConfig, DisaggConfig
from ray_tpu.serve.kv_transfer import DisaggContext, set_disagg
from ray_tpu.serve.llm_engine import (
    EngineConfig,
    LLMEngine,
    LLMServer,
    llama_paged_adapter,
)
from ray_tpu.utils.test_utils import REPLICA_READY_S
from tests import oracle

CFG = llama.LlamaConfig(
    vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, max_seq_len=128, remat=False, dtype=jnp.float32,
    param_dtype=jnp.float32,
)

PAGE = 4


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.key(0), CFG)


def greedy_reference(params, prompt, n_tokens):
    return oracle.greedy_tokens(params, CFG, prompt, n_tokens)


def _engine(params, **kw):
    cfg = dict(max_slots=4, max_seq_len=64, min_prefill_bucket=16,
               page_size=PAGE, ragged_batching=True, token_budget=64,
               prefix_cache=True)
    cfg.update(kw)
    return LLMEngine(params, llama_paged_adapter(CFG), EngineConfig(**cfg))


def _assert_pool_consistent(eng):
    """test_prefix_cache's invariant, extended with the migration
    lease: every physical page in exactly one of free / cached /
    slot-owned, borrowed ⊆ cached, AND leased ⊆ cached (a lease pins,
    it does not own)."""
    free = list(eng._free_pages)
    assert len(free) == len(set(free)), "duplicate pages on free list"
    free = set(free)
    cached = eng._prefix.pages()
    owned = set()
    for slot, pages in eng._slot_pages.items():
        b = eng._slot_borrowed.get(slot, [])
        tail = pages[len(b):]
        assert not owned & set(tail), "page owned by two slots"
        owned |= set(tail)
    leased = eng._prefix.leased_pages()
    assert leased <= cached, "leased page not owned by the index"
    assert not free & cached and not free & owned
    assert not cached & owned
    assert len(free) + len(cached) + len(owned) == eng._num_pages, (
        f"pool leak: {len(free)} free + {len(cached)} cached + "
        f"{len(owned)} owned != {eng._num_pages}")


def _metric_total(pattern: str) -> float:
    """Sum of samples whose exposition line matches ``pattern``
    (regex over family + label block)."""
    from ray_tpu.util import metrics

    total = 0.0
    pat = re.compile(rf"^{pattern}[^ ]* (\S+)$")
    for line in metrics.export_prometheus().splitlines():
        m = pat.match(line)
        if m:
            total += float(m.group(1))
    return total


# -- config + role validation ------------------------------------------------

def test_disagg_config_validation(params):
    with pytest.raises(ValueError, match="prefill_replicas"):
        DisaggConfig(prefill_replicas=0)
    with pytest.raises(ValueError, match="transfer"):
        DisaggConfig(transfer="fp4")
    with pytest.raises(ValueError, match="handoff_after_tokens"):
        DisaggConfig(handoff_after_tokens=0)
    with pytest.raises(ValueError, match="migration_timeout_s"):
        DisaggConfig(migration_timeout_s=0.0)
    # At least one decode replica must exist.
    with pytest.raises(ValueError, match="num_replicas > prefill"):
        DeploymentConfig(num_replicas=1, disagg=DisaggConfig())
    from ray_tpu.serve.config import AutoscalingConfig
    with pytest.raises(ValueError, match="autoscaling"):
        DeploymentConfig(
            disagg=DisaggConfig(),
            autoscaling_config=AutoscalingConfig(min_replicas=2,
                                                 max_replicas=4))
    # A role other than unified requires the prefix trie — migration
    # is keyed by its chained path hashes.
    set_disagg(DisaggContext(role="prefill"))
    try:
        with pytest.raises(ValueError, match="prefix_cache"):
            LLMServer(CFG, EngineConfig(max_slots=2, max_seq_len=64,
                                        prefix_cache=False),
                      lambda: params)
    finally:
        set_disagg(None)


# -- migration verbs + lease accounting (engine level) -----------------------

def test_migration_lease_pins_against_eviction(params):
    """Pages under a migration lease are eviction-proof: traffic that
    forces refcount-0 LRU eviction must skip them, the export stays
    valid, and after release they evict normally.  Includes the
    cancel-mid-migration path: a stream borrowing leased pages is
    cancelled and the pool accounting still balances."""
    rng = np.random.default_rng(11)
    hot = rng.integers(1, 127, size=2 * PAGE).tolist()
    eng = _engine(params, max_slots=2, num_pages=16)
    try:
        want = greedy_reference(params, hot, 4)
        assert eng.generate(hot, max_new_tokens=4, temperature=0.0) == want
        lease = eng.migration_lease(hot + want)
        assert lease is not None
        # The full-page depth of the finished sequence is leased.
        n_leased = (len(hot) + 4 - 1) // PAGE
        assert len(lease["pages"]) == n_leased
        assert lease["tokens"] == (hot + want)[:n_leased * PAGE]
        _assert_pool_consistent(eng)

        # Cancel mid-migration: a stream borrowing the leased prefix is
        # cancelled; borrow returns, lease stays, nothing leaks.
        s = eng.submit(hot + [9, 9], max_new_tokens=20, temperature=0.0)
        for _tok in s:
            break
        assert s._req.prefix_hit == 2 * PAGE
        s.cancel()
        s.result(timeout_s=120)

        # Eviction pressure: distinct prompts overflow the 12-page pool.
        for i in range(6):
            p = rng.integers(1, 127, size=2 * PAGE + 3).tolist()
            assert eng.generate(p, max_new_tokens=4, temperature=0.0) \
                == greedy_reference(params, p, 4)
        assert eng.stats()["prefix"]["evicted_pages"] > 0
        # The leased pages survived every eviction wave...
        assert set(lease["pages"]) <= eng._prefix.pages()
        assert eng._prefix.leased_pages() == set(lease["pages"])
        _assert_pool_consistent(eng)
        # ...so the export is still content-correct.
        transfer = eng.migration_export(lease["lease_id"], mode="exact")
        kv_transfer.verify_transfer(transfer)
        assert transfer["tokens"] == lease["tokens"]

        assert eng.migration_release(lease["lease_id"]) is True
        assert eng.migration_release(lease["lease_id"]) is False  # idempotent
        assert eng._prefix.leased_pages() == set()
        _assert_pool_consistent(eng)
        # Released pages are evictable again.
        evicted = eng._prefix.evict(eng._num_pages)
        assert set(lease["pages"]) <= set(evicted)
        eng._free_pages.extend(evicted)
        _assert_pool_consistent(eng)
    finally:
        eng.shutdown()


def test_prefix_migration_cold_engine_no_recompute(params):
    """Acceptance: hot prefixes exported from a warm engine and
    ingested by a cold one are admitted as a prefix-cache hit equal to
    the transferred pages — the migrated tokens are never recomputed —
    and exact-mode transfers keep greedy decoding byte-identical."""
    rng = np.random.default_rng(12)
    prompt = rng.integers(1, 127, size=2 * PAGE).tolist()
    want = greedy_reference(params, prompt, 12)
    warm, cold = _engine(params), _engine(params)
    try:
        assert warm.generate(prompt, max_new_tokens=12,
                             temperature=0.0) == want
        cached = warm._prefix.cached_pages
        assert cached == (len(prompt) + 12 - 1) // PAGE

        transfers = warm.export_hot_prefixes(mode="exact")
        assert transfers, "warm engine exported nothing"
        assert max(len(t["hashes"]) for t in transfers) == cached
        out_pages = warm.stats()["kv_migration"]["pages_out"]
        assert out_pages >= cached
        assert warm.stats()["kv_migration"]["bytes_out"] > 0
        # Every lease was released on the way out.
        assert warm._prefix.leased_pages() == set()

        ingested = sum(cold.migration_ingest(t) for t in transfers)
        assert ingested == cached  # dedup: overlapping paths land once
        st = cold.stats()
        assert st["kv_migration"]["pages_in"] == cached
        assert st["prefix"]["cached_pages"] == cached
        # Re-ingesting is a no-op: every depth is already cached.
        assert cold.migration_ingest(transfers[-1]) == 0

        # A probe over the migrated depth is admitted entirely from
        # the transferred pages: prefix_hit == transferred pages, so
        # none of the migrated tokens were recomputed.
        probe = (prompt + want)[:cached * PAGE] + [99, 99, 99]
        s = cold.submit(probe, max_new_tokens=6, temperature=0.0)
        got = s.result(timeout_s=120)
        assert s._req.prefix_hit == cached * PAGE
        assert got == greedy_reference(params, probe, 6)
        # And the original prompt replays byte-identically.
        s2 = cold.submit(prompt, max_new_tokens=12, temperature=0.0)
        assert s2.result(timeout_s=120) == want
        _assert_pool_consistent(cold)
    finally:
        warm.shutdown()
        cold.shutdown()


def test_transfer_rejects_content_mismatch(params):
    """Corrupted tokens (hash chain mismatch) never touch the pool."""
    rng = np.random.default_rng(13)
    prompt = rng.integers(1, 127, size=2 * PAGE).tolist()
    warm, cold = _engine(params), _engine(params)
    try:
        warm.generate(prompt, max_new_tokens=4, temperature=0.0)
        transfer = max(warm.export_hot_prefixes(mode="int8"),
                       key=lambda t: len(t["hashes"]))
        bad = dict(transfer)
        bad["tokens"] = list(transfer["tokens"])
        bad["tokens"][0] ^= 1
        with pytest.raises(ValueError, match="content-identity"):
            cold.migration_ingest(bad)
        assert cold.stats()["kv_migration"]["pages_in"] == 0
        assert cold._prefix.cached_pages == 0
        # The intact transfer still lands.
        assert cold.migration_ingest(transfer) == len(transfer["hashes"])
    finally:
        warm.shutdown()
        cold.shutdown()


# -- disaggregated serving e2e -----------------------------------------------

APP = "llmdisagg"
DEP = "LLMServer"
ROUTER_RING = f"router:{APP}/{DEP}"

N_STREAMS = 6
N_NEW = 12


def _prompts(seed, n):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 127, size=2 * PAGE).tolist() for _ in range(n)]


def _serve_app(params, *, disagg, adapter_factory=llama_paged_adapter):
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
    serve.start()
    app = serve.deployment(num_replicas=2, max_ongoing_requests=8,
                           disagg=disagg)(LLMServer).bind(
        CFG,
        EngineConfig(max_slots=8, max_seq_len=64, min_prefill_bucket=16,
                     page_size=PAGE, ragged_batching=True, token_budget=64,
                     decode_chunk=1, prefix_cache=True),
        lambda: params,
        adapter_factory=adapter_factory,
    )
    return serve.run(app, name=APP, route_prefix=None,
                     timeout_s=REPLICA_READY_S)


def _wait_roles():
    """Poll until the replica set is RUNNING with one prefill and one
    decode replica; returns {role: replica_id}."""
    from ray_tpu.util import state

    deadline = time.monotonic() + REPLICA_READY_S
    while time.monotonic() < deadline:
        rows = state.list_replicas()
        running = [r for r in rows if r["state"] == "RUNNING"]
        roles = sorted(r["role"] for r in running)
        if roles == ["decode", "prefill"]:
            return {r["role"]: r["replica_id"] for r in running}
        time.sleep(0.01)
    raise TimeoutError(f"roles never settled: {rows}")


def _replica_handles():
    from ray_tpu.serve.handle import _routers

    router = _routers[(APP, DEP)]
    with router._lock:
        return {rid: info.handle
                for rid, info in router._replicas.items()}


def _consume_streams(gens):
    outs = [[] for _ in gens]
    errs = [None] * len(gens)

    def consume(i):
        try:
            for tok in gens[i]:
                outs[i].append(tok)
        except BaseException as e:
            errs[i] = e

    threads = [threading.Thread(target=consume, args=(i,), daemon=True)
               for i in range(len(gens))]
    for t in threads:
        t.start()
    return outs, errs, threads


def test_disagg_streams_byte_identical_to_unified_oracle(params):
    """Acceptance: greedy streams under disaggregation (prefill
    handoff → exact KV migration → decode-replica resume) emit exactly
    the oracle token sequences; MIGRATING rides the router ring; the
    role column is served deterministically; and a cold replica pulls
    hot prefixes instead of recomputing them."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core import api
    from ray_tpu.serve import request_events
    from ray_tpu.util import state

    prompts = _prompts(21, N_STREAMS)
    wants = [greedy_reference(params, p, N_NEW) for p in prompts]
    pull_prompts = _prompts(22, 2)
    pull_wants = [greedy_reference(params, p, 2) for p in pull_prompts]

    # Every handoff below is counted (migrated == N_STREAMS, failed ==
    # 0), so none may ride the clock: a migration op that the decode
    # replica's loop, busy compiling beside five other test workers,
    # takes up later than the default 5 s falls back to recompute by
    # design, and that is another test's subject.
    handle = _serve_app(
        params,
        disagg={"prefill_replicas": 1, "transfer": "exact",
                "handoff_after_tokens": 2,
                "migration_timeout_s": REPLICA_READY_S})
    try:
        roles = _wait_roles()

        # -- `raytpu list replicas` role column: deterministic --------
        rows1 = state.list_replicas()
        rows2 = state.list_replicas()
        assert rows1 == rows2, "list_replicas is not deterministic"
        assert set(rows1[0]) == {"app", "deployment", "replica_id",
                                 "state", "role", "shard_group",
                                 "mesh_shape", "members",
                                 "target_groups", "actual_groups",
                                 "autoscale", "ctl_epoch",
                                 "last_recovery"}
        assert sorted(r["role"] for r in rows1) == ["decode", "prefill"]
        from ray_tpu.scripts import cli
        assert "role" in cli._LIST_ROUTES["replicas"][1]

        # -- Phase A: short streams stay local on the prefill replica
        # (requested <= handoff_after_tokens), so only it gets warm.
        shandle = handle.options(stream=True)
        for p, w in zip(pull_prompts, pull_wants):
            assert shandle.remote(
                {"tokens": p, "max_new_tokens": 2, "temperature": 0.0}
            ).result(timeout_s=300) == w
        handles = _replica_handles()  # router exists after first request
        assert set(handles) == set(roles.values())

        def _dstats(role):
            return api.get(handles[roles[role]].handle_request.remote(
                "disagg_stats", (), {}), timeout=60)

        def _stats(role):
            return api.get(handles[roles[role]].handle_request.remote(
                "stats", (), {}), timeout=60)

        ds = _dstats("prefill")
        assert ds["role"] == "prefill"
        assert ds["handoffs"]["local"] >= 2
        assert ds["handoffs"]["migrated"] == 0
        warm_stats = _stats("prefill")
        assert warm_stats["prefix"]["cached_pages"] > 0
        assert _dstats("decode")["role"] == "decode"
        assert _stats("decode")["prefix"]["cached_pages"] == 0

        # -- Cold pull: the decode replica ingests the prefill
        # replica's hot prefixes once its summary has propagated.
        deadline = time.monotonic() + 120
        pulled = 0
        while time.monotonic() < deadline:
            pulled = api.get(handles[roles["decode"]].handle_request
                             .remote("pull_prefix_cache", (256,), {},
                                     None), timeout=60)
            if pulled > 0:
                break
            time.sleep(0.25)
        assert pulled == warm_stats["prefix"]["cached_pages"], \
            "cold replica did not ingest the survivor's hot prefixes"
        cold_stats = _stats("decode")
        assert cold_stats["kv_migration"]["pages_in"] == pulled
        assert cold_stats["prefix"]["cached_pages"] >= pulled

        # -- Phase B: long streams run the full handoff protocol -----
        retries_before = _metric_total(
            r"raytpu_serve_request_retries_total")
        gens = [shandle.remote({"tokens": prompts[i],
                                "max_new_tokens": N_NEW,
                                "temperature": 0.0})
                for i in range(N_STREAMS)]
        outs, errs, threads = _consume_streams(gens)
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads), \
            f"streams hung: {[len(o) for o in outs]}"
        assert errs == [None] * N_STREAMS, f"streams failed: {errs}"
        assert outs == wants  # byte-identical to the unified oracle

        ds = _dstats("prefill")
        assert ds["handoffs"]["migrated"] == N_STREAMS
        assert ds["handoffs"]["failed"] == 0
        assert ds["requests"] >= 2 + N_STREAMS
        assert ds["kv_migration"]["pages_out"] > 0
        assert ds["kv_migration"]["bytes_out"] > 0
        dd = _dstats("decode")
        assert dd["kv_migration"]["pages_in"] > pulled  # handoff pages
        assert dd["requests"] >= N_STREAMS  # resumed streams
        # A handoff is a SUCCESSFUL attempt, not a failure: the
        # router-side retries counter must not move.
        assert _metric_total(
            r"raytpu_serve_request_retries_total") == retries_before

        # -- Router ring: every stream records the planned MIGRATING
        # transition (attempt bumped, retries NOT charged) and ends
        # FINISHED with the handoff in its attempt history.
        rows = [r for r in request_events.snapshot_rows()
                if r["engine"] == ROUTER_RING]
        by_id = {r["request_id"]: r for r in rows}
        for g in gens:
            r = by_id[g.request_id]
            assert r["state"] == "FINISHED"
            assert "MIGRATING" in r["state_ts"]
            assert r["attempt"] >= 1
            mig = [a for a in r["attempts"]
                   if str(a.get("reason", "")).startswith("migrated:")]
            assert mig and mig[0]["reason"].endswith(roles["decode"])
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def _slow_paged_adapter_factory(cfg):
    """Paged adapter with a throttled ragged step so the prefill phase
    of a handoff spans an observable window and the kill reliably
    lands before the handoff completes (jax.debug.callback: the step is
    traced under jit, a bare sleep would fire at trace time only)."""
    import dataclasses

    base = llama_paged_adapter(cfg)

    def slow_step(*args, **kwargs):
        jax.debug.callback(lambda: time.sleep(0.03), ordered=True)
        return base.ragged_step(*args, **kwargs)

    return dataclasses.replace(base, ragged_step=slow_step)


def test_disagg_prefill_kill_falls_back_to_recompute(params):
    """Acceptance: SIGKILL the prefill replica while streams are
    mid-handoff — every stream still finishes byte-identical to the
    oracle via the continuation replay (local recompute on a
    survivor), and the ring records the RETRYING/MIGRATING story."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core import api
    from ray_tpu.serve import request_events
    from ray_tpu.utils.test_utils import ReplicaKiller

    prompts = _prompts(31, N_STREAMS)
    wants = [greedy_reference(params, p, N_NEW) for p in prompts]

    handle = _serve_app(
        params,
        disagg={"prefill_replicas": 1, "transfer": "exact",
                "handoff_after_tokens": 6},
        adapter_factory=_slow_paged_adapter_factory)
    try:
        roles = _wait_roles()
        # Prime the router (created lazily on first request) so the
        # replica handles are inspectable; short request stays local.
        handle.options(stream=True).remote(
            {"tokens": [1, 2, 3], "max_new_tokens": 1,
             "temperature": 0.0}).result(timeout_s=300)
        handles = _replica_handles()

        shandle = handle.options(stream=True, max_retries=8)
        gens = [shandle.remote({"tokens": prompts[i],
                                "max_new_tokens": N_NEW,
                                "temperature": 0.0})
                for i in range(N_STREAMS)]
        outs, errs, threads = _consume_streams(gens)

        # Wait until every stream is decoding on the prefill replica
        # (past prefill, before the 6-token handoff point at 0.03 s a
        # step), then SIGKILL it — mid-handoff by construction.
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if all(len(o) >= 1 for o in outs):
                break
            time.sleep(0.002)
        assert all(len(o) >= 1 for o in outs), "streams never started"
        killer = ReplicaKiller(api.runtime(), seed=0)
        victim = handles[roles["prefill"]]
        assert killer.kill_one(actor_id=victim._actor_id) is not None

        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads), \
            f"streams hung after kill: {[len(o) for o in outs]}"
        assert errs == [None] * N_STREAMS, f"streams failed: {errs}"
        assert outs == wants  # replay recomputed, not one token lost

        rows = [r for r in request_events.snapshot_rows()
                if r["engine"] == ROUTER_RING]
        by_id = {r["request_id"]: r for r in rows}
        retried = 0
        for g in gens:
            r = by_id[g.request_id]
            assert r["state"] == "FINISHED"
            # Every stream either hit the kill (RETRYING + local
            # recompute) or had already handed off (MIGRATING).
            assert ("RETRYING" in r["state_ts"]
                    or "MIGRATING" in r["state_ts"]), r["state_ts"]
            retried += "RETRYING" in r["state_ts"]
        assert retried > 0, "kill landed but nothing retried"
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
