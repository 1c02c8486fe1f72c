"""Speculative decoding on the ragged unified step: draft-model
drafting, k-token verify rows, rejection-safe paged-KV rollback.

Correctness contract: `EngineConfig(spec_decode=True)` is an
OPTIMIZATION, never a semantics change — greedy (temperature=0)
streams from a speculative engine are byte-identical to the SPEC-OFF
engine oracle (the base ragged program is untouched by the spec plane,
so spec-off output is the oracle by construction), across unified
serving, prefix-cache hits, mixed-LoRA batches, disaggregated
prefill→decode handoff, and SIGKILL mid-stream failover.

Accounting contract: rejection rolls back via the host lens mirror
(never a device copy), rejected positions are never attended nor
prefix-cache-visible, and the TARGET pool invariant (every physical
page in exactly one of free / cached / slot-owned) holds through
accept, reject, eviction pressure, and release — as does the DRAFT
pool's own free/slot-owned partition.

Scheduler contract: speculation degrades to plain decode (never
queues behind itself) for sampled or adapter rows, and a
cold-acceptance EMA pauses it for spec_cooldown_rounds dispatches —
with output unchanged either way.
"""

import dataclasses
import functools
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
from ray_tpu.utils.test_utils import REPLICA_READY_S
from tests import midstream_kill
from tests.midstream_kill import hold  # noqa: F401 (fixture)

pytestmark = pytest.mark.long_file(289)

CFG = llama.LlamaConfig(
    vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, max_seq_len=128, remat=False, dtype=jnp.float32,
    param_dtype=jnp.float32,
)

PAGE = 16


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.key(0), CFG)


@pytest.fixture(scope="module")
def wrong_draft_params():
    """A draft model with the right shape and the WRONG weights: its
    proposals almost never match the target's argmax, so every round
    exercises the rejection/rollback path."""
    return llama.init_params(jax.random.key(7), CFG)


def _engine(params, *, spec, **kw):
    draft = kw.pop("_draft_params", None)
    cfg = dict(max_slots=4, max_seq_len=128, min_prefill_bucket=16,
               page_size=PAGE, ragged_batching=True, token_budget=36,
               spec_decode=spec)
    cfg.update(kw)
    return LLMEngine(params, llama_paged_adapter(CFG),
                     EngineConfig(**cfg), draft_params=draft)


# The spec-off engine at the file's default configuration, which four
# cases ask for their oracle: built (and its programs compiled) once.  It
# keeps nothing between requests (no prefix cache) and no case asserts
# on it.  An oracle at any other configuration is built for its case.
_default_oracle = []


@pytest.fixture(scope="module", autouse=True)
def _shut_default_oracle():
    yield
    while _default_oracle:
        _default_oracle.pop().shutdown()


def _spec_off_oracle(params, reqs, **ekw):
    """The oracle this whole file is measured against: the SAME engine
    configuration with spec_decode=False, greedy."""
    if ekw:
        eng = _engine(params, spec=False, **ekw)
    else:
        if not _default_oracle:
            _default_oracle.append(_engine(params, spec=False))
        eng = _default_oracle[0]
    try:
        streams = [eng.submit(p, max_new_tokens=n, temperature=0.0)
                   for p, n in reqs]
        return [s.result(timeout_s=300) for s in streams]
    finally:
        if ekw:
            eng.shutdown()


def _assert_pool_consistent(eng):
    """test_prefix_cache's invariant: every physical TARGET page in
    exactly one of free / cached / slot-owned, extended with the draft
    pool's own partition (free ∪ slot-owned, no overlap, no leak)."""
    free = list(eng._free_pages)
    assert len(free) == len(set(free)), "duplicate pages on free list"
    free = set(free)
    cached = eng._prefix.pages() if eng._prefix is not None else set()
    owned, borrowed = set(), set()
    for slot, pages in eng._slot_pages.items():
        b = eng._slot_borrowed.get(slot, []) if eng._prefix else []
        assert pages[:len(b)] == b
        borrowed |= set(pages[:len(b)])
        tail = pages[len(b):]
        assert not owned & set(tail), "page owned by two slots"
        owned |= set(tail)
    assert borrowed <= cached, "borrowed page not owned by the index"
    assert not free & cached and not free & owned
    assert not cached & owned
    assert len(free) + len(cached) + len(owned) == eng._num_pages, (
        f"pool leak: {len(free)} free + {len(cached)} cached + "
        f"{len(owned)} owned != {eng._num_pages}")
    if getattr(eng, "_spec_on", False):
        dfree = list(eng._draft_free)
        assert len(dfree) == len(set(dfree)), "duplicate draft pages"
        dfree = set(dfree)
        downed = set()
        for slot, pages in eng._draft_slot_pages.items():
            assert not downed & set(pages), "draft page owned twice"
            downed |= set(pages)
        assert not dfree & downed
        assert len(dfree) + len(downed) == eng._draft_pages, (
            f"draft pool leak: {len(dfree)} free + {len(downed)} "
            f"owned != {eng._draft_pages}")


def _settle(eng, timeout_s=60.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if (len(eng._free_slots) == eng.config.max_slots
                and eng._waiting.empty() and not eng._prefilling
                and not eng._backlog):
            return
        time.sleep(0.005)
    raise TimeoutError("engine never went quiescent")


# -- tentpole: unified-step parity + the speedup actually happens ------------

def test_spec_unified_parity_and_accepted_tokens_per_step(params):
    """Self-draft speculative serving emits byte-identical greedy
    streams to the spec-off oracle, and the engine actually
    speculated: rounds > 0, every drafted token accepted (self-draft),
    and MORE than one token emitted per verify step (the bonus
    token) — the whole point of the feature."""
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(1, 127, size=n).tolist(), 16)
            for n in (3, 7, 12, 5, 9, 4)]
    want = _spec_off_oracle(params, reqs)
    eng = _engine(params, spec=True)
    try:
        streams = [eng.submit(p, max_new_tokens=n, temperature=0.0)
                   for p, n in reqs]
        got = [s.result(timeout_s=300) for s in streams]
        assert got == want
        sp = eng.stats()["spec"]
        assert sp["rounds"] > 0
        assert sp["drafted_tokens"] > 0
        assert sp["accept_ratio"] == 1.0  # self-draft accepts all
        accepted_per_step = (sp["accepted_tokens"] + sp["rounds"]) \
            / sp["rounds"]
        assert accepted_per_step > 1.0
        # Per-request spec counters rode the Request into the ring.
        assert all(s._req.spec_drafted > 0 for s in streams) \
            or any(s._req.spec_drafted > 0 for s in streams)
        _settle(eng)
        _assert_pool_consistent(eng)
    finally:
        eng.shutdown()


def test_spec_mixed_temperatures_only_greedy_rows_speculate(params):
    """Sampled (temperature > 0) rows never speculate but still finish
    correctly alongside speculating greedy rows in the same ragged
    batch — and the greedy rows stay byte-identical to the oracle."""
    rng = np.random.default_rng(4)
    greedy = [(rng.integers(1, 127, size=n).tolist(), 12)
              for n in (4, 8)]
    want = _spec_off_oracle(params, greedy)
    eng = _engine(params, spec=True)
    try:
        hot = eng.submit(rng.integers(1, 127, size=6).tolist(),
                         max_new_tokens=12, temperature=0.8)
        streams = [eng.submit(p, max_new_tokens=n, temperature=0.0)
                   for p, n in greedy]
        assert [s.result(timeout_s=300) for s in streams] == want
        sampled = hot.result(timeout_s=300)
        assert len(sampled) == 12
        assert eng.stats()["spec"]["rounds"] > 0
    finally:
        eng.shutdown()


# -- prefix-cache interaction ------------------------------------------------

def test_spec_prefix_cache_parity_and_rollback_invisibility(params):
    """Speculative serving over a shared-prefix workload: byte-
    identical to the spec-off cache-enabled oracle, the cache still
    hits, and the pool invariant (including the draft pool) holds
    after every stream — i.e. rejected speculative positions never
    became prefix-cache-visible pages."""
    rng = np.random.default_rng(5)
    shared = rng.integers(1, 127, size=2 * PAGE).tolist()
    reqs = [(shared + rng.integers(1, 127, size=3).tolist(), 12)
            for _ in range(4)]
    ekw = dict(prefix_cache=True)
    want = _spec_off_oracle(params, reqs, **ekw)
    eng = _engine(params, spec=True, **ekw)
    try:
        # Sequential first (plants the prefix), then a batched replay.
        first = eng.submit(*reqs[0][:1], max_new_tokens=reqs[0][1],
                           temperature=0.0)
        assert first.result(timeout_s=300) == want[0]
        streams = [eng.submit(p, max_new_tokens=n, temperature=0.0)
                   for p, n in reqs[1:]]
        assert [s.result(timeout_s=300) for s in streams] == want[1:]
        assert any(s._req.prefix_hit > 0 for s in streams), \
            "no request ever hit the cache — the test proves nothing"
        assert eng.stats()["spec"]["rounds"] > 0
        _settle(eng)
        _assert_pool_consistent(eng)
    finally:
        eng.shutdown()


# -- rejection / rollback ----------------------------------------------------

def test_spec_rejection_rollback_parity_under_eviction(params,
                                                       wrong_draft_params):
    """A WRONG draft model rejects essentially every proposal: output
    must still be byte-identical to the spec-off oracle, and under a
    small pool with eviction pressure the target invariant
    (free ∪ cached ∪ slot-owned) and the draft partition both hold —
    the rollback path leaks nothing and caches nothing it rolled
    back."""
    rng = np.random.default_rng(6)
    # 8 physical pages vs ~3 pages per distinct request: the prefix
    # index must evict refcount-0 pages to admit each newcomer.
    ekw = dict(prefix_cache=True, num_pages=8, max_slots=2)
    reqs = [(rng.integers(1, 127, size=2 * PAGE + 3).tolist(), 8)
            for _ in range(6)]
    want = _spec_off_oracle(params, reqs, **ekw)
    eng = _engine(params, spec=True, _draft_params=wrong_draft_params,
                  spec_cold_accept=0.0,  # never cool down: keep rejecting
                  **ekw)
    try:
        got = [eng.submit(p, max_new_tokens=n,
                          temperature=0.0).result(timeout_s=300)
               for p, n in reqs]
        assert got == want
        sp = eng.stats()["spec"]
        assert sp["rounds"] > 0
        assert sp["accept_ratio"] < 0.5, \
            "the wrong draft was mostly accepted — rollback untested"
        assert eng.stats()["prefix"]["evicted_pages"] > 0, \
            "no eviction pressure — the invariant was never stressed"
        _settle(eng)
        _assert_pool_consistent(eng)
    finally:
        eng.shutdown()
    # Release returned every draft page.
    assert sorted(eng._draft_free) == list(range(eng._draft_pages))


def test_spec_cold_acceptance_cooldown_engages(params,
                                               wrong_draft_params):
    """Cold acceptance pauses speculation: with a wrong draft and the
    default cold-accept threshold, the EMA crosses under it, the
    cooldown counter moves, and rounds stop growing while cooling —
    with output still byte-identical."""
    rng = np.random.default_rng(8)
    reqs = [(rng.integers(1, 127, size=5).tolist(), 20)
            for _ in range(3)]
    want = _spec_off_oracle(params, reqs)
    eng = _engine(params, spec=True, _draft_params=wrong_draft_params,
                  spec_cold_accept=0.3, spec_cooldown_rounds=8)
    try:
        got = [eng.submit(p, max_new_tokens=n,
                          temperature=0.0).result(timeout_s=300)
               for p, n in reqs]
        assert got == want
        sp = eng.stats()["spec"]
        assert sp["cooldowns"] > 0, "acceptance never ran cold"
        assert sp["rounds"] > 0
    finally:
        eng.shutdown()


# -- LoRA-mixed batches ------------------------------------------------------

def test_spec_mixed_lora_batch_parity(params):
    """Base-model rows speculate INSIDE a ragged batch that also
    carries LoRA-adapter rows (which decode plain): every request —
    adapter and base — is byte-identical to the spec-off engine, and
    the engine really speculated while adapters were resident."""
    from ray_tpu.ops import segmented_lora as _sl

    lora_cfg = dataclasses.replace(
        CFG, lora=_sl.LoRAConfig(rank=4, alpha=8.0))
    reqs = [([1, 2, 3], ""), ([4, 5, 6, 7], "tenant-a"),
            ([9, 3, 1], ""), ([2, 8, 5], "tenant-b")]

    def _lora_engine(spec):
        return LLMEngine(
            params, llama_paged_adapter(lora_cfg),
            EngineConfig(max_slots=4, max_seq_len=128,
                         min_prefill_bucket=16, page_size=PAGE,
                         ragged_batching=True, token_budget=36,
                         spec_decode=spec))

    off = _lora_engine(False)
    try:
        want = [off.submit(p, max_new_tokens=10, temperature=0.0,
                           adapter_id=a).result(timeout_s=300)
                for p, a in reqs]
    finally:
        off.shutdown()
    eng = _lora_engine(True)
    try:
        streams = [eng.submit(p, max_new_tokens=10, temperature=0.0,
                              adapter_id=a) for p, a in reqs]
        assert [s.result(timeout_s=300) for s in streams] == want
        sp = eng.stats()["spec"]
        assert sp["rounds"] > 0, "base rows never speculated"
        # Adapter rows never draft: drafted tokens all came from ""
        # rows, and the adapter requests carry no spec counters.
        for s, (_p, a) in zip(streams, reqs):
            if a:
                assert s._req.spec_drafted == 0
    finally:
        eng.shutdown()


# -- disaggregated prefill/decode handoff ------------------------------------

def test_spec_disagg_handoff_parity(params):
    """Speculative decode replicas behind a prefill→decode handoff:
    greedy streams through the disaggregated app are byte-identical to
    the spec-off unified single-engine oracle, and the decode side
    really speculated."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.util import state

    rng = np.random.default_rng(21)
    prompts = [rng.integers(1, 127, size=8).tolist() for _ in range(4)]
    reqs = [(p, 12) for p in prompts]
    want = _spec_off_oracle(params, reqs, max_seq_len=64, page_size=4,
                            token_budget=64, prefix_cache=True)

    ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
    serve.start()
    app = serve.deployment(
        num_replicas=2, max_ongoing_requests=8,
        disagg={"prefill_replicas": 1, "transfer": "exact",
                "handoff_after_tokens": 2})(LLMServer).bind(
        CFG,
        EngineConfig(max_slots=4, max_seq_len=64, min_prefill_bucket=16,
                     page_size=4, ragged_batching=True, token_budget=64,
                     prefix_cache=True, spec_decode=True),
        lambda: params,
        adapter_factory=llama_paged_adapter,
    )
    handle = serve.run(app, name="llmspecdis", route_prefix=None,
                       timeout_s=REPLICA_READY_S)
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            rows = [r for r in state.list_replicas()
                    if r["state"] == "RUNNING"]
            if sorted(r["role"] for r in rows) == ["decode", "prefill"]:
                break
            time.sleep(0.01)
        shandle = handle.options(stream=True)
        gens = [shandle.remote({"tokens": p, "max_new_tokens": 12,
                                "temperature": 0.0}) for p in prompts]
        got = [[t for t in g] for g in gens]
        assert got == want
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


# -- SIGKILL mid-stream failover ---------------------------------------------

def _slow_spec_adapter_factory(cfg, hold):
    """Paged adapter with throttled ragged steps (plain AND verify) so
    streams span an observable window, and steps that stand still while
    the file ``hold`` exists, so the kill lands mid-decode.
    The wait rides jax.debug.callback: the steps are traced under
    jit, so a bare time.sleep would only fire at trace time."""
    base = llama_paged_adapter(cfg)
    throttle = midstream_kill.throttle(hold, 0.02)

    # wraps: the engine reads the step's signature for ``logit_idx=``
    # before it speculates, and refuses to build over a step that hides
    # it (every replica's constructor then raised, the controller
    # replaced each for ever, and the app was never healthy: this test
    # was red alone, not for a slow start under load)
    @functools.wraps(base.ragged_step)
    def slow_step(*args, **kwargs):
        jax.debug.callback(throttle, ordered=True)
        return base.ragged_step(*args, **kwargs)

    return dataclasses.replace(base, ragged_step=slow_step)


def test_spec_midstream_kill_failover_parity(params, hold):
    """Hard-kill the replica serving speculative streams mid-decode:
    every stream finishes byte-identical to the spec-off oracle — the
    continuation replay (prompt + delivered prefix) re-enters the
    speculative engine on a survivor and still cannot change a
    token."""
    import ray_tpu
    from ray_tpu import serve

    n_streams, n_new = 4, 24
    prompts = [[i + 1, i + 2, i + 3] for i in range(n_streams)]
    want = _spec_off_oracle(params, [(p, n_new) for p in prompts])

    ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
    serve.start()
    app = serve.deployment(num_replicas=2, max_ongoing_requests=8)(
        LLMServer
    ).bind(
        CFG,
        EngineConfig(max_slots=4, max_seq_len=128,
                     min_prefill_bucket=16, page_size=PAGE,
                     ragged_batching=True, token_budget=36,
                     spec_decode=True),
        lambda: params,
        adapter_factory=functools.partial(_slow_spec_adapter_factory,
                                          hold=str(hold)),
    )
    handle = serve.run(app, name="llmspecft", route_prefix=None,
                       timeout_s=REPLICA_READY_S)
    try:
        shandle = handle.options(stream=True)
        gens = [shandle.remote({"tokens": p, "max_new_tokens": n_new,
                                "temperature": 0.0}) for p in prompts]
        outs = [[] for _ in gens]
        errs = [None] * len(gens)

        def consume(i):
            try:
                for tok in gens[i]:
                    outs[i].append(tok)
            except BaseException as e:
                errs[i] = e

        threads = [threading.Thread(target=consume, args=(i,),
                                    daemon=True)
                   for i in range(len(gens))]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            if all(len(o) >= 2 for o in outs):
                break
            time.sleep(0.005)
        assert all(len(o) >= 2 for o in outs), "streams never started"

        # a replica that still holds a stream: the one that compiled
        # first may have ended its own by now
        midstream_kill.kill_a_replica_mid_stream(
            "llmspecft", "LLMServer", hold)

        for t in threads:
            t.join(timeout=180)
        assert not any(t.is_alive() for t in threads), \
            f"streams hung after kill: {[len(o) for o in outs]}"
        assert errs == [None] * len(gens), f"streams failed: {errs}"
        assert outs == want  # exact continuation: no loss/dup/change
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


# -- satellites: request plane, CLI, telemetry, bench contract ---------------

def test_spec_column_in_request_rows_and_cli(params):
    """accepted/drafted rides the request-plane rows end to end:
    ring -> state.list_requests keep-tuple -> `raytpu list requests`
    column (right after adapter_id), deterministic across snapshots,
    and empty (absent-not-zero) for requests that never speculated."""
    from ray_tpu.scripts import cli
    from ray_tpu.util import state

    cols = cli._LIST_ROUTES["requests"][1]
    assert "spec" in cols
    assert cols.index("spec") == cols.index("adapter_id") + 1

    eng = _engine(params, spec=True)
    try:
        s1 = eng.submit([1, 2, 3], max_new_tokens=12, temperature=0.0)
        s1.result(timeout_s=300)
        s2 = eng.submit([4, 5, 6], max_new_tokens=4, temperature=0.9)
        s2.result(timeout_s=300)
        for _snap in range(2):  # deterministic across snapshots
            rows = {r["request_id"]: r for r in state.list_requests(
                filters=[("engine", "=", eng.engine_id)], limit=10)}
            spec1 = rows[s1.request_id]["spec"]
            acc, drafted = map(int, spec1.split("/"))
            assert drafted > 0 and 0 <= acc <= drafted
            assert acc == s1._req.spec_accepted
            # The sampled request never speculated: empty, not "0/0".
            assert rows[s2.request_id]["spec"] == ""
    finally:
        eng.shutdown()


def test_spec_metric_families_live_and_required(params):
    """After a speculative run the pinned families carry real traffic
    and the --require contract holds on the live exposition."""
    import importlib.util
    import pathlib
    import re

    from ray_tpu.util import metrics

    eng = _engine(params, spec=True)
    try:
        eng.submit([5, 6, 7], max_new_tokens=12,
                   temperature=0.0).result(timeout_s=300)
    finally:
        eng.shutdown()
    text = metrics.export_prometheus()

    def total(family):
        out = 0.0
        pat = re.compile(rf"^{family}[^ ]* (\S+)$")
        for line in text.splitlines():
            m = pat.match(line)
            if m:
                out += float(m.group(1))
        return out

    assert total("raytpu_serve_spec_rounds_total") > 0
    assert total("raytpu_serve_spec_drafted_tokens_total") > 0
    assert total("raytpu_serve_spec_accepted_tokens_total") > 0
    path = (pathlib.Path(__file__).resolve().parent.parent
            / "scripts" / "check_metrics.py")
    spec = importlib.util.spec_from_file_location("check_metrics", path)
    cm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cm)
    assert cm.check_exposition(
        text,
        require=["raytpu_serve_spec_rounds_total",
                 "raytpu_serve_spec_drafted_tokens_total",
                 "raytpu_serve_spec_accepted_tokens_total",
                 "raytpu_serve_spec_accept_ratio"]) == []


def test_bench_spec_block_from_live_stats_validates(params):
    """The bench record's spec block, built from a REAL speculative
    engine's stats() with bench.py's arithmetic, satisfies
    scripts/bench_schema._check_spec — the schema and the engine can
    never drift on what 'accept_ratio' or 'accepted per step' mean."""
    import importlib.util
    import pathlib

    path = (pathlib.Path(__file__).resolve().parent.parent
            / "scripts" / "bench_schema.py")
    mspec = importlib.util.spec_from_file_location("bench_schema", path)
    schema = importlib.util.module_from_spec(mspec)
    mspec.loader.exec_module(schema)

    eng = _engine(params, spec=True)
    try:
        rng = np.random.default_rng(9)
        for _ in range(3):
            eng.submit(rng.integers(1, 127, size=6).tolist(),
                       max_new_tokens=12,
                       temperature=0.0).result(timeout_s=300)
        sp = eng.stats()["spec"]
    finally:
        eng.shutdown()
    assert sp["rounds"] > 0
    block = {  # bench.py `_measure_serving` builds exactly this shape
        "rounds": int(sp["rounds"]),
        "drafted_tokens": int(sp["drafted_tokens"]),
        "accepted_tokens": int(sp["accepted_tokens"]),
        "accept_ratio": (
            round(sp["accepted_tokens"] / sp["drafted_tokens"], 3)
            if sp["drafted_tokens"] else None),
        "accepted_tokens_per_step": round(
            (sp["accepted_tokens"] + sp["rounds"]) / sp["rounds"], 2),
        "cooldowns": int(sp["cooldowns"]),
        "k": int(sp["k"]),
        "draft": "self",
    }
    problems = []
    schema._check_spec("live.spec", block, problems)
    assert problems == []
    assert block["accepted_tokens_per_step"] > 1.0  # self-draft


def test_spec_requires_ragged_batching(params):
    """spec_decode without the ragged unified step is a loud config
    error, not a silent no-op."""
    with pytest.raises(ValueError, match="ragged"):
        LLMEngine(params, llama_paged_adapter(CFG),
                  EngineConfig(max_slots=2, max_seq_len=64,
                               page_size=PAGE, spec_decode=True))
