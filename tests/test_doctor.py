"""Invariant audit plane: the cross-plane consistency doctor.

Detection contract: each RAYTPU_FAILPOINTS-gated corruption injector
(a leaked trie borrow ref, an unreleased draft page, a dropped
broadcast row) is found by one deep-audit cycle, increments
``raytpu_doctor_violations_total{check}``, and produces a
flight-recorder bundle whose manifest names the violated check.

Cleanliness contract: a clean engine — including the cross-feature
gauntlet of spec-decode × migration-lease × adapter-pool under
eviction pressure with a mid-stream replica SIGKILL — deep-audits to
zero violations (the conftest autouse fixture additionally enforces
this after every engine-spawning tier-1 test).
"""

import dataclasses
import functools
import glob
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.ops import segmented_lora as _sl
from ray_tpu.serve import audit
from ray_tpu.serve.llm_engine import (
    EngineConfig,
    LLMEngine,
    LLMServer,
    llama_paged_adapter,
)
from ray_tpu.utils.test_utils import REPLICA_READY_S
from ray_tpu.util import doctor, flight_recorder

pytestmark = pytest.mark.long_file(64)

CFG = llama.LlamaConfig(
    vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, max_seq_len=128, remat=False, dtype=jnp.float32,
    param_dtype=jnp.float32,
)
LORA = _sl.LoRAConfig(rank=4, alpha=8.0)
LORA_CFG = dataclasses.replace(CFG, lora=LORA)

PAGE = 16


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.key(0), CFG)


def _engine(params, cfg=CFG, **kw):
    ecfg = dict(max_slots=4, max_seq_len=128, min_prefill_bucket=16,
                page_size=PAGE, ragged_batching=True, token_budget=36)
    ecfg.update(kw)
    return LLMEngine(params, llama_paged_adapter(cfg),
                     EngineConfig(**ecfg))


def _violations_total(check):
    """Current raytpu_doctor_violations_total for one check label,
    summed over severities."""
    from ray_tpu.util import metrics

    total = 0.0
    for fam, _typ, _help, samples in metrics.snapshot_samples():
        if fam != "raytpu_doctor_violations_total":
            continue
        for s in samples:
            if ("check", check) in tuple(s[1]):
                total += s[2]
    return total


def _violated_checks(report):
    """Check-name set of every violation in a per-process report."""
    return {v["check"] for row in report["checks"]
            for v in row["violations"]}


@pytest.fixture
def dump_dir(tmp_path):
    """Arm flight-recorder auto-dump into a fresh directory with the
    rate limit off, restoring the recorder's config afterwards."""
    d = tmp_path / "flightrec"
    d.mkdir()
    flight_recorder.configure(dump_dir=str(d), auto_dump=True,
                              min_dump_interval_s=0.0)
    yield str(d)
    flight_recorder.configure(dump_dir="", min_dump_interval_s=2.0)


def _manifest_details(dump_dir):
    out = []
    for path in sorted(glob.glob(os.path.join(dump_dir, "flightrec-*"))):
        with open(os.path.join(path, "manifest.json")) as f:
            out.append(json.load(f))
    return out


# -- doctor core (util/doctor) ----------------------------------------------

def test_run_audit_report_shape_and_metrics():
    cd = doctor.register_check(
        "test.shape", 1, doctor.DEEP, "error", "test-only check")
    bad = doctor.InvariantViolation(
        "test.shape", "error", "unit-7", expected=0, actual=1)
    before = _violations_total("test.shape")
    report = doctor.run_audit(
        "proc-x", [(cd, lambda: [bad])], deep=True)
    assert report["proc"] == "proc-x"
    assert report["deep"] is True
    assert report["checks_run"] == 1
    assert report["violations"] == 1
    assert report["audit_seconds"] >= 0.0
    (row,) = report["checks"]
    assert (row["check"], row["status"]) == ("test.shape", "violated")
    (v,) = row["violations"]
    assert v["subject"] == "unit-7"
    assert v["epoch"] == report["epoch"] > 0
    assert _violations_total("test.shape") == before + 1
    # A clean re-run flips the status (and the last-audit gauge) back.
    clean = doctor.run_audit("proc-x", [(cd, lambda: [])], deep=False)
    assert clean["violations"] == 0
    assert clean["checks"][0]["status"] == "ok"


def test_raising_check_body_is_itself_a_violation():
    cd = doctor.register_check(
        "test.raises", 1, doctor.DEEP, "critical", "test-only check")

    def broken():
        raise RuntimeError("auditor bug")

    report = doctor.run_audit("proc-y", [(cd, broken)], deep=True)
    (v,) = report["checks"][0]["violations"]
    assert v["subject"] == "check-body"
    assert "auditor bug" in v["actual"]


def test_register_check_conflict_raises():
    doctor.register_check("test.conflict", 1, doctor.DEEP, "error", "a")
    # Same definition: idempotent.
    doctor.register_check("test.conflict", 1, doctor.DEEP, "error", "a")
    with pytest.raises(ValueError, match="re-registered"):
        doctor.register_check("test.conflict", 2, doctor.DEEP,
                              "error", "a")
    with pytest.raises(ValueError, match="re-registered"):
        doctor.register_check("test.conflict", 1, doctor.INCREMENTAL,
                              "error", "a")


def test_merge_reports_sums():
    merged = doctor.merge_reports([
        {"checks_run": 3, "violations": 1, "audit_seconds": 0.5},
        {"checks_run": 2, "violations": 0, "audit_seconds": 0.25},
        None,  # dead fan-out entries are dropped
    ], deep=True)
    assert merged["deep"] is True
    assert merged["checks_run"] == 5
    assert merged["violations"] == 1
    assert merged["audit_seconds"] == 0.75
    assert len(merged["reports"]) == 2


# -- clean engines audit clean ----------------------------------------------

def test_clean_engine_deep_audit_zero_violations(params):
    """Spec + prefix-cache traffic, then an explicit deep audit: every
    registered engine check runs and none fires."""
    eng = _engine(params, spec_decode=True, prefix_cache=True)
    try:
        rng = np.random.default_rng(3)
        shared = rng.integers(1, 127, size=PAGE).tolist()
        for i in range(3):
            tail = rng.integers(1, 127, size=4).tolist()
            eng.generate(shared + tail, max_new_tokens=8,
                         temperature=0.0)
        report = eng.doctor(deep=True)
        assert report["violations"] == 0, report
        ran = {row["check"] for row in report["checks"]}
        assert {"kv.page_conservation", "kv.pool_partition",
                "kv.trie_integrity", "kv.lease_accounting",
                "spec.draft_conservation", "spec.draft_partition",
                "slots.table", "ring.terminal_slots"} <= ran
        assert eng.doctor_report() is report
    finally:
        eng.shutdown()


def test_engine_doctor_after_stop_runs_inline(params):
    eng = _engine(params)
    eng.generate([1, 2, 3], max_new_tokens=2, temperature=0.0)
    eng.shutdown()
    report = eng.doctor(deep=True)  # loop gone: audits inline
    assert report["violations"] == 0, report


# -- failpoint corruption injectors -----------------------------------------

@pytest.mark.doctor_corrupt
def test_trie_ref_leak_detected(params, monkeypatch, dump_dir):
    """Armed doctor.leak_trie_ref skips one borrowed-page release: the
    deep audit's trie refcount recount finds the phantom ref, the
    violation counter moves, and a bundle manifest names the check."""
    eng = _engine(params, prefix_cache=True)
    try:
        rng = np.random.default_rng(5)
        shared = rng.integers(1, 127, size=2 * PAGE).tolist()
        # Donate the shared prefix to the trie, unarmed.
        eng.generate(shared + [1, 2], max_new_tokens=2, temperature=0.0)
        before = _violations_total("kv.trie_integrity")
        monkeypatch.setenv("RAYTPU_FAILPOINTS", "doctor.leak_trie_ref:1")
        # This request borrows the cached pages; its release leaks one.
        eng.generate(shared + [3, 4], max_new_tokens=2, temperature=0.0)
        report = eng.doctor(deep=True)
        assert "kv.trie_integrity" in _violated_checks(report), report
        assert _violations_total("kv.trie_integrity") > before
        details = {m.get("detail") for m in _manifest_details(dump_dir)}
        assert "kv.trie_integrity" in details or \
            "kv.borrow_balance" in details, details
        # Telemetry history plane: the violation counter lands in the
        # timeseries rings, so `raytpu top` can chart doctor signals.
        # Counters are rate-sampled: tick twice (baseline, then delta).
        from ray_tpu.util import timeseries
        t0 = timeseries.query()["now"]
        timeseries.sample_now(now=t0 + 1.0)
        timeseries.sample_now(now=t0 + 2.0)
        series = timeseries.query(family="raytpu_doctor")["series"]
        assert any(s["family"] == "raytpu_doctor_violations_total"
                   for s in series), [s["family"] for s in series]
    finally:
        monkeypatch.delenv("RAYTPU_FAILPOINTS", raising=False)
        eng.shutdown()


@pytest.mark.doctor_corrupt
def test_draft_page_leak_detected(params, monkeypatch, dump_dir):
    """Armed doctor.leak_draft_page skips one draft-page free on slot
    release: the draft-pool partition walk reports the unowned page."""
    eng = _engine(params, spec_decode=True)
    try:
        before = _violations_total("spec.draft_partition")
        monkeypatch.setenv("RAYTPU_FAILPOINTS",
                           "doctor.leak_draft_page:1")
        out = eng.generate([5, 6, 7, 8], max_new_tokens=12,
                           temperature=0.0)
        assert len(out) == 12
        report = eng.doctor(deep=True)
        violated = _violated_checks(report)
        assert "spec.draft_partition" in violated, report
        assert "spec.draft_conservation" in violated, report
        assert _violations_total("spec.draft_partition") > before
        details = {m.get("detail") for m in _manifest_details(dump_dir)}
        assert details & {"spec.draft_partition",
                          "spec.draft_conservation"}, details
    finally:
        monkeypatch.delenv("RAYTPU_FAILPOINTS", raising=False)
        eng.shutdown()


@pytest.mark.doctor_corrupt
def test_broadcast_desync_detected(monkeypatch, dump_dir):
    """Armed doctor.broadcast_desync drops one row from a controller
    broadcast: the controller's census↔broadcast audit reports the
    missing replica and the bundle manifest names the check.

    THREAD worker mode (the annotated exception; process is the
    default): the injector is armed via the driver's RAYTPU_FAILPOINTS
    env, and the detection evidence (violation counters, the
    flight-recorder bundle) is read from driver-process state — both
    require the controller to share the driver process."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core import api
    from ray_tpu.serve.controller import CONTROLLER_NAME

    monkeypatch.setenv("RAYTPU_WORKERS", "thread")
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)
    serve.start()
    try:
        @serve.deployment(num_replicas=2)
        class Echo:
            def __call__(self, x):
                return x

        serve.run(Echo.bind(), name="docapp", route_prefix=None)
        controller = api.get_actor(CONTROLLER_NAME)
        rows = api.get(controller.list_replicas.remote())
        rows = [r for r in rows if r["app"] == "docapp"]
        assert len(rows) == 2
        before = _violations_total("controller.census_broadcast")
        # Persistent-bug model: EVERY broadcast drops a row while
        # armed, so detection cannot race a clean rebroadcast (the
        # reconcile loop re-announces whenever replica state shifts).
        monkeypatch.setenv("RAYTPU_FAILPOINTS",
                           "doctor.broadcast_desync:1000")
        # Force a (corrupted) rebroadcast without touching the
        # census: an adapter-summary push re-announces the table.
        api.get(controller.record_adapter_summary.remote(
            "docapp", "Echo", rows[0]["replica_id"],
            {"adapters": ["x"]}))
        report = api.get(controller.doctor.remote(False, None))
        assert report["violations"] >= 1, report
        violated = {v["check"] for rep in report["reports"]
                    for row in rep.get("checks", ())
                    for v in row["violations"]}
        assert "controller.census_broadcast" in violated, report
        assert report["census"]["docapp/Echo"], report
        assert _violations_total("controller.census_broadcast") > before
        details = {m.get("detail") for m in _manifest_details(dump_dir)}
        assert "controller.census_broadcast" in details, details
    finally:
        monkeypatch.delenv("RAYTPU_FAILPOINTS", raising=False)
        serve.shutdown()
        ray_tpu.shutdown()


# -- satellite: cross-feature leak gauntlet ---------------------------------

def _slow_lora_adapter_factory(cfg):
    """Throttled segmented-LoRA ragged step so the mid-stream kill
    lands while decode is in flight (same device-callback trick as
    test_prefix_cache)."""
    base = llama_paged_adapter(cfg)

    # wraps: the engine reads the step's signature for ``logit_idx=``
    # before it speculates (see tests/test_spec_decode.py)
    @functools.wraps(base.ragged_step)
    def slow_step(*args, **kwargs):
        jax.debug.callback(lambda: time.sleep(0.02), ordered=True)
        return base.ragged_step(*args, **kwargs)

    return dataclasses.replace(base, ragged_step=slow_step)


def test_cross_feature_survivor_audits_clean(params):
    """Spec-decode × migration-lease × adapter-pool under adapter
    eviction pressure (8-page pool) with a mid-stream SIGKILL: after
    the stream fails over, the survivor's deep audit is clean — no KV
    page, trie ref, lease, draft page or adapter borrow leaked."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core import api
    from ray_tpu.utils.test_utils import ReplicaKiller

    rng = np.random.default_rng(11)
    shared = rng.integers(1, 127, size=2 * PAGE).tolist()

    ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
    serve.start()
    try:
        app = serve.deployment(num_replicas=2, max_ongoing_requests=8)(
            LLMServer
        ).bind(
            LORA_CFG,
            EngineConfig(max_slots=4, max_seq_len=128,
                         min_prefill_bucket=16, page_size=PAGE,
                         ragged_batching=True, token_budget=36,
                         prefix_cache=True, spec_decode=True,
                         adapter_pool_pages=8,
                         adapter_page_elems=1024),
            lambda: params,
            adapter_factory=_slow_lora_adapter_factory,
        )
        handle = serve.run(app, name="llmdoc", route_prefix=None,
                           timeout_s=REPLICA_READY_S)
        # Adapter-pool churn beyond residency (8 pages) + trie warmth:
        # distinct tenants over a shared prefix force refcount-0 LRU
        # eviction while spec rounds draft against every stream.
        for i in range(6):
            out = handle.remote(
                {"tokens": shared + [i + 1, i + 2],
                 "max_new_tokens": 4, "temperature": 0.0,
                 "adapter_id": f"tenant-{i}"}).result(timeout_s=300)
            assert len(out["tokens"]) == 4
        from ray_tpu.serve.handle import _routers
        router = _routers[("llmdoc", "LLMServer")]
        with router._lock:
            replicas = {rid: info.handle
                        for rid, info in router._replicas.items()}
        assert len(replicas) == 2
        # Migration-lease leg: each replica pulls hot prefixes from
        # its peer — lease + export + release on the source engine.
        for rid, h in replicas.items():
            api.get(h.handle_request.remote(
                "pull_prefix_cache", (256,), {},
                {"app_name": "llmdoc", "deployment_name": "LLMServer",
                 "replica_id": rid}), timeout=300)

        gen = handle.options(stream=True).remote(
            {"tokens": shared + [99], "max_new_tokens": 10,
             "temperature": 0.0, "adapter_id": "tenant-kill"})
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
        assert len(outs) == 10

        (survivor_rid,) = [r for r in replicas if r != victim_rid]
        report = api.get(replicas[survivor_rid].doctor.remote(True),
                         timeout=120)
        assert report is not None
        assert report["violations"] == 0, report
        assert report["deep"] is True
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


# -- drain/stop leak-freedom (satellite 6) ----------------------------------

def test_stop_releases_leases_and_audits_clean(params):
    """An engine stopped while holding an open migration lease (crash
    cleanup never ran) releases it on the clean-stop path; the final
    shutdown audit — and an explicit post-stop audit — are clean."""
    eng = _engine(params, prefix_cache=True)
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, 127, size=2 * PAGE).tolist()
    eng.generate(prompt + [1], max_new_tokens=2, temperature=0.0)
    lease = eng.migration_lease(prompt)
    assert lease is not None and lease["pages"]
    assert eng._mig_leases  # held open across the stop on purpose
    eng.shutdown()
    eng._thread.join(timeout=30)  # shutdown() is async: let the tail run
    assert not eng._mig_leases
    report = eng.doctor(deep=True)
    assert report["violations"] == 0, report
    assert _violated_checks(report) == set()
