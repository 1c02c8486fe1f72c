"""Per-request critical-path latency attribution + SLO-miss flight
recorder (the observability PR's acceptance suite).

The waterfall invariant under test: ``latency_attribution.waterfall``
partitions a terminal request's stitched end-to-end wall into named
components (route / queue / compile / prefill_device / control_plane /
kv_transfer / retry_reprefill / decode_device / inter_step_gap) that
sum back to e2e — asserted within 5% on three stream shapes:

- unified: a directly-driven engine (no router row — route = 0);
- disagg: a serve-path prefill→decode handoff, whose MIGRATING
  interlude lands in ``kv_transfer`` and whose rows span >= 2 worker
  processes plus the driver;
- failover: a SIGKILLed replica mid-decode, whose survivor re-prefill
  lands in ``retry_reprefill`` and whose stitched ttft/e2e are
  measured from FIRST admission, not the resumed attempt.

Plus: an induced SLO miss writes a flight-recorder bundle holding the
offending request's events from >= 2 processes; ``raytpu trace`` is
byte-deterministic over static terminal rows; and the bench legs'
``dispatch_overhead`` block validates against scripts/bench_schema.
"""

import dataclasses
import functools
import importlib.util
import io
import json
import os
import pathlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.models import llama
from ray_tpu.serve import latency_attribution as lat
from ray_tpu.serve import request_events
from ray_tpu.serve.llm_engine import (
    SLO,
    EngineConfig,
    LLMEngine,
    LLMServer,
    llama_paged_adapter,
)
from ray_tpu.util import flight_recorder
from tests import midstream_kill
from tests.midstream_kill import hold  # noqa: F401 (fixture)

REPO = pathlib.Path(__file__).resolve().parent.parent

CFG = llama.LlamaConfig(
    vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, max_seq_len=128, remat=False, dtype=jnp.float32,
    param_dtype=jnp.float32,
)

PAGE = 4
N_NEW = 8
PROMPTS = [[i + 1, i + 2, i + 3] for i in range(3)]

APP = "latattr"
DEP = "LLMServer"
ROUTER_RING = f"router:{APP}/{DEP}"


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.key(0), CFG)


def _assert_waterfall(wf, rel=0.05):
    """The tier-1 invariant: components sum to stitched e2e within
    ``rel`` (exact by construction, so 5% is generous slack), every
    component is non-negative, and the share is a fraction."""
    assert wf is not None
    comp = wf["components"]
    assert set(comp) == set(lat.COMPONENTS)
    for c, v in comp.items():
        assert v >= -1e-9, f"negative component {c}={v}"
    total = sum(comp.values())
    assert abs(total - wf["e2e_s"]) <= rel * max(wf["e2e_s"], 1e-9) + 1e-6, \
        f"waterfall does not sum to e2e: {total} vs {wf['e2e_s']} ({comp})"
    assert 0.0 <= wf["control_plane_share"] <= 1.0 + 1e-9


# -- unified (directly-driven engine) ---------------------------------------

@pytest.fixture(scope="module")
def unified(params):
    """A fresh engine serving three greedy streams to completion; the
    engine is cold, so the first stream's prefill phase overlaps the
    serve.prefill / serve.decode compile windows."""
    eng = LLMEngine(
        params, llama_paged_adapter(CFG),
        EngineConfig(max_slots=4, max_seq_len=64, min_prefill_bucket=16),
    )
    streams = [eng.submit(p, max_new_tokens=N_NEW, temperature=0.0)
               for p in PROMPTS]
    for s in streams:
        s.result(timeout_s=300)
    yield eng, streams
    eng.shutdown()


def test_unified_waterfall_sums_to_e2e(unified):
    _eng, streams = unified
    for s in streams:
        wf = lat.waterfall(s.request_id)
        _assert_waterfall(wf)
        assert wf["state"] == "FINISHED"
        assert wf["generated_tokens"] == N_NEW
        # No router row on a directly-driven engine: nothing to blame
        # on routing.
        assert wf["components"]["route"] == 0.0


def test_cold_start_compile_is_attributed_and_excluded(unified):
    """Satellite 1: the first dispatch's trace+compile wall lands in
    the ``compile`` component (the sum stays exact) but is excluded
    from the control-plane share — the victim request is not blamed
    for cold-start compilation."""
    _eng, streams = unified
    wf0 = lat.waterfall(streams[0].request_id)
    assert wf0["components"]["compile"] > 0.0
    assert wf0["compile_excluded"]
    share_incl = wf0["components"]["control_plane"] / wf0["e2e_s"]
    assert wf0["control_plane_share"] >= share_incl  # smaller denominator


def test_terminal_observation_feeds_pinned_families(unified):
    from ray_tpu.util import metrics

    text = metrics.export_prometheus()
    assert "raytpu_serve_request_overhead_seconds" in text
    assert 'component="control_plane"' in text
    assert "raytpu_serve_control_plane_share" in text
    for fam in ("raytpu_flightrec_events", "raytpu_flightrec_triggers_total",
                "raytpu_flightrec_dumps_total"):
        assert fam in text
    agg = lat.aggregate(since=0.0)
    assert agg is not None and agg["requests"] >= len(PROMPTS)
    assert 0.0 <= agg["control_plane_share"] <= 1.0


def test_flight_recorder_holds_span_and_ring_events(unified):
    """The always-on ring saw the streams: request transitions at
    minimum (span events additionally when tracing is enabled)."""
    _eng, streams = unified
    evs = flight_recorder.snapshot(request_id=streams[0].request_id,
                                   window_s=600.0)["driver"]
    kinds = {e["kind"] for e in evs}
    assert "ring" in kinds or "span" in kinds, \
        f"no ring/span events for the request: {evs[:5]}"


# -- trace CLI + dump endpoint over the dashboard ---------------------------

def _run_cli(argv):
    from ray_tpu.scripts.cli import main

    out = io.StringIO()
    code = main(argv, out=out)
    return code, out.getvalue()


def test_cli_trace_deterministic(unified, tmp_path):
    """Satellite 3b: two ``raytpu trace`` runs over the same static
    terminal rows emit byte-identical waterfalls; unknown ids are a
    clean 404; ``raytpu flightrec dump`` writes a bundle."""
    from ray_tpu.dashboard import start_dashboard

    _eng, streams = unified
    rid = streams[1].request_id
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    dash = start_dashboard()
    try:
        code1, text1 = _run_cli(["--address", dash.address, "trace", rid])
        code2, text2 = _run_cli(["--address", dash.address, "trace", rid])
        assert code1 == 0 and code2 == 0
        assert text1 == text2, "trace output is not deterministic"
        assert rid in text1
        for c in lat.COMPONENTS:
            assert c in text1
        assert "control_plane_share=" in text1

        code, text = _run_cli(["--address", dash.address, "trace",
                               "no-such-request"])
        assert code == 1 and "no terminal request" in text

        code, text = _run_cli(["--address", dash.address, "flightrec",
                               "dump", "--dump-dir", str(tmp_path)])
        assert code == 0
        bundle = pathlib.Path(text.strip())
        assert (bundle / "manifest.json").exists()
        assert (bundle / "events.json").exists()
        assert (bundle / "metrics.prom").exists()
        assert json.loads((bundle / "manifest.json").read_text())[
            "reason"] == "manual"
    finally:
        dash.stop()
        ray_tpu.shutdown()


# -- bench dispatch_overhead block vs scripts/bench_schema ------------------

def _load_schema():
    path = REPO / "scripts" / "bench_schema.py"
    spec = importlib.util.spec_from_file_location("bench_schema", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_dispatch_overhead_block_is_schema_valid(unified):
    """Satellite 5: the block ``aggregate()`` hands the bench legs
    passes scripts/bench_schema's dispatch_overhead checks, and the
    checks reject zero-request blocks (absent-not-zero), out-of-range
    shares and negative components."""
    schema = _load_schema()
    good = lat.aggregate(since=0.0)
    assert good is not None
    problems = []
    schema._check_dispatch_overhead("serving", good, problems)
    assert problems == [], problems

    bad = dict(good, requests=0)
    problems = []
    schema._check_dispatch_overhead("serving", bad, problems)
    assert problems, "zero-request block must be rejected (absent-not-zero)"

    bad = dict(good, control_plane_share=1.5)
    problems = []
    schema._check_dispatch_overhead("serving", bad, problems)
    assert problems

    bad = dict(good, components=dict(good["components"], queue=-0.1))
    problems = []
    schema._check_dispatch_overhead("serving", bad, problems)
    assert problems


# -- disagg (serve path, cross-process) -------------------------------------

def _wait_roles():
    from ray_tpu.util import state

    deadline = time.monotonic() + 120
    rows = []
    while time.monotonic() < deadline:
        rows = state.list_replicas()
        roles = sorted(r["role"] for r in rows if r["state"] == "RUNNING")
        if roles == ["decode", "prefill"]:
            return
        time.sleep(0.01)
    raise TimeoutError(f"roles never settled: {rows}")


def test_disagg_waterfall_attributes_kv_transfer(params):
    """A prefill→decode handoff stream's waterfall spans the driver
    plus both worker processes, classifies the MIGRATING interlude as
    ``kv_transfer``, and still sums to the stitched e2e."""
    prompt = np.random.default_rng(5).integers(1, 127, size=2 * PAGE).tolist()
    ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
    serve.start()
    app = serve.deployment(
        num_replicas=2, max_ongoing_requests=8,
        disagg={"prefill_replicas": 1, "transfer": "exact",
                "handoff_after_tokens": 2})(LLMServer).bind(
        CFG,
        EngineConfig(max_slots=8, max_seq_len=64, min_prefill_bucket=16,
                     page_size=PAGE, ragged_batching=True, token_budget=64,
                     decode_chunk=1, prefix_cache=True),
        lambda: params,
        adapter_factory=llama_paged_adapter,
    )
    handle = serve.run(app, name=APP, route_prefix=None)
    try:
        _wait_roles()
        g = handle.options(stream=True).remote(
            {"tokens": prompt, "max_new_tokens": N_NEW, "temperature": 0.0})
        out = g.result(timeout_s=600)
        assert len(out) == N_NEW
        rid = g.request_id

        # The handoff rode the router ring (driver-side, immediate).
        router_rows = [r for r in request_events.snapshot_rows()
                       if r["engine"] == ROUTER_RING
                       and r["request_id"] == rid]
        assert router_rows and "MIGRATING" in router_rows[0]["state_ts"]

        # Engine rows federate on reply piggybacks (<= 1 s cadence):
        # wait until the join sees both worker processes and the
        # decode-side resume interlude.
        deadline = time.monotonic() + 120
        wf = None
        while time.monotonic() < deadline:
            wf = lat.waterfall(rid)
            if (wf is not None and len(wf["procs"]) >= 3
                    and wf["components"]["kv_transfer"] > 0):
                break
            time.sleep(0.05)
        _assert_waterfall(wf)
        assert len(wf["procs"]) >= 2, wf["procs"]  # acceptance floor
        assert wf["components"]["kv_transfer"] > 0.0, wf["components"]
        assert wf["components"]["retry_reprefill"] == 0.0  # planned, not
        assert wf["state"] == "FINISHED"
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


# -- failover (SIGKILL) + SLO-miss flight-recorder bundle -------------------

FAIL_STREAMS = 4
FAIL_NEW = 12
FAIL_PROMPTS = [[i + 1, i + 2, i + 3] for i in range(FAIL_STREAMS)]


def _slow_adapter_factory(cfg, hold):
    """Throttled decode (jax.debug.callback: decode_slots is traced, a
    bare sleep would fire at trace time only) so every stream spans a
    few row-federation cadences (~1 s), and one that stands still while
    the file ``hold`` exists, so the kill lands mid-decode with the
    victim's DECODING row already on the driver."""
    base = llama_paged_adapter(cfg)
    throttle = midstream_kill.throttle(hold, 0.2)

    def slow_decode(*args, **kwargs):
        jax.debug.callback(throttle, ordered=True)
        return base.decode_slots(*args, **kwargs)

    return dataclasses.replace(base, decode_slots=slow_decode)


def _engine_rows(rid):
    return [r for r in request_events.snapshot_rows()
            if r["request_id"] == rid
            and not str(r.get("engine", "")).startswith("router:")]


def test_failover_waterfall_and_slo_miss_bundle(params, tmp_path, hold):
    """SIGKILL a replica mid-decode: the retried stream's waterfall
    books the survivor re-prefill under ``retry_reprefill`` and its
    stitched ttft/e2e run from FIRST admission (satellite 2); every
    finished stream misses the (absurdly tight) e2e SLO, so the flight
    recorder writes a bundle holding the offending request's events
    from >= 2 processes."""
    flight_recorder.clear()
    flight_recorder.configure(dump_dir=str(tmp_path), auto_dump=True,
                              min_dump_interval_s=0.0)
    ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
    serve.start()
    app = serve.deployment(num_replicas=2, max_ongoing_requests=8)(
        LLMServer
    ).bind(
        CFG,
        # decode_chunk=1 + 0.2 s throttle: ~2.4 s per stream, so the
        # kill reliably lands mid-decode.  slo.e2e_s=1 ms: every
        # finish is an SLO miss — the trigger under test.
        EngineConfig(max_slots=8, max_seq_len=128, min_prefill_bucket=16,
                     decode_chunk=1, slo=SLO(e2e_s=0.001)),
        lambda: params,
        adapter_factory=functools.partial(_slow_adapter_factory,
                                          hold=str(hold)),
    )
    handle = serve.run(app, name=APP, route_prefix=None)
    try:
        shandle = handle.options(stream=True)
        gens = [shandle.remote({"tokens": FAIL_PROMPTS[i],
                                "max_new_tokens": FAIL_NEW,
                                "temperature": 0.0})
                for i in range(FAIL_STREAMS)]
        outs = [[] for _ in range(FAIL_STREAMS)]
        errs = [None] * FAIL_STREAMS

        def consume(i):
            try:
                for tok in gens[i]:
                    outs[i].append(tok)
            except BaseException as e:
                errs[i] = e

        threads = [threading.Thread(target=consume, args=(i,), daemon=True)
                   for i in range(FAIL_STREAMS)]
        for t in threads:
            t.start()

        # Kill only once the driver's federated view has every victim
        # candidate's DECODING stamp — the waterfall's t_dec0 anchor
        # must survive the SIGKILL.
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            if (all(len(o) >= 2 for o in outs)
                    and all(any("DECODING" in r.get("state_ts", {})
                                for r in _engine_rows(g.request_id))
                            for g in gens)):
                break
            time.sleep(0.02)
        else:
            raise TimeoutError(
                f"DECODING rows never federated: {[len(o) for o in outs]}")

        # a replica that still holds a stream (and so its DECODING row)
        midstream_kill.kill_a_replica_mid_stream(APP, DEP, hold)
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads), \
            f"streams hung after kill: {[len(o) for o in outs]}"
        assert errs == [None] * FAIL_STREAMS, f"streams failed: {errs}"
        assert all(len(o) == FAIL_NEW for o in outs)

        router_rows = [r for r in request_events.snapshot_rows()
                       if r["engine"] == ROUTER_RING]
        by_id = {r["request_id"]: r for r in router_rows}
        retried = [g.request_id for g in gens
                   if by_id[g.request_id]["attempt"] >= 1]
        assert retried, "kill landed mid-decode but nothing retried"

        # Satellite 2: the stitched view runs from FIRST admission.
        rid = retried[0]
        st = request_events.stitch_request(rid)
        assert st["state"] == "FINISHED" and st["attempts"] >= 1
        first_admit = min(r["state_ts"]["QUEUED"]
                          for r in request_events.snapshot_rows()
                          if r["request_id"] == rid
                          and "QUEUED" in r.get("state_ts", {}))
        assert st["t_admitted"] == first_admit
        assert st["ttft_s"] is not None and st["e2e_s"] is not None
        assert 0 <= st["ttft_s"] <= st["e2e_s"]
        assert st["generated_tokens"] == FAIL_NEW  # delivered, not replayed

        # The survivor's re-prefill books as retry_reprefill (poll: its
        # terminal row federates on the next reply cadence).
        deadline = time.monotonic() + 120
        wf = None
        while time.monotonic() < deadline:
            wf = lat.waterfall(rid)
            if wf is not None and wf["components"]["retry_reprefill"] > 0:
                break
            time.sleep(0.05)
        _assert_waterfall(wf)
        assert wf["components"]["retry_reprefill"] > 0.0, wf["components"]
        assert wf["components"]["kv_transfer"] == 0.0  # unplanned, not
        assert wf["attempts"] >= 1
        assert wf["e2e_s"] == st["e2e_s"]

        # SLO-miss bundle: worker triggers ship on the NEXT reply, so
        # nudge traffic until the driver-side auto-dump lands.
        def slo_bundles():
            # manifest.json is written last: its presence marks a
            # fully-written bundle (the dir appears first).
            return sorted(p for p in tmp_path.iterdir()
                          if p.is_dir() and p.name.endswith("slo_miss")
                          and (p / "manifest.json").exists())

        deadline = time.monotonic() + 120
        while not slo_bundles() and time.monotonic() < deadline:
            shandle.remote({"tokens": [1, 2], "max_new_tokens": 1,
                            "temperature": 0.0}).result(timeout_s=300)
            time.sleep(0.1)
        bundles = slo_bundles()
        assert bundles, f"no slo_miss bundle in {list(tmp_path.iterdir())}"
        doc = json.loads((bundles[-1] / "events.json").read_text())
        assert doc["reason"] == "slo_miss"
        events = doc["events"]
        triggers = [e for evs in events.values() for e in evs
                    if e.get("kind") == "trigger"
                    and e.get("reason") == "slo_miss"]
        assert triggers, "bundle holds no slo_miss trigger event"
        offender = next(t["request_id"] for t in triggers
                        if t.get("request_id"))
        procs_with_offender = [
            p for p, evs in events.items()
            if any(e.get("request_id") == offender for e in evs)]
        assert len(procs_with_offender) >= 2, \
            (f"offender {offender!r} seen in {procs_with_offender}, "
             f"procs={sorted(events)}")
        manifest = json.loads((bundles[-1] / "manifest.json").read_text())
        assert len(manifest["procs"]) >= 2

        # ISSUE 18 history proof: the bundle carries the trailing
        # time-series window, with its procs listed in the manifest.
        assert (bundles[-1] / "history.json").exists()
        hist = json.loads((bundles[-1] / "history.json").read_text())
        assert hist["window_s"] >= 60.0
        assert manifest["history_procs"] == sorted(
            {s["proc"] for s in hist["series"]})
        # The >= 2-process serve-plane claim polls first: worker
        # sampler points ride the reply cadence (1 s ticks), so nudge
        # traffic until they federate, then cut a manual bundle.
        from ray_tpu.util import timeseries

        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            sprocs = {s["proc"] for s in timeseries.query(
                family="raytpu_serve_")["series"]}
            if len(sprocs) >= 2:
                break
            shandle.remote({"tokens": [1, 2], "max_new_tokens": 1,
                            "temperature": 0.0}).result(timeout_s=300)
            time.sleep(0.2)
        hpath = flight_recorder.dump(reason="history")
        hist = json.loads(
            (pathlib.Path(hpath) / "history.json").read_text())
        sprocs = {s["proc"] for s in hist["series"]
                  if s["family"].startswith("raytpu_serve_")}
        assert len(sprocs) >= 2, sorted(sprocs)
    finally:
        flight_recorder.configure(dump_dir="", min_dump_interval_s=2.0)
        serve.shutdown()
        ray_tpu.shutdown()
