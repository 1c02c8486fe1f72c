"""Request-lifecycle plane, end to end: the per-engine request ring
(serve/request_events) driven through mixed finished / cancelled /
failed requests, read back through every consumer — state.list_requests
/ summarize_requests, the dashboard's /api/v0/requests routes, the
token-latency + SLO metric families, and the request rows in the merged
timeline — plus the terminal-accounting regressions (cancel releases
slots and pages; a queued cancel never fabricates phase timestamps).
"""

import json
import time
import urllib.request

import jax
import pytest

import ray_tpu
from ray_tpu.models import llama
from ray_tpu.serve import request_events as reqev
from ray_tpu.serve.llm_engine import (
    SLO,
    EngineConfig,
    LLMEngine,
    PagedEngineAdapter,
    llama_paged_adapter,
)
from ray_tpu.util import metrics, state

CFG = llama.LlamaConfig(
    vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, max_seq_len=128, remat=False,
)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.key(0), CFG)


def _family_total(text, sample_prefix):
    """Sum every exposition sample whose name (incl. any label block the
    caller bakes into the prefix) matches — 0.0 when absent."""
    total = 0.0
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        if (line.startswith(sample_prefix + " ")
                or line.startswith(sample_prefix + "{")):
            total += float(line.rsplit(" ", 1)[1])
    return total


def _first_tokens(stream, n=1):
    """Pull n tokens off a live stream without consuming it to the end."""
    it = iter(stream)
    return [next(it) for _ in range(n)]


def _monotone(row):
    ts = list(row["state_ts"].values())
    return all(a <= b for a, b in zip(ts, ts[1:]))


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_request_plane_e2e(params):
    """The acceptance path: one paged engine, two finished requests, one
    cancelled mid-decode, one failed (loop crash), then every read-side
    surface must agree on the same four lifecycles."""
    from ray_tpu.dashboard import start_dashboard

    reqev.clear()
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    dash = start_dashboard()

    good = llama_paged_adapter(CFG)
    fail = {"on": False}

    def prefill_batch(p, tokens, true_lens, pages_rows, cache):
        # Runs at trace time: only a prompt hitting a FRESH compile
        # bucket (len 17..32 -> bucket 32 here) sees a raise.
        if fail["on"]:
            raise RuntimeError("injected prefill failure")
        return good.prefill_batch(p, tokens, true_lens, pages_rows, cache)

    adapter = PagedEngineAdapter(
        init_cache=good.init_cache,
        prefill_slot=good.prefill_slot,
        decode_slots=good.decode_slots,
        prefill_batch=prefill_batch,
    )
    eng = LLMEngine(params, adapter, EngineConfig(
        max_slots=4, max_seq_len=128, min_prefill_bucket=16,
        page_size=16, decode_chunk=4,
        slo=SLO(ttft_s=60.0, e2e_s=120.0),
    ))
    before = metrics.export_prometheus()
    try:
        # Two requests that FINISH (and, with the generous SLO, meet it).
        sa = eng.submit([1, 2, 3], max_new_tokens=6)
        sb = eng.submit([4, 5, 6], max_new_tokens=6)
        assert len(sa.result(timeout_s=120)) == 6
        assert len(sb.result(timeout_s=120)) == 6

        # One cancelled mid-decode: first token proves DECODING was
        # reached, then the cancel resolves on the engine loop.
        sc = eng.submit([7, 8, 9], max_new_tokens=500)
        _first_tokens(sc, 1)
        sc.cancel()
        got_c = sc.result(timeout_s=120)
        assert 1 <= len(got_c) < 125  # tokens before the cancel stay

        # One FAILED: the injected raise fires on the fresh 32-token
        # prefill bucket and crashes the loop.
        fail["on"] = True
        sd = eng.submit(list(range(1, 21)), max_new_tokens=4)
        with pytest.raises(RuntimeError, match="engine loop crashed"):
            sd.result(timeout_s=120)

        ids = {"A": sa.request_id, "B": sb.request_id,
               "C": sc.request_id, "D": sd.request_id}
        after = metrics.export_prometheus()

        # -- ring rows: every request in its correct terminal state ----
        rows = state.list_requests(
            filters=[("engine", "=", eng.engine_id)],
            limit=100, detail=True)
        by_id = {r["request_id"]: r for r in rows}
        assert set(ids.values()) <= set(by_id)
        a, b, c, d = (by_id[ids[k]] for k in "ABCD")
        assert a["state"] == b["state"] == "FINISHED"
        assert a["terminal_cause"] == "max_new_tokens"
        assert c["state"] == "CANCELLED"
        assert c["terminal_cause"] == "cancelled"
        assert d["state"] == "FAILED"
        assert "injected prefill failure" in d["terminal_cause"]
        for row in (a, b, c, d):
            assert _monotone(row), row["state_ts"]
        # Token counts, slot/page assignment, derived latencies.
        assert a["generated_tokens"] == b["generated_tokens"] == 6
        assert c["generated_tokens"] >= 1
        assert d["generated_tokens"] == 0
        for row in (a, b, c):
            assert row["slot"] is not None
            assert row["num_pages"] >= 1
            assert "DECODING" in row["state_ts"]
            assert row["ttft_s"] is not None and row["ttft_s"] >= 0
        # D never left the queue: no phase stamps, absent (not zero)
        # latency views.
        assert d["slot"] is None
        assert "DECODING" not in d["state_ts"]
        assert d["ttft_s"] is None and d["tpot_s"] is None
        assert a["tpot_s"] is not None and a["e2e_s"] is not None

        # -- summarize matches the row set ----------------------------
        all_rows = state.list_requests(limit=100000)
        summ = state.summarize_requests()
        assert summ["total"] == len(all_rows)
        by_state = {}
        by_cause = {}
        for r in all_rows:
            by_state[r["state"]] = by_state.get(r["state"], 0) + 1
            if r["terminal_cause"] is not None:
                by_cause[r["terminal_cause"]] = \
                    by_cause.get(r["terminal_cause"], 0) + 1
        assert summ["by_state"] == by_state
        assert summ["by_terminal_cause"] == by_cause
        assert summ["by_state"].get("FINISHED", 0) >= 2
        assert summ["by_state"].get("CANCELLED", 0) >= 1
        assert summ["by_state"].get("FAILED", 0) >= 1

        # -- dashboard serves the same rows ---------------------------
        with urllib.request.urlopen(
                dash.address + "/api/v0/requests?limit=100000",
                timeout=5) as r:
            served = json.loads(r.read())["result"]
        assert ({(r["request_id"], r["state"]) for r in served}
                == {(r["request_id"], r["state"]) for r in all_rows})
        with urllib.request.urlopen(
                dash.address + "/api/v0/requests/summarize",
                timeout=5) as r:
            assert json.loads(r.read())["result"] == summ

        # -- token-latency histograms: exactly the finished requests --
        for fam in ("raytpu_serve_ttft_seconds_count",
                    "raytpu_serve_tpot_seconds_count",
                    "raytpu_serve_request_itl_seconds_count"):
            delta = _family_total(after, fam) - _family_total(before, fam)
            assert delta == 2, (fam, delta)

        # -- SLO met/missed sums to the terminal count ----------------
        met = (_family_total(
                   after, 'raytpu_serve_request_slo_total{outcome="met"}')
               - _family_total(
                   before,
                   'raytpu_serve_request_slo_total{outcome="met"}'))
        missed = (_family_total(
                      after,
                      'raytpu_serve_request_slo_total{outcome="missed"}')
                  - _family_total(
                      before,
                      'raytpu_serve_request_slo_total{outcome="missed"}'))
        assert met == 2 and missed == 2
        for st, n in (("FINISHED", 2), ("CANCELLED", 1), ("FAILED", 1)):
            fam = f'raytpu_serve_request_terminal_total{{state="{st}"}}'
            assert (_family_total(after, fam)
                    - _family_total(before, fam)) == n
        good_ratio = _family_total(after, "raytpu_serve_goodput_ratio")
        assert 0.0 < good_ratio < 1.0  # cancelled tokens drag it under 1

        # The scrape-time request gauge reflects the live ring, and the
        # full exposition (incl. the new families) passes the smoke
        # check with its label-consistency rule.
        assert _family_total(
            after, 'raytpu_serve_requests{State="FINISHED"}') == 2
        assert _family_total(
            after, 'raytpu_serve_requests{State="FAILED"}') == 1
        import importlib.util
        import pathlib
        cm_path = (pathlib.Path(__file__).resolve().parent.parent
                   / "scripts" / "check_metrics.py")
        spec = importlib.util.spec_from_file_location("check_metrics",
                                                      cm_path)
        cm = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cm)
        assert cm.check_exposition(after, require=[
            "raytpu_serve_request_itl_seconds",
            "raytpu_serve_request_slo_total",
            "raytpu_serve_request_terminal_total",
            "raytpu_serve_goodput_ratio",
            "raytpu_serve_requests",
            "raytpu_serve_step_tokens_total",
            "raytpu_serve_kv_pages_free",
            "raytpu_serve_kv_pages_cached",
            # Multi-host serving plane: the engine declares the
            # per-link collective families even off-mesh, so the
            # scrape never silently loses them.
            "raytpu_serve_collective_bytes_total",
            "raytpu_serve_collective_seconds",
            # Disaggregated serving plane: declared at engine
            # construction so the scrape pins them even when no
            # migration ever runs.
            "raytpu_serve_kv_migration_pages_total",
            "raytpu_serve_kv_migration_bytes_total",
            "raytpu_serve_kv_migration_seconds",
            "raytpu_serve_disagg_handoffs_total",
            "raytpu_serve_disagg_requests_total",
            # LoRA multiplexing plane: the paged adapter pool's
            # families are declared with the engine telemetry even
            # when no adapter is ever loaded.
            "raytpu_serve_adapter_pool_pages",
            "raytpu_serve_adapter_resident",
            "raytpu_serve_adapter_hits_total",
            "raytpu_serve_adapter_misses_total",
            "raytpu_serve_adapter_evictions_total",
            # Latency-attribution + flight-recorder planes: declared
            # with the engine telemetry even when no request ever
            # misses its SLO.
            "raytpu_serve_request_overhead_seconds",
            "raytpu_serve_control_plane_share",
            "raytpu_flightrec_events",
            "raytpu_flightrec_triggers_total",
            "raytpu_flightrec_dumps_total",
            # Speculative-decoding families: declared with the engine
            # telemetry even when the engine never speculates.
            "raytpu_serve_spec_rounds_total",
            "raytpu_serve_spec_drafted_tokens_total",
            "raytpu_serve_spec_accepted_tokens_total",
            "raytpu_serve_spec_accept_ratio",
        ]) == []

        # -- timeline: request rows, slot threads, globally ts-sorted -
        events = state.timeline()
        req_events = [e for e in events if e.get("ph") == "X"
                      and str(e.get("pid", "")).startswith("llmreq:")]
        assert {e["pid"] for e in req_events} \
            == {f"llmreq:{eng.engine_id}"}
        assert any(str(e["tid"]).startswith("slot") for e in req_events)
        assert any(e["tid"] == "queue" for e in req_events)  # D
        names = {e["name"] for e in req_events}
        assert {"queued", "prefill", "decode"} <= names
        ts = [e["ts"] for e in events if "ts" in e]
        assert ts == sorted(ts)
        seen_ts = False
        for e in events:
            if "ts" in e:
                seen_ts = True
            else:
                assert not seen_ts, "metadata row after a timestamped one"
    finally:
        dash.stop()
        eng.shutdown()
        ray_tpu.shutdown()


def test_cancel_releases_slot_and_pages(params):
    """Regression: a cancelled decode must free its slot AND its pages —
    with one slot and a fully-committed pool, the next request can only
    run if the cancel path released everything."""
    reqev.clear()
    eng = LLMEngine(params, llama_paged_adapter(CFG), EngineConfig(
        max_slots=1, max_seq_len=128, min_prefill_bucket=16,
        page_size=16, decode_chunk=4,
    ))
    try:
        s1 = eng.submit([1, 2, 3], max_new_tokens=500)  # claims all 8 pages
        _first_tokens(s1, 1)
        s1.cancel()
        s1.result(timeout_s=120)
        # The follow-up request needs the slot and pages back, and its
        # output must match an untouched engine (freed pages are really
        # reusable, not aliased into a stale block table).
        want = eng.submit([9, 8, 7], max_new_tokens=6)
        got = want.result(timeout_s=120)
        assert len(got) == 6
        assert len(eng._free_slots) == 1
        assert len(eng._free_pages) == eng._num_pages
        rows = {r["request_id"]: r for r in state.list_requests(
            filters=[("engine", "=", eng.engine_id)], limit=10,
            detail=True)}
        assert rows[s1.request_id]["state"] == "CANCELLED"
        assert rows[want.request_id]["state"] == "FINISHED"
        assert eng.stats()["requests"] == {"CANCELLED": 1, "FINISHED": 1}
    finally:
        eng.shutdown()


def test_cancel_queued_request_never_ran(params):
    """A request cancelled while still queued reaches CANCELLED without
    ever fabricating PREFILLING/DECODING stamps or a page count (absent,
    not zero); the request that ran records the pages it held."""
    reqev.clear()
    eng = LLMEngine(params, llama_paged_adapter(CFG), EngineConfig(
        max_slots=1, max_seq_len=128, min_prefill_bucket=16,
    ))
    try:
        s1 = eng.submit([1, 2, 3], max_new_tokens=500, request_id="hog")
        _first_tokens(s1, 1)  # s1 owns the only slot
        s2 = eng.submit([4, 5, 6], max_new_tokens=4, request_id="starved")
        assert s2.request_id == "starved"
        s2.cancel()
        s2.result(timeout_s=120)
        s1.cancel()
        s1.result(timeout_s=120)
        rows = {r["request_id"]: r for r in state.list_requests(
            filters=[("engine", "=", eng.engine_id)], limit=10,
            detail=True)}
        queued = rows["starved"]
        assert queued["state"] == "CANCELLED"
        assert set(queued["state_ts"]) == {"QUEUED", "CANCELLED"}
        assert queued["slot"] is None
        assert queued["num_pages"] is None  # absent, not zero
        assert queued["ttft_s"] is None
        running = rows["hog"]
        assert running["state"] == "CANCELLED"
        assert "DECODING" in running["state_ts"]
        assert running["ttft_s"] is not None
        assert running["num_pages"] == 2  # 128 tokens of 64-token pages
        # Cancel is idempotent: unknown/terminal ids are a no-op.
        eng.cancel("starved")
        eng.cancel("no-such-request")
    finally:
        eng.shutdown()


def test_request_id_propagates_through_serve(params):
    """router-minted id -> request metadata -> replica contextvar ->
    LLMEngine.submit: the response and the (federated) ring row carry
    the same req- id."""
    from ray_tpu import serve
    from ray_tpu.serve.llm_engine import LLMServer

    reqev.clear()
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    serve.start()
    try:
        app = serve.deployment(max_ongoing_requests=8)(LLMServer).bind(
            CFG, EngineConfig(max_slots=2, max_seq_len=128,
                              min_prefill_bucket=16),
            lambda: params,
        )
        handle = serve.run(app, name="llm-reqplane", route_prefix=None)
        out = handle.remote(
            {"tokens": [1, 2, 3], "max_new_tokens": 4}
        ).result(timeout_s=120)
        rid = out["request_id"]
        assert rid.startswith("req-")

        # The replica may live in a worker process: its ring rows ride
        # task replies (worker_main -> runtime merge), so drive more
        # traffic until the federated snapshot lands driver-side.
        row = None
        deadline = time.time() + 60
        while time.time() < deadline:
            rows = state.list_requests(
                filters=[("request_id", "=", rid)], limit=10)
            if rows and rows[0]["state"] == "FINISHED":
                row = rows[0]
                break
            handle.remote(
                {"tokens": [2, 2], "max_new_tokens": 2}
            ).result(timeout_s=120)
            time.sleep(0.25)
        assert row is not None, "request row never federated to driver"
        assert row["state"] == "FINISHED"
        assert row["generated_tokens"] == 4
        # An explicit payload id wins over the router-minted one.
        out2 = handle.remote(
            {"tokens": [5, 6], "max_new_tokens": 2,
             "request_id": "client-chosen"}
        ).result(timeout_s=120)
        assert out2["request_id"] == "client-chosen"
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
