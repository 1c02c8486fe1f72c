"""Cluster-wide telemetry history plane (util/timeseries, ISSUE 18).

The invariants under test:

- Rollup correctness: raw 1 s points fold deterministically into the
  coarser rings — counter deltas sum, gauges average, histogram deltas
  (count/sum/nonzero buckets) sum — driven through ``sample_now(now=)``
  so the timeline is synthetic and exact.
- Counter-reset tolerance: a cumulative total that goes backwards (the
  observing process restarted) yields the new total as the delta —
  never a negative delta or rate anywhere in any ring.
- Hard memory bound: series admission reserves worst-case ring cost, so
  ``memory_bytes()`` stays under the configured budget no matter how
  many families/tag-sets the registry grows; refusals are counted on
  ``raytpu_timeseries_dropped_series_total``, never silent.
- Cross-process federation: worker points cursor-ship exactly once
  (``ship``/``ingest``) and appear under their proc key in ``query()``
  — unit-level, and end-to-end riding a real task reply.
- ``raytpu top``: the frame renderer is pure, and ``top --once``
  against the dashboard endpoint is byte-deterministic over a static
  store.
- Flight recorder (satellite 2): ``configure`` idempotently re-trims
  local AND remote rings (capacity + window take effect physically,
  not just at snapshot time), and a dump bundle carries the trailing
  ``history.json`` window with its procs listed in the manifest.
"""

import io
import json
import pathlib
import time

import pytest

import ray_tpu
from ray_tpu.util import flight_recorder, metrics, timeseries

T0 = 1_000_000.0  # synthetic epoch, divisible by every ring resolution


@pytest.fixture(autouse=True)
def fresh_plane():
    metrics.registry().clear()
    timeseries.stop()
    timeseries.clear()
    timeseries.configure(period_s=1.0, rings=timeseries._DEFAULT_RINGS,
                         max_bytes=8 << 20)
    yield
    timeseries.stop()
    timeseries.clear()
    timeseries.configure(period_s=1.0, rings=timeseries._DEFAULT_RINGS,
                         max_bytes=8 << 20)
    metrics.registry().clear()


# -- rollup correctness -----------------------------------------------------

def test_counter_and_gauge_rollup_exact():
    c = metrics.Counter("raytpu_test_flow_total", "t")
    g = metrics.Gauge("raytpu_test_depth", "t")
    # Tick 0 is the counter's baseline (no delta derivable); the gauge
    # samples from the first tick.
    for i in range(21):
        c.inc(i % 3)
        g.set(float(i))
        timeseries.sample_now(now=T0 + i)

    q = timeseries.query(family="raytpu_test_flow_total", step=1)
    (ser,) = q["series"]
    assert (ser["proc"], ser["kind"], ser["tags"]) == ("driver",
                                                       "counter", {})
    assert [p["delta"] for p in ser["points"]] == [i % 3
                                                   for i in range(1, 21)]
    assert [p["t"] for p in ser["points"]] == [T0 + i
                                               for i in range(1, 21)]
    # Raw ring resolution is 1 s, so rate == delta there.
    assert all(p["rate"] == p["delta"] for p in ser["points"])

    # 10 s ring: a bucket flushes when a later tick crosses its
    # boundary — after tick 20 the first two buckets are closed.
    q10 = timeseries.query(family="raytpu_test_flow_total", step=10)
    (s10,) = q10["series"]
    assert q10["step"] == 10.0
    assert [(p["t"], p["delta"]) for p in s10["points"]] == [
        (T0, float(sum(i % 3 for i in range(1, 10)))),
        (T0 + 10, float(sum(i % 3 for i in range(10, 20)))),
    ]
    assert all(p["rate"] == p["delta"] / 10.0 for p in s10["points"])

    # Gauge rollup is the bucket mean.
    g10 = timeseries.query(family="raytpu_test_depth", step=10)
    (sg,) = g10["series"]
    assert [(p["t"], p["value"]) for p in sg["points"]] == [
        (T0, sum(range(10)) / 10.0),
        (T0 + 10, sum(range(10, 20)) / 10.0),
    ]


def test_histogram_deltas_and_sparse_buckets():
    h = metrics.Histogram("raytpu_test_lat_seconds", "t",
                          boundaries=[0.1, 1.0])
    h.observe(0.05)
    timeseries.sample_now(now=T0)        # baseline
    h.observe(0.5)
    h.observe(5.0)
    timeseries.sample_now(now=T0 + 1)

    (ser,) = timeseries.query(family="raytpu_test_lat_seconds")["series"]
    assert ser["kind"] == "histogram"
    (p,) = ser["points"]
    assert p["count"] == 2.0
    assert abs(p["sum"] - 5.5) < 1e-9
    # Bucket deltas are cumulative-exposition diffs with the zero rows
    # dropped: the 0.1 bucket saw nothing this tick.
    assert p["buckets"] == {"1.0": 1.0, "+Inf": 2.0}


def test_counter_reset_never_yields_negative_rates():
    c = metrics.Counter("raytpu_test_reset_total", "t")
    c.inc(10)
    timeseries.sample_now(now=T0)        # baseline
    c.inc(5)
    timeseries.sample_now(now=T0 + 1)    # delta 5
    # Restart: a fresh process re-registers the family and its
    # cumulative total starts over, BELOW the previous observation.
    metrics.registry().clear()
    c2 = metrics.Counter("raytpu_test_reset_total", "t")
    c2.inc(2)
    timeseries.sample_now(now=T0 + 2)    # total 2 < prev 15

    (ser,) = timeseries.query(family="raytpu_test_reset_total")["series"]
    assert [p["delta"] for p in ser["points"]] == [5.0, 2.0]
    assert all(p["rate"] >= 0.0 for p in ser["points"])


# -- hard memory bound ------------------------------------------------------

def test_memory_bound_is_structural_and_drops_are_counted():
    # Tiny rings and a budget that admits exactly 4 counter/gauge
    # series ((8 + 4) points * 120 bytes = 1440 each).
    timeseries.configure(rings=((1.0, 8), (10.0, 4)), max_bytes=4 * 1440)
    # The counter is the process's and outlives ``registry().clear()`` in
    # its module: a file this worker ran before has left its drops in
    # it, so what THIS load adds is what is compared.
    dropped = timeseries._telemetry()["dropped"]
    dropped_before = sum(s[2] for s in dropped._samples())
    g = metrics.Gauge("raytpu_test_wide", "t", tag_keys=("i",))
    for i in range(20):
        g.set(float(i), tags={"i": str(i)})
    for tick in range(30):  # sustained load, rings wrap
        timeseries.sample_now(now=T0 + tick)

    assert timeseries.memory_bytes() <= 4 * 1440
    series = timeseries.query(family="raytpu_test_wide")["series"]
    assert len(series) == 4, [s["tags"] for s in series]
    assert metrics.registry().get(
        "raytpu_timeseries_dropped_series_total") is dropped
    assert sum(s[2] for s in dropped._samples()) - dropped_before == 16.0
    # Admitted series kept sampling: rings are full, not starved.
    assert all(len(s["points"]) == 8 for s in series)


# -- federation -------------------------------------------------------------

def test_ship_ingest_places_series_under_proc_key():
    c = metrics.Counter("raytpu_test_fed_total", "t")
    c.inc(1)
    timeseries.sample_now(now=T0)
    c.inc(4)
    timeseries.sample_now(now=T0 + 1)
    recs = timeseries.ship()
    assert recs, "sampled points never reached the outbox"
    assert timeseries.ship() is None, "cursor did not drain"

    # Simulate the driver side: a clean store ingesting the shipment.
    timeseries.clear()
    timeseries.ingest("pool-worker-3", recs)
    (ser,) = timeseries.query(family="raytpu_test_fed_total")["series"]
    assert ser["proc"] == "pool-worker-3"
    assert ser["points"][-1]["delta"] == 4.0
    assert timeseries.query(family="raytpu_test_fed_total",
                            proc="driver")["series"] == []
    # Idempotence is the ship cursor's job: re-ingesting the same batch
    # is the only way to duplicate, and ship() already returned None.


def test_worker_points_ride_task_replies():
    """End-to-end: a worker process samples its own registry; the
    points cursor-ship on the task reply and land under the worker's
    proc key in the driver's query surface."""
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    try:
        @ray_tpu.remote
        def emit():
            from ray_tpu.util import metrics as wm
            from ray_tpu.util import timeseries as wts

            c = wm.registry().get("raytpu_test_e2e_total")
            if c is None:
                c = wm.Counter("raytpu_test_e2e_total", "t")
            c.inc(3)
            wts.sample_now()
            c.inc(2)
            wts.sample_now()
            return True

        assert ray_tpu.get(emit.remote())
        deadline = time.monotonic() + 60
        procs = set()
        while time.monotonic() < deadline:
            q = timeseries.query(family="raytpu_test_e2e_total")
            procs = {s["proc"] for s in q["series"]}
            if any(p != "driver" for p in procs):
                break
            # Any reply ships the outbox; re-running the task is the
            # nudge.
            ray_tpu.get(emit.remote())
        assert any(p != "driver" for p in procs), procs
        worker_series = [s for s in q["series"] if s["proc"] != "driver"]
        for s in worker_series:
            assert s["kind"] == "counter"
            assert all(p["delta"] >= 0.0 for p in s["points"])
    finally:
        ray_tpu.shutdown()


# -- derived signals --------------------------------------------------------

def test_arrival_signal_slope_detects_ramp_and_tolerates_reset():
    from ray_tpu.serve.signals import ArrivalSignal

    sig = ArrivalSignal(half_life_s=1.0, window_s=10.0)
    total = 0.0
    for i in range(10):
        total += i  # accelerating arrivals: i per second at tick i
        sig.observe(float(i), total)
    assert sig.rate() > 0.0
    assert sig.slope() > 0.0
    # Cumulative total going backwards means the observed process
    # restarted: the new total is the count since reset — never a
    # negative instantaneous rate folded into the EWMA.
    sig.observe(10.0, 2.0)
    assert sig.rate() >= 0.0


def test_derived_signals_burn_and_rates():
    from ray_tpu.serve import signals

    arrived = metrics.Counter("raytpu_serve_requests_arrived_total", "t")
    shed = metrics.Counter("raytpu_serve_shed_total", "t")
    slo = metrics.Counter("raytpu_serve_request_slo_total", "t",
                          tag_keys=("outcome",))
    now = time.time()
    # inc(0) materialises each tag row so the first sample is a true
    # baseline — a counter's first observation never yields a delta.
    arrived.inc(0)
    shed.inc(0)
    slo.inc(0, tags={"outcome": "met"})
    slo.inc(0, tags={"outcome": "missed"})
    timeseries.sample_now(now=now - 2)   # counters' baseline tick
    arrived.inc(30)
    shed.inc(6)
    slo.inc(3, tags={"outcome": "met"})
    slo.inc(1, tags={"outcome": "missed"})
    timeseries.sample_now(now=now - 1)

    sig = signals.derived_signals(window_s=60.0)
    assert sig["driver"]["request_rate"] == pytest.approx(30 / 60.0)
    assert sig["driver"]["shed_rate"] == pytest.approx(6 / 60.0)
    assert sig["driver"]["slo_burn_rate"] == pytest.approx(0.25)


# -- raytpu top -------------------------------------------------------------

def _top_payload():
    return {
        "now": T0 + 3, "step": 1.0,
        "series": [
            {"proc": "driver", "family": "raytpu_serve_requests_arrived_total",
             "kind": "counter", "tags": {},
             "points": [{"t": T0 + 1, "delta": 4.0, "rate": 4.0},
                        {"t": T0 + 2, "delta": 6.0, "rate": 6.0}]},
            {"proc": "driver", "family": "raytpu_serve_goodput_ratio",
             "kind": "gauge", "tags": {},
             "points": [{"t": T0 + 2, "value": 0.875}]},
            {"proc": "pool-worker-1",
             "family": "raytpu_serve_admission_queue_age_seconds",
             "kind": "gauge", "tags": {},
             "points": [{"t": T0 + 2, "value": 0.0128}]},
            {"proc": "pool-worker-1",
             "family": "raytpu_serve_step_tokens_total",
             "kind": "counter", "tags": {"phase": "decode"},
             "points": [{"t": T0 + 2, "delta": 32.0, "rate": 32.0}]},
            {"proc": "pool-worker-1",
             "family": "raytpu_serve_step_tokens_total",
             "kind": "counter", "tags": {"phase": "prefill"},
             "points": [{"t": T0 + 2, "delta": 16.0, "rate": 16.0}]},
            {"proc": "pool-worker-1", "family": "raytpu_serve_kv_pages_free",
             "kind": "gauge", "tags": {},
             "points": [{"t": T0 + 2, "value": 96.0}]},
            {"proc": "pool-worker-1",
             "family": "raytpu_serve_spec_accept_ratio",
             "kind": "gauge", "tags": {},
             "points": [{"t": T0 + 2, "value": 0.75}]},
        ],
    }


def test_format_top_is_pure_and_deterministic():
    from ray_tpu.scripts.cli import format_top

    frame = format_top(_top_payload())
    assert frame == format_top(_top_payload())
    lines = frame.splitlines()
    header, rows = lines[0], lines[2:]
    assert header.split() == ["proc", "req/s", "tok/s", "goodput",
                              "qage_s", "kv_free", "kv_cached",
                              "adapters", "spec_acc"]
    assert len(rows) == 2
    # req/s is the window-mean rate; tok/s sums the phase tag splits.
    assert rows[0].split() == ["driver", "5.00", "-", "0.875", "-",
                               "-", "-", "-", "-"]
    assert rows[1].split() == ["pool-worker-1", "-", "48.0", "-",
                               "0.013", "96", "-", "-", "0.750"]
    assert format_top({"now": 0, "step": 1.0, "series": []}) \
        == "(no serving series in the window)"


def test_top_once_over_dashboard_is_byte_deterministic():
    from ray_tpu.dashboard import start_dashboard
    from ray_tpu.scripts.cli import main

    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    # Freeze the store: stop the background sampler, then lay down a
    # fixed window by hand so two CLI renders see identical state.
    timeseries.stop()
    timeseries.clear()
    g = metrics.Gauge("raytpu_serve_goodput_ratio", "t")
    c = metrics.Counter("raytpu_serve_requests_arrived_total", "t")
    base = time.time()
    for i in range(3):
        c.inc(4)
        g.set(1.0)
        timeseries.sample_now(now=base - 3 + i)
    dash = start_dashboard()
    try:
        outs = []
        for _ in range(2):
            buf = io.StringIO()
            code = main(["--address", dash.address, "top", "--once",
                         "--window", "30"], out=buf)
            assert code == 0
            outs.append(buf.getvalue())
        assert outs[0] == outs[1], "top --once is not deterministic"
        assert "driver" in outs[0]
        assert "4.00" in outs[0]      # mean arrived rate
        assert "1.000" in outs[0]     # goodput gauge
    finally:
        dash.stop()
        ray_tpu.shutdown()


def test_timeseries_endpoint_schema():
    from ray_tpu.dashboard import start_dashboard
    import urllib.request

    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    timeseries.stop()
    timeseries.clear()
    g = metrics.Gauge("raytpu_serve_test_depth", "t")
    g.set(3.0)
    timeseries.sample_now(now=time.time())
    dash = start_dashboard()
    try:
        with urllib.request.urlopen(
                dash.address + "/api/v0/timeseries?family=raytpu_serve_"
                "&step=1", timeout=10) as r:
            payload = json.loads(r.read())["result"]
        assert set(payload) == {"now", "step", "series"}
        assert payload["step"] == 1.0
        fams = {s["family"] for s in payload["series"]}
        assert "raytpu_serve_test_depth" in fams
        for s in payload["series"]:
            assert set(s) == {"proc", "family", "kind", "tags", "points"}
    finally:
        dash.stop()
        ray_tpu.shutdown()


# -- flight recorder: configure re-trim + history.json ----------------------

def test_flightrec_configure_retrims_local_and_remote_rings():
    """Satellite 2 regression: before the fix, remote rings captured
    ``maxlen`` at creation (a mid-session capacity change never
    applied) and a shrunk window only filtered at snapshot time (a
    wide-window snapshot still showed dropped-horizon events)."""
    flight_recorder.clear()
    try:
        flight_recorder.configure(window_s=600.0, capacity=100)
        now = time.time()
        flight_recorder.ingest(
            "w1", [{"ts": now, "seq": i, "kind": "x"} for i in range(5)])
        flight_recorder.configure(capacity=3)
        assert len(flight_recorder.snapshot()["w1"]) == 3

        flight_recorder.ingest(
            "w2", [{"ts": now - 100, "seq": 1, "kind": "x"}])
        flight_recorder.record("fresh")
        flight_recorder.configure(window_s=10.0)
        # Read back with a WIDE window: the trim must have physically
        # dropped the stale events, not merely hidden them.
        snap = flight_recorder.snapshot(window_s=600.0)
        assert not snap.get("w2"), snap.get("w2")
        assert all(e["ts"] >= now - 11 for e in snap["driver"])
        assert any(e["kind"] == "fresh" for e in snap["driver"])
    finally:
        flight_recorder.clear()
        flight_recorder.configure(window_s=60.0, capacity=4096)


def test_dump_bundle_carries_history_json(tmp_path):
    """A bundle's ``history.json`` holds the trailing multi-process
    time-series window (>= 60 s, raw resolution) and the manifest
    lists the procs it federates."""
    flight_recorder.clear()
    now = time.time()
    # Local serve-plane history spanning > 60 s of synthetic ticks...
    c = metrics.Counter("raytpu_serve_test_flow_total", "t")
    for i in range(90):
        c.inc(1)
        timeseries.sample_now(now=now - 90 + i)
    # ...plus a federated worker's shipped points under its proc key.
    recs = timeseries.ship()
    timeseries.ingest("pool-worker-7", recs)
    try:
        path = flight_recorder.dump(reason="manual",
                                    dump_dir=str(tmp_path))
        bundle = pathlib.Path(path)
        hist = json.loads((bundle / "history.json").read_text())
        assert hist["window_s"] >= 60.0
        serve_series = [s for s in hist["series"]
                        if s["family"].startswith("raytpu_serve_")]
        procs = {s["proc"] for s in serve_series}
        assert {"driver", "pool-worker-7"} <= procs, procs
        spans = [s["points"][-1]["t"] - s["points"][0]["t"]
                 for s in serve_series]
        assert max(spans) >= 60.0, spans
        manifest = json.loads((bundle / "manifest.json").read_text())
        assert manifest["history_procs"] == sorted(
            {s["proc"] for s in hist["series"]})
    finally:
        flight_recorder.clear()
