"""Always-on bounded flight recorder for the serving planes.

Every process keeps the last N seconds of observability events — span
finishes (util/tracing), request-ring transitions (serve/request_events)
and metric-counter deltas — in a bounded ring buffer.  Recording is
always on and costs one deque append per event; nothing is written to
disk until something goes wrong.

Four incident classes arm the recorder (``trigger()``): an SLO miss, an
admission shed, a retry storm (attempt count over the storm threshold)
and an autoscale veto.  A trigger stamps a ``trigger`` event into the
ring, bumps ``raytpu_flightrec_triggers_total{reason=...}``, samples the
counter deltas since the last sample, and — when a dump directory is
configured (``configure(dump_dir=...)`` or ``RAYTPU_FLIGHTREC_DIR``) —
writes a bundle directory containing every process's recent events, a
full Prometheus scrape and a trailing time-series window
(``history.json``, from util/timeseries — what load was doing in the
minutes before the incident), rate-limited so a storm produces one
bundle, not one per request.

Cross-process: worker processes ship their ring incrementally on task
replies (``core/worker_main._run_op`` → ``rep["flightrec"]`` →
``core/runtime.apply_ref_batches`` → ``ingest()``), the same piggyback
contract as metrics/span/request-row federation.  A trigger event
arriving from a worker fires the driver-side auto-dump, so the bundle
holds the offending request's events from every process that saw it.

Start-up record: events of the start-up kinds (``STARTUP_KINDS``: a
``startup`` span's end from util/tracing, a ``compile`` stage from
util/xprof's compile watch, and the engine's one-off
``serve_cache_parts`` / ``serve_model_parts`` / ``ragged_weight_routes``)
go into the ring like any event AND into a list of their own, the first
``STARTUP_CAP`` of each process, which ring traffic never evicts and the
window never filters.  They ship on the same piggyback; ``startup()``
reads them and every bundle's ``events.json`` holds them, so the
process that started a deployment can say how it started an hour later,
and after ``ray_tpu.shutdown()``.

Surfaces: ``raytpu flightrec dump`` (CLI) and
``POST /api/v0/flightrec/dump`` (dashboard) force a manual bundle;
``snapshot()`` backs both plus the tests.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

_TELEMETRY = None

_lock = threading.Lock()
_seq = 0                       # monotone event id, for the ship cursor
_events: "collections.deque" = collections.deque(maxlen=4096)
_remote: Dict[str, "collections.deque"] = {}
_window_s = 60.0               # how far back a bundle reaches
_dump_dir: Optional[str] = os.environ.get("RAYTPU_FLIGHTREC_DIR") or None
_auto_dump = True              # dump on trigger when a dump dir is set
_ship_seq = 0                  # last local seq shipped to the driver
_dump_n = 0
_last_auto_dump_t = 0.0
_min_dump_interval_s = 2.0
_counter_baseline: Dict[str, float] = {}
STARTUP_KINDS = frozenset({"startup", "compile", "serve_cache_parts",
                           "serve_model_parts", "ragged_weight_routes"})
STARTUP_CAP = 512              # start-up events kept, a process
_startup: List[Dict[str, Any]] = []
_remote_startup: Dict[str, List[Dict[str, Any]]] = {}


def _telemetry():
    """Flight-recorder metric singletons (re-registered on refetch —
    see serve/llm_engine._telemetry for the registry-clear rationale)."""
    global _TELEMETRY
    from ray_tpu.util import metrics

    if _TELEMETRY is None:
        _TELEMETRY = {
            "events": metrics.Gauge(
                "raytpu_flightrec_events",
                "Events currently held in this process's flight-"
                "recorder ring buffer.",
            ),
            "triggers": metrics.Counter(
                "raytpu_flightrec_triggers_total",
                "Flight-recorder trigger events (slo_miss / shed / "
                "retry_storm / autoscale_veto / manual), by reason.",
                tag_keys=("reason",),
            ),
            "dumps": metrics.Counter(
                "raytpu_flightrec_dumps_total",
                "Flight-recorder dump bundles written by this process.",
            ),
        }
    else:
        for m in _TELEMETRY.values():
            metrics.registry().register(m)
    return _TELEMETRY


def configure(window_s: Optional[float] = None,
              capacity: Optional[int] = None,
              dump_dir: Optional[str] = None,
              auto_dump: Optional[bool] = None,
              min_dump_interval_s: Optional[float] = None) -> None:
    """Adjust the recorder.  All arguments optional; None = keep.

    Idempotently re-trims on every call: remote rings are rebuilt to
    the (possibly new) capacity — they capture ``_events.maxlen`` at
    creation, so a mid-session reconfigure would otherwise leave them
    on the old bound forever — and events older than the current
    window are physically dropped from every ring, so a shrunk window
    takes effect immediately rather than only at snapshot time."""
    global _window_s, _events, _dump_dir, _auto_dump, _min_dump_interval_s
    with _lock:
        if window_s is not None:
            _window_s = float(window_s)
        if capacity is not None:
            _events = collections.deque(_events, maxlen=int(capacity))
        if dump_dir is not None:
            _dump_dir = dump_dir or None
        if auto_dump is not None:
            _auto_dump = bool(auto_dump)
        if min_dump_interval_s is not None:
            _min_dump_interval_s = float(min_dump_interval_s)
        horizon = time.time() - _window_s
        _events = collections.deque(
            (e for e in _events if e["ts"] >= horizon),
            maxlen=_events.maxlen)
        for proc in list(_remote):
            _remote[proc] = collections.deque(
                (e for e in _remote[proc] if e["ts"] >= horizon),
                maxlen=_events.maxlen)


def clear() -> None:
    """Drop every recorded event and reset cursors (tests)."""
    global _seq, _ship_seq, _dump_n, _last_auto_dump_t
    with _lock:
        _events.clear()
        _remote.clear()
        _startup.clear()
        _remote_startup.clear()
        _counter_baseline.clear()
        _seq = _ship_seq = _dump_n = 0
        _last_auto_dump_t = 0.0


def record(kind: str, **fields: Any) -> int:
    """Append one event to the local ring.  Cheap and always on."""
    global _seq
    ev = {"ts": time.time(), "kind": kind}
    ev.update(fields)
    with _lock:
        _seq += 1
        ev["seq"] = _seq
        _events.append(ev)
        if kind in STARTUP_KINDS and len(_startup) < STARTUP_CAP:
            _startup.append(ev)
        n = len(_events)
    try:
        _telemetry()["events"].set(float(n))
    except Exception:
        pass  # metrics plane unavailable (interpreter teardown)
    return ev["seq"]


def _sample_counter_deltas_locked(now: float) -> None:
    """Diff counter families against the last sample and record one
    ``metric_delta`` event per family that moved (the "metric-delta"
    third of the event feed).  Caller holds ``_lock``."""
    global _seq
    try:
        from ray_tpu.util import metrics
        fams = metrics.snapshot_samples()
    except Exception:
        return
    for fam, typ, _help, samples in fams:
        if typ != "counter" or fam.startswith("raytpu_flightrec_"):
            continue
        total = sum(s[2] for s in samples)
        prev = _counter_baseline.get(fam)
        _counter_baseline[fam] = total
        if prev is None or total == prev:
            continue
        _seq += 1
        _events.append({"ts": now, "seq": _seq, "kind": "metric_delta",
                        "family": fam, "delta": total - prev,
                        "total": total})


def trigger(reason: str, request_id: Optional[str] = None,
            detail: Optional[str] = None, **fields: Any) -> Optional[str]:
    """Record an incident trigger; auto-dump when configured.  Returns
    the bundle path when a dump was written, else None.  ``detail``
    refines the reason without widening the counter's label set (the
    doctor passes the violated check's name here, so the bundle
    manifest names the invariant while the reason label stays
    ``invariant``)."""
    now = time.time()
    with _lock:
        global _seq
        _seq += 1
        ev = {"ts": now, "seq": _seq, "kind": "trigger", "reason": reason,
              "request_id": request_id}
        if detail is not None:
            ev["detail"] = detail
        ev.update(fields)
        _events.append(ev)
        _sample_counter_deltas_locked(now)
    try:
        _telemetry()["triggers"].inc(tags={"reason": reason})
    except Exception:
        pass
    return _maybe_auto_dump(reason, detail=detail)


def _maybe_auto_dump(reason: str,
                     detail: Optional[str] = None) -> Optional[str]:
    global _last_auto_dump_t
    with _lock:
        if not (_dump_dir and _auto_dump):
            return None
        now = time.time()
        if now - _last_auto_dump_t < _min_dump_interval_s:
            return None
        _last_auto_dump_t = now
    return dump(reason=reason, detail=detail)


# -- cross-process federation ----------------------------------------------

def ship() -> List[Dict[str, Any]]:
    """Events appended since the last ship (worker-side half of the
    reply piggyback).  Advances the cursor; returns [] when idle."""
    global _ship_seq
    with _lock:
        evs = []
        if _startup and _startup[-1]["seq"] > _ship_seq:
            # a start-up event the ring lost before any reply carried it
            oldest = _events[0]["seq"] if _events else _seq + 1
            evs = [dict(e) for e in _startup
                   if _ship_seq < e["seq"] < oldest]
        evs.extend(dict(e) for e in _events if e["seq"] > _ship_seq)
        if evs:
            _ship_seq = evs[-1]["seq"]
    return evs


def ingest(proc: str, events: List[Dict[str, Any]]) -> Optional[str]:
    """Driver-side half: append a worker's shipped events under its
    proc key.  A trigger event arriving from a worker fires the
    driver's auto-dump so the bundle spans both processes."""
    if not events:
        return None
    with _lock:
        ring = _remote.get(proc)
        if ring is None:
            ring = _remote[proc] = collections.deque(
                maxlen=_events.maxlen)
        ring.extend(dict(e) for e in events)
        kept = _remote_startup.setdefault(proc, [])
        kept.extend(dict(e) for e in events
                    if e.get("kind") in STARTUP_KINDS)
        del kept[STARTUP_CAP:]
    triggers = [e for e in events if e.get("kind") == "trigger"]
    if triggers:
        return _maybe_auto_dump(triggers[0].get("reason", "remote"),
                                detail=triggers[0].get("detail"))
    return None


def snapshot(request_id: Optional[str] = None,
             window_s: Optional[float] = None) -> Dict[str, List[Dict]]:
    """Per-process view of the recent ring: ``{"driver": [...], proc:
    [...]}``.  Local events land under "driver" (worker-local calls
    see their own events there — same convention as request_events).
    ``request_id`` filters to one request's events plus triggers."""
    horizon = time.time() - (window_s if window_s is not None
                             else _window_s)

    def keep(e: Dict[str, Any]) -> bool:
        if e["ts"] < horizon:
            return False
        if request_id is None:
            return True
        return e.get("request_id") == request_id or e["kind"] == "trigger"

    with _lock:
        out = {"driver": [dict(e) for e in _events if keep(e)]}
        for proc, ring in sorted(_remote.items()):
            out[proc] = [dict(e) for e in ring if keep(e)]
    return {p: evs for p, evs in out.items() if evs or p == "driver"}


def startup(proc: Optional[str] = None):
    """The start-up record: ``{"driver": [...], proc: [...]}``, each
    process's events of the start-up kinds in the order they happened,
    whatever the ring has dropped since and however old they are (local
    events under "driver", as in ``snapshot``).  With ``proc``, that
    process's list alone."""
    with _lock:
        out = {"driver": [dict(e) for e in _startup]}
        for p, evs in sorted(_remote_startup.items()):
            out[p] = [dict(e) for e in evs]
    return out if proc is None else out.get(proc, [])


def dump(reason: str = "manual", dump_dir: Optional[str] = None,
         detail: Optional[str] = None) -> Optional[str]:
    """Write a bundle directory (events.json + metrics.prom +
    manifest.json) and return its path; None when no directory is
    configured.  Manual dumps bypass the auto-dump rate limit.
    ``detail`` (e.g. the violated invariant's check name) lands in the
    manifest next to the reason."""
    global _dump_n
    d = dump_dir or _dump_dir
    if not d:
        return None
    with _lock:
        _dump_n += 1
        n = _dump_n
    path = os.path.join(d, f"flightrec-{n:04d}-{reason}")
    os.makedirs(path, exist_ok=True)
    events = snapshot()
    with open(os.path.join(path, "events.json"), "w") as f:
        json.dump({"reason": reason, "created_at": time.time(),
                   "window_s": _window_s, "events": events,
                   "startup": startup()}, f, indent=1)
    try:
        from ray_tpu.util import metrics
        with open(os.path.join(path, "metrics.prom"), "w") as f:
            f.write(metrics.export_prometheus())
    except Exception:
        pass
    # Trailing time-series window from every process (util/timeseries):
    # the "what was load doing before this" half of the bundle that
    # point-in-time events + one scrape cannot answer.
    history_procs: List[str] = []
    try:
        from ray_tpu.util import timeseries
        hist = timeseries.history(window_s=max(_window_s, 120.0))
        history_procs = sorted({s["proc"] for s in hist["series"]})
        with open(os.path.join(path, "history.json"), "w") as f:
            json.dump(hist, f, indent=1)
    except Exception:
        pass
    with open(os.path.join(path, "manifest.json"), "w") as f:
        json.dump({"reason": reason, "detail": detail,
                   "created_at": time.time(),
                   "procs": sorted(events),
                   "history_procs": history_procs,
                   "n_events": sum(len(v) for v in events.values())},
                  f, indent=1)
    try:
        _telemetry()["dumps"].inc()
    except Exception:
        pass
    return path
