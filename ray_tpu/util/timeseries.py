"""Always-on bounded multi-resolution time-series store.

The metrics registry (util/metrics) answers "what is the value NOW";
this module retains "what has it been" — the history that turns metrics
into operational signals (arrival-rate slopes for predictive
autoscaling, the load curve preceding an SLO miss in a flight-recorder
bundle, the `raytpu top` fleet view).

Every process samples its own registry on a fixed cadence
(``ensure_started``, default 1 s) into per-series rings:

  * counters   → per-tick deltas (reset-tolerant: a restarted process
                 whose cumulative total went backwards yields the new
                 total as the delta, never a negative rate);
  * gauges     → last observed value;
  * histograms → per-tick count/sum + nonzero bucket deltas, so p50/p99
                 are derivable for any window without storing samples.

Raw ~1 s points roll up into coarser rings (10 s / 60 s by default:
counter deltas sum, gauges average, histogram deltas sum) under a hard
memory bound: each ring is a fixed-capacity deque and a NEW series is
admitted only while the store's reserved byte estimate stays under
``max_bytes`` (rejections are counted, never silent).

Cross-process: worker stores cursor-ship appended points on task replies
(``core/worker_main._run_op`` → ``rep["timeseries"]`` →
``core/runtime.apply_ref_batches`` → ``ingest()``), the same piggyback
discipline as metrics snapshots and flight-recorder rings, into a
driver-side aggregation keyed by ``proc``.

Surfaces: ``query()`` (schema-stable, JSON-able) behind
``GET /api/v0/timeseries`` and ``state.query_timeseries``; ``history()``
feeds the flight recorder's ``history.json`` bundle member; the
``raytpu top`` CLI renders the newest window per process.
"""

from __future__ import annotations

import collections
import math
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.util import tracing

_TELEMETRY = None

# (resolution seconds, capacity points) — index 0 is the raw ring fed
# directly by the sampler; later entries aggregate the raw feed.
_DEFAULT_RINGS: Tuple[Tuple[float, int], ...] = (
    (1.0, 120), (10.0, 90), (60.0, 60))

# Per-point byte estimates for the memory bound.  A histogram point
# carries up to _BUCKET_ALLOWANCE nonzero (le, delta) pairs — deltas
# are sparse, and points are truncated to the allowance so the
# reservation arithmetic is an invariant, not a hope.
_PT_BYTES = 120
_BUCKET_BYTES = 40
_BUCKET_ALLOWANCE = 24

_lock = threading.Lock()
_seq = 0
_period_s = 1.0
_rings: Tuple[Tuple[float, int], ...] = _DEFAULT_RINGS
_max_bytes = 8 << 20
# (family, tags) -> series dict {"kind", "rings": [deque, ...],
# "accum": [None, ...]} for this process; _remote mirrors the shape
# one level down, keyed by proc.
_store: Dict[Tuple[str, tuple], Dict[str, Any]] = {}
_remote: Dict[str, Dict[Tuple[str, tuple], Dict[str, Any]]] = {}
_reserved_bytes = 0
_dropped_keys: set = set()
# Absolute-value baselines for delta computation, per (family, tags).
_counter_prev: Dict[Tuple[str, tuple], float] = {}
_hist_prev: Dict[Tuple[str, tuple], Tuple[float, float, Dict[str, float]]] = {}
# Points appended since the last ship(), bounded so a worker that never
# replies cannot grow without limit.
_outbox: "collections.deque" = collections.deque(maxlen=8192)
_thread: Optional[threading.Thread] = None
_stop = threading.Event()


def _telemetry():
    """Time-series self-metrics (re-registered on refetch — see
    serve/llm_engine._telemetry for the registry-clear rationale)."""
    global _TELEMETRY
    from ray_tpu.util import metrics

    if _TELEMETRY is None:
        _TELEMETRY = {
            "points": metrics.Gauge(
                "raytpu_timeseries_points",
                "Time-series points currently held by this process's "
                "store (all series, all resolutions, local + "
                "federated).",
            ),
            "memory": metrics.Gauge(
                "raytpu_timeseries_memory_bytes",
                "Estimated bytes held by the time-series store — "
                "structurally bounded by the configured max_bytes.",
            ),
            "samples": metrics.Counter(
                "raytpu_timeseries_samples_total",
                "Sampler ticks taken over the metric registry.",
            ),
            "dropped": metrics.Counter(
                "raytpu_timeseries_dropped_series_total",
                "Series refused because admitting them would push the "
                "store past its byte budget.",
            ),
        }
    else:
        reg = metrics.registry()
        for m in _TELEMETRY.values():
            reg.register(m)
    return _TELEMETRY


def configure(period_s: Optional[float] = None,
              rings: Optional[Tuple[Tuple[float, int], ...]] = None,
              max_bytes: Optional[int] = None) -> None:
    """Adjust the store.  Changing ``rings`` drops existing points
    (capacities are baked into the deques); the sampler cadence and
    byte budget apply from the next tick."""
    global _period_s, _rings, _max_bytes
    with _lock:
        if period_s is not None:
            if period_s <= 0:
                raise ValueError("period_s must be positive")
            _period_s = float(period_s)
        if max_bytes is not None:
            if max_bytes <= 0:
                raise ValueError("max_bytes must be positive")
            _max_bytes = int(max_bytes)
        if rings is not None:
            if not rings or rings[0][0] <= 0:
                raise ValueError("rings must be ((res_s, capacity), ...)")
            _rings = tuple((float(r), int(c)) for r, c in rings)
            _clear_locked()


def clear() -> None:
    """Drop every series, baseline and cursor (tests)."""
    with _lock:
        _clear_locked()


def _clear_locked() -> None:
    global _seq, _reserved_bytes
    _store.clear()
    _remote.clear()
    _counter_prev.clear()
    _hist_prev.clear()
    _outbox.clear()
    _dropped_keys.clear()
    _seq = 0
    _reserved_bytes = 0


def clear_remote() -> None:
    """Drop federated per-process series (driver shutdown: those
    processes are gone — same rationale as metrics.clear_remote)."""
    global _reserved_bytes
    with _lock:
        for store in _remote.values():
            _reserved_bytes -= sum(_series_cost(s["kind"])
                                   for s in store.values())
        _remote.clear()
        _reserved_bytes = max(0, _reserved_bytes)


# -- store internals --------------------------------------------------------

def _series_cost(kind: str) -> int:
    per_pt = _PT_BYTES + (_BUCKET_BYTES * _BUCKET_ALLOWANCE
                          if kind == "histogram" else 0)
    return sum(cap for _res, cap in _rings) * per_pt


def _get_series(store: Dict[Tuple[str, tuple], Dict[str, Any]],
                family: str, kind: str,
                tags: tuple) -> Optional[Dict[str, Any]]:
    """Find-or-admit a series under the byte budget.  Caller holds
    ``_lock``.  Returns None (and counts the drop) when admitting the
    series would exceed ``max_bytes``."""
    global _reserved_bytes
    key = (family, tags)
    ser = store.get(key)
    if ser is not None:
        return ser
    cost = _series_cost(kind)
    if _reserved_bytes + cost > _max_bytes:
        if (id(store), key) not in _dropped_keys:
            _dropped_keys.add((id(store), key))
            try:
                _telemetry()["dropped"].inc()
            except Exception:
                pass
        return None
    _reserved_bytes += cost
    ser = store[key] = {
        "kind": kind,
        "rings": [collections.deque(maxlen=cap) for _res, cap in _rings],
        "accum": [None] * len(_rings),
    }
    return ser


def _truncate_buckets(buckets: Dict[str, float]) -> tuple:
    items = [(le, d) for le, d in buckets.items() if d]
    if len(items) > _BUCKET_ALLOWANCE:
        items.sort(key=lambda kv: -abs(kv[1]))
        items = items[:_BUCKET_ALLOWANCE]
    return tuple(sorted(items))


def _append(family: str, kind: str, tags: tuple, ser: Dict[str, Any],
            now: float, point: tuple) -> None:
    """Append one raw point and fold it into the rollup accumulators,
    flushing any accumulator whose time bucket just closed.  Caller
    holds ``_lock``."""
    ser["rings"][0].append(point)
    _outbox.append((family, kind, tags, 0, point))
    for i in range(1, len(_rings)):
        res = _rings[i][0]
        bucket = math.floor(now / res) * res
        acc = ser["accum"][i]
        if acc is not None and acc[0] != bucket:
            rolled = _flush_accum(kind, acc)
            ser["rings"][i].append(rolled)
            _outbox.append((family, kind, tags, i, rolled))
            acc = None
        if acc is None:
            acc = ser["accum"][i] = _new_accum(kind, bucket)
        _fold_accum(kind, acc, point)


def _new_accum(kind: str, bucket: float) -> list:
    if kind == "gauge":
        return [bucket, 0.0, 0]                  # bucket, sum, n
    if kind == "histogram":
        return [bucket, 0.0, 0.0, {}]            # bucket, count, sum, les
    return [bucket, 0.0]                         # bucket, delta sum


def _fold_accum(kind: str, acc: list, point: tuple) -> None:
    if kind == "gauge":
        acc[1] += point[1]
        acc[2] += 1
    elif kind == "histogram":
        acc[1] += point[1]
        acc[2] += point[2]
        for le, d in point[3]:
            acc[3][le] = acc[3].get(le, 0.0) + d
    else:
        acc[1] += point[1]


def _flush_accum(kind: str, acc: list) -> tuple:
    if kind == "gauge":
        return (acc[0], acc[1] / max(acc[2], 1))
    if kind == "histogram":
        return (acc[0], acc[1], acc[2], _truncate_buckets(acc[3]))
    return (acc[0], acc[1])


# -- sampling ---------------------------------------------------------------

def sample_now(now: Optional[float] = None) -> int:
    """Take one sampler tick over the local metric registry; returns
    the number of points appended.  ``now`` is injectable so tests can
    drive deterministic timelines; production ticks use wall time."""
    now = time.time() if now is None else float(now)
    from ray_tpu.util import metrics

    fams = metrics.snapshot_samples()
    appended = 0
    with _lock:
        for fam, kind, _help, samples in fams:
            if fam.startswith("raytpu_timeseries_"):
                continue  # the store does not feed on itself
            if kind == "histogram":
                appended += _sample_histogram_locked(fam, samples, now)
            elif kind == "counter":
                appended += _sample_counter_locked(fam, samples, now)
            else:
                appended += _sample_gauge_locked(fam, kind, samples, now)
    tm = _telemetry()
    try:
        tm["samples"].inc()
        tm["points"].set(float(point_count()))
        tm["memory"].set(float(memory_bytes()))
    except Exception:
        pass
    return appended


def _sample_counter_locked(fam: str, samples: list, now: float) -> int:
    totals: Dict[tuple, float] = {}
    for s in samples:
        tags = tuple(map(tuple, s[1]))
        totals[tags] = totals.get(tags, 0.0) + s[2]
    n = 0
    for tags, total in totals.items():
        key = (fam, tags)
        prev = _counter_prev.get(key)
        _counter_prev[key] = total
        if prev is None:
            continue  # baseline tick: no delta derivable yet
        # Reset tolerance: a cumulative total that went BACKWARDS means
        # the observing process restarted — the new total is the count
        # since the reset, never a negative delta.
        delta = total if total < prev else total - prev
        ser = _get_series(_store, fam, "counter", tags)
        if ser is not None:
            _append(fam, "counter", tags, ser, now, (now, delta))
            n += 1
    return n


def _sample_gauge_locked(fam: str, kind: str, samples: list,
                         now: float) -> int:
    totals: Dict[tuple, float] = {}
    for s in samples:
        tags = tuple(map(tuple, s[1]))
        totals[tags] = totals.get(tags, 0.0) + s[2]
    n = 0
    for tags, value in totals.items():
        ser = _get_series(_store, fam, "gauge", tags)
        if ser is not None:
            _append(fam, "gauge", tags, ser, now, (now, value))
            n += 1
    return n


def _sample_histogram_locked(fam: str, samples: list, now: float) -> int:
    # Group the exposition-shaped samples (_bucket/_count/_sum) back
    # into one aggregate per tag set, `le` stripped.
    agg: Dict[tuple, list] = {}  # tags -> [count, sum, {le: cum}]
    for s in samples:
        sname, tags, value = s[0], tuple(map(tuple, s[1])), s[2]
        if sname.endswith("_bucket"):
            le = next((v for k, v in tags if k == "le"), "+Inf")
            base = tuple((k, v) for k, v in tags if k != "le")
            a = agg.setdefault(base, [0.0, 0.0, {}])
            a[2][le] = a[2].get(le, 0.0) + value
        elif sname.endswith("_count"):
            agg.setdefault(tags, [0.0, 0.0, {}])[0] += value
        elif sname.endswith("_sum"):
            agg.setdefault(tags, [0.0, 0.0, {}])[1] += value
    n = 0
    for tags, (cnt, total, les) in agg.items():
        key = (fam, tags)
        prev = _hist_prev.get(key)
        _hist_prev[key] = (cnt, total, dict(les))
        if prev is None:
            continue
        pc, ps, pb = prev
        if cnt < pc:  # observing process restarted
            dc, ds, db = cnt, total, dict(les)
        else:
            dc, ds = cnt - pc, total - ps
            db = {le: v - pb.get(le, 0.0) for le, v in les.items()}
        ser = _get_series(_store, fam, "histogram", tags)
        if ser is not None:
            _append(fam, "histogram", tags, ser, now,
                    (now, dc, ds, _truncate_buckets(db)))
            n += 1
    return n


def ensure_started(period_s: Optional[float] = None) -> None:
    """Start the background sampler thread (idempotent).  Called from
    driver init (core/api.init) and worker startup
    (core/worker_main)."""
    global _thread
    if period_s is not None:
        configure(period_s=period_s)
    with _lock:
        if _thread is not None and _thread.is_alive():
            return
        _stop.clear()
        _thread = threading.Thread(target=_sample_loop,
                                   name="timeseries-sampler", daemon=True)
        _thread.start()


def _sample_loop() -> None:
    while not _stop.wait(_period_s):
        try:
            with tracing.span("telemetry.sample", record=False):
                sample_now()
        except Exception:
            pass  # sampling is best-effort; next tick retries


def stop() -> None:
    """Stop AND join the sampler thread (same discipline as the
    dashboard sampler: a merely-signalled daemon thread can still be
    mid-sample at teardown)."""
    global _thread
    _stop.set()
    t = _thread
    if t is not None and t.is_alive():
        t.join(timeout=_period_s + 2.0)
    _thread = None


def shutdown() -> None:
    """Driver/worker teardown: stop the sampler and drop all state so
    the next runtime starts from an empty plane."""
    stop()
    clear()


# -- memory accounting ------------------------------------------------------

def _point_bytes(kind: str, point: tuple) -> int:
    if kind == "histogram":
        return _PT_BYTES + _BUCKET_BYTES * len(point[3])
    return _PT_BYTES


def memory_bytes() -> int:
    """Estimated bytes held across every series (local + federated).
    Structurally <= the configured max_bytes: rings have fixed
    capacities and series admission reserves worst-case cost."""
    with _lock:
        total = 0
        for store in [_store] + list(_remote.values()):
            for ser in store.values():
                kind = ser["kind"]
                for ring in ser["rings"]:
                    for p in ring:
                        total += _point_bytes(kind, p)
        return total


def point_count() -> int:
    with _lock:
        return sum(len(ring)
                   for store in [_store] + list(_remote.values())
                   for ser in store.values() for ring in ser["rings"])


# -- cross-process federation ----------------------------------------------

def ship() -> Optional[list]:
    """Points appended since the last ship (worker-side half of the
    reply piggyback).  Drains the outbox so every point crosses exactly
    once; returns None when idle."""
    with _lock:
        if not _outbox:
            return None
        out = list(_outbox)
        _outbox.clear()
    return out


def ingest(proc: str, records: list) -> None:
    """Driver-side half: append a worker's shipped points under its
    proc key, same ring shape and byte budget as local series."""
    with _lock:
        store = _remote.setdefault(proc, {})
        for fam, kind, tags, ring_idx, point in records:
            tags = tuple(map(tuple, tags))
            ser = _get_series(store, fam, kind, tags)
            if ser is None or ring_idx >= len(ser["rings"]):
                continue
            ser["rings"][ring_idx].append(tuple(point))


# -- query surface ----------------------------------------------------------

def _point_dict(kind: str, res: float, point: tuple) -> Dict[str, Any]:
    if kind == "gauge":
        return {"t": point[0], "value": point[1]}
    if kind == "histogram":
        return {"t": point[0], "count": point[1], "sum": point[2],
                "buckets": dict(point[3])}
    return {"t": point[0], "delta": point[1],
            "rate": point[1] / res if res > 0 else 0.0}


def query(family: Optional[str] = None, since: Optional[float] = None,
          step: float = 1.0,
          proc: Optional[str] = None) -> Dict[str, Any]:
    """Schema-stable, JSON-able view of the cluster's series.

    ``family`` is a name prefix filter (``raytpu_serve_`` selects the
    serving plane), ``since`` a wall-clock lower bound, ``step`` picks
    the coarsest ring no coarser than requested (1 → raw, 10/60 →
    rollups), ``proc`` filters to one process (local series appear as
    ``"driver"``, the flight-recorder convention).

    Returns ``{"now", "step", "series": [{"proc", "family", "kind",
    "tags", "points"}, ...]}`` with points sorted oldest-first and
    series sorted by (proc, family, tags)."""
    idx = 0
    for i, (res, _cap) in enumerate(_rings):
        if res <= step:
            idx = i
    res = _rings[idx][0]
    out: List[Dict[str, Any]] = []
    with _lock:
        stores = [("driver", _store)] + sorted(_remote.items())
        for pname, store in stores:
            if proc is not None and pname != proc:
                continue
            for (fam, tags), ser in store.items():
                if family is not None and not fam.startswith(family):
                    continue
                ring = ser["rings"][idx] if idx < len(ser["rings"]) else ()
                pts = [p for p in ring
                       if since is None or p[0] >= since]
                if not pts:
                    continue
                out.append({
                    "proc": pname,
                    "family": fam,
                    "kind": ser["kind"],
                    "tags": {k: v for k, v in tags},
                    "points": [_point_dict(ser["kind"], res, p)
                               for p in pts],
                })
    out.sort(key=lambda s: (s["proc"], s["family"],
                            tuple(sorted(s["tags"].items()))))
    return {"now": time.time(), "step": res, "series": out}


def history(window_s: float = 120.0,
            family: Optional[str] = None) -> Dict[str, Any]:
    """Trailing raw-resolution window across every process — the
    flight recorder writes this as a bundle's ``history.json`` so an
    incident dump shows what load was doing beforehand."""
    payload = query(family=family, since=time.time() - float(window_s),
                    step=_rings[0][0])
    payload["window_s"] = float(window_s)
    return payload
