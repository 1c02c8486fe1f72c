"""Device-plane observability: XLA program cost attribution, roofline
utilization, shared device-memory gauges, and on-demand profiler
capture.

The host-side telemetry plane (util/metrics.py + util/tracing.py) sees
walls and queues; this module is its device-side half:

  * ``record_compiled(name, lowered)`` — every named jitted program
    registers its ``cost_analysis()`` flops / bytes-accessed and first
    -call compile wall into ``raytpu_xla_*`` families.  Producers:
    train/step.py (the SPMD train step) and serve/llm_engine.py
    (prefill + decode programs).
  * ``roofline()`` — joins the registered cost numbers against the
    span walls the producers already emit (train.compute, llm.decode)
    and the chip's peak flops / HBM bandwidth
    (utils/accelerator.chip_spec; a device without published peaks is
    an error) into achieved-vs-peak utilization gauges.
  * ``sample_device_memory()`` — per-device HBM watermarks, shared by
    every plane (the trainer's private gauges moved here).
  * ``capture()`` / ``distributed_capture()`` — a bounded
    ``jax.profiler`` trace into a per-process directory; the
    distributed form fans a "profile" control op to every pool worker
    (core/worker_main.py) and returns all collected trace paths.
    Surfaced as ``POST /api/v0/profile`` on the dashboard and
    ``raytpu profile`` in the CLI.
  * ``device_timeline_events()`` — one chrome-trace row per local
    device carrying the joined program events, so ``ray_tpu.timeline``
    shows host spans and device programs in one Perfetto view.

Everything degrades to ABSENT on CPU or partial backends: missing
``cost_analysis`` keys, ``memory_stats() -> None`` and an unavailable
profiler yield no samples — never zeros, never raises.  Nothing here
initialises a JAX backend: a process that holds none (the driver, the
dashboard) reports no devices, because looking would take the chip
from the process that owns it.
"""

from __future__ import annotations

import dataclasses
import os
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

_TELEMETRY = None
_lock = threading.Lock()
_programs: "Dict[str, ProgramRecord]" = {}
_capture_lock = threading.Lock()


@dataclasses.dataclass
class ProgramRecord:
    """One named compiled program and its static cost numbers."""

    name: str
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    compile_time_s: Optional[float] = None
    # Which tracer span carries this program's measured wall, and which
    # span attribute holds the number of device steps the wall covers
    # (None = the span is one step).
    span_name: Optional[str] = None
    steps_attr: Optional[str] = None
    # How many tokens (serving) / steps the recorded cost numbers
    # cover — lets latency_attribution turn flops/bytes into a
    # per-token device estimate.  None = unknown, no estimate.
    cost_steps: Optional[float] = None
    # Wall-clock END of the first-call trace+compile; with
    # compile_time_s this bounds the compile window so a waterfall can
    # exclude compilation from the victim request's attribution even
    # when span capture is off.
    compiled_at: Optional[float] = None


def _telemetry():
    """Device-plane metric singletons (re-registered on refetch — see
    serve/llm_engine._telemetry for the registry-clear rationale)."""
    global _TELEMETRY
    from ray_tpu.util import metrics

    if _TELEMETRY is None:
        _TELEMETRY = {
            "flops": metrics.Gauge(
                "raytpu_xla_program_flops",
                "XLA cost-analysis flop count of one named compiled "
                "program (per execution).",
                tag_keys=("program",),
            ),
            "bytes": metrics.Gauge(
                "raytpu_xla_program_bytes_accessed",
                "XLA cost-analysis bytes accessed (HBM traffic bound) "
                "of one named compiled program.",
                tag_keys=("program",),
            ),
            "compile": metrics.Counter(
                "raytpu_xla_compile_seconds_total",
                "First-call trace+compile wall seconds, by program.",
                tag_keys=("program",),
            ),
            "flops_util": metrics.Gauge(
                "raytpu_xla_roofline_flops_utilization",
                "Achieved flops / chip peak flops for one program, "
                "from cost analysis over the measured span wall.",
                tag_keys=("program",),
            ),
            "bw_util": metrics.Gauge(
                "raytpu_xla_roofline_hbm_utilization",
                "Achieved HBM bandwidth / chip peak bandwidth for one "
                "program, from cost analysis over the measured span "
                "wall.",
                tag_keys=("program",),
            ),
            "hbm_in_use": metrics.Gauge(
                "raytpu_device_hbm_bytes_in_use",
                "Device memory currently allocated, by local device.",
                tag_keys=("device",),
            ),
            "hbm_peak": metrics.Gauge(
                "raytpu_device_hbm_bytes_peak",
                "Device memory high watermark, by local device.",
                tag_keys=("device",),
            ),
        }
    else:
        reg = metrics.registry()
        for m in _TELEMETRY.values():
            reg.register(m)
    return _TELEMETRY


def _cost_value(cost: Dict[str, Any], key: str) -> Optional[float]:
    """One cost-analysis number, or None when the backend doesn't
    report it (CPU builds omit keys; some report -1 sentinels)."""
    try:
        v = float(cost.get(key))
    except (TypeError, ValueError):
        return None
    return v if v >= 0.0 else None


def _cost_dict(program) -> Dict[str, Any]:
    """Normalized cost_analysis(): jax's Lowered returns a dict,
    Compiled returns a list of per-computation dicts."""
    try:
        cost = program.cost_analysis()
    except Exception:
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost if isinstance(cost, dict) else {}


def record_compiled(name: str, program,
                    compile_time_s: Optional[float] = None,
                    span_name: Optional[str] = None,
                    steps_attr: Optional[str] = None,
                    cost_steps: Optional[float] = None,
                    compiled_at: Optional[float] = None,
                    ) -> Optional[ProgramRecord]:
    """Register one named compiled program (a ``jax.stages.Lowered`` or
    ``Compiled``) in the device plane.  Extracted cost numbers land as
    ``raytpu_xla_*`` samples; keys the backend doesn't report stay
    absent.  ``span_name``/``steps_attr`` declare which tracer span
    measures this program's wall, for the roofline join."""
    cost = _cost_dict(program)
    rec = ProgramRecord(
        name=name,
        flops=_cost_value(cost, "flops"),
        bytes_accessed=_cost_value(cost, "bytes accessed"),
        compile_time_s=compile_time_s,
        span_name=span_name,
        steps_attr=steps_attr,
        cost_steps=cost_steps,
        compiled_at=(compiled_at if compiled_at is not None
                     else (time.time() if compile_time_s else None)),
    )
    with _lock:
        _programs[name] = rec
    tm = _telemetry()
    tags = {"program": name}
    if rec.flops is not None:
        tm["flops"].set(rec.flops, tags=tags)
    if rec.bytes_accessed is not None:
        tm["bytes"].set(rec.bytes_accessed, tags=tags)
    if compile_time_s is not None and compile_time_s >= 0:
        tm["compile"].inc(compile_time_s, tags=tags)
    return rec


def programs() -> Dict[str, ProgramRecord]:
    with _lock:
        return dict(_programs)


def clear() -> None:
    """Drop every registered program (test isolation)."""
    with _lock:
        _programs.clear()


# -- roofline attribution ---------------------------------------------------

def _program_walls() -> Dict[str, List[float]]:
    """Per-program measured per-step walls, joined from the tracer's
    finished spans via each record's (span_name, steps_attr)."""
    from ray_tpu.util import tracing

    by_span: Dict[str, List] = {}
    for rec in programs().values():
        if rec.span_name:
            by_span.setdefault(rec.span_name, []).append(rec)
    walls: Dict[str, List[float]] = {}
    for s in tracing.finished_spans():
        recs = by_span.get(s.get("name"))
        if not recs or s.get("end") is None:
            continue
        if (s.get("attributes") or {}).get("compile"):
            continue  # first-dispatch trace+compile wall, not a step
        dur = s["end"] - s["start"]
        if dur <= 0:
            continue
        for rec in recs:
            steps = 1.0
            if rec.steps_attr:
                try:
                    steps = float(
                        s.get("attributes", {}).get(rec.steps_attr, 1.0))
                except (TypeError, ValueError):
                    steps = 1.0
            walls.setdefault(rec.name, []).append(dur / max(1.0, steps))
    return walls


def _local_devices() -> list:
    """This process's devices, or [] when it has initialised no backend
    (asking JAX would initialise one)."""
    from ray_tpu.utils.accelerator import backend_initialised

    if not backend_initialised():
        return []
    import jax

    return jax.local_devices()


def roofline(spec: Optional[Dict[str, Any]] = None
             ) -> Dict[str, Dict[str, Any]]:
    """Per-program achieved-vs-peak attribution.

    For each registered program with a measured span wall:

        achieved_flops/s = cost flops / median per-step wall
        flops_util       = achieved_flops/s / chip peak flops
        achieved_bytes/s = cost bytes accessed / median per-step wall
        hbm_util         = achieved_bytes/s / chip peak HBM bandwidth

    ``spec`` is a utils/accelerator.chip_spec() row; by default the one
    of the device this process computes on, which raises LookupError
    where that device has no published peaks (a CPU: tests pass a spec).
    A process that computes on nothing has nothing to attribute: {}.
    Results land in the ``raytpu_xla_roofline_*`` gauges and come back
    as a dict."""
    if spec is None:
        devices = _local_devices()
        if not devices:
            return {}
        from ray_tpu.utils.accelerator import chip_spec

        spec = chip_spec(devices[0].device_kind)
    peak_flops = spec.get("peak_flops")
    peak_bw = spec.get("peak_hbm_bytes_per_s")
    walls = _program_walls()
    tm = _telemetry()
    out: Dict[str, Dict[str, Any]] = {}
    for name, rec in programs().items():
        ws = sorted(walls.get(name, ()))
        if not ws:
            continue
        wall = ws[len(ws) // 2]  # median — robust to first-call compile
        row: Dict[str, Any] = {"wall_s_per_step": wall,
                               "chip": spec.get("chip", "?")}
        tags = {"program": name}
        if rec.flops is not None:
            row["achieved_flops_per_s"] = rec.flops / wall
            if peak_flops:
                row["peak_flops"] = peak_flops
                row["flops_utilization"] = rec.flops / wall / peak_flops
                tm["flops_util"].set(row["flops_utilization"], tags=tags)
        if rec.bytes_accessed is not None:
            row["achieved_hbm_bytes_per_s"] = rec.bytes_accessed / wall
            if peak_bw:
                row["peak_hbm_bytes_per_s"] = peak_bw
                row["hbm_utilization"] = (rec.bytes_accessed / wall
                                          / peak_bw)
                tm["bw_util"].set(row["hbm_utilization"], tags=tags)
        out[name] = row
    return out


# -- device memory ----------------------------------------------------------

def sample_device_memory() -> None:
    """Per-device HBM watermarks → shared gauges.  TPU/GPU backends
    expose memory_stats(); CPU returns None/raises — then the gauges
    simply never appear."""
    tm = _telemetry()
    for d in _local_devices():
        try:
            stats = d.memory_stats()
        except Exception:
            return
        if not stats:
            continue
        tags = {"device": f"{d.platform}:{d.id}"}
        if "bytes_in_use" in stats:
            tm["hbm_in_use"].set(stats["bytes_in_use"], tags=tags)
        if "peak_bytes_in_use" in stats:
            tm["hbm_peak"].set(stats["peak_bytes_in_use"], tags=tags)


# -- timeline ---------------------------------------------------------------

def device_timeline_events() -> List[Dict[str, Any]]:
    """Chrome-trace rows, one per local device, carrying the joined
    per-program events (a registered program's span walls replayed on
    the device row with its cost numbers in args).  Mergeable with
    core/events.chrome_tracing_dump()."""
    devices = _local_devices()
    from ray_tpu.util import tracing

    by_span: Dict[str, List[ProgramRecord]] = {}
    for rec in programs().values():
        if rec.span_name:
            by_span.setdefault(rec.span_name, []).append(rec)
    if not by_span:
        return []
    out: List[Dict[str, Any]] = []
    spans = [s for s in tracing.finished_spans()
             if s.get("name") in by_span and s.get("end") is not None]
    if not spans:
        return []
    for d in devices:
        pid = f"device:{d.platform}:{d.id}"
        out.append({"ph": "M", "pid": pid, "name": "process_name",
                    "args": {"name": pid}})
        for s in spans:
            for rec in by_span[s["name"]]:
                args: Dict[str, Any] = {"program": rec.name}
                if rec.flops is not None:
                    args["flops"] = rec.flops
                if rec.bytes_accessed is not None:
                    args["bytes_accessed"] = rec.bytes_accessed
                out.append({
                    "ph": "X",
                    "name": rec.name,
                    "cat": "xla",
                    "pid": pid,
                    "tid": "programs",
                    "ts": s["start"] * 1e6,
                    "dur": max(0.0, s["end"] - s["start"]) * 1e6,
                    "args": args,
                })
    return out


# -- profiler capture -------------------------------------------------------

def capture(duration_s: float,
            out_dir: Optional[str] = None) -> Optional[List[str]]:
    """One bounded ``jax.profiler`` trace of THIS process.  Returns the
    collected trace file paths, or None when the profiler is
    unavailable (no jax, no backend support, or a capture already in
    flight)."""
    try:
        import jax.profiler as profiler
    except Exception:
        return None
    duration_s = min(max(float(duration_s), 0.0), 60.0)
    if not _capture_lock.acquire(blocking=False):
        return None  # one capture at a time per process
    try:
        out_dir = out_dir or tempfile.mkdtemp(prefix="raytpu-xprof-")
        os.makedirs(out_dir, exist_ok=True)
        try:
            profiler.start_trace(out_dir)
        except Exception:
            return None
        try:
            time.sleep(duration_s)
        finally:
            try:
                profiler.stop_trace()
            except Exception:
                return None
        paths: List[str] = []
        for root, _dirs, files in os.walk(out_dir):
            paths.extend(os.path.join(root, f) for f in files)
        return sorted(paths)
    finally:
        _capture_lock.release()


def distributed_capture(duration_s: float,
                        base_dir: Optional[str] = None) -> List[str]:
    """Profile the whole local cluster at once: the driver process
    (covers thread-mode runtimes, where user code runs here) plus every
    live pool worker via the "profile" control op.  Workers capture
    concurrently into per-proc subdirectories of ``base_dir``; the
    returned list is every trace file collected anywhere."""
    base_dir = base_dir or tempfile.mkdtemp(prefix="raytpu-profile-")
    traces: List[str] = []
    local = capture(duration_s, os.path.join(base_dir, "driver"))
    if local:
        traces.extend(local)

    pool = None
    try:
        from ray_tpu.core import api

        if api.is_initialized():
            pool = getattr(api.runtime(), "worker_pool", None)
    except Exception:
        pool = None
    if pool is None:
        return traces

    workers = pool.all_workers()
    results: List[Optional[List[str]]] = [None] * len(workers)

    def one(i: int, wh) -> None:
        try:
            results[i] = wh.call(
                "profile", rpc_timeout=duration_s + 30.0,
                duration_s=duration_s,
                out_dir=os.path.join(base_dir, f"proc-{wh.pid}"))
        except Exception:
            results[i] = None  # a dying worker must not fail the sweep

    threads = [threading.Thread(target=one, args=(i, wh), daemon=True)
               for i, wh in enumerate(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=duration_s + 35.0)
    for r in results:
        if r:
            traces.extend(r)
    return traces
