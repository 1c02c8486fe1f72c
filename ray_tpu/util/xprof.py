"""Device-plane observability: XLA program cost attribution, roofline
utilization, shared device-memory gauges, and on-demand profiler
capture.

The host-side telemetry plane (util/metrics.py + util/tracing.py) sees
walls and queues; this module is its device-side half:

  * ``record_compiled(name, lowered)`` — every named jitted program
    registers its ``cost_analysis()`` flops / bytes-accessed into
    ``raytpu_xla_*`` families.  ``first_call()`` is the one wrapper
    round a named program's first call (train/step.py's train step,
    serve/llm_engine.py's programs at each shape): it lowers for the
    cost analysis, runs the call to its result under a start-up span
    and takes the program's compile window from the compile watch.
  * ``watch_compiles()`` — the compile watch: one listener a process on
    ``jax.monitoring`` that sees the trace, the lowering and the
    backend compile (or persistent-cache read) of EVERY jitted function
    by name, with no wrapper at any call site.  Stages of 50 ms or more
    are flight-recorder events of kind ``compile``; all feed
    ``raytpu_xla_compile_seconds_total{program, stage}`` and
    ``raytpu_xla_compile_cache_total{result}``.
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
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

from ray_tpu.util import flight_recorder, tracing

_TELEMETRY = None
_lock = threading.Lock()
_programs: "Dict[str, ProgramRecord]" = {}
_capture_lock = threading.Lock()

# jax/_src/dispatch.py's three events, one a stage of a jitted
# function's way to an executable, and the persistent cache's.
_STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
           "/jax/core/compile/backend_compile_duration": "compile"}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
# A stage this long is an event of its own; shorter ones (a thousand
# eager operations' worth) add to one tally a stage, which leaves an
# event each time it has gathered TALLY_EVENT_S more.
STAGE_EVENT_S = 0.05
TALLY_EVENT_S = 1.0
_watching = False
_watch_tls = threading.local()
_tally: Dict[str, Dict[str, float]] = {}


@dataclasses.dataclass
class ProgramRecord:
    """One named compiled program and its static cost numbers."""

    name: str
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    # Which tracer span carries this program's measured wall, and which
    # span attribute holds the number of device steps the wall covers
    # (None = the span is one step).
    span_name: Optional[str] = None
    steps_attr: Optional[str] = None
    # How many tokens (serving) / steps the recorded cost numbers
    # cover — lets latency_attribution turn flops/bytes into a
    # per-token device estimate.  None = unknown, no estimate.
    cost_steps: Optional[float] = None
    # The compile window, from the compile watch: ``compiled_at`` is
    # the wall-clock END of the last stage (trace, lowering, compile or
    # cache read) seen during the program's first call and
    # ``compile_time_s`` reaches back to the first one's start, so a
    # waterfall can exclude compilation from the victim request's
    # attribution even when span capture is off.
    compile_time_s: Optional[float] = None
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
                "Seconds jitted functions spent on the way to an "
                "executable, by stage (trace, lower, compile: the "
                "backend compile or the persistent cache's read) and "
                "program: the registered name during a named "
                "program's first call, else the function's, and "
                "'short' for stages under 50 ms outside one.",
                tag_keys=("program", "stage"),
            ),
            "compile_cache": metrics.Counter(
                "raytpu_xla_compile_cache_total",
                "Backend compiles by what the persistent compilation "
                "cache did: hit, miss (compiled and written) or "
                "uncached (under the cache's thresholds, or no cache).",
                tag_keys=("result",),
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
    measures this program's wall, for the roofline join;
    ``compile_time_s``/``compiled_at`` are the program's compile window
    (``first_call`` reads it off the compile watch)."""
    cost = _cost_dict(program)
    rec = ProgramRecord(
        name=name,
        flops=_cost_value(cost, "flops"),
        bytes_accessed=_cost_value(cost, "bytes accessed"),
        compile_time_s=compile_time_s,
        span_name=span_name,
        steps_attr=steps_attr,
        cost_steps=cost_steps,
        compiled_at=compiled_at,
    )
    with _lock:
        _programs[name] = rec
    tm = _telemetry()
    tags = {"program": name}
    if rec.flops is not None:
        tm["flops"].set(rec.flops, tags=tags)
    if rec.bytes_accessed is not None:
        tm["bytes"].set(rec.bytes_accessed, tags=tags)
    return rec


def programs() -> Dict[str, ProgramRecord]:
    with _lock:
        return dict(_programs)


def clear() -> None:
    """Drop every registered program (test isolation)."""
    with _lock:
        _programs.clear()


# -- the compile watch ------------------------------------------------------

def watch_compiles() -> bool:
    """Register the compile watch on ``jax.monitoring`` (once a process;
    False, and nothing imported, where JAX is not imported yet)."""
    global _watching
    if "jax" not in sys.modules:
        return False
    from jax import monitoring

    with _lock:
        if not _watching:
            monitoring.register_event_listener(_on_cache_event)
            monitoring.register_event_duration_secs_listener(
                _on_cache_seconds)
            monitoring.register_event_time_span_listener(_on_stage)
            _watching = True
    return True


def _on_cache_event(event: str, **_: Any) -> None:
    # fires on the compiling thread inside that program's compile stage
    result = _CACHE_EVENTS.get(event)
    if result is not None:
        _watch_tls.cache = result


def _on_cache_seconds(event: str, duration: float, **_: Any) -> None:
    if event == _CACHE_RETRIEVAL:
        _watch_tls.retrieval_s = duration


def _on_stage(event: str, start: float, end: float, **kw: Any) -> None:
    stage = _STAGES.get(event)
    if stage is None:
        return
    # tracing names the function ``f``, lowering and compiling its
    # module ``jit(f)``: one program
    program = str(kw.get("fun_name"))
    if program.startswith("jit(") and program.endswith(")"):
        program = program[4:-1]
    try:
        _record_stage(stage, program, start, end)
    except Exception:
        pass    # the watch must never fail a compilation


def _record_stage(stage: str, program: str, start: float,
                  end: float) -> None:
    rec: Dict[str, Any] = {"program": program, "stage": stage,
                           "start": start, "end": end}
    if stage == "compile":
        rec["cache"] = getattr(_watch_tls, "cache", None) or "uncached"
        retrieval_s = getattr(_watch_tls, "retrieval_s", None)
        if retrieval_s is not None:
            rec["retrieval_s"] = retrieval_s
        _watch_tls.cache = _watch_tls.retrieval_s = None
    calls = getattr(_watch_tls, "calls", None)
    if calls:       # inside first_call(): a stage of that program's
        rec["registered"] = calls[-1][0]
        calls[-1][1].append((start, end))
    seconds = end - start
    # what the cache held or was given is an event however short
    long = (seconds >= STAGE_EVENT_S
            or rec.get("cache") in ("hit", "miss"))
    tm = _telemetry()
    tm["compile"].inc(max(seconds, 0.0), tags={
        "program": rec.get("registered") or (program if long else "short"),
        "stage": stage})
    if stage == "compile":
        tm["compile_cache"].inc(tags={"result": rec["cache"]})
    if not long:
        with _lock:
            t = _tally.setdefault(stage, {"n": 0, "seconds": 0.0,
                                          "shown_n": 0, "shown_s": 0.0,
                                          "start": start})
            t["n"] += 1
            t["seconds"] += seconds
            if t["seconds"] - t["shown_s"] < TALLY_EVENT_S:
                return
            rec = {"program": "short", "stage": stage, "tally": True,
                   "n": t["n"] - t["shown_n"],
                   "seconds": t["seconds"] - t["shown_s"],
                   "start": t["start"], "end": end}
            t.update(shown_n=t["n"], shown_s=t["seconds"], start=end)
    flight_recorder.record("compile", pid=os.getpid(),
                           parent=tracing.current_startup(), **rec)


def first_call(name: str, fn, args, *, span_name: str,
               steps_attr: Optional[str] = None,
               cost_steps: Optional[float] = None,
               tag_compile: bool = False, **attributes: Any):
    """The first call of the jitted ``fn`` as the program ``name`` (one
    compiled shape of it): registers it in the device plane and returns
    the call's result.  The cost analysis wants a lowering of its own,
    and BEFORE the call (a step donates its state: afterwards those
    buffers are deleted): that is the start-up span
    ``<plane>.cost_analysis`` (``<plane>`` is ``span_name``'s: ``llm``,
    ``train``), inside ``<plane>.first_step{program, **attributes}``,
    which runs on from the call to its result ready: trace, lowering,
    compile or cache read, first execution.  The compile watch says
    which stages ran on this thread
    meanwhile; their extent is the program's compile window
    (``ProgramRecord.compile_time_s`` / ``compiled_at``).
    ``tag_compile`` also records the call as a ``span_name`` span
    tagged ``compile=true`` (tracing enabled), for a plane whose step
    spans are recorded one by one: the roofline join and a request's
    waterfall skip it."""
    import jax

    watch_compiles()
    stages: List = []
    calls = getattr(_watch_tls, "calls", None)
    if calls is None:
        calls = _watch_tls.calls = []
    calls.append((name, stages))
    plane = span_name.split(".")[0]
    try:
        with tracing.span(plane + ".first_step", startup=True,
                          attributes=dict(attributes, program=name)) as sp:
            rec = None
            with tracing.span(plane + ".cost_analysis", startup=True):
                try:
                    rec = record_compiled(
                        name, fn.lower(*args), span_name=span_name,
                        steps_attr=steps_attr, cost_steps=cost_steps)
                except Exception:
                    pass    # device-plane attribution is best-effort
            out = jax.block_until_ready(fn(*args))
    finally:
        calls.pop()
    if rec is not None and stages:
        rec.compiled_at = max(e for _s, e in stages)
        rec.compile_time_s = rec.compiled_at - min(s for s, _e in stages)
    if tag_compile and tracing.is_enabled():
        tracing.record_span(span_name, sp.start, sp.end,
                            attributes={"compile": True, "program": name})
    return out


def startup_table() -> Dict[str, Any]:
    """How THIS process started, for an operator
    (``LLMEngine.stats()["startup"]``, ``JaxTrainer``'s result): seconds
    by start-up span, ``ready_s`` (process start to the first step's
    result), the compile watch's stages by program and the cache's
    results, from the flight recorder's start-up record; ``short``
    is the tally of the stages under 50 ms."""
    phases: Dict[str, float] = {}
    progs: Dict[str, Dict[str, Any]] = {}
    cache = {"hit": 0, "miss": 0, "uncached": 0}
    first_end = None
    for ev in flight_recorder.startup("driver"):
        if ev["kind"] == "startup":
            phases[ev["name"]] = (phases.get(ev["name"], 0.0)
                                  + ev["end"] - ev["start"])
            if ev["name"].endswith(".first_step"):
                first_end = min(first_end or ev["end"], ev["end"])
        elif ev["kind"] == "compile" and not ev.get("tally"):
            row = progs.setdefault(
                ev.get("registered") or ev["program"], {})
            st = row.setdefault(ev["stage"], {"n": 0, "seconds": 0.0})
            st["n"] += 1
            st["seconds"] += ev["end"] - ev["start"]
            if "cache" in ev:
                row.setdefault("cache", []).append(ev["cache"])
                cache[ev["cache"]] += 1
    born = tracing.process_start()
    with _lock:
        short = {stage: {"n": t["n"], "seconds": t["seconds"]}
                 for stage, t in _tally.items()}
    return {"seconds": phases,
            "ready_s": (None if first_end is None or born is None
                        else first_end - born),
            "programs": progs, "programs_compiled": len(progs),
            "cache": cache, "cache_misses": cache["miss"],
            "short": short}


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

def hbm_peak_bytes() -> Optional[int]:
    """``peak_bytes_in_use`` of the fullest local device; None where
    this process holds no backend or the backend keeps no such count
    (a CPU)."""
    peaks = []
    for d in _local_devices():
        try:
            peaks.append((d.memory_stats() or {}).get("peak_bytes_in_use"))
        except Exception:
            pass
    return max((p for p in peaks if p is not None), default=None)


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
