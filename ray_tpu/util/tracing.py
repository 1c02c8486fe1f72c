"""Distributed tracing: spans propagated through remote calls.

Parity: the reference's OpenTelemetry integration (ray:
python/ray/util/tracing/tracing_helper.py —
_inject_tracing_into_function:326 wraps every remote function so the
caller's span context rides inside task metadata and the worker opens
a child span; opt-in via RAY_TRACING_ENABLED / ray.init tracing hook).

Self-contained tracer (no opentelemetry dependency): spans carry
(trace_id, span_id, parent_id, name, start/end, attributes), finished
spans land in a bounded in-memory buffer and optionally a JSONL file.
The runtime calls ``capture_context()`` at submit time and
``activate(ctx)`` around execution — the exact two hook points the
reference's propagator uses.

One bridge to the profiler: wherever JAX is already imported in the
process, every ``span`` also holds a ``jax.profiler.TraceAnnotation``
open, whether or not tracing was enabled, so a profiler capture shows
the program's spans (the engine loop's phases, the trainer's, the
background threads') beside the device's operations on one clock.  This
module never imports JAX and never initialises a backend.

One bridge to the flight recorder that is always on: a span opened
with ``startup=True`` (a phase of a process's start) leaves one event
of kind ``startup`` when it ends, tracing enabled or not, which the
recorder keeps apart from its ring (util/flight_recorder).
"""

from __future__ import annotations

import collections
import contextlib
import functools
import json
import os
import sys
import threading
import time
import uuid
from typing import Any, Dict, List, Optional

_enabled = False
_lock = threading.Lock()
_finished: "collections.deque" = collections.deque(maxlen=10000)
_export_path: Optional[str] = None
_tls = threading.local()
_annotation_cls = None   # jax.profiler.TraceAnnotation, once JAX is imported


def enable_tracing(export_file: Optional[str] = None) -> None:
    """Turn tracing on (parity: RAY_TRACING_ENABLED +
    _tracing_startup_hook)."""
    global _enabled, _export_path
    _enabled = True
    _export_path = export_file


def disable_tracing() -> None:
    global _enabled
    _enabled = False


def is_enabled() -> bool:
    return _enabled


def finished_spans() -> List[Dict[str, Any]]:
    with _lock:
        return list(_finished)


def clear() -> None:
    with _lock:
        _finished.clear()


def drain_finished() -> List[Dict[str, Any]]:
    """Atomically take every finished span.  Worker processes call this
    to piggyback their spans on a task reply; the driver ingests them
    into its own buffer so one process holds the whole trace."""
    with _lock:
        out = list(_finished)
        _finished.clear()
        return out


def ingest(spans: List[Dict[str, Any]]) -> None:
    """Append span records finished in another process (the receiving
    end of the reply piggyback)."""
    for rec in spans:
        _finish(rec)


def _current() -> Optional[Dict[str, str]]:
    return getattr(_tls, "ctx", None)


def capture_context() -> Optional[Dict[str, str]]:
    """Snapshot the caller's span context for injection into a task
    (parity: the serialized span context in task metadata)."""
    cur = _current()
    if cur is not None:
        # An activated context counts even when this process never
        # called enable_tracing itself — worker processes carry the
        # driver's context this way.
        return {"trace_id": cur["trace_id"], "span_id": cur["span_id"]}
    if not _enabled:
        return None
    # Root: start a fresh trace at the call boundary.
    return {"trace_id": uuid.uuid4().hex, "span_id": ""}


@contextlib.contextmanager
def activate(ctx: Optional[Dict[str, str]]):
    """Install a remote caller's span context as current WITHOUT
    opening a span (the caller's side records the span; this side only
    needs nested submissions to parent correctly — parity: context
    attach on the worker before user code runs)."""
    if ctx is None:
        yield
        return
    prev = _current()
    _tls.ctx = dict(ctx)
    try:
        yield
    finally:
        _tls.ctx = prev


def _finish(rec: Dict[str, Any]) -> None:
    with _lock:
        _finished.append(rec)
        if _export_path:
            try:
                with open(_export_path, "a") as f:
                    f.write(json.dumps(rec) + "\n")
            except OSError:
                pass
    # Feed the always-on flight recorder (one ring-buffer append; the
    # recorder must never take the tracer down with it).
    try:
        from ray_tpu.util import flight_recorder
        flight_recorder.record(
            "span", name=rec.get("name"), start=rec.get("start"),
            end=rec.get("end"),
            request_id=(rec.get("attributes") or {}).get("request_id"))
    except Exception:
        pass


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` once JAX is imported in this
    process, else None.  Read from ``sys.modules``: this module never
    imports JAX itself, and the class initialises no backend."""
    global _annotation_cls
    if _annotation_cls is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        _annotation_cls = getattr(profiler, "TraceAnnotation", None)
    return _annotation_cls


class span:
    """Open a span; ``ctx`` (from capture_context) makes it a child of
    the remote caller's span.

    Two records, one call.  The span's own record (trace id, span id,
    parent, start, end, attributes) is kept only after
    ``enable_tracing()``.  Whether or not it was called, the span also
    holds a ``jax.profiler.TraceAnnotation`` open for its whole extent
    wherever JAX is already imported, so any profiler capture
    (``jax.profiler.start_trace``, ``xprof.capture()``, ``raytpu
    profile``, the benchmark's traced window) shows the program's spans
    on the clock of the device's operations, with the attributes as the
    event's stats.  With tracing off and no capture running the
    annotation is a flag test: no lock, no buffer, no file, no flight
    recorder.

    ``set(**attrs)`` adds attributes known only once the work is done
    (the counts at a phase's boundary); they reach both records.
    ``record=False`` is for a span that fires in every iteration of a
    hot loop (the engine loop's phases, a background thread's tick): a
    capture sees it, the span buffer and the flight recorder never do,
    so turning tracing on does not flood either.

    ``startup=True`` is for a phase of a process's start (an import, the
    runtime's start, a replica's construction, the first call of a
    program): whether or not tracing is enabled the span's end also
    leaves one flight-recorder event of kind ``startup`` (see
    ``startup_event``), which the recorder keeps apart from its ring.
    ``parent`` there is the start-up span open round it on this
    thread."""

    __slots__ = ("record", "start", "end", "_ann", "_prev", "_startup")

    def __init__(self, name: str, ctx: Optional[Dict[str, str]] = None,
                 attributes: Optional[Dict[str, Any]] = None, *,
                 record: bool = True, startup: bool = False):
        cls = _trace_annotation()
        self._ann = (cls(name, **attributes) if attributes else cls(name)
                     ) if cls is not None else None
        self.record = None
        self._startup = ((name, dict(attributes or {})) if startup
                         else None)
        if _enabled and record:
            parent = ctx if ctx is not None else _current()
            self.record = {
                "trace_id": ((parent or {}).get("trace_id")
                             or uuid.uuid4().hex),
                "span_id": uuid.uuid4().hex[:16],
                "parent_id": (parent or {}).get("span_id") or "",
                "name": name,
                "start": 0.0,
                "attributes": dict(attributes or {}),
            }

    def set(self, **attrs: Any) -> None:
        if self._ann is not None:
            self._ann.set_metadata(**attrs)
        if self.record is not None:
            self.record["attributes"].update(attrs)
        if self._startup is not None:
            self._startup[1].update(attrs)

    def __enter__(self) -> "span":
        if self._startup is not None:
            _startup_stack().append(self._startup[0])
            self.start = time.time()
        rec = self.record
        if rec is not None:
            self._prev = _current()
            _tls.ctx = {"trace_id": rec["trace_id"],
                        "span_id": rec["span_id"]}
            rec["start"] = time.time()
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        rec = self.record
        if rec is not None:
            if exc is not None:
                rec["attributes"]["error"] = repr(exc)
            rec["end"] = time.time()
            _tls.ctx = self._prev
            _finish(rec)
        if self._startup is not None:
            self.end = time.time()
            stack = _startup_stack()
            stack.pop()
            name, attrs = self._startup
            startup_event(name, self.start, self.end,
                          parent=stack[-1] if stack else None, **attrs)


def import_span(package: str) -> span:
    """The start-up span ``import{package}``, entered: a package stamps
    the top of its ``__init__`` with this and the bottom with the
    span's ``__exit__(None, None, None)``; nested imports nest."""
    return span("import", attributes={"package": package},
                startup=True).__enter__()


def in_startup_span(name: str):
    """Decorator: the call runs under the start-up span ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with span(name, startup=True):
                return fn(*args, **kwargs)
        return wrapped
    return wrap


def _startup_stack() -> List[str]:
    stack = getattr(_tls, "startup", None)
    if stack is None:
        stack = _tls.startup = []
    return stack


def current_startup() -> Optional[str]:
    """The innermost start-up span open on this thread, by name."""
    stack = _startup_stack()
    return stack[-1] if stack else None


def startup_event(name: str, start: float, end: float, *,
                  parent: Optional[str] = None, **attributes: Any) -> None:
    """One flight-recorder event of kind ``startup``: ``{name, start,
    end, parent, pid, **attributes}`` on the wall clock.  Always on; a
    process leaves a few tens.  Where a backend is ALREADY initialised
    the event also says how full the fullest local device has been
    (``hbm_peak_bytes``); this never initialises one."""
    from ray_tpu.util import flight_recorder

    ev = dict(attributes, name=name, start=start, end=end, parent=parent,
              pid=os.getpid())
    if "jax" in sys.modules:
        try:
            from ray_tpu.util import xprof

            peak = xprof.hbm_peak_bytes()
            if peak is not None:
                ev["hbm_peak_bytes"] = peak
        except Exception:
            pass   # the record must never take a start down with it
    flight_recorder.record("startup", **ev)


def process_start() -> Optional[float]:
    """Wall-clock time this process started, good to a clock tick: its
    age is ``/proc/self/stat``'s start time (field 22, in ticks since
    boot) against ``CLOCK_BOOTTIME``.  None where there is no /proc."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
        return time.time() - age
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def record_span(name: str, start: float, end: float, *,
                ctx: Optional[Dict[str, str]] = None,
                span_id: Optional[str] = None,
                attributes: Optional[Dict[str, Any]] = None,
                ) -> Optional[Dict[str, Any]]:
    """Append an already-measured span (wall-clock ``start``/``end``)
    without touching the thread-local context.  For code that measures
    phases itself — an engine loop stamping request lifecycles, a
    streaming executor closing an operator stage — where a live
    ``with span(...)`` cannot bracket the work.  ``ctx`` is the PARENT
    context; ``span_id`` pins the id so children recorded elsewhere can
    parent to a span before it is finished.  Returns the record (its
    trace_id/span_id make a ctx for children), or None when tracing is
    disabled."""
    if not _enabled:
        return None
    rec = {
        "trace_id": (ctx or {}).get("trace_id") or uuid.uuid4().hex,
        "span_id": span_id or uuid.uuid4().hex[:16],
        "parent_id": (ctx or {}).get("span_id") or "",
        "name": name,
        "start": start,
        "end": end,
        "attributes": dict(attributes or {}),
    }
    _finish(rec)
    return rec


def new_span_id() -> str:
    """A fresh span id for record_span(span_id=...) pre-allocation."""
    return uuid.uuid4().hex[:16]


def task_span(name: str, ctx: Optional[Dict[str, str]],
              attributes: Optional[Dict[str, Any]] = None):
    """Span for one task execution on a worker thread (parity: the
    server-side wrapper in tracing_helper)."""
    return span(name, ctx=ctx, attributes=attributes)


def _stamp_package_import() -> None:
    """``import{ray_tpu}`` from the two times ``ray_tpu/__init__.py``
    took (it cannot import this module and stay light)."""
    times = getattr(sys.modules.get("ray_tpu"), "_import_times", None)
    if times and times[1] is not None:
        startup_event("import", times[0], times[1], package="ray_tpu")


_stamp_package_import()
