"""Where the engine loop's wall time goes, always on.

One ``LoopClock`` per ``LLMEngine``.  The loop thread opens every phase
of an iteration through ``clock.phase(name)``, which is both the span
``llm.<name>`` (util/tracing: a profiler capture sees it on the
device's clock) and two float adds into this clock: a per-phase total
of seconds and the iteration's own share.  No lock and no registry
call per step; ``snapshot()`` and the scrape-time metric family read
the floats from other threads, where a torn read is off by one
iteration at most.

Two of the phases are waits, and they are not the same wait.  ``idle``
is the loop with NO request anywhere in the engine (nothing queued,
nothing live, nothing in flight): time there is nobody's loss.
``wait`` is the loop with steps in flight and rows live and nothing it
could dispatch, blocked until the fetch thread hands a step back: the
pipeline is full (the span's ``in_flight`` reads the pipeline's depth:
the device sets the pace, as it should) or a step could not be packed
(below it: every live row's budget is out with its tokens in flight).
Until PR 37 both were ``idle``.

Beside the wall the clock keeps the loop thread's own CPU seconds
(``cpu_s``, ``time.thread_time()`` over each iteration; the ``llm.loop``
span carries the iteration's as ``cpu_us``).  ``wall_s`` less the two
waits less ``cpu_s`` is time the thread wanted to run and did not: it
waited for the interpreter lock, which every request's thread shares,
for a core, or inside a native call.  (Where the thread's CPU clock ticks
coarsely, 10 ms on some virtual machines, one iteration's ``cpu_us``
reads 0 or a tick: sum over iterations, as ``cpu_s`` does.)

Two things are judged against the running median step interval (the
time between consecutive fetched steps while the pipeline holds work,
over the last 64 of them, once there are 8):

- an iteration of the loop longer than ``STALL_FACTOR`` x that median
  and ``STALL_FLOOR_S`` is reported through ``on_stall`` with the phase
  that held most of it: the host stood still;
- a step interval longer than ``STALL_FACTOR`` x the median counts as
  a stall event (``stall_events``), and is reported the same way,
  naming the phase that held most of the interval (``wait``: the loop
  was waiting for the device or the fetch thread with rows live),
  unless an iteration inside it was reported already.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from typing import Any, Callable, Dict, Optional

from ray_tpu.util import metrics, tracing

# A step interval, or an iteration of the loop, this many times the
# running median step interval is a stall worth naming (BENCH_r05's
# 1.14B collapse showed p95 TTFT 200x p50 with no engine-side signal
# of WHERE time went).
STALL_FACTOR = 5.0
STALL_FLOOR_S = 0.25
MIN_HISTORY = 8

PHASES = ("control", "admit", "pack", "dispatch", "commit", "emit", "wait",
          "idle")

_lock = threading.Lock()
_live: "weakref.WeakSet[LoopClock]" = weakref.WeakSet()
_retired: Dict[str, float] = {p: 0.0 for p in PHASES}


class _Phase:
    __slots__ = ("_clock", "_name", "_span", "_t0")

    def __init__(self, clock: "LoopClock", name: str,
                 attributes: Optional[Dict[str, Any]]):
        self._clock, self._name = clock, name
        self._span = tracing.span("llm." + name, attributes=attributes,
                                  record=False)

    def __enter__(self) -> tracing.span:
        self._t0 = time.perf_counter()
        return self._span.__enter__()

    def __exit__(self, exc_type, exc, tb) -> None:
        self._span.__exit__(exc_type, exc, tb)
        dt = time.perf_counter() - self._t0
        clock = self._clock
        clock.seconds[self._name] += dt
        clock._iter[self._name] += dt


class LoopClock:
    def __init__(self, on_stall: Optional[Callable[..., None]] = None,
                 on_high_water: Optional[Callable[[float], None]] = None):
        self.seconds: Dict[str, float] = {p: 0.0 for p in PHASES}
        self.iterations = 0
        self.wall_s = 0.0
        self.cpu_s = 0.0
        self.longest = {"wall_ms": 0.0, "phase": None, "seq": None}
        self.stall_events = 0
        self.interval_high_water_s = 0.0
        self._on_stall = on_stall
        self._on_high_water = on_high_water
        self._iter: Dict[str, float] = {p: 0.0 for p in PHASES}
        self._t_iter = time.perf_counter()
        self._cpu_iter = 0.0   # the loop thread's own clock: begin() reads it
        self._intervals: deque = deque(maxlen=64)
        self._median_s: Optional[float] = None
        self._pace_t: Optional[float] = None   # None: pipeline empty
        self._pace_seconds = dict(self.seconds)
        self._reported_at = 0.0
        with _lock:
            _live.add(self)

    # -- one iteration of the loop ------------------------------------

    def begin(self) -> None:
        it = self._iter
        for p in PHASES:
            it[p] = 0.0
        self._t_iter = time.perf_counter()
        self._cpu_iter = time.thread_time()

    def phase(self, name: str,
              attributes: Optional[Dict[str, Any]] = None) -> _Phase:
        return _Phase(self, name, attributes)

    def cpu_spent(self) -> float:
        """CPU seconds the calling thread (the loop's) has used since
        ``begin()``, added to ``cpu_s``.  Once an iteration, inside the
        ``llm.loop`` span, which carries it as ``cpu_us``."""
        cpu = time.thread_time() - self._cpu_iter
        self.cpu_s += cpu
        return cpu

    def end(self, seq: Optional[int] = None) -> None:
        now = time.perf_counter()
        wall = now - self._t_iter
        self.iterations += 1
        self.wall_s += wall
        if wall * 1e3 > self.longest["wall_ms"]:
            self.longest = {"wall_ms": wall * 1e3,
                            "phase": self._held(self._iter), "seq": seq}
        median = self._median_s
        if (median is not None and wall > STALL_FLOOR_S
                and wall > STALL_FACTOR * median):
            self._report(now, self._held(self._iter), wall, seq)

    # -- the pace of the steps ----------------------------------------

    def step_dispatched(self) -> None:
        """A step entered the pipeline.  Where the pipeline was empty
        the next interval starts here, so idle time is no interval."""
        if self._pace_t is None:
            self._pace_t = time.perf_counter()
            self._pace_seconds = dict(self.seconds)

    def steps_fetched(self, n_steps: int, in_flight: int,
                      seq: Optional[int] = None) -> bool:
        """``n_steps`` steps came back together; ``in_flight`` are still
        out.  Returns True where their interval was a stall."""
        now = time.perf_counter()
        start, self._pace_t = self._pace_t, (now if in_flight else None)
        before, self._pace_seconds = self._pace_seconds, dict(self.seconds)
        if start is None or n_steps <= 0:
            return False
        interval = (now - start) / n_steps
        median = self._median_s
        self._intervals.append(interval)
        if len(self._intervals) >= MIN_HISTORY:
            ordered = sorted(self._intervals)
            self._median_s = ordered[len(ordered) // 2]
        if interval > self.interval_high_water_s:
            self.interval_high_water_s = interval
            if self._on_high_water is not None:
                self._on_high_water(interval)
        if not (median and interval > STALL_FACTOR * median):
            return False
        self.stall_events += 1
        if self._reported_at < start and interval > STALL_FLOOR_S:
            held = {p: self.seconds[p] - before[p] for p in PHASES}
            self._report(now, self._held(held), interval, seq)
        return True

    # -- readers ------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        median = self._median_s
        return {
            "iterations": self.iterations,
            "wall_s": self.wall_s,
            "cpu_s": self.cpu_s,
            "seconds": dict(self.seconds),
            "longest": dict(self.longest),
            "step_interval_median_ms": (None if median is None
                                        else median * 1e3),
            "step_interval_max_ms": self.interval_high_water_s * 1e3,
        }

    def retire(self) -> None:
        """The loop ended: its seconds stay in the exported totals."""
        with _lock:
            if self in _live:
                _live.discard(self)
                for p in PHASES:
                    _retired[p] += self.seconds[p]

    # -- internals ----------------------------------------------------

    @staticmethod
    def _held(seconds: Dict[str, float]) -> str:
        return max(PHASES, key=lambda p: seconds[p])

    def _report(self, now: float, phase: str, wall_s: float,
                seq: Optional[int]) -> None:
        self._reported_at = now
        if self._on_stall is not None:
            self._on_stall(phase=phase, wall_ms=wall_s * 1e3, seq=seq,
                           median_ms=(self._median_s or 0.0) * 1e3)


class LoopSecondsFamily(metrics.Metric):
    """``raytpu_serve_loop_seconds_total{phase}``, computed at scrape
    time from the clocks of this process's engines (the loop itself
    never calls the registry)."""

    _type = "counter"

    def __init__(self):
        super().__init__(
            "raytpu_serve_loop_seconds_total",
            "Seconds the engine loop thread spent in each phase of its "
            "iterations (control, admit, pack, dispatch, commit, emit, "
            "wait: steps in flight and nothing to dispatch, idle: no "
            "request in the engine), summed over this process's engines.",
            tag_keys=("phase",))

    def _samples(self):
        with _lock:
            totals = dict(_retired)
            for clock in list(_live):
                for p in PHASES:
                    totals[p] += clock.seconds[p]
        return [(self.name, (("phase", p),), totals[p], "counter")
                for p in PHASES]
