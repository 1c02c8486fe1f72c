"""Reduction from a profiler trace to device metrics.

``load_xplane`` reads the ``.xplane.pb`` the JAX profiler wrote (with
nothing but JAX) into plain lists of ``(name, start_ns, duration_ns)``;
``reduce`` works on those lists alone, so it is checked on a hand-built
event list (tests/yardstick/test_trace_reduce.py) and computes the same
numbers for every later PR.

Definitions (per device, then averaged over the devices used):

- window: the harness's own host span ``bench.trace_window``, which the
  chip's owner holds open from just after the profiler started to just
  before it stops, on the trace's clock; device operations are cut to
  it, so idle time at either edge of the trace counts as idle;
- busy: the union of the intervals in which an operation ran;
- self time of an operation: its duration minus its direct children's
  (a ``while`` spans its body's operations on the same line), so that
  shares and the top list count every nanosecond once;
- Pallas time: self time of custom calls (Mosaic kernels);
- collective time: the union of the intervals of all-gather, all-reduce,
  reduce-scatter, collective-permute and all-to-all events, on the main
  line (their ``-start``/``-done`` halves included) and on the
  asynchronous line; exposed is the part during which no other leaf
  operation ran on that device;
- idle gap: a stretch of the window with no operation, named by the
  innermost host span that covers its middle.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"    # where a TPU trace puts asynchronous collectives
COLLECTIVE_PREFIXES = ("all-gather", "all-reduce", "reduce-scatter",
                       "collective-permute", "all-to-all")
PALLAS_MARKS = ("tpu_custom_call", "pallas", "mosaic")
MIN_GAP_NS = 20_000
WINDOW_SPAN = "bench.trace_window"


def is_collective(name: str) -> bool:
    return name.lstrip("%").startswith(COLLECTIVE_PREFIXES)


def is_pallas(name: str) -> bool:
    """On a TPU the trace names an operation by its whole HLO text; a
    Mosaic kernel is a custom call whose target is tpu_custom_call."""
    low = name.lower()
    return any(m in low for m in PALLAS_MARKS)


def short_name(name: str) -> str:
    """``%closed_call.15 = (...) custom-call(...)`` -> ``closed_call.15``,
    with ``__pallas`` appended for a Mosaic kernel."""
    short = name.split(" = ", 1)[0].strip().lstrip("%")[:48]
    if is_pallas(name) and "pallas" not in short.lower():
        short += "__pallas"
    return short


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load_xplane(path: str) -> Dict[str, Any]:
    """{"devices": {plane: {"ops": [Event], "modules": [Event],
    "async": [Event]}}, "host": [Event]}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: Dict[str, Any] = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": [], "async": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules",
                       ASYNC_LINE: "async"}.get(line.name)
                if key is None:
                    continue
                dev[key] = [(e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events]
            if dev["ops"]:
                out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    (e.name, int(e.start_ns), int(e.duration_ns))
                    for e in line.events if e.duration_ns >= 10_000)
    return out


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted, non-overlapping [start, end) intervals."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Sequence[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Tuple[int, int]],
             b: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events: Sequence[Event]) -> List[Tuple[str, int, int, int]]:
    """(name, start, duration, self duration) for events of one line,
    where an event that lies inside another is its child."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out = [[n, s, d, d] for n, s, d in order]
    stack: List[int] = []
    for i, (_n, s, d, _sd) in enumerate(out):
        # an event is a child only where it lies wholly inside
        while stack and s + d > out[stack[-1]][1] + out[stack[-1]][2]:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= d
        stack.append(i)
    return [(n, s, d, max(sd, 0)) for n, s, d, sd in out]


def clip(events: Sequence[Event], lo: int, hi: int) -> List[Event]:
    """The parts of ``events`` inside [lo, hi)."""
    out = []
    for n, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((n, a, b - a))
    return out


def window_span(host: Sequence[Event]) -> Optional[Tuple[int, int]]:
    """[start, end) of the harness's window span, the longest if the
    trace holds several."""
    spans = [(d, s) for n, s, d in host if n == WINDOW_SPAN]
    if not spans:
        return None
    d, s = max(spans)
    return s, s + d


def _innermost_host_span(host: Sequence[Event], t: int) -> str:
    best = None
    for name, s, d in host:
        if name == WINDOW_SPAN:
            continue
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0][:64] if best else "no_host_span"


def reduce_device(ops: Sequence[Event], host: Sequence[Event] = (),
                  async_ops: Sequence[Event] = (),
                  window: Optional[Tuple[int, int]] = None
                  ) -> Dict[str, Any]:
    """``async_ops`` are the events of the trace's asynchronous line: a
    collective there runs from its start to its done beside the
    operations of the main line, and is exposed where none of those
    runs.  Without a ``window`` the operations' own extent is taken."""
    if window is None:
        window = (min(s for _n, s, _d in ops),
                  max(s + d for _n, s, d in ops))
    start, end = window
    st = self_times(clip(ops, start, end))
    async_ops = clip(async_ops, start, end)
    busy = union([(s, s + d) for _n, s, d, _sd in st])
    by_name: Dict[str, int] = {}
    pallas = coll = 0
    coll_iv, other_leaf_iv = [], []
    for n, s, d, sd in st:
        by_name[short_name(n)] = by_name.get(short_name(n), 0) + sd
        leaf = sd == d
        if is_collective(n):
            coll += sd
            coll_iv.append((s, s + d))
        else:
            if is_pallas(n):
                pallas += sd
            if leaf:
                other_leaf_iv.append((s, s + d))
    for n, s, d in async_ops:
        if is_collective(n):
            coll_iv.append((s, s + d))
    coll_iv = union(coll_iv)
    coll = total(coll_iv)
    exposed = total(subtract(coll_iv, union(other_leaf_iv)))
    gaps: Dict[str, int] = {}
    for s, e in subtract([(start, end)], busy):
        if e - s >= MIN_GAP_NS:
            name = _innermost_host_span(host, (s + e) // 2)
            gaps[name] = gaps.get(name, 0) + (e - s)
    return {"window_ns": end - start, "busy_ns": total(busy),
            "pallas_ns": pallas, "collective_ns": coll,
            "collective_exposed_ns": exposed, "by_name": by_name,
            "gaps": gaps}


def module_durations_ms(modules: Sequence[Event]) -> Dict[str, List[float]]:
    out: Dict[str, List[float]] = {}
    for n, _s, d in modules:
        out.setdefault(short_name(n), []).append(d / 1e6)
    return out


def reduce(trace: Dict[str, Any]) -> Dict[str, Any]:
    """The summary the readers and the result line use.  Seconds are
    averaged over the devices that ran anything."""
    host = trace.get("host", ())
    window = window_span(host)
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span: the "
                         f"traced window is the harness's to mark")
    devs = [reduce_device(d["ops"], host, d.get("async", ()), window)
            for d in trace["devices"].values()
            if clip(d["ops"], *window)]
    if not devs:
        raise ValueError("no device operation ran in the traced window")
    n = len(devs)

    def avg(key: str) -> float:
        return sum(d[key] for d in devs) / n / 1e9

    names: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    for d in devs:
        for k, v in d["by_name"].items():
            names[k] = names.get(k, 0.0) + v / n / 1e9
        for k, v in d["gaps"].items():
            gaps[k] = gaps.get(k, 0.0) + v / n / 1e9
    top = sorted(names.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    step_ms: List[float] = []
    step_name = None
    mods: Dict[str, List[float]] = {}
    for d in trace["devices"].values():
        whole = [(n, s0, d0) for n, s0, d0 in d.get("modules", ())
                 if window[0] <= s0 and s0 + d0 <= window[1]]
        for k, v in module_durations_ms(whole).items():
            mods.setdefault(k, []).extend(v)
    if mods:
        step_name = max(mods, key=lambda k: sum(mods[k]))
        step_ms = mods[step_name]
    return {"devices": n, "window_s": avg("window_ns"),
            "busy_s": avg("busy_ns"), "pallas_s": avg("pallas_ns"),
            "collective_s": avg("collective_ns"),
            "collective_exposed_s": avg("collective_exposed_ns"),
            "device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in top_gaps],
            "step_program": step_name, "step_ms": step_ms}


def load_and_reduce(trace_dir: str, *, allow_empty: bool = False
                    ) -> Optional[Dict[str, Any]]:
    """The newest trace under ``trace_dir``, reduced.  A trace with no
    device operation is an error, except in a rehearsal on a CPU
    (``allow_empty``), which has no device plane and gets None."""
    path = find_xplane(trace_dir)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    trace = load_xplane(path)
    if allow_empty and not trace["devices"]:
        return None
    return reduce(trace)
