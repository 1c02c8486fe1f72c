"""Set-up seen from inside the program: the seven ``setup_*`` readers'
one reduction of the program's start-up record.

The program leaves, in every process, one flight-recorder event for each
start-up span (``kind: startup``: an import, the runtime's start, a
replica's construction, a program's first call, ...) and one for each
stage of a jitted function's way to an executable (``kind: compile``:
trace, lower, compile with what the persistent cache did), all on the
wall clock.  Workers ship theirs to the driver on their replies, so
after a run the benchmark's own process holds every process's record
(``ray_tpu.util.flight_recorder.startup()``).

``reduce`` lays them all on the wall clock between the benchmark
process's start and the window's start less the ramp (``ramp_of``: the
lead the load generator took, which for an open loop is the first ramp
request's due time and not the whole of the traffic's ``ramp_s``: the
warm-up's last steps end inside that difference), gives
each instant to the innermost interval over it (a compile stage goes
over any span; among spans, or stages, the shortest: the inner of two
that nest, and a worker's own span under the driver's wait for it), and
sums the instants by class.  An instant has one innermost interval, so
the classes are disjoint and cannot sum past ``setup_s - ramp_s``; what
is left is ``setup_unnamed_s``, split by the span that covers it and
``dark``, which no span of any process covers.  Nothing of the record
can precede the process's start: the books are not ``ok`` where an
event does by half a second (a process start or a clock misread).

A program without the record (this PR's parent) reads None everywhere.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

# the class of a span's self time; a span of no class is unnamed
SPAN_CLASS = {
    "import": "import",
    "runtime.init": "runtime", "serve.run": "runtime",
    "serve.deploy": "runtime", "serve.wait_ready": "runtime",
    "worker.boot": "runtime", "runtime.claim_tpu": "runtime",
    "train.build": "runtime",
    "llm.load_weights": "weights", "llm.init_cache": "weights",
    "train.init_state": "weights",
}
STAGE_CLASS = {"trace": "trace_lower", "lower": "trace_lower",
               "compile": "compile"}
CLASSES = ("import", "runtime", "weights", "trace_lower", "compile")
NOTES_KEY = "setup"


def process_start() -> float:
    """Wall-clock time this process started, good to a clock tick: its
    age is /proc/self/stat's start time (field 22, ticks since boot)
    against CLOCK_BOOTTIME (/proc/stat's btime has whole seconds)."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    age = (time.clock_gettime(time.CLOCK_BOOTTIME)
           - ticks / os.sysconf("SC_CLK_TCK"))
    return time.time() - age


def record() -> Optional[Dict[str, List[Dict[str, Any]]]]:
    """Every process's start-up events as this process holds them, or
    None where the program keeps no such record or it is empty."""
    try:
        from ray_tpu.util import flight_recorder

        rec = flight_recorder.startup()
    except (ImportError, AttributeError):
        return None
    return rec if any(rec.values()) else None


def _label(ev: Dict[str, Any]) -> str:
    """``import{ray_tpu.train}``, ``llm.first_step{serve.ragged@8}``."""
    for key in ("package", "program"):
        if key in ev:
            return f"{ev['name']}{{{ev[key]}}}"
    return ev["name"]


class Interval(NamedTuple):
    start: float            # clipped to the stretch that is split
    end: float
    is_stage: bool          # a compile stage, else a start-up span
    cls: Optional[str]
    label: str
    ev: Dict[str, Any]


def _intervals(rec, lo: float, hi: float) -> List[Interval]:
    """Every span and stage of every process, clipped to [lo, hi]."""
    out = []
    for events in rec.values():
        for ev in events:
            kind = ev.get("kind")
            if kind == "startup":
                row = (False, SPAN_CLASS.get(ev["name"]), _label(ev))
            elif kind == "compile" and not ev.get("tally"):
                row = (True, STAGE_CLASS[ev["stage"]],
                       ev.get("registered") or ev["program"])
            else:
                continue
            a, b = max(ev["start"], lo), min(ev["end"], hi)
            if b > a:
                out.append(Interval(a, b, *row, ev))
    return out


def _sweep(intervals, lo: float, hi: float):
    """Yield (seconds, winner or None) for every stretch of [lo, hi]
    between two boundaries: the winner is the innermost interval over
    it, a stage before a span and then the shortest."""
    cuts = sorted({lo, hi} | {t for iv in intervals
                              for t in (iv.start, iv.end)})
    pending = sorted(intervals, key=lambda iv: iv.start, reverse=True)
    active: List[Interval] = []
    for a, b in zip(cuts, cuts[1:]):
        while pending and pending[-1].start <= a:
            active.append(pending.pop())
        active = [iv for iv in active if iv.end > a]
        yield b - a, max(
            active, default=None,
            key=lambda iv: (iv.is_stage, iv.ev["start"] - iv.ev["end"]))


def reduce(rec: Dict[str, List[Dict[str, Any]]], t_start: float,
           setup_s: float, ramp_s: float,
           run_peak_bytes: Optional[int] = None) -> Dict[str, Any]:
    """The books of one run's set-up (see the module's text)."""
    t_window = t_start + setup_s
    hi = t_window - ramp_s
    classes = {c: 0.0 for c in CLASSES}
    unnamed: Dict[str, float] = {"dark": 0.0}
    self_s: Dict[str, float] = {}
    for seconds, win in _sweep(_intervals(rec, t_start, hi), t_start, hi):
        if win is None:
            unnamed["dark"] += seconds
            continue
        if not win.is_stage:
            self_s[win.label] = self_s.get(win.label, 0.0) + seconds
        if win.cls is not None:
            classes[win.cls] += seconds
        else:
            name = win.ev["name"]
            unnamed[name] = unnamed.get(name, 0.0) + seconds

    by_span: Dict[str, Dict[str, float]] = {}
    by_program: Dict[str, Dict[str, Any]] = {}
    short: Dict[str, Dict[str, float]] = {}
    missed: List[str] = []
    in_ramp: List[str] = []
    earliest = t_window
    peaks: List[Tuple[float, int, str]] = []
    procs: Dict[str, int] = {}
    for proc, events in rec.items():
        for ev in events:
            kind = ev.get("kind")
            if kind not in ("startup", "compile") or ev["start"] >= t_window:
                continue
            procs[proc] = ev.get("pid", procs.get(proc))
            seconds = ev["end"] - ev["start"]
            if not ev.get("tally"):
                earliest = min(earliest, ev["start"])
            if kind == "startup":
                row = by_span.setdefault(
                    _label(ev), {"n": 0, "seconds": 0.0, "self_s": 0.0})
                row["n"] += 1
                row["seconds"] += seconds
                if "hbm_peak_bytes" in ev:
                    peaks.append((ev["end"], ev["hbm_peak_bytes"],
                                  _label(ev)))
            elif ev.get("tally"):
                row = short.setdefault(ev["stage"], {"n": 0, "seconds": 0.0})
                row["n"] += ev["n"]
                row["seconds"] += ev["seconds"]
            else:
                name = ev.get("registered") or ev["program"]
                prog = by_program.setdefault(name, {})
                row = prog.setdefault(ev["stage"], {"n": 0, "seconds": 0.0})
                row["n"] += 1
                row["seconds"] += seconds
                if "cache" in ev:
                    prog.setdefault("cache", []).append(ev["cache"])
                    if ev["cache"] == "miss":
                        missed.append(name)
                if ev["end"] > hi:
                    in_ramp.append(f"{name}:{ev['stage']}")
    for label, seconds in self_s.items():
        by_span[label]["self_s"] = seconds
    peak = None
    if peaks:
        top = max(p for _t, p, _l in peaks)
        at, label = min((t, lab) for t, p, lab in peaks if p == top)
        peak = {"bytes": top, "first_shown_by": label,
                "at_s": at - t_start, "run_peak_bytes": run_peak_bytes}
    named = sum(classes.values())
    return {
        "setup_s": setup_s, "ramp_s": ramp_s, "classes": classes,
        "unnamed_s": setup_s - ramp_s - named, "unnamed_by_span": unnamed,
        "by_span": by_span, "replica_init_self_s":
            by_span.get("serve.replica_init", {}).get("self_s"),
        "by_program": by_program, "short_stages": short,
        "cache_missed": sorted(missed), "stages_in_ramp": in_ramp,
        "hbm_peak": peak, "processes": procs,
        "books": {"named_s": named, "limit_s": setup_s - ramp_s,
                  "first_event_s": earliest - t_start,
                  "ok": bool(setup_s - ramp_s - named >= -0.5
                             and earliest - t_start >= -0.5)},
    }


def ramp_of(run) -> float:
    """Seconds before the window in which the load generator already
    sends: an open loop starts its clock ``0.05 - first due`` before
    the window (``loadgen.run_open_loop``), a closed loop the traffic's
    ``ramp_s``; training has none."""
    ramp_s = float(run.traffic.get("ramp_s", 0.0))
    dues = [r["due"] for r in run.requests if r.get("due") is not None]
    if run.traffic.get("loop") == "open" and dues:
        return min(ramp_s, max(0.0, -min(dues))) + 0.05
    return ramp_s


def books(run) -> Optional[Dict[str, Any]]:
    """``reduce`` over the run that just ended in this process, once a
    run: the first reader writes it into the run's notes (as
    ``live_idle_ms_per_step`` writes its books) and the others read it
    there.  None where the program keeps no start-up record."""
    if NOTES_KEY in run.notes:
        return run.notes[NOTES_KEY]
    rec = record()
    if rec is None:
        return None
    out = reduce(rec, process_start(), float(run.setup_s), ramp_of(run),
                 run.device.get("memory_peak_bytes"))
    run.notes[NOTES_KEY] = out
    return out


def class_seconds(run, cls: str) -> Optional[float]:
    out = books(run)
    return None if out is None else out["classes"][cls]
