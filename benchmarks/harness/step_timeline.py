"""A step's way from dispatch to the device, and a token's way from the
device to the transport, on the capture's clock.

Per step of the serving loop that ``program_spans.join_steps`` joins to
its execution, the instants the program's spans and the device's module
events give (picoseconds, one clock):

    dispatch_end   the step's ``llm.dispatch`` returned (loop thread)
    exec_start     the device began the step's execution
    exec_end       and ended it
    fetch_end      the ``llm.fetch`` that brought the step's tokens to
                   the host ended (fetch thread)
    emit_start     the ``llm.emit`` that handed them to the requests
                   began (loop thread); None where it lies outside the
                   window
    item_ends      the end of every ``serve.stream_item`` that names the
                   step: the replica's thread of a request has encoded
                   the token and the transport has it

and the window's idle stretches of the device told apart by what the
loop was waiting for: ``llm.idle`` is the engine with no request
anywhere, everything else is idle time with a request live.

Everything works on ``program_spans.load_xplane``'s plain lists and is
checked on a hand-built one (tests/yardstick/test_step_timeline_readers.py).
A program without these spans gives None, as does a window whose join
fails its checks.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmarks.harness import program_spans as ps
from benchmarks.harness import trace_reduce

MS = 1e9    # picoseconds
STREAM_ITEM = "serve.stream_item"
# under three arrivals a median is one request's reading
MIN_ARRIVALS = 3


@dataclasses.dataclass
class Step:
    seq: int
    dispatch_end: int
    exec_start: int
    exec_end: int
    fetch_end: int
    emit_start: Optional[int] = None
    item_ends: List[int] = dataclasses.field(default_factory=list)


def build(trace: Dict[str, Any]) -> Optional[Dict[int, Step]]:
    """{seq: Step} over the joined steps of the window (kept with the
    trace: several readers ask), or None where there is no join."""
    if "_step_timeline" in trace:
        return trace["_step_timeline"]
    joined = ps.join_steps(trace)
    steps = None
    if joined is not None:
        lines = ps.program_lines(trace)
        # the join has checked that each step has both in the window
        dispatch_end = {s[3]["seq"]: s[1] + s[2]
                        for s in ps.named(lines, "llm.dispatch")
                        if "seq" in s[3]}
        fetch_end = {seq: s[1] + s[2] for s in ps.named(lines, "llm.fetch")
                     for seq in ps.seqs_of(s)}
        steps = {seq: Step(seq, dispatch_end[seq], start, end,
                           fetch_end[seq])
                 for seq, (start, end) in joined.items()}
        for emit in ps.named(lines, "llm.emit"):
            for seq in ps.seqs_of(emit):
                if seq in steps:
                    steps[seq].emit_start = emit[1]
        for item in ps.named(lines, STREAM_ITEM):
            step = steps.get(item[3].get("seq"))
            if step is not None:
                step.item_ends.append(item[1] + item[2])
    trace["_step_timeline"] = steps
    return steps


def of(run) -> Optional[Dict[int, Step]]:
    """``build`` of the run's trace, for the readers."""
    trace = ps.trace_of(run)
    return build(trace) if trace is not None else None


def lags_ms(run, later: str, earlier: str) -> Optional[List[float]]:
    """Per joined step, instant ``later`` less instant ``earlier`` in
    ms, over the steps that have both."""
    steps = of(run)
    if steps is None:
        return None
    return [(getattr(s, later) - getattr(s, earlier)) / MS
            for s in steps.values()
            if getattr(s, later) is not None]


def token_out_lags_ms(run) -> Optional[List[float]]:
    """Per streamed item of the window whose step is joined: the end of
    its ``serve.stream_item`` less the end of the step's execution."""
    steps = of(run)
    if steps is None:
        return None
    return [(t - s.exec_end) / MS for s in steps.values()
            for t in s.item_ends]


def stream_send_ms_per_step(run) -> Optional[float]:
    """Self time of every ``serve.stream_item`` span of the window, on
    all threads, over the joined steps."""
    steps = of(run)
    own = ps.self_time_by_name(ps.lines_of(run)).get(STREAM_ITEM)
    if steps is None or own is None:
        return None
    return own / MS / len(steps)


def loop_cpu_ms(run) -> Optional[List[float]]:
    """``cpu_us`` of the loop iterations (``llm.loop``) that dispatched
    the joined steps, in ms."""
    steps = of(run)
    if steps is None:
        return None
    return [s[3]["cpu_us"] / 1e3
            for s in ps.named(ps.lines_of(run), "llm.loop")
            if s[3].get("seq") in steps and "cpu_us" in s[3]]


def ingress_ms(run) -> Optional[List[float]]:
    """Per request that arrived in the window: from the start of its
    ``serve.replica`` span to the end of the ``llm.submit`` nested in it
    on the same thread.  (A capture keeps a span that ended inside it:
    a request still streaming when the capture stops is not here.)  A
    window whose join fails is not read at all."""
    if of(run) is None:
        return None
    out = []
    for line in ps.lines_of(run):
        submits = [s for s in line if s[0] == "llm.submit"]
        for whole in (s for s in line if s[0] == "serve.replica"):
            inside = [s for s in submits if whole[1] <= s[1]
                      and s[1] + s[2] <= whole[1] + whole[2]]
            if inside:
                out.append((inside[0][1] + inside[0][2] - whole[1]) / MS)
    return out


# -- the device's idle time, by what the loop was waiting for ------------

def _covered(spans: Sequence[ps.Span], t: int) -> bool:
    return any(s[1] <= t < s[1] + s[2] for s in spans)


def idle_books(trace: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The window's time with no device operation, averaged over the
    devices, in ms and in four parts that sum to window less busy:

    ``empty``: stretches of at least MIN_GAP whose middle an ``llm.idle``
    span covers (no request anywhere in the engine: nobody's loss);
    ``live``: the other stretches of at least MIN_GAP, the device
    standing still while the engine held a request; ``unnamed`` is the
    part of ``live`` whose middle no ``llm.loop`` span covers either (a
    capture keeps a span that began and ended inside it, so the loop's
    iteration at each edge of the capture is missing: an engine empty
    there cannot be told from one with rows live); ``short``: the
    stretches under MIN_GAP, between two operations of a step.  Spans
    clipped by the window count here, as in ``gap_table``.  ``longest``
    is the longest ``live`` stretch and the shortest program span over
    its middle."""
    win = ps.window(trace)
    if win is None or not trace["devices"]:
        return None
    spans = [s for line in trace["host"] for s in line
             if s[0].startswith(ps.SPAN_PREFIXES) and s[0] != ps.WINDOW_SPAN]
    idle = [s for s in spans if s[0] == "llm.idle"]
    loops = [s for s in spans if s[0] == "llm.loop"]
    parts = {"empty": 0, "live": 0, "unnamed": 0, "short": 0}
    longest: Tuple[int, str] = (0, "")
    for dev in trace["devices"].values():
        ops = trace_reduce.clip(list(dev["ops"]), *win)
        busy = trace_reduce.union([(s, s + d) for _n, s, d in ops])
        for s, e in trace_reduce.subtract([win], busy):
            mid = (s + e) // 2
            if e - s < ps.MIN_GAP_PS:
                parts["short"] += e - s
            elif _covered(idle, mid):
                parts["empty"] += e - s
            else:
                parts["live"] += e - s
                if not _covered(loops, mid):
                    parts["unnamed"] += e - s
                if e - s > longest[0]:
                    cover = [sp for sp in spans
                             if sp[1] <= mid < sp[1] + sp[2]]
                    longest = (e - s, min(cover, key=lambda sp: sp[2])[0]
                               if cover else "no_program_span")
    n = len(trace["devices"])
    books = {k + "_ms": v / n / MS for k, v in parts.items()}
    books["longest_ms"], books["longest_under"] = longest[0] / MS, longest[1]
    return books
