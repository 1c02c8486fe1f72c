"""The program's own spans and the device's operations, from one trace.

The program (ray_tpu/util/tracing.py) opens a ``TraceAnnotation`` for
every span, so a traced run's ``.xplane.pb`` holds ``llm.loop``,
``llm.pack`` (with the step's counts as stats), ``llm.dispatch``,
``llm.fetch``, ``train.step`` and the rest on the host plane, by thread,
on the clock of the device's operations.  This module reads them, and
reads the device plane with what ``jax.profiler.ProfileData`` leaves
out: each operation's *metadata*, where a TPU trace keeps the name path
of the JAX operations an HLO instruction came from (stat ``tf_op``,
``jit(serve_ragged)/while/body/closed_call/weight_slice/squeeze:``).
The event's own name is the instruction's HLO text without metadata,
and its own stats are offsets and durations only, so the scope path is
nowhere else.  The file is therefore parsed here as the protocol buffer
it is (``XSpace``), with message classes declared below for the fields
used; nothing but ``google.protobuf`` is needed.

``load`` turns the file into plain lists; everything else works on
those lists alone and is checked on a hand-built event list
(tests/yardstick/test_program_spans.py).  Times are picoseconds on the
trace's clock.

A program that opens no such span (the parent of the PR that added
this) gives empty lists, and every reader then returns None.
"""

from __future__ import annotations

import functools
import os
import re
import sys
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from benchmarks.harness import stats as st
from benchmarks.harness import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

# name, start_ps, duration_ps, stats
Span = Tuple[str, int, int, Dict[str, Any]]

SPAN_PREFIXES = ("llm.", "train.", "serve.", "telemetry.", "bench.")
WINDOW_SPAN = trace_reduce.WINDOW_SPAN
# The scopes the program opens inside its jitted steps (PERF.md section
# 3); an operation belongs to the innermost one on its name path.
SCOPES = ("embed", "weight_slice", "fused_layer", "attention", "mlp",
          "kv_append", "lm_head", "sample", "loss", "optimizer",
          "grad_norm")
UNSCOPED = "unscoped"
# The loop's working phases: what the host spends on a step besides
# waiting (``llm.idle``).
WORK_PHASES = ("llm.control", "llm.admit", "llm.pack", "llm.dispatch",
               "llm.commit", "llm.emit")
SERVE_MODULE = "jit_serve_ragged"
TRAIN_MODULE = "jit_train_step"
# A Mosaic kernel is the custom call itself; an operation that merely
# takes one's result (``%pallas_call.54``) is not.
MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'
MIN_CLASS_STEPS = 5
MIN_GAP_PS = trace_reduce.MIN_GAP_NS * 1000


# -- the file ---------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _xspace_class():
    """Message classes for the part of tsl's ``xplane.proto`` read here
    (maps are declared as the repeated key/value entries they are on
    the wire)."""
    from google.protobuf import (
        descriptor_pb2,
        descriptor_pool,
        message_factory,
    )

    F = descriptor_pb2.FieldDescriptorProto
    pkg = "bench_xplane"
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package=pkg, syntax="proto3")

    def message(name: str, *fields, oneof: str = "") -> None:
        msg = fdp.message_type.add(name=name)
        if oneof:
            msg.oneof_decl.add(name=oneof)
        for fname, number, ftype, repeated in fields:
            field = msg.field.add(
                name=fname, number=number,
                label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if isinstance(ftype, str):
                field.type = F.TYPE_MESSAGE
                field.type_name = f".{pkg}.{ftype}"
            else:
                field.type = ftype
            if oneof and fname != "metadata_id":
                field.oneof_index = 0

    message("XStat", ("metadata_id", 1, F.TYPE_INT64, False),
            ("double_value", 2, F.TYPE_DOUBLE, False),
            ("uint64_value", 3, F.TYPE_UINT64, False),
            ("int64_value", 4, F.TYPE_INT64, False),
            ("str_value", 5, F.TYPE_STRING, False),
            ("ref_value", 7, F.TYPE_UINT64, False), oneof="value")
    message("XEvent", ("metadata_id", 1, F.TYPE_INT64, False),
            ("offset_ps", 2, F.TYPE_INT64, False),
            ("duration_ps", 3, F.TYPE_INT64, False),
            ("stats", 4, "XStat", True))
    message("XLine", ("id", 1, F.TYPE_INT64, False),
            ("name", 2, F.TYPE_STRING, False),
            ("timestamp_ns", 3, F.TYPE_INT64, False),
            ("events", 4, "XEvent", True))
    message("XEventMetadata", ("id", 1, F.TYPE_INT64, False),
            ("name", 2, F.TYPE_STRING, False),
            ("display_name", 4, F.TYPE_STRING, False),
            ("stats", 5, "XStat", True))
    message("XStatMetadata", ("id", 1, F.TYPE_INT64, False),
            ("name", 2, F.TYPE_STRING, False))
    message("EventMetadataEntry", ("key", 1, F.TYPE_INT64, False),
            ("value", 2, "XEventMetadata", False))
    message("StatMetadataEntry", ("key", 1, F.TYPE_INT64, False),
            ("value", 2, "XStatMetadata", False))
    message("XPlane", ("id", 1, F.TYPE_INT64, False),
            ("name", 2, F.TYPE_STRING, False),
            ("lines", 3, "XLine", True),
            ("event_metadata", 4, "EventMetadataEntry", True),
            ("stat_metadata", 5, "StatMetadataEntry", True))
    message("XSpace", ("planes", 1, "XPlane", True))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{pkg}.XSpace"))


def _stat_value(stat, stat_names: Dict[int, str]):
    kind = stat.WhichOneof("value")
    if kind is None:
        return None
    if kind == "ref_value":
        return _typed(stat_names.get(stat.ref_value, ""))
    value = getattr(stat, kind)
    return _typed(value) if kind == "str_value" else value


def _typed(text: str):
    """An annotation's stat arrives as text; numbers read as numbers,
    as jax.profiler.ProfileData gives them."""
    try:
        return int(text)
    except ValueError:
        try:
            return float(text)
        except ValueError:
            return text


def _stats(xstats, stat_names: Dict[int, str]) -> Dict[str, Any]:
    return {stat_names.get(s.metadata_id, str(s.metadata_id)):
            _stat_value(s, stat_names) for s in xstats}


def load_xplane(path: str) -> Dict[str, Any]:
    """{"host": [[Span, ...] per thread line],
        "devices": {plane: {"ops": [(label, start, dur)],
                            "modules": [(name, start, dur, run_id)]}}}

    Host lines keep the program's spans (SPAN_PREFIXES) and any event
    that names an execution (stat ``run_id``); a device operation's
    label is ``op_label`` of its instruction name, HLO text and name
    path."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out: Dict[str, Any] = {"host": [], "devices": {}}
    for plane in space.planes:
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                base = line.timestamp_ns * 1000
                spans: List[Span] = []
                for ev in line.events:
                    name = meta[ev.metadata_id].name
                    if name.startswith(SPAN_PREFIXES):
                        spans.append((name, base + ev.offset_ps,
                                      ev.duration_ps,
                                      _stats(ev.stats, stat_names)))
                    elif ev.stats:
                        stats = _stats(ev.stats, stat_names)
                        if "run_id" in stats:
                            spans.append((name, base + ev.offset_ps,
                                          ev.duration_ps,
                                          {"run_id": stats["run_id"]}))
                if spans:
                    out["host"].append(sorted(spans, key=lambda s: s[1]))
        elif plane.name.startswith("/device:TPU:"):
            labels: Dict[int, str] = {}
            dev: Dict[str, list] = {"ops": [], "modules": []}
            for line in plane.lines:
                base = line.timestamp_ns * 1000
                if line.name == trace_reduce.OPS_LINE:
                    for ev in line.events:
                        label = labels.get(ev.metadata_id)
                        if label is None:
                            m = meta[ev.metadata_id]
                            path_ = _stats(m.stats, stat_names).get(
                                "tf_op", "")
                            label = labels[ev.metadata_id] = op_label(
                                m.display_name or
                                trace_reduce.short_name(m.name),
                                m.name, str(path_))
                        dev["ops"].append((label, base + ev.offset_ps,
                                           ev.duration_ps))
                elif line.name == trace_reduce.MODULES_LINE:
                    for ev in line.events:
                        name = meta[ev.metadata_id].name
                        run_id = _stats(ev.stats, stat_names).get("run_id")
                        dev["modules"].append(
                            (name.split("(", 1)[0], base + ev.offset_ps,
                             ev.duration_ps, run_id))
            if dev["ops"]:
                out["devices"][plane.name] = dev
    return out


def op_label(instruction: str, hlo_text: str, name_path: str) -> str:
    """What a device operation's time is booked under: a Mosaic kernel
    under its own name (``fused_ragged_layer.9`` -> ``fused_ragged_layer``:
    the compiler names the custom call after ``pallas_call(name=...)``),
    any other operation under the innermost of the program's scopes on
    its name path, or UNSCOPED."""
    if MOSAIC_CALL in hlo_text:
        return re.sub(r"\.\d+$", "", instruction.lstrip("%"))
    found = UNSCOPED
    for token in re.findall(r"[A-Za-z_]\w*", name_path):
        if token in SCOPES:
            found = token
    return found


@functools.lru_cache(maxsize=4)
def _load_cached(path: str, _mtime: float) -> Dict[str, Any]:
    return load_xplane(path)


def trace_of(run) -> Optional[Dict[str, Any]]:
    """The trace the run wrote, at the path benchmarks/run.py fixes for
    its cell; loaded once a process.  None where there is none."""
    path = trace_reduce.find_xplane(
        os.path.join(ROOT, "benchmarks_out", "trace", run.cell))
    if path is None:
        return None
    return _load_cached(path, os.path.getmtime(path))


def lines_of(run) -> List[List[Span]]:
    """The program's whole spans inside the run's traced window, by
    thread; nothing where the run left no trace."""
    trace = trace_of(run)
    return program_lines(trace) if trace is not None else []


def kernel_ms_per_step(run, module: str, labels: Sequence[str]
                       ) -> Optional[float]:
    """``label_ms_per_step`` of the run's trace, for the readers."""
    trace = trace_of(run)
    if trace is None:
        return None
    return label_ms_per_step(trace, module, labels)


# -- host spans ---------------------------------------------------------------

def window(trace: Dict[str, Any]) -> Optional[Tuple[int, int]]:
    """[start, end) of the harness's window span (the longest)."""
    found = [(d, s) for line in trace["host"] for n, s, d, _ in line
             if n == WINDOW_SPAN]
    if not found:
        return None
    d, s = max(found)
    return s, s + d


def _in(span: Span, lo: int, hi: int) -> bool:
    return lo <= span[1] and span[1] + span[2] <= hi


def program_lines(trace: Dict[str, Any]) -> List[List[Span]]:
    """Per thread line, the program's whole spans inside the window."""
    win = window(trace)
    if win is None:
        return []
    return [[s for s in line if s[0].startswith(SPAN_PREFIXES)
             and s[0] != WINDOW_SPAN and _in(s, *win)]
            for line in trace["host"]]


def with_self_times(line: Sequence[Span]) -> List[Tuple[Span, int]]:
    """(span, self time): a span's duration minus what the spans nested
    directly inside it on the same thread cover."""
    order = sorted(line, key=lambda s: (s[1], -s[2]))
    selfs = [s[2] for s in order]
    stack: List[int] = []
    for i, (_n, start, dur, _st) in enumerate(order):
        while stack and start + dur > (order[stack[-1]][1]
                                       + order[stack[-1]][2]):
            stack.pop()
        if stack:
            selfs[stack[-1]] -= dur
        stack.append(i)
    return [(s, max(v, 0)) for s, v in zip(order, selfs)]


def self_time_by_name(lines: Iterable[Sequence[Span]]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for line in lines:
        for (name, *_), own in with_self_times(line):
            out[name] = out.get(name, 0) + own
    return out


def named(lines: Iterable[Sequence[Span]], name: str) -> List[Span]:
    return sorted((s for line in lines for s in line if s[0] == name),
                  key=lambda s: s[1])


def packs_by_seq(lines: Iterable[Sequence[Span]]) -> Dict[int, Dict[str, Any]]:
    """The counts at each step's boundary, by the engine's step
    ordinal: ``llm.pack`` spans that packed a step."""
    return {s[3]["seq"]: s[3] for s in named(lines, "llm.pack")
            if "seq" in s[3]}


def seqs_of(span: Span) -> List[int]:
    """The steps an ``llm.fetch`` or ``llm.emit`` span covers: entries
    leave in dispatch order, so the span names the first and the last."""
    if "seq_first" not in span[3]:
        return []
    return list(range(span[3]["seq_first"], span[3]["seq_last"] + 1))


def host_ms_per_step(lines: Sequence[Sequence[Span]]) -> List[float]:
    """Per step, the self time of the loop's working phases in the
    iteration (``llm.loop``) that dispatched it."""
    out = []
    for line in lines:
        for loop in (s for s in line if s[0] == "llm.loop"
                     and "seq" in s[3]):
            inside = [s for s in line
                      if _in(s, loop[1], loop[1] + loop[2])]
            out.append(sum(own for (name, *_), own in with_self_times(inside)
                           if name in WORK_PHASES) / 1e9)
    return out


# -- device operations ----------------------------------------------------

def whole_modules(trace: Dict[str, Any], module: str
                  ) -> List[Tuple[str, int, int, Any]]:
    """(plane, start, dur, run_id) of the module's executions that lie
    wholly inside the window, in time order."""
    win = window(trace)
    if win is None:
        return []
    return sorted(((plane, s, d, rid)
                   for plane, dev in trace["devices"].items()
                   for n, s, d, rid in dev["modules"]
                   if n == module and win[0] <= s and s + d <= win[1]),
                  key=lambda m: m[1])


def label_ps_per_execution(trace: Dict[str, Any], module: str
                           ) -> List[Dict[str, int]]:
    """Per whole execution of ``module``, the self time of the device's
    operations inside it by label (kept with the trace: several
    readers ask)."""
    memo = trace.setdefault("_label_ps", {})
    if module in memo:
        return memo[module]
    out = memo[module] = []
    by_plane: Dict[str, list] = {}
    for plane, start, dur, _rid in whole_modules(trace, module):
        ops = by_plane.get(plane)
        if ops is None:
            ops = by_plane[plane] = sorted(
                trace["devices"][plane]["ops"], key=lambda o: o[1])
        inside = [o for o in ops if start <= o[1] and o[1] + o[2]
                  <= start + dur]
        booked: Dict[str, int] = {}
        for label, _s, _d, own in trace_reduce.self_times(inside):
            booked[label] = booked.get(label, 0) + own
        out.append(booked)
    return out


def label_ms_per_step(trace: Dict[str, Any], module: str,
                      labels: Sequence[str]) -> Optional[float]:
    """Mean over the module's whole executions of the self time under
    ``labels``; None where none of them ran (a program without these
    names)."""
    per = label_ps_per_execution(trace, module)
    if not per or not any(lb in booked for booked in per for lb in labels):
        return None
    return st.mean([sum(booked.get(lb, 0) for lb in labels) / 1e9
                    for booked in per])


def label_table(trace: Dict[str, Any], module: str
                ) -> List[Tuple[str, float, float]]:
    """(label, ms per execution, share of the execution's busy time %),
    largest first: the table PERF.md section 5 prints."""
    per = label_ps_per_execution(trace, module)
    if not per:
        return []
    total: Dict[str, int] = {}
    for booked in per:
        for k, v in booked.items():
            total[k] = total.get(k, 0) + v
    busy = sum(total.values())
    return sorted(((k, v / len(per) / 1e9, 100.0 * v / busy)
                   for k, v in total.items()), key=lambda r: -r[1])


# -- module execution <-> step ---------------------------------------------

def join_steps(trace: Dict[str, Any], module: str = SERVE_MODULE
               ) -> Optional[Dict[int, Tuple[int, int]]]:
    """{seq: (start, end) of the module execution that ran the step}.

    By ``run_id`` where the trace has it.  A module event carries its
    execution's id, and so does the host's enqueue of the execution,
    which a worker thread makes while the step's ``llm.dispatch`` is
    open or just after it returned.  Ids and steps both count up by
    one, so ``seq - run_id`` is one constant over the window: it is
    taken from the enqueues (each against the latest dispatch that began
    before it; the most common difference wins) and applied to every
    execution.  Else by anchoring: the execution that ended last before
    an ``llm.fetch`` ended ran the last step that fetch brought back,
    and the executions before it ran the steps before, one each.

    The join is checked, not trusted: every matched execution starts
    after its ``llm.dispatch`` began and ends before its ``llm.fetch``
    ended.  One violation and there is no join (None)."""
    lines = program_lines(trace)
    dispatches = {s[3]["seq"]: s for s in named(lines, "llm.dispatch")
                  if "seq" in s[3]}
    fetch_end = {seq: s[1] + s[2] for s in named(lines, "llm.fetch")
                 for seq in seqs_of(s)}
    modules = whole_modules(trace, module)
    if not dispatches or not fetch_end or not modules:
        return None
    joined: Dict[int, Tuple[int, int]] = {}
    if all(m[3] is not None for m in modules):
        ids = {m[3] for m in modules}
        enqueued: Dict[Any, int] = {}
        for line in trace["host"]:
            for _n, start, _d, stats in line:
                rid = stats.get("run_id")
                if rid in ids and start < enqueued.get(rid, 1 << 62):
                    enqueued[rid] = start
        spans = sorted(dispatches.values(), key=lambda s: s[1])
        votes: Dict[int, int] = {}
        for rid, t in enqueued.items():
            before = [s for s in spans if s[1] <= t]
            if before:
                diff = before[-1][3]["seq"] - rid
                votes[diff] = votes.get(diff, 0) + 1
        if not votes:
            return None
        diff = max(votes, key=lambda k: votes[k])
        joined = {m[3] + diff: (m[1], m[1] + m[2]) for m in modules}
    else:
        ends = [m[1] + m[2] for m in modules]
        for s in named(lines, "llm.fetch"):
            seqs = seqs_of(s)
            last = max((i for i, e in enumerate(ends) if e <= s[1] + s[2]),
                       default=None)
            if last is None:
                continue
            for back, seq in enumerate(reversed(seqs)):
                if last - back >= 0 and seq not in joined:
                    m = modules[last - back]
                    joined[seq] = (m[1], m[1] + m[2])
    checked = {}
    for seq, (start, end) in joined.items():
        if seq not in dispatches or seq not in fetch_end:
            continue    # its dispatch or its fetch lies outside the window
        if start < dispatches[seq][1] or end > fetch_end[seq]:
            return None
        checked[seq] = (start, end)
    return checked or None


def step_device_ms(trace: Dict[str, Any], *, prefill: bool
                   ) -> Optional[List[float]]:
    """Device times of the serving step's executions whose step carried
    prompt tokens (``prefill``) or none; None without a checked join."""
    joined = join_steps(trace)
    if joined is None:
        return None
    packs = packs_by_seq(program_lines(trace))
    return [(end - start) / 1e9 for seq, (start, end) in sorted(
                joined.items())
            if seq in packs and (packs[seq]["n_prefill"] > 0) == prefill]


def step_device_ms_p50(run, *, prefill: bool) -> Optional[float]:
    """The reader behind ``step_device_ms_p50_decode`` and ``_prefill``:
    a class with fewer than MIN_CLASS_STEPS steps in the window reads
    None, and the run's notes say so."""
    trace = trace_of(run)
    if trace is None:
        return None
    got = step_device_ms(trace, prefill=prefill)
    if got is None:
        return None
    if len(got) < MIN_CLASS_STEPS:
        kind = "with" if prefill else "without"
        run.notes[f"step_device_ms_p50_{'prefill' if prefill else 'decode'}"] \
            = (f"{len(got)} joined steps {kind} prompt tokens in the "
               f"window, fewer than {MIN_CLASS_STEPS}: not reported")
        return None
    return st.percentile(got, 50)


# -- idle gaps ---------------------------------------------------------------

def gap_table(trace: Dict[str, Any]) -> List[Tuple[str, float]]:
    """(program span, idle ms) over the window, largest first: each
    stretch of at least MIN_GAP with no device operation is named by the
    innermost of the program's spans, on any thread, that covers its
    middle."""
    win = window(trace)
    if win is None or not trace["devices"]:
        return []
    spans = [s for line in trace["host"] for s in line
             if s[0].startswith(SPAN_PREFIXES) and s[0] != WINDOW_SPAN]
    out: Dict[str, float] = {}
    for dev in trace["devices"].values():
        ops = trace_reduce.clip([(n, s, d) for n, s, d in dev["ops"]], *win)
        busy = trace_reduce.union([(s, s + d) for _n, s, d in ops])
        for s, e in trace_reduce.subtract([win], busy):
            if e - s < MIN_GAP_PS:
                continue
            mid = (s + e) // 2
            cover = [sp for sp in spans if sp[1] <= mid < sp[1] + sp[2]]
            name = (min(cover, key=lambda sp: sp[2])[0] if cover
                    else "no_program_span")
            out[name] = out.get(name, 0.0) + (e - s) / 1e9
    n = len(trace["devices"])
    return sorted(((k, v / n) for k, v in out.items()), key=lambda r: -r[1])


# -- the builder's tables -------------------------------------------------

def main(argv: Sequence[str]) -> int:
    """python3 -m benchmarks.harness.program_spans <trace dir>: the
    per-kernel and per-scope table of each step program in the trace,
    the program's spans by self time, and the named idle gaps."""
    path = trace_reduce.find_xplane(argv[0])
    if path is None:
        print(f"no .xplane.pb under {argv[0]}")
        return 1
    trace = load_xplane(path)
    for module in (SERVE_MODULE, TRAIN_MODULE):
        rows = label_table(trace, module)
        if rows:
            n = len(label_ps_per_execution(trace, module))
            print(f"\n{module}: {n} whole executions in the window")
            for label, ms, share in rows:
                print(f"| `{label}` | {ms:.3f} | {share:.2f} |")
    lines = program_lines(trace)
    own = self_time_by_name(lines)
    counts = {n: len(named(lines, n)) for n in own}
    print("\nprogram spans: name, count, self ms")
    for name, ps in sorted(own.items(), key=lambda kv: -kv[1]):
        print(f"| `{name}` | {counts[name]} | {ps / 1e9:.3f} |")
    print("\nidle gaps by program span: name, ms")
    for name, ms in gap_table(trace):
        print(f"| `{name}` | {ms:.3f} |")
    joined = join_steps(trace)
    print("\njoined steps:", None if joined is None else len(joined))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
