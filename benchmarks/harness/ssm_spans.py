"""The state-space layers' share of a traced serving step.

``program_spans`` books a device operation under the innermost of the
scopes it knows (``program_spans.SCOPES``); the scopes a Mamba mixer
opens (``ssm_proj``, ``ssm_conv``, ``ssm_scan``) are not among them, so
its table shows the mixer as ``unscoped`` beside the kernel
``ssm_scan``.  This module reads the same file through the same code
with those three scopes added for the length of the call, and leaves
``program_spans`` as it found it.  A program that opens no such scope
(any other model) gives a table without them, and every reader here
then returns None.
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import Any, Dict, List, Optional, Tuple

from benchmarks.harness import program_spans, trace_reduce

SSM_SCOPES = ("ssm_proj", "ssm_conv", "ssm_scan")
SCAN = "ssm_scan"


@contextlib.contextmanager
def scopes_added():
    saved = program_spans.SCOPES
    program_spans.SCOPES = saved + SSM_SCOPES
    try:
        yield
    finally:
        program_spans.SCOPES = saved


@functools.lru_cache(maxsize=2)
def _load(path: str, _mtime: float) -> Dict[str, Any]:
    with scopes_added():
        return program_spans.load_xplane(path)


def trace_of(run) -> Optional[Dict[str, Any]]:
    path = trace_reduce.find_xplane(os.path.join(
        program_spans.ROOT, "benchmarks_out", "trace", run.cell))
    if path is None:
        return None
    return _load(path, os.path.getmtime(path))


def per_execution(trace: Dict[str, Any]
                  ) -> List[Tuple[int, Dict[str, int]]]:
    """(start, self time by label) of each whole execution of the
    serving step in the window, in time order."""
    module = program_spans.SERVE_MODULE
    return [(m[1], booked) for m, booked in zip(
        program_spans.whole_modules(trace, module),
        program_spans.label_ps_per_execution(trace, module))]


def mixer_time_share(trace: Dict[str, Any]) -> Optional[float]:
    """Self time under the three scopes (the kernel ``ssm_scan``
    included) over the busy time of the step's executions, %."""
    per = [booked for _s, booked in per_execution(trace)]
    if not any(lb in booked for booked in per for lb in SSM_SCOPES):
        return None
    busy = sum(sum(booked.values()) for booked in per)
    return 100.0 * sum(booked.get(lb, 0) for booked in per
                       for lb in SSM_SCOPES) / busy


def decode_scan_steps(trace: Dict[str, Any]
                      ) -> Optional[List[Tuple[int, float]]]:
    """(rows, seconds under ``ssm_scan``) of each joined step that
    carried no prompt token; None without a checked join or a scan."""
    joined = program_spans.join_steps(trace)
    if joined is None:
        return None
    packs = program_spans.packs_by_seq(program_spans.program_lines(trace))
    by_start = dict(per_execution(trace))
    out = []
    for seq, (start, _end) in joined.items():
        booked = by_start.get(start)
        pack = packs.get(seq)
        if (booked is None or pack is None or pack["n_prefill"] > 0
                or SCAN not in booked):
            continue
        out.append((int(pack["rows"]), booked[SCAN] / 1e12))
    return out or None
