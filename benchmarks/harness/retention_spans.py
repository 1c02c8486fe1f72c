"""The power-retention layers' share of a traced serving step.

The scopes a retention mixer opens (``ret_proj``, ``retention``) are not
among ``program_spans.SCOPES``; they are added for the length of a read
the way ``ssm_spans`` adds its own (its context manager, entered first,
is what saves and restores the tuple).  The two kernels,
``retention_decode`` and ``retention_chunk``, are booked under their own
names as every Mosaic kernel is.  A program that opens no such scope
(any other model, and the parent of the PR that added this file) gives a
table without them, and every reader here then returns None.
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import Any, Dict, List, Optional, Tuple

from benchmarks.harness import program_spans, ssm_spans, trace_reduce
from benchmarks.harness.ssm_spans import per_execution

RET_SCOPES = ("ret_proj", "retention")
DECODE_KERNEL, CHUNK_KERNEL = "retention_decode", "retention_chunk"
# the scope ``retention`` (the kernels' operands, the normaliser's
# scatter, the division) and the kernels inside it
RETENTION = ("retention", DECODE_KERNEL, CHUNK_KERNEL)


@contextlib.contextmanager
def scopes_added():
    with ssm_spans.scopes_added():
        program_spans.SCOPES = program_spans.SCOPES + RET_SCOPES
        yield


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


def mixer_time_share(trace: Dict[str, Any]) -> Optional[float]:
    """Self time under ``ret_proj``, ``retention`` and the two kernels
    over the busy time of the step's executions, %."""
    labels = ("ret_proj",) + RETENTION
    per = [booked for _s, booked in per_execution(trace)]
    if not any(lb in booked for booked in per for lb in labels):
        return None
    busy = sum(sum(booked.values()) for booked in per)
    return 100.0 * sum(booked.get(lb, 0) for booked in per
                       for lb in labels) / busy


def joined_steps(trace: Dict[str, Any], *, prefill: bool
                 ) -> Optional[List[Tuple[Dict[str, Any], Dict[str, int]]]]:
    """(``llm.pack``'s counts, self time by label) of each joined step
    that carried prompt tokens (``prefill``) or none; None without a
    checked join."""
    joined = program_spans.join_steps(trace)
    if joined is None:
        return None
    packs = program_spans.packs_by_seq(program_spans.program_lines(trace))
    by_start = dict(per_execution(trace))
    out = []
    for seq, (start, _end) in joined.items():
        booked, pack = by_start.get(start), packs.get(seq)
        if (booked is not None and pack is not None
                and (pack["n_prefill"] > 0) == prefill):
            out.append((pack, booked))
    return out or None
