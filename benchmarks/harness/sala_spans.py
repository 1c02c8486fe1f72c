"""The shares of a traced serving step that belong to a hybrid of
lightning (linear-attention) and block-sparse layers: the lightning
mixers' projections (scope ``lin_proj``) and recurrence (``lin_attn``
with its kernels ``lightning_decode`` and ``lightning_chunk``), and the
sparse layers' compressed keys (``bsa_compress``), block scores
(``bsa_score``), top-k (``bsa_select``) and the walk over the selected
pages (``sparse_attn`` with its kernel ``block_sparse_walk``).

The six scopes are not among ``program_spans.SCOPES``; they are added
for the length of a read the way ``ssm_spans`` adds its own (its context
manager, entered first, saves and restores the tuple).  Mosaic kernels
are booked under their own names.  A program that opens no such scope
(any other model, and the parent of the PR that added this file) gives a
table without them, and every reader here then returns None.
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmarks.harness import program_spans, ssm_spans, trace_reduce
from benchmarks.harness.retention_spans import joined_steps
from benchmarks.harness.ssm_spans import per_execution

SALA_SCOPES = ("lin_proj", "lin_attn", "bsa_compress", "bsa_score",
               "bsa_select", "sparse_attn")
DECODE_KERNEL, CHUNK_KERNEL = "lightning_decode", "lightning_chunk"
WALK_KERNEL = "block_sparse_walk"
LIN_ATTN = ("lin_attn", DECODE_KERNEL, CHUNK_KERNEL)
SELECT = ("bsa_compress", "bsa_score", "bsa_select")
WALK = ("sparse_attn", WALK_KERNEL)
MIXERS = ("lin_proj",) + LIN_ATTN + SELECT + WALK


@contextlib.contextmanager
def scopes_added():
    with ssm_spans.scopes_added():
        program_spans.SCOPES = program_spans.SCOPES + SALA_SCOPES
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


def ms_per_step(run, labels: Sequence[str]) -> Optional[float]:
    """Mean device time under ``labels`` per whole execution of the
    serving step; None without them."""
    trace = trace_of(run)
    if trace is None:
        return None
    return program_spans.label_ms_per_step(
        trace, program_spans.SERVE_MODULE, labels)


def time_share(run, labels: Sequence[str]) -> Optional[float]:
    """Self time under ``labels`` over the busy time of the step's
    executions, %; None where no execution shows any of the six scopes."""
    trace = trace_of(run)
    if trace is None:
        return None
    per = [booked for _s, booked in per_execution(trace)]
    if not any(lb in booked for booked in per for lb in SALA_SCOPES):
        return None
    busy = sum(sum(booked.values()) for booked in per)
    return 100.0 * sum(booked.get(lb, 0) for booked in per
                       for lb in labels) / busy


def steps(run, *, prefill: bool
          ) -> Optional[List[Tuple[Dict[str, Any], Dict[str, int]]]]:
    """(``llm.pack``'s counts, self time by label) of each joined step
    that carried prompt tokens (``prefill``) or none; None without a
    checked join or such a step."""
    trace = trace_of(run)
    return None if trace is None else joined_steps(trace, prefill=prefill)


def roofline_share(run, labels: Sequence[str], least_seconds, *,
                   prefill: bool) -> Optional[float]:
    """Over the joined steps with (``prefill``) or without prompt
    tokens: the least time the chip could take, ``least_seconds(config,
    pack, peaks)`` summed, over the device time under ``labels``, %.
    None where no such step shows the labels."""
    from benchmarks.harness import peaks

    found = steps(run, prefill=prefill)
    took = sum(booked.get(lb, 0) for _p, booked in found or ()
               for lb in labels) / 1e12
    if not took:
        return None
    chip = peaks.peaks(run.device["kind"])
    least = sum(least_seconds(run.config, pack, chip) for pack, _b in found)
    return 100.0 * least / took
