"""The shares of a traced serving step that belong to learned sparse
attention: the indexer's scores (scope ``dsa_index``), the top-k
selection (``dsa_select``) and the attention over what was selected
(``latent_attn``, with the kernel ``ragged_latent_attention`` that walks
a prompt chunk's context under the selection).

The two ``dsa_*`` scopes are not among ``program_spans.SCOPES`` nor
``xing_spans.XING_SCOPES``; they are added for the length of a read on
top of both (``xing_spans.scopes_added``, entered first, saves and
restores the tuple).  A program that opens no such scope (any other
model, and the parent of the PR that added this file) gives a table
without them, and every reader here then returns None.  ``llm.pack``'s
``sel_tokens`` (the cached positions a step's queries select, from the
adapter's ``ragged_sel_tokens``) reaches a read with the joined steps.
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmarks.harness import program_spans, trace_reduce, xing_spans
from benchmarks.harness.retention_spans import joined_steps
from benchmarks.harness.ssm_spans import per_execution

DSA_SCOPES = ("dsa_index", "dsa_select")
INDEX = ("dsa_index",)
SELECT = ("dsa_select",)
SPARSE_ATTN = INDEX + SELECT + xing_spans.LATENT


@contextlib.contextmanager
def scopes_added():
    with xing_spans.scopes_added():
        program_spans.SCOPES = program_spans.SCOPES + DSA_SCOPES
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


def time_share(run, labels: Sequence[str],
               needs: Sequence[str] = DSA_SCOPES) -> Optional[float]:
    """Self time under ``labels`` over the busy time of the step's
    executions, %; None where no execution shows any of ``needs``."""
    trace = trace_of(run)
    if trace is None:
        return None
    per = [booked for _s, booked in per_execution(trace)]
    if not any(lb in booked for booked in per for lb in needs):
        return None
    busy = sum(sum(booked.values()) for booked in per)
    return 100.0 * sum(booked.get(lb, 0) for booked in per
                       for lb in labels) / busy


def decode_steps(run) -> Optional[List[Tuple[Dict[str, Any], Dict[str, int]]]]:
    """(``llm.pack``'s counts, self time by label) of each joined step
    that carried no prompt token; None without a checked join."""
    trace = trace_of(run)
    return None if trace is None else joined_steps(trace, prefill=False)


def decode_roofline_share(run, labels: Sequence[str], count: str,
                          least_bytes) -> Optional[float]:
    """Over the joined decode-only steps: the least time the chip's
    memory could take for ``least_bytes(config, pack[count])`` over the
    device time under ``labels``, %.  None where no such step shows the
    labels or ``llm.pack`` lacks ``count``."""
    from benchmarks.harness import peaks

    steps = decode_steps(run)
    took = sum(booked.get(lb, 0) for _p, booked in steps or ()
               for lb in labels) / 1e12
    if not took or not all(count in pack for pack, _b in steps):
        return None
    rate = peaks.peaks(run.device["kind"])["hbm_bytes_per_s"]
    least = sum(least_bytes(run.config, int(pack[count]))
                for pack, _b in steps) / rate
    return 100.0 * least / took
