"""The shares of a traced serving step that belong to a model of latent
attention, routed experts and a hyper-connection residual.

The scopes such a step opens (``hc_mix``, ``mla_proj``, ``latent_attn``,
``moe_route``, ``moe_experts``, ``moe_shared``) are not among
``program_spans.SCOPES``; they are added for the length of a read the
way ``ssm_spans`` adds its own (its context manager, entered first, is
what saves and restores the tuple).  Its kernels are booked under their
own names as every Mosaic kernel is, and a reader names both: the time
under ``moe_experts`` is the scope's XLA operations (the sort, the
gathers) plus the kernel that does the grouped products,
``moe_grouped_ffn``.  A program that opens no such scope (any other
model, and the parent of the PR that added this file) gives a table
without them, and every reader here then returns None.

The experts' counters (``moe_distinct``: experts hit, summed over steps
and kept on the device) reach a read through the reduced trace:
``serve_xing``'s replica notes them, with the step whose end they show
(the engine's loop hands out both together), when the traced window
starts and when it stops.
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmarks.harness import program_spans, ssm_spans, trace_reduce
from benchmarks.harness.retention_spans import joined_steps  # noqa: F401
from benchmarks.harness.ssm_spans import per_execution

XING_SCOPES = ("hc_mix", "mla_proj", "latent_attn", "moe_route",
               "moe_experts", "moe_shared")
HC = ("hc_mix",)
LATENT = ("latent_attn", "ragged_latent_attention")
EXPERTS = ("moe_experts", "moe_grouped_ffn")
MOE = ("moe_route", "moe_shared") + EXPERTS


@contextlib.contextmanager
def scopes_added():
    with ssm_spans.scopes_added():
        program_spans.SCOPES = program_spans.SCOPES + XING_SCOPES
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
    executions, %; None where no execution shows any of them."""
    trace = trace_of(run)
    if trace is None:
        return None
    per = [booked for _s, booked in per_execution(trace)]
    if not any(lb in booked for booked in per for lb in labels):
        return None
    busy = sum(sum(booked.values()) for booked in per)
    return 100.0 * sum(booked.get(lb, 0) for booked in per
                       for lb in labels) / busy


def experts_hit_per_layer_step(run) -> Optional[float]:
    """Distinct experts a routed layer's step reads, mean over the steps
    between the traced window's two ends."""
    ends = (run.trace or {}).get("model_counters")
    if not ends or len(ends) != 2 or "moe_distinct" not in ends[0]:
        return None
    steps = ends[1]["steps"] - ends[0]["steps"]
    layers = len(ends[0]["moe_distinct"])
    if steps <= 0 or not layers:
        return None
    hit = sum(ends[1]["moe_distinct"]) - sum(ends[0]["moe_distinct"])
    return hit / (steps * layers)


def decode_steps(run) -> Optional[List[Tuple[Dict[str, Any], Dict[str, int]]]]:
    """(``llm.pack``'s counts, self time by label) of each joined step
    that carried no prompt token."""
    trace = trace_of(run)
    return None if trace is None else joined_steps(trace, prefill=False)
