"""What a runner hands to the metric readers: everything one run
observed, and nothing computed from it yet."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional


@dataclasses.dataclass
class Run:
    cell: str
    config: Dict[str, Any]            # the configuration file
    traffic: Dict[str, Any]           # the traffic file
    chips: int
    seconds: float                    # length of the measured window
    setup_s: float                    # process start to the window's start
    device: Dict[str, Any]            # platform, kind, count, memory peak
    attempted: int = 0
    failed: int = 0
    correct: bool = False
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # serving: the load generator's records (times from the window's start)
    requests: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    # serving: the engine's request ring, by request id, and its counters
    # at the window's start and end
    ring: Dict[str, Dict[str, Any]] = dataclasses.field(default_factory=dict)
    counters0: Dict[str, Any] = dataclasses.field(default_factory=dict)
    counters1: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # training: one entry per optimizer step that ended in the window
    steps: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    # --trace 1: trace_reduce.reduce()'s summary
    trace: Optional[Dict[str, Any]] = None


def steps_wall_s(run: Run) -> Optional[float]:
    """Training: the time the whole optimizer steps of the window took."""
    if not run.steps:
        return None
    return run.steps[-1]["end"] - run.steps[0]["start"]


def train_tokens_per_s(run: Run) -> Optional[float]:
    """Training, per chip: the tokens of the whole optimizer steps that
    ended in the window over the time those steps took."""
    took = steps_wall_s(run)
    if not took:
        return None
    return sum(s["tokens"] for s in run.steps) / took / run.chips
