"""Reductions from the load generator's per-request records, the
engine's request ring and its counters to the quantities the readers
report.  A record's times are seconds from the window's start, by this
process's clock."""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional


def measured(run) -> List[Dict[str, Any]]:
    return [r for r in run.requests if r["measured"]]


def completed(run) -> List[Dict[str, Any]]:
    return [r for r in measured(run) if r["ok"]]


def origin(rec: Dict[str, Any]) -> float:
    """The instant a request's latency counts from: when it was due in
    an open loop, when it was sent in a closed one."""
    return rec["due"] if rec["due"] is not None else rec["sent"]


def ttfts_ms(recs: Iterable[Dict[str, Any]]) -> List[float]:
    return [(r["first"] - origin(r)) * 1e3 for r in recs
            if r["first"] is not None]


def tpots_ms(recs: Iterable[Dict[str, Any]]) -> List[float]:
    """Per request: (last token - first token) / (tokens - 1)."""
    return [(r["last"] - r["first"]) * 1e3 / (r["n_out"] - 1)
            for r in recs if r["first"] is not None and r["n_out"] > 1]


def ring_ts(run, rec: Dict[str, Any], state: str) -> Optional[float]:
    """When the engine's request ring saw ``rec`` enter ``state``."""
    row = run.ring.get(rec.get("request_id") or "")
    return None if row is None else row["state_ts"].get(state)


def counter_delta(run, *path: str) -> Optional[float]:
    """An engine counter's growth over the window (and its drain)."""
    a, b = run.counters0, run.counters1
    for k in path:
        a = (a or {}).get(k)
        b = (b or {}).get(k)
    if a is None or b is None:
        return None
    return b - a
