"""The one general load generator.  A traffic mix is a data file of
parameters under ``benchmarks/traffic/``; this module turns it and a
seed into requests and drives them against a ``send`` callable.

What repeats and what the seed changes
--------------------------------------
Every length comes from a *quantile grid*: the n values at the
quantiles (i + 0.5) / n of the stated distribution.  The grid is the
same multiset in every run.  An open loop offers exactly
``round(rate * seconds)`` requests in the window, as that many sorted
uniform arrival times (a Poisson process given its count), after a ramp
at the same rate that is sent and not measured.

The order of the lengths and the arrival times are the *schedule*.  A
traffic file that gives a ``schedule_seed`` draws it from that: the mix
is one recorded realisation of its arrival process, replayed in every
run, as a trace is, and ``--seed`` draws only the token ids (and, in
the runner, the weights).  A traffic file without one draws the
schedule from ``--seed``.  Which to take is the mix's choice, and the
reason belongs in its file: with thirty to fifty requests in a window,
arrivals redrawn for every seed put the latencies wherever that draw's
clusters fell (PERF.md, Findings, PR 23); replayed, two runs differ by
what the system does, which is what a bound is for.

Timing
------
An open-loop request is timed from the instant it was *due*, so a
stall that delays later sends counts against them; ``sent - due`` is
reported as the generator's lateness.  A closed-loop request is timed
from when it was sent (its client was waiting for the last answer).
All times are ``time.perf_counter()`` of this process.

Copied and corrected from ``bench.py`` ``open_loop_point`` (evenly
spaced arrivals, TTFT from the submit stamp): see PERF.md section 3.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, Iterator, List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


def length_grid(spec: Dict[str, Any], n: int) -> List[int]:
    """The n-point quantile grid of ``spec``: ``{"dist": "lognormal",
    "median", "sigma", "min", "max"}``, ``{"dist": "loguniform", "min",
    "max"}`` or ``{"dist": "fixed", "value"}``.  Sorted ascending."""
    qs = [(i + 0.5) / n for i in range(n)]
    if spec["dist"] == "lognormal":
        vals = [math.exp(math.log(spec["median"])
                         + spec["sigma"] * _NORMAL.inv_cdf(q)) for q in qs]
    elif spec["dist"] == "loguniform":
        vals = [spec["min"] * (spec["max"] / spec["min"]) ** q for q in qs]
    elif spec["dist"] == "fixed":
        return [int(spec["value"])] * n
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return [int(round(min(max(v, spec["min"]), spec["max"]))) for v in vals]


@dataclasses.dataclass
class Planned:
    """One request before it is sent."""

    idx: int
    prompt: List[int]
    max_new_tokens: int
    due: Optional[float] = None      # seconds from the window's start
    measured: bool = True
    client: int = 0
    doc: int = -1                    # closed loop: which document
    doc_len: int = 0                 # closed loop: its shared prefix
    turn: int = 0                    # closed loop: which question on it


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(1, vocab, n).tolist()


def _seed32(seed: int) -> int:
    return int(seed) % (2**32)


def _schedule_seed(traffic: Dict[str, Any], seed: int) -> int:
    return _seed32(traffic.get("schedule_seed", seed))


def open_loop_plan(traffic: Dict[str, Any], seconds: float, seed: int,
                   vocab: int) -> List[Planned]:
    """Ramp requests (due < 0, not measured) then the window's."""
    rate = float(traffic["rate_per_s"])
    ramp_s = float(traffic.get("ramp_s", 0.0))
    rng = np.random.default_rng([_schedule_seed(traffic, seed), 0xA11])
    trng = np.random.default_rng([_seed32(seed), 0x70C])
    plan: List[Planned] = []
    for phase, span, t_lo in (("ramp", ramp_s, -ramp_s),
                              ("window", float(seconds), 0.0)):
        n = int(round(rate * span))
        if n <= 0:
            continue
        p_lens = rng.permutation(length_grid(traffic["prompt_len"], n))
        o_lens = rng.permutation(length_grid(traffic["output_len"], n))
        dues = np.sort(rng.uniform(t_lo, t_lo + span, n))
        for i in range(n):
            plan.append(Planned(
                idx=len(plan), prompt=_tokens(trng, int(p_lens[i]), vocab),
                max_new_tokens=int(o_lens[i]), due=float(dues[i]),
                measured=(phase == "window")))
    return plan


def closed_loop_stream(traffic: Dict[str, Any], seed: int, vocab: int,
                       client: int) -> Iterator[Planned]:
    """Client ``client``'s endless stream of documents, ``turns``
    questions on each.  Pass k of the stream deals the k-th
    permutation (drawn from the schedule's seed) of the document grid
    round the clients, so the clients together start every grid document
    once per pass; ``seed`` draws the token ids."""
    n_clients = int(traffic["clients"])
    g = int(traffic["grid_size"])
    turns = int(traffic["turns"])
    docs = length_grid(traffic["doc_len"], g)
    sufs = length_grid(traffic["suffix_len"], g * turns)
    outs = length_grid(traffic["output_len"], g * turns)
    idx = 0
    for k in range(10**9):
        rng = np.random.default_rng(
            [_schedule_seed(traffic, seed), 0xD0C, k])
        d_perm = rng.permutation(g)
        s_perm = rng.permutation(g * turns)
        o_perm = rng.permutation(g * turns)
        for j in range(client, g, n_clients):
            doc_id = k * g + int(d_perm[j])
            trng = np.random.default_rng([_seed32(seed), 0x70C, k, j])
            doc = _tokens(trng, docs[int(d_perm[j])], vocab)
            for t in range(turns):
                suf = _tokens(trng, sufs[int(s_perm[j * turns + t])], vocab)
                yield Planned(idx=idx, prompt=doc + suf,
                              max_new_tokens=outs[int(o_perm[j * turns + t])],
                              client=client, doc=doc_id, turn=t,
                              doc_len=len(doc))
                idx += 1


def new_record(p: Planned) -> Dict[str, Any]:
    return {"idx": p.idx, "client": p.client, "doc": p.doc, "turn": p.turn,
            "doc_len": p.doc_len, "due": p.due, "measured": p.measured,
            "prompt_len": len(p.prompt), "max_new_tokens": p.max_new_tokens,
            "sent": None, "first": None, "last": None, "n_out": 0,
            "token_times": [], "tokens_ok": True, "ok": False,
            "error": None, "request_id": None}


def drive_one(p: Planned, rec: Dict[str, Any], send: Callable,
               t0: float, vocab: int) -> None:
    """Send one request and follow it to its end.  Times are seconds
    from ``t0`` (the window's start)."""
    try:
        rec["sent"] = time.perf_counter() - t0
        stream = send(p)
        rec["request_id"] = getattr(stream, "request_id", None)
        times = rec["token_times"]
        for tok in stream:
            times.append(time.perf_counter() - t0)
            if not (isinstance(tok, int) and 0 <= tok < vocab):
                rec["tokens_ok"] = False
        rec["n_out"] = len(times)
        if times:
            rec["first"], rec["last"] = times[0], times[-1]
        rec["ok"] = (rec["tokens_ok"] and rec["n_out"] == p.max_new_tokens)
        if not rec["ok"] and rec["error"] is None:
            rec["error"] = (f"returned {rec['n_out']} of "
                            f"{p.max_new_tokens} tokens")
    except BaseException as e:  # a refused or failed request is a failure
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
        if not isinstance(e, Exception):
            raise


def run_open_loop(plan: List[Planned], send: Callable, vocab: int, *,
                  max_inflight: int = 128, drain_timeout_s: float = 120.0,
                  on_window_start: Optional[Callable[[], None]] = None,
                  ) -> Dict[str, Any]:
    """Send every request of ``plan`` at its due time, each from a pool
    thread that follows it to its end.  Returns the records and the
    clock reading of the window's start (``t0``)."""
    first_due = min(p.due for p in plan)
    t0 = time.perf_counter() + max(0.0, -first_due) + 0.05  # due 0
    records = [new_record(p) for p in plan]
    futures = []
    started = False
    with ThreadPoolExecutor(max_workers=max_inflight,
                            thread_name_prefix="loadgen") as pool:
        for p, rec in zip(plan, records):
            if not started and p.due >= 0.0:
                _sleep_until(t0)
                started = True
                if on_window_start is not None:
                    on_window_start()
            _sleep_until(t0 + p.due)
            futures.append(pool.submit(drive_one, p, rec, send, t0, vocab))
        deadline = time.perf_counter() + drain_timeout_s
        for f in futures:
            try:
                f.result(timeout=max(0.0, deadline - time.perf_counter()))
            except Exception:
                pass  # the record says what happened (or stays not ok)
        for rec in records:
            if not rec["ok"] and rec["error"] is None:
                rec["error"] = "not finished when the drain limit passed"
    return {"records": records, "t0": t0}


def run_closed_loop(streams: List[Iterator[Planned]], send: Callable,
                    vocab: int, *, ramp_s: float, seconds: float,
                    drain_timeout_s: float = 120.0,
                    on_window_start: Optional[Callable[[], None]] = None,
                    ) -> Dict[str, Any]:
    """One thread per client; each sends its next request when the last
    was answered, and none after the window's end.  A request counts as
    measured when it was sent inside the window."""
    t0 = time.perf_counter() + ramp_s + 0.05
    records: List[Dict[str, Any]] = []
    lock = threading.Lock()

    def client(stream: Iterator[Planned]) -> None:
        for p in stream:
            now = time.perf_counter() - t0
            if now >= seconds:
                return
            rec = new_record(p)
            rec["measured"] = now >= 0.0
            with lock:
                records.append(rec)
            drive_one(p, rec, send, t0, vocab)

    threads = [threading.Thread(target=client, args=(s,), daemon=True,
                                name=f"loadgen-client-{i}")
               for i, s in enumerate(streams)]
    for t in threads:
        t.start()
    _sleep_until(t0)
    if on_window_start is not None:
        on_window_start()
    deadline = t0 + seconds + drain_timeout_s
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.perf_counter()))
    with lock:
        out = list(records)
    for rec in out:
        if not rec["ok"] and rec["error"] is None:
            rec["error"] = "not finished when the drain limit passed"
    return {"records": out, "t0": t0}


def _sleep_until(t: float) -> None:
    while True:
        d = t - time.perf_counter()
        if d <= 0:
            return
        time.sleep(d)
