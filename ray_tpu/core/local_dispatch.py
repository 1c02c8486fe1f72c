"""Daemon-local task dispatch over a synced cluster resource view.

Parity: the reference's Ray Syncer + raylet-local scheduling.  There,
raylets own their node's resources, gossip resource views through the
GCS (ray: src/ray/common/ray_syncer/ray_syncer.h:86), and a worker's
nested submission is scheduled by its LOCAL raylet — the centralized
control plane is off the task hot path.  Here the head owns the
authoritative ledgers (single-writer), so the sync direction inverts:
the head broadcasts seq-versioned per-node availability to every
daemon (`resource_view` casts from NodeServer), and each daemon runs a
LOCAL fast path for its workers' nested submissions against its own
slice of that view:

  worker submit_task → daemon eligibility check → lease a LOCAL worker
  → push → seal locally, with one fire-and-forget `local_task` cast to
  the head (ordered ahead of every later op on the same channel) that
  registers lineage, return-oid pins, arg pins, events, and the ledger
  debit.  The head round-trip leaves the submit critical path.

Consistency model (the reference's, deliberately): scheduling decisions
use an eventually-consistent view, bounded overcommit within one sync
period; the hard limits are enforced by the daemon's worker-pool cap
and the unacked-delta ledger below.  Ordering makes the bookkeeping
race-free: the `local_task` cast is sent on the daemon→head channel
BEFORE the submit reply, so the head registers pins before it can see
any ref-drop or get for the minted ids.

Failure model: an app exception seals an error on the return oids (cast
`local_task_failed`, retryable=False); a local worker crash hands the
task BACK to the head (retryable=True) which re-enqueues it through the
normal scheduler — the head hydrates fn/args from the cast's spec, so
retries and daemon-death recovery reuse the existing retry/lineage
machinery (`runtime.finish_external_task`).
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict, List, Optional

from ray_tpu.utils.ids import ActorID, ObjectID, TaskID


class UnackedLedger:
    """Local resource deltas not yet reflected in the head's view.

    Every local dispatch debits, every completion credits; each delta
    carries a monotonically increasing ``lseq`` that rides its cast to
    the head.  The head's view-sync echoes the highest lseq it has
    applied for this node, at which point the delta is part of the
    synced availability and is dropped here.  Effective availability =
    synced - sum(unacked debits) + sum(unacked credits).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._lseq = 0
        # (lseq, sign, demand) — sign -1 debit, +1 credit.
        self._deltas: "collections.deque" = collections.deque()

    def next_delta(self, sign: int, demand: Dict[str, float]) -> int:
        with self._lock:
            self._lseq += 1
            self._deltas.append((self._lseq, sign, demand))
            return self._lseq

    def ack(self, lseq: int) -> None:
        with self._lock:
            while self._deltas and self._deltas[0][0] <= lseq:
                self._deltas.popleft()

    def effective(self, synced: Dict[str, float]) -> Dict[str, float]:
        out = dict(synced)
        with self._lock:
            for _, sign, demand in self._deltas:
                for k, v in demand.items():
                    out[k] = out.get(k, 0.0) + sign * v
        return out

    def reset(self) -> None:
        with self._lock:
            self._deltas.clear()


class LocalDispatcher:
    """Per-daemon fast path for nested task submissions."""

    def __init__(self, daemon):
        self.d = daemon
        self.ledger = UnackedLedger()
        self._view_lock = threading.Lock()
        self._view: Optional[Dict[str, Dict[str, Dict[str, float]]]] = None
        self._view_ts = 0.0
        self._inflight_lock = threading.Lock()
        # task_bin -> {"wh": worker handle or None, "cancelled": bool}
        self._inflight: Dict[bytes, Dict[str, Any]] = {}
        from ray_tpu.core.runtime import _CachedThreadPool

        self._exec = _CachedThreadPool(name="local-dispatch")
        self._last_reclaim = 0.0
        self.stats_counters = {"dispatched": 0, "forwarded": 0,
                               "completed": 0, "failed": 0,
                               "returned_to_head": 0}

    # -- view sync ---------------------------------------------------------

    def on_view(self, nodes: Dict[str, Dict[str, Dict[str, float]]],
                ack_lseq: int) -> None:
        with self._view_lock:
            self._view = nodes
            self._view_ts = time.monotonic()
        self.ledger.ack(ack_lseq)

    def view_fresh(self, max_age: float = 5.0) -> bool:
        with self._view_lock:
            return (self._view is not None
                    and time.monotonic() - self._view_ts <= max_age)

    def cluster_available(self) -> Optional[Dict[str, float]]:
        """Cluster-wide availability from the synced view (serves a
        worker's ``available_resources()`` without a head RPC); None
        when the view is stale."""
        if not self.view_fresh():
            return None
        with self._view_lock:
            nodes = dict(self._view)
        total: Dict[str, float] = {}
        for hexid, entry in nodes.items():
            avail = entry.get("available") or {}
            if hexid == self.d.node_hex:
                avail = self.ledger.effective(avail)
            for k, v in avail.items():
                total[k] = total.get(k, 0.0) + max(0.0, v)
        return total

    def reset(self) -> None:
        """Head restart: in-flight local tasks died with the previous
        epoch's workers (the rejoin contract kills them), their casts
        are gone with the old channel — drop all local state and stay
        off the fast path until the new head's first view sync."""
        with self._view_lock:
            self._view = None
        self.ledger.reset()
        with self._inflight_lock:
            self._inflight.clear()

    # -- submission --------------------------------------------------------

    def maybe_submit(self, msg: Dict[str, Any],
                     worker_chan) -> Optional[Dict[str, Any]]:
        """Local fast path for one worker ``submit_task`` op.  Returns
        the submit reply, or None to forward to the head (ineligible,
        stale view, no capacity — the head path is always correct)."""
        opts = msg.get("options")
        deps = msg.get("deps")
        if opts is None or deps is None:
            return None  # pre-deps client shape: head path
        if (opts.num_returns == "streaming" or opts.runtime_env
                or opts.effective_strategy() != "DEFAULT"):
            return None
        if not self.view_fresh():
            return None
        demand = opts.resource_demand()
        if demand.get("TPU"):
            return None  # chip leases are the head path's (one owner)
        with self._view_lock:
            mine = (self._view or {}).get(self.d.node_hex)
        if mine is None:
            return None
        avail = self.ledger.effective(mine.get("available") or {})
        for k, v in demand.items():
            if v > 0 and avail.get(k, 0.0) < v:
                self.stats_counters["forwarded"] += 1
                return None
        # Dependencies must be locally sealed: the head path owns
        # parking/wakeup; a blocked local worker would be a wasted slot.
        store = self.d.store
        for b in deps:
            if not store.contains(ObjectID(b)):
                self.stats_counters["forwarded"] += 1
                return None
        wh = self.d.pool.lease(dedicated=False, block=False)
        if wh is None:
            # The pool is often exhausted not by running tasks but by
            # the HEAD's cached idle leases (lease pipelining keeps
            # released workers head-leased for remote_lease_idle_s).
            # Ask it to return the idle ones so the NEXT local submit
            # finds capacity; rate-limited to one nudge per 100 ms.
            now = time.monotonic()
            if now - self._last_reclaim > 0.1:
                self._last_reclaim = now
                self.d.head.cast("reclaim_leases")
            self.stats_counters["forwarded"] += 1
            return None
        self.d._hook_death(wh)

        task_id = TaskID.of(ActorID.nil_for_job(self.d.job_id))
        n_returns = opts.num_returns
        return_bins = [
            ObjectID.for_task_return(task_id, i).binary()
            for i in range(n_returns)
        ]
        from ray_tpu.core.worker_pool import _wkey

        submit_key = self.d._key_prefix + _wkey(worker_chan)
        lseq = self.ledger.next_delta(-1, demand)
        try:
            # MUST precede the reply: same-channel FIFO guarantees the
            # head pins returns/args before any later ref-drop or get.
            self.d.head.cast(
                "local_task", task=task_id.binary(), returns=return_bins,
                spec=msg["spec"], options=opts, deps=deps,
                pins=msg.get("pins") or [], demand=demand,
                wkey=submit_key, trace_ctx=msg.get("trace_ctx"),
                lseq=lseq,
            )
        except Exception:
            self.ledger.ack(lseq)  # drop the delta; nothing registered
            self.d.pool.release(wh)
            return None
        with self._inflight_lock:
            self._inflight[task_id.binary()] = {"wh": wh,
                                                "cancelled": False}
        self.stats_counters["dispatched"] += 1
        self._exec.submit(
            lambda: self._run(task_id, wh, msg, return_bins, demand))
        return {"oids": return_bins}

    # -- execution ---------------------------------------------------------

    def _run(self, task_id: TaskID, wh, msg: Dict[str, Any],
             return_bins: List[bytes], demand: Dict[str, float]) -> None:
        from ray_tpu.core.exceptions import WorkerDiedError
        from ray_tpu.core.wire import ChannelClosedError

        opts = msg["options"]
        task_bin = task_id.binary()
        rep = None
        err: Optional[BaseException] = None
        retryable = False
        try:
            rep = wh.call(
                "task", spec=msg["spec"], name=opts.name or "nested",
                fn_hash=None, fn_blob=None, streaming=False,
                task=task_bin, num_returns=opts.num_returns,
                returns=return_bins, env=None,
                trace_ctx=msg.get("trace_ctx"),
            )
        except (WorkerDiedError, ChannelClosedError) as e:
            # Infra failure: hand the task back to the head, which
            # re-enqueues through the normal scheduler (any node).
            err, retryable = e, True
        except BaseException as e:
            err, retryable = e, False  # app exception → seal error
        finally:
            try:
                self.d.pool.release(wh)
            except Exception:
                pass
            with self._inflight_lock:
                entry = self._inflight.pop(task_bin, None)
        lseq = self.ledger.next_delta(+1, demand)
        if rep is not None:
            # Local store index first (authority for peer pulls and
            # local gets), then the owner-side seal at the head.
            for oid_bin, (kind, payload) in zip(return_bins,
                                                rep.get("results") or ()):
                if kind == "shm":
                    self.d.store.mark_shm_sealed(ObjectID(oid_bin), payload)
            self.stats_counters["completed"] += 1
            self.d.head.cast("local_task_done", task=task_bin,
                             returns=return_bins, rep=rep,
                             exec_wkey=self.d._worker_key(wh), lseq=lseq)
            return
        if entry is not None and entry.get("cancelled"):
            retryable = False  # cancelled tasks never retry
        if retryable:
            self.stats_counters["returned_to_head"] += 1
        else:
            self.stats_counters["failed"] += 1
        self.d.head.cast("local_task_failed", task=task_bin,
                         returns=return_bins, error=err,
                         retryable=retryable, lseq=lseq)

    # -- cancellation ------------------------------------------------------

    def cancel(self, task_bin: bytes, force: bool) -> None:
        with self._inflight_lock:
            entry = self._inflight.get(task_bin)
            if entry is None:
                return
            entry["cancelled"] = True
            wh = entry.get("wh")
        if wh is None:
            return
        try:
            if force:
                wh.terminate(graceful=False)
            else:
                wh.call("cancel", task=task_bin)
        except Exception:
            pass

    def stats(self) -> Dict[str, Any]:
        with self._inflight_lock:
            inflight = len(self._inflight)
        return {**self.stats_counters, "inflight": inflight,
                "view_fresh": self.view_fresh()}
