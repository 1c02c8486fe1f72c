"""Per-node daemon + head-side node server: the multi-host runtime.

Parity: the reference's raylet/GCS split — a head process hosts the
control plane (here the existing ``LocalRuntime``) and every other
machine runs a node daemon that registers over TCP and then owns a
local worker pool, shared-memory arena, and spill directory (ray:
src/ray/raylet/main.cc:81 raylet startup, gcs/gcs_server/gcs_server.h:79
node registration, protobuf/node_manager.proto:363 the raylet RPC
surface).  Scheduling stays centralized at the head (one cluster view);
dispatch to a remote node rides the daemon's channel, and the object
plane does chunked node-to-node pulls with owner-recorded locations
(src/ray/object_manager/object_manager.h:117, pull_manager.h:52,
push_manager.h:30, ownership_based_object_directory.cc).

Wire security matches client mode: set ``RAYTPU_CLUSTER_TOKEN`` and
every join/peer connection must pass the HMAC challenge before the
first pickle frame is parsed (frames are cloudpickle — the trust model
is the reference's: anyone who can speak the protocol owns the
cluster).

Start a head:     ``ray_tpu start --head --port 6380``
Join a machine:   ``ray_tpu start --address HOST:6380``
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import socket
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.core.wire import ChannelClosedError, MsgChannel
from ray_tpu.utils.ids import JobID, NodeID, ObjectID

PULL_CHUNK = 8 << 20  # 8 MiB per pull RPC (chunked object transfer)


def _cluster_token(token: Optional[str]) -> Optional[str]:
    return (token if token is not None
            else os.environ.get("RAYTPU_CLUSTER_TOKEN"))


def _pull_bytes(call, oid_bin: bytes, size: int) -> bytes:
    """Client side of the chunked pull protocol: fetch ``size`` framed
    bytes of one object through ``call`` (a channel-call closure)."""
    if size <= PULL_CHUNK:
        data = call("pull", oid=oid_bin, off=0, len=size)
        if len(data) != size:
            raise OSError(f"truncated pull: {len(data)}/{size}")
        return data
    parts = []
    off = 0
    while off < size:
        chunk = call("pull", oid=oid_bin, off=off,
                     len=min(PULL_CHUNK, size - off))
        if not chunk:
            raise OSError(f"truncated pull at {off}/{size}")
        parts.append(chunk)
        off += len(chunk)
    return b"".join(parts)


# ---------------------------------------------------------------------------
# Head side
# ---------------------------------------------------------------------------


class RemoteWorkerHandle:
    """Head-side handle for one worker process living on a remote node
    daemon — the same lease/call/terminate surface as
    ``worker_pool.WorkerHandle`` so tasks and actor shells dispatch
    identically to local and remote workers.

    Calls prefer a DIRECT channel to the worker's own listener (parity:
    the owner's per-worker gRPC channel, direct_task_transport.cc →
    PushTask) — the daemon then only handles leasing and the object
    plane instead of re-framing every task, which caps a node's task
    rate at one Python process's pickle throughput.  Falls back to the
    daemon proxy path when the direct dial fails."""

    def __init__(self, agent: "RemoteNodeAgent", wid: str, key: str,
                 pid: int, wport: Optional[int] = None):
        self.agent = agent
        self.wid = wid
        self.ref_key = key      # borrower identity at the head
        self.pid = pid
        self.wport = wport
        self.node_hex = agent.node_hex
        self.dead = False
        self.dedicated = False
        self.tpu_chips = 0  # chips bound to the process (0 = off them)
        self.on_death = None
        self._direct: Optional[MsgChannel] = None
        self._direct_retry_at = 0.0
        self._direct_lock = threading.Lock()
        # chan attr parity with WorkerHandle (some callers key on it).
        self.chan = agent.chan

    def _direct_chan(self) -> Optional[MsgChannel]:
        with self._direct_lock:
            ch = self._direct
            if ch is not None and not ch.closed:
                return ch
            node = self.agent._node
            if not self.wport or node is None or not node.addr:
                return None
            # Dial failures back off instead of latching: the first
            # call can race the worker's bootstrap (its accept loop
            # starts after runtime construction), and a permanent
            # downgrade to the proxy path would silently cost the 15x
            # this transport exists for.
            now = time.monotonic()
            if now < self._direct_retry_at:
                return None
            from ray_tpu.util.client.common import client_handshake

            try:
                sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
                sock.settimeout(10.0)
                sock.connect((node.addr[0] or "127.0.0.1", self.wport))
                client_handshake(
                    sock, _cluster_token(None) or None)
                sock.settimeout(None)
            except Exception:
                self._direct_retry_at = now + 5.0
                return None
            ch = MsgChannel(sock, lambda c, m: None,
                            name=f"direct-{self.wid[:8]}").start()
            self._direct = ch
            return ch

    def close_direct(self) -> None:
        """Drop the direct channel (socket + reader thread) — required
        whenever the head forgets a handle while the worker lives on."""
        with self._direct_lock:
            ch = self._direct
            self._direct = None
        if ch is not None:
            ch.close()

    def call(self, op: str, rpc_timeout: Optional[float] = None,
             **payload):
        from ray_tpu.core.exceptions import WorkerDiedError

        direct = self._direct_chan()
        if direct is not None:
            try:
                return direct.call(op, rpc_timeout=rpc_timeout, **payload)
            except ChannelClosedError:
                # The worker's own channel dropping means the worker is
                # gone (same contract as a local AF_UNIX close).
                self.dead = True
                raise WorkerDiedError(
                    f"worker {self.wid[:8]} connection lost") from None
            except WorkerDiedError:
                self.dead = True
                raise
        try:
            return self.agent.chan.call(
                "wcall", rpc_timeout=rpc_timeout,
                wid=self.wid, wop=op, pl=payload,
            )
        except ChannelClosedError as e:
            # The daemon itself died: every worker it hosted is gone.
            self.dead = True
            raise WorkerDiedError(
                f"node {self.node_hex[:12]} daemon died: {e}") from None
        except WorkerDiedError:
            self.dead = True
            raise

    def terminate(self, graceful: bool = True) -> None:
        self.dead = True
        self.close_direct()
        self.agent.chan.cast("kill_worker", wid=self.wid,
                             graceful=graceful)
        self.agent._forget(self.wid)


class RemoteNodeAgent:
    """Head-side handle for one joined node daemon: leases workers,
    pulls objects, frees remote copies (parity: the raylet client the
    GCS/owner holds per node).

    Lease pipelining (parity: OnWorkerIdle pushing queued tasks onto an
    already-leased worker, direct_task_transport.cc:191): released
    non-dedicated workers go into a head-side free list instead of a
    release round trip, so the next task on this node dispatches with
    ONE wcall instead of lease + release traffic — measured 43 ms →
    sub-ms per task, because a release cast racing the next lease
    request used to spawn a fresh worker process nearly every cycle.
    Surplus leases return to the daemon after ``remote_lease_idle_s``."""

    local_lseq = 0  # highest applied local-dispatch delta (view sync ack)

    def __init__(self, chan: MsgChannel, node_hex: str):
        self.chan = chan
        self.node_hex = node_hex
        self._rt = None
        self._node = None
        self._lock = threading.Lock()
        self._leased: Dict[str, RemoteWorkerHandle] = {}
        self._free: List[RemoteWorkerHandle] = []
        # FIFO of parked lease() callers: a freed worker is handed to
        # exactly ONE waiter ([event, slot] pairs) — notify_all here
        # would wake every queued task per release (thundering herd; at
        # a 5k-task burst that herd WAS the throughput ceiling).
        self._waiters: "collections.deque" = collections.deque()
        self._inflight_leases = 0
        # After a busy (at-cap) lease reply, don't re-probe the daemon
        # until this time — tasks ride worker handoffs meanwhile.
        self._busy_until = 0.0
        self._closed = False

    def bind(self, rt, node) -> None:
        self._rt = rt
        self._node = node

    # -- worker leasing (same surface as WorkerPool) -----------------------

    def lease(self, dedicated: bool = False,
              tpu_chips: int = 0) -> RemoteWorkerHandle:
        """Free-listed lease with bounded in-flight lease RPCs: a burst
        of N tasks must not turn into N concurrent lease requests (and
        N spawn attempts) at the daemon — excess requesters park in a
        FIFO and are handed a freed worker directly (parity: bounded
        pending lease requests + OnWorkerIdle pushing onto released
        workers, direct_task_transport.cc:191).  A ``tpu_chips`` lease
        goes straight to the daemon: free-listed workers were started
        off the chip (WorkerPool.lease)."""
        from ray_tpu.utils.config import get_config

        if tpu_chips:
            return self._adopt(
                self.chan.call("lease", dedicated=dedicated,
                               tpu_chips=tpu_chips),
                dedicated, tpu_chips)

        cfg = get_config()
        max_inflight = max(
            1, cfg.max_pending_lease_requests_per_scheduling_class)
        deadline = time.monotonic() + cfg.worker_lease_timeout_s
        while True:
            waiter = None
            past_deadline = time.monotonic() >= deadline
            with self._lock:
                while self._free:
                    wh = self._free.pop()
                    if not wh.dead:
                        wh.dedicated = dedicated
                        return wh
                if self._closed:
                    raise ChannelClosedError(
                        f"node {self.node_hex[:12]}: agent closed")
                if (dedicated or past_deadline
                        or (self._inflight_leases < max_inflight
                            and time.monotonic() >= self._busy_until)):
                    self._inflight_leases += 1
                else:
                    waiter = [threading.Event(), None]
                    self._waiters.append(waiter)
            if waiter is not None:
                # Long park: grants wake us directly; the timeout only
                # backstops the deadline fallback (a short poll here
                # becomes a time-distributed thundering herd at 5k
                # queued tasks).
                waiter[0].wait(min(10.0, max(
                    0.05, deadline - time.monotonic())))
                with self._lock:
                    wh = waiter[1]
                    if wh is None:
                        # Spurious/timeout wake: withdraw and retry
                        # (a grant racing this withdraw lands in slot
                        # 1 before the remove).
                        try:
                            self._waiters.remove(waiter)
                        except ValueError:
                            wh = waiter[1]  # granted concurrently
                if wh is not None:
                    if wh.dead:
                        continue
                    wh.dedicated = dedicated
                    return wh
                continue
            try:
                # Non-blocking past the daemon's cap until OUR deadline:
                # a busy reply parks the task for handoff instead of
                # pinning a daemon handler thread for its full timeout.
                rep = self.chan.call("lease", dedicated=dedicated,
                                     block=past_deadline)
            finally:
                with self._lock:
                    self._inflight_leases -= 1
            if rep.get("busy"):
                with self._lock:
                    self._busy_until = time.monotonic() + 0.5
                continue
            return self._adopt(rep, dedicated)

    def _adopt(self, rep: Dict[str, Any], dedicated: bool,
               tpu_chips: int = 0) -> RemoteWorkerHandle:
        wh = RemoteWorkerHandle(self, rep["wid"], rep["key"], rep["pid"],
                                wport=rep.get("wport"))
        wh.dedicated = dedicated
        wh.tpu_chips = tpu_chips
        with self._lock:
            self._leased[wh.wid] = wh
        return wh

    def release(self, wh: RemoteWorkerHandle) -> None:
        # A chip lease is never cached head-side: it goes back to the
        # daemon's pool, whose release ends the process (one owner).
        if not wh.dead and not wh.dedicated and not wh.tpu_chips:
            with self._lock:
                if not self._closed:
                    # Hand the worker straight to the oldest parked
                    # lease; cache it only when nobody is waiting.
                    while self._waiters:
                        waiter = self._waiters.popleft()
                        waiter[1] = wh
                        waiter[0].set()
                        return
                    wh.idle_since = time.monotonic()
                    self._free.append(wh)
                    return
        self._forget(wh.wid)
        wh.close_direct()
        if not wh.dead and not wh.dedicated:
            self.chan.cast("release_worker", wid=wh.wid)

    def reap_idle_leases(self, idle_s: float) -> None:
        """Return leases idle longer than ``idle_s`` to the daemon (so
        held leases don't pin the node's worker pool forever)."""
        now = time.monotonic()
        with self._lock:
            keep, surplus = [], []
            for wh in self._free:
                if (not wh.dead
                        and now - getattr(wh, "idle_since", now) >= idle_s):
                    surplus.append(wh)
                else:
                    keep.append(wh)
            self._free = keep
            for wh in surplus:
                self._leased.pop(wh.wid, None)
        for wh in surplus:
            wh.close_direct()  # the worker lives on; our socket must not
            self.chan.cast("release_worker", wid=wh.wid)

    def _forget(self, wid: str) -> None:
        with self._lock:
            self._leased.pop(wid, None)

    def worker_gone(self, wid: str) -> None:
        """Daemon reported one of its workers died."""
        with self._lock:
            wh = self._leased.pop(wid, None)
            if wh is not None and wh in self._free:
                self._free.remove(wh)
            if wh is not None and self._waiters:
                # Lost capacity: wake one parked lease so it re-probes
                # (the daemon can now spawn a replacement).
                waiter = self._waiters.popleft()
                waiter[0].set()
        if wh is not None:
            wh.dead = True
            wh.close_direct()
            cb = wh.on_death
            if cb is not None:
                try:
                    cb()
                except Exception:
                    pass

    # -- object plane ------------------------------------------------------

    def pull(self, oid: ObjectID, size: int) -> bytes:
        return _pull_bytes(self.chan.call, oid.binary(), size)

    def free(self, oid_bins: List[bytes]) -> None:
        self.chan.cast("free", oids=oid_bins)

    # -- lifecycle ---------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        return self.chan.call("stats")

    def shutdown_daemon(self) -> None:
        self._closed = True
        self.chan.cast("shutdown")
        self.chan.close()

    def close(self) -> None:
        self._closed = True
        self.chan.close()
        # Every leased worker died with the daemon.
        with self._lock:
            leased = list(self._leased.values())
            self._leased.clear()
            self._free.clear()
            waiters = list(self._waiters)
            self._waiters.clear()
        for waiter in waiters:
            waiter[0].set()  # parked leases wake, see closed, raise
        for wh in leased:
            wh.dead = True
            wh.close_direct()
            cb = wh.on_death
            if cb is not None:
                try:
                    cb()
                except Exception:
                    pass


class NodeServer:
    """The head's TCP join endpoint: node daemons register here and
    stay connected for their lifetime (parity: GcsServer's node
    registration + the per-node raylet channel)."""

    def __init__(self, runtime, host: Optional[str] = None, port: int = 0,
                 token: Optional[str] = None):
        self._rt = runtime
        self._token = token
        if host is None:
            # Non-loopback binds require the HMAC token — frames are
            # cloudpickle, so an open port is arbitrary code execution
            # (same rule as client mode's TRUST BOUNDARY note).
            host = ("0.0.0.0" if _cluster_token(token) else "127.0.0.1")
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind((host, port))
        self._listener.listen(64)
        self.port = self._listener.getsockname()[1]
        self._closed = False
        threading.Thread(target=self._accept_loop, name="node-accept",
                         daemon=True).start()
        from ray_tpu.utils.config import get_config

        if get_config().health_check_period_s > 0:
            threading.Thread(target=self._health_loop, daemon=True,
                             name="node-health").start()
        if get_config().resource_view_sync_period_s > 0:
            threading.Thread(target=self._view_sync_loop, daemon=True,
                             name="node-view-sync").start()

    @property
    def address(self) -> str:
        return f"{socket.gethostname()}:{self.port}"

    def _accept_loop(self) -> None:
        while not self._closed:
            try:
                conn, peer = self._listener.accept()
            except OSError:
                return
            threading.Thread(target=self._register, args=(conn, peer),
                             daemon=True, name="node-register").start()

    def _register(self, conn: socket.socket, peer) -> None:
        import cloudpickle

        from ray_tpu.protocol import Frame, JoinReply
        from ray_tpu.util.client.common import (
            recv_msg,
            send_frame,
            server_handshake,
        )

        token = (self._token if self._token is not None
                 else os.environ.get("RAYTPU_CLUSTER_TOKEN"))
        conn.settimeout(10.0)
        try:
            if not server_handshake(conn, token or None):
                conn.close()
                return
            hello = recv_msg(conn)
            if hello.get("op") != "register":
                conn.close()
                return
            conn.settimeout(None)
        except Exception:
            conn.close()
            return
        rt = self._rt

        def handler(chan, msg):
            return self._handle(agent, chan, msg)

        # Bookkeeping ops whose relative order IS the protocol: a local
        # dispatch's register must be processed before its completion
        # and before any later ref-drop from the submitting worker —
        # the concurrent handler pool would reorder them (wire.py
        # serial_ops runs these on a per-channel FIFO lane).
        chan = MsgChannel(
            conn, handler, name=f"node-{peer[0]}",
            serial_ops=frozenset({
                "local_task", "local_task_done", "local_task_failed",
                "ref", "worker_gone",
            }))
        agent = RemoteNodeAgent(chan, "")
        # Register BEFORE welcome: the daemon's first forwarded op must
        # find the node present.
        addr = hello.get("addr") or (peer[0], 0)
        # The daemon advertises a port; trust the observed source host
        # over a default advertise host (NAT-less clusters).
        if addr[0] in ("", "0.0.0.0"):
            addr = (peer[0], addr[1])
        reset_workers = False
        if hello.get("node_id"):
            # Rejoin: the daemon was already a member (this head
            # restarted, or its channel blipped).  The runtime decides
            # whether the old identity is still usable.
            node_id, accepted = rt.rejoin_remote_node(
                agent, hello["node_id"], hello["resources"],
                hello.get("labels"), addr, hello.get("objects") or [],
            )
            if not accepted:
                try:
                    send_frame(conn, Frame(
                        kind=Frame.REP,
                        join_reply=JoinReply(ok=False, stale=True)))
                except Exception:
                    pass
                chan.close()
                return
            # The new head has no record of the daemon's previous
            # leases/borrows — previous-epoch workers are leaked.
            reset_workers = True
        else:
            node_id = rt.register_remote_node(
                agent, hello["resources"], hello.get("labels"), addr
            )
        agent.node_hex = node_id.hex()
        chan.on_close = lambda: self._node_lost(node_id)
        from ray_tpu.utils.config import get_config

        try:
            send_frame(conn, Frame(kind=Frame.REP, join_reply=JoinReply(
                ok=True,
                node_id=node_id.binary(),
                job_id=rt.job_id.hex(),
                config_pickle=cloudpickle.dumps(get_config().snapshot()),
                sys_path=list(sys.path),
                cwd=os.getcwd(),
                reset_workers=reset_workers,
            )))
        except Exception:
            chan.close()
            rt.kill_node(node_id)
            return
        chan.start()

    def _node_lost(self, node_id: NodeID) -> None:
        if not self._closed:
            self._rt.kill_node(node_id)

    def _handle(self, agent: RemoteNodeAgent, chan: MsgChannel,
                msg: Dict[str, Any]) -> Any:
        """Daemon → head ops: forwarded worker control ops (with the
        worker's borrower key) plus daemon-specific notifications."""
        from ray_tpu.core.worker_pool import handle_control_op

        op = msg["op"]
        if op == "worker_gone":
            self._rt.refs.drop_worker(msg["wkey"])
            agent.worker_gone(msg.get("wid", ""))
            return None
        if op == "log_batch":
            self._rt.ingest_logs(agent.node_hex or "?", msg["file"],
                                 msg.get("lines") or [],
                                 truncated=msg.get("truncated", False))
            return None
        if op == "heartbeat":
            return time.time()
        if op == "reclaim_leases":
            # The daemon's local fast path found its pool exhausted by
            # our cached idle leases — return them now instead of
            # waiting out remote_lease_idle_s.
            agent.reap_idle_leases(0.0)
            return None
        # Daemon-local dispatch bookkeeping (core/local_dispatch.py):
        # ordered casts; the lseq rides back on the next view sync so
        # the daemon can drop its unacked ledger deltas.
        if op == "local_task":
            self._rt.register_external_task(
                msg["task"], msg["returns"], msg["spec"], msg["options"],
                msg.get("deps") or [], msg.get("demand") or {},
                msg["wkey"], agent.node_hex, pins=msg.get("pins"))
            agent.local_lseq = max(agent.local_lseq, msg.get("lseq", 0))
            return None
        if op == "local_task_done":
            self._rt.finish_external_task(
                msg["task"], msg["returns"], msg["rep"],
                msg.get("exec_wkey"), agent.node_hex)
            agent.local_lseq = max(agent.local_lseq, msg.get("lseq", 0))
            return None
        if op == "local_task_failed":
            self._rt.finish_external_task(
                msg["task"], msg["returns"], None, None, agent.node_hex,
                error=msg.get("error"),
                retryable=bool(msg.get("retryable")))
            agent.local_lseq = max(agent.local_lseq, msg.get("lseq", 0))
            return None
        key = msg.get("wkey") or f"{agent.node_hex[:12]}/daemon"
        return handle_control_op(self._rt, key, msg,
                                 node_hex=agent.node_hex)

    def _health_loop(self) -> None:
        from ray_tpu.utils.config import get_config

        cfg = get_config()
        period = cfg.health_check_period_s
        window = period * max(1, cfg.health_check_failure_threshold)
        while not self._closed:
            time.sleep(period)
            with self._rt._lock:
                agents = [n.agent for n in self._rt._nodes.values()
                          if n.alive and n.agent is not None]
            for agent in agents:
                agent.reap_idle_leases(cfg.remote_lease_idle_s)
                threading.Thread(target=self._probe, args=(agent, window),
                                 daemon=True, name="node-probe").start()

    def _view_sync_loop(self) -> None:
        """Broadcast the cluster resource view to every daemon (parity:
        the Ray Syncer's periodic resource broadcast,
        ray_syncer.h:86).  Each cast carries the receiving daemon's
        highest applied local-dispatch lseq so it can drop unacked
        ledger deltas; daemons schedule nested submissions against
        this view without a head round-trip."""
        from ray_tpu.utils.config import get_config

        period = get_config().resource_view_sync_period_s
        while not self._closed:
            time.sleep(period)
            with self._rt._lock:
                agents = [n.agent for n in self._rt._nodes.values()
                          if n.alive and n.agent is not None]
            if not agents:
                continue
            view = self._rt.resource_view()
            for agent in agents:
                agent.chan.cast("resource_view", nodes=view,
                                ack=agent.local_lseq)

    def _probe(self, agent: RemoteNodeAgent, window: float) -> None:
        try:
            agent.chan.call("ping", rpc_timeout=window)
        except TimeoutError:
            # Unresponsive for the whole window → declare the node dead
            # (parity: GcsHealthCheckManager failure_threshold).
            agent.chan.close()  # on_close → kill_node
        except Exception:
            pass

    def close(self) -> None:
        self._closed = True
        try:
            self._listener.close()
        except OSError:
            pass


# ---------------------------------------------------------------------------
# Daemon side
# ---------------------------------------------------------------------------


class _ForwardRefs:
    """Daemon-side stand-in for the runtime's ReferenceCounter: worker
    death forwards the borrower-drop to the head (which owns all
    refcounts).  Keys arrive unprefixed from WorkerHandle._on_close;
    the node prefix is added here so they match what this daemon
    attached to forwarded ops."""

    def __init__(self, daemon: "NodeDaemon"):
        self._daemon = daemon

    def drop_worker(self, wkey: str) -> None:
        self._daemon.head.cast(
            "worker_gone", wkey=self._daemon._key_prefix + wkey, wid="")


class _DaemonRT:
    """The minimal runtime surface DaemonWorkerPool needs."""

    def __init__(self, daemon: "NodeDaemon", store, job_id: JobID):
        self._daemon = daemon
        self.store = store
        self.job_id = job_id
        self.refs = _ForwardRefs(daemon)
        self.log_dir = daemon.log_dir


def make_daemon_pool(daemon: "NodeDaemon", rt_shim: "_DaemonRT"):
    """A WorkerPool (same spawn/registration/health machinery) whose
    worker ops route to the daemon: control-plane ops forward to the
    head with the worker's borrower key; object-plane ops serve from
    the daemon's local store, pulling remote copies on miss."""
    from ray_tpu.core.worker_pool import WorkerPool

    class _Pool(WorkerPool):
        def _handle(self, chan, msg):
            return daemon.handle_worker_op(chan, msg)

    return _Pool(rt_shim)


class _StaleNodeError(ConnectionError):
    """The head rejected a rejoin under the old node identity (it never
    restarted and already declared this node dead)."""


class NodeDaemon:
    """One machine's membership in the cluster: local worker pool +
    local object plane, a channel to the head, and a peer server for
    node-to-node object pulls.

    Head fault tolerance: if the head channel drops, the daemon keeps
    its workers and arena alive and retries the join under its existing
    node id for ``head_reconnect_window_s``, re-advertising its object
    inventory so a restarted head re-pins locations (parity: raylets
    reconnecting to a Redis-recovered GCS, gcs/gcs_client reconnect +
    python/ray/tests/test_gcs_fault_tolerance.py)."""

    def __init__(self, head_addr: Tuple[str, int], *,
                 resources: Dict[str, float],
                 labels: Optional[Dict[str, str]] = None,
                 peer_port: int = 0,
                 advertise_host: str = "",
                 token: Optional[str] = None):
        self._token = _cluster_token(token)
        self._exit = threading.Event()
        self._head_ok = threading.Event()
        self._head_addr = (head_addr[0], int(head_addr[1]))
        self._resources = dict(resources)
        self._labels = dict(labels or {})
        self._advertise_host = advertise_host
        # Peer listener FIRST (its port goes into the register frame).
        # Loopback unless the cluster token authenticates peers (same
        # trust rule as the head's join port).
        self._peer_listener = socket.socket(socket.AF_INET,
                                            socket.SOCK_STREAM)
        self._peer_listener.setsockopt(socket.SOL_SOCKET,
                                       socket.SO_REUSEADDR, 1)
        self._peer_listener.bind(
            ("0.0.0.0" if self._token else "127.0.0.1", peer_port))
        self._peer_listener.listen(64)
        self.peer_port = self._peer_listener.getsockname()[1]

        # Join the head.
        sock, welcome = self._dial_head(rejoin=False)
        self.node_id = NodeID(welcome["node_id"])
        self.node_hex = self.node_id.hex()
        self._key_prefix = self.node_hex[:12] + "/"
        self.job_id = JobID(bytes.fromhex(welcome["job_id"]))
        # Head config first, so store caps / thresholds match the
        # cluster; local env overrides still win (utils/config.py
        # priority: env > snapshot).
        from ray_tpu.utils.config import get_config

        try:
            get_config().update(welcome.get("config") or {})
        except Exception:
            pass
        for p in welcome.get("sys_path") or []:
            if p not in sys.path:
                sys.path.append(p)
        try:
            if welcome.get("cwd"):
                os.chdir(welcome["cwd"])
        except OSError:
            pass

        # Local object plane: own arena + spill dir (parity: per-node
        # plasma + LocalObjectManager).
        from ray_tpu.core.store import LocalObjectStore

        self.store = LocalObjectStore()
        self._pulls: Dict[bytes, threading.Event] = {}
        self._pull_lock = threading.Lock()
        self._peer_chans: Dict[Tuple[str, int], MsgChannel] = {}
        self._peer_lock = threading.Lock()

        # Head channel (wrapped AFTER registration).
        self.head = MsgChannel(sock, self._handle_head_op, name="head",
                               on_close=self._on_head_lost)
        # Local worker pool (spawns ray_tpu.core.worker_main processes
        # that attach THIS daemon's arena).  Worker stdout/stderr land
        # in this node's log dir; the monitor ships complete lines to
        # the head over the channel (parity: per-node log_monitor.py
        # publishing to the GCS log channel).
        from ray_tpu.util.log_monitor import LogMonitor, resolve_log_dir

        self.log_dir = resolve_log_dir()
        self._rt_shim = _DaemonRT(self, self.store, self.job_id)
        self.pool = make_daemon_pool(self, self._rt_shim)
        from ray_tpu.core.local_dispatch import LocalDispatcher

        self.local = LocalDispatcher(self)
        from ray_tpu.utils.config import get_config as _gc

        self._log_monitor = LogMonitor(
            self.log_dir, self._publish_logs,
            _gc().log_monitor_period_s)
        self.head.start()
        self._head_ok.set()
        threading.Thread(target=self._peer_accept_loop, daemon=True,
                         name="peer-accept").start()

    # -- head connection ---------------------------------------------------

    def _dial_head(self, rejoin: bool) -> Tuple[socket.socket,
                                                Dict[str, Any]]:
        """Connect + handshake + register with the head.  A rejoin
        carries the existing node id and the local object inventory so
        a restarted head can re-pin locations."""
        from ray_tpu.protocol import Frame, JoinRequest, ObjectMeta
        from ray_tpu.util.client.common import (
            client_handshake,
            recv_msg,
            send_frame,
        )

        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.settimeout(15.0)
        try:
            sock.connect(self._head_addr)
            client_handshake(sock, self._token or None)
            # Typed join (raytpu.proto JoinRequest): the head parses the
            # registration without executing any pickle.
            join = JoinRequest(
                resources={k: float(v)
                           for k, v in (self._resources or {}).items()},
                labels={k: str(v) for k, v in (self._labels or {}).items()},
                advertise_host=self._advertise_host or "",
                peer_port=self.peer_port,
                pid=os.getpid(),
            )
            if rejoin:
                join.node_id = self.node_id.binary()
                join.objects.extend(
                    ObjectMeta(id=oid, size=size)
                    for oid, size in self.store.inventory())
            send_frame(sock, Frame(kind=Frame.REQ, op="register", join=join))
            welcome = recv_msg(sock)
        except BaseException:
            sock.close()
            raise
        if not welcome.get("ok"):
            sock.close()
            if welcome.get("stale"):
                raise _StaleNodeError(
                    f"head declared node {getattr(self, 'node_hex', '?')[:12]}"
                    " dead; identity not reusable")
            raise ConnectionError(f"head rejected registration: {welcome}")
        sock.settimeout(None)
        return sock, welcome

    # -- lifecycle ---------------------------------------------------------

    def _on_head_lost(self) -> None:
        from ray_tpu.utils.config import get_config

        self._head_ok.clear()
        window = get_config().head_reconnect_window_s
        if self._exit.is_set() or window <= 0:
            # Clean shutdown, or reconnect disabled: pre-FT behavior.
            self._exit.set()
            return
        threading.Thread(target=self._rejoin_loop, args=(window,),
                         daemon=True, name="head-rejoin").start()

    def _rejoin_loop(self, window: float) -> None:
        from ray_tpu.utils.config import get_config

        retry = max(0.05, get_config().head_reconnect_retry_s)
        deadline = time.monotonic() + window
        while not self._exit.is_set() and time.monotonic() < deadline:
            try:
                sock, welcome = self._dial_head(rejoin=True)
            except _StaleNodeError:
                # The head never restarted: it declared this node dead
                # and already recovered its actors/objects elsewhere.
                # Resuming under the old identity would race that
                # recovery — exit; the process manager restarts us as a
                # fresh node.
                break
            except Exception:
                time.sleep(retry)
                continue
            self._adopt_head(sock, welcome)
            return
        self._exit.set()

    def _adopt_head(self, sock: socket.socket,
                    welcome: Dict[str, Any]) -> None:
        """Swap in a fresh head channel after a successful rejoin.
        Workers keep their channels to THIS daemon throughout, so a
        head restart is invisible to the object plane; only the
        control plane pauses (callers block in _head_call)."""
        self.job_id = JobID(bytes.fromhex(welcome["job_id"]))
        self._rt_shim.job_id = self.job_id
        self.head = MsgChannel(sock, self._handle_head_op, name="head",
                               on_close=self._on_head_lost)
        # The new head never saw this epoch's local-dispatch casts:
        # drop view/ledger state and wait for its first sync.
        self.local.reset()
        if welcome.get("reset_workers"):
            self._reset_workers()
        self.head.start()
        self._head_ok.set()

    def _reset_workers(self) -> None:
        """Kill every previous-epoch worker: the restarted head has no
        record of their leases/borrows (its reconcile contract — leaked
        actors die; detached actors re-create from the restored spec)."""
        self.pool.kill_all(graceful=False)

    def _head_call(self, op: str, **payload):
        """head.call for IDEMPOTENT (object-plane read) ops that rides
        out a head restart: while the daemon is rejoining, callers
        block; once the new channel is up, the op retries.  Worker
        control-plane ops must NOT go through here — a mutating op
        whose effect survived via GCS persistence would double-execute
        on replay; those fail fast instead (_forward), and the
        previous-epoch workers die on rejoin anyway (_reset_workers)."""
        while True:
            try:
                return self.head.call(op, **payload)
            except ChannelClosedError:
                if self._exit.is_set():
                    raise
                self._head_ok.wait(1.0)

    def wait(self) -> None:
        self._exit.wait()

    def _publish_logs(self, file: str, lines: List[str],
                      truncated: bool = False) -> None:
        # Best-effort cast: log lines are droppable while the head is
        # away (the local files keep everything).
        self.head.cast("log_batch", file=file, lines=lines,
                       truncated=truncated)

    def shutdown(self) -> None:
        self._exit.set()
        try:
            self.pool.shutdown()
        except Exception:
            pass
        try:
            # AFTER the pool: the final sweep ships what dying workers
            # flushed (best-effort — the head may already be gone).
            self._log_monitor.stop()
        except Exception:
            pass
        try:
            self._peer_listener.close()
        except OSError:
            pass
        with self._peer_lock:
            chans = list(self._peer_chans.values())
            self._peer_chans.clear()
        for ch in chans:
            ch.close()
        self.head.close()
        self.store.close()

    # -- head → daemon ops -------------------------------------------------

    def _handle_head_op(self, chan: MsgChannel, msg: Dict[str, Any]) -> Any:
        op = msg["op"]
        if op == "lease":
            wh = self.pool.lease(dedicated=msg.get("dedicated", False),
                                 block=msg.get("block", True),
                                 tpu_chips=msg.get("tpu_chips", 0))
            if wh is None:
                return {"busy": True}
            self._hook_death(wh)
            return {"wid": wh.wid, "key": self._worker_key(wh),
                    "pid": wh.pid, "wport": getattr(wh, "wport", None)}
        if op == "release_worker":
            wh = self.pool._all.get(msg["wid"])
            if wh is not None:
                wh.dedicated = False
                self.pool.release(wh)
            return None
        if op == "wcall":
            wh = self.pool._all.get(msg["wid"])
            if wh is None or wh.dead:
                from ray_tpu.core.exceptions import WorkerDiedError

                raise WorkerDiedError(f"worker {msg['wid'][:8]} is gone")
            pl = msg.get("pl") or {}
            rep = wh.call(msg["wop"], **pl)
            # Result values the worker wrote into THIS node's arena must
            # enter the local store index (the authority for serving
            # peer pulls / local get_raw) before the head records their
            # location here.
            if isinstance(rep, dict) and rep.get("results"):
                for oid_bin, (kind, payload) in zip(pl.get("returns") or (),
                                                    rep["results"]):
                    if kind == "shm":
                        self.store.mark_shm_sealed(ObjectID(oid_bin),
                                                   payload)
            return rep
        if op == "kill_worker":
            wh = self.pool._all.get(msg["wid"])
            if wh is not None:
                wh.terminate(graceful=msg.get("graceful", True))
            return None
        if op == "free":
            for b in msg["oids"]:
                self.store.release(ObjectID(b))
            return None
        if op == "pull":
            return self.store.read_range(ObjectID(msg["oid"]), msg["off"],
                                         msg["len"])
        if op == "stats":
            st = self.pool.stats()
            st["store"] = self.store.stats()
            st["local_dispatch"] = self.local.stats()
            return st
        if op == "ping":
            return "pong"
        if op == "resource_view":
            self.local.on_view(msg["nodes"], msg.get("ack", 0))
            return None
        if op == "cancel_local":
            self.local.cancel(msg["task"], bool(msg.get("force")))
            return None
        if op == "shutdown":
            self._exit.set()
            return None
        raise ValueError(f"unknown head op {op!r}")

    def _worker_key(self, wh) -> str:
        from ray_tpu.core.worker_pool import _wkey

        return self._key_prefix + _wkey(wh.chan)

    def _hook_death(self, wh) -> None:
        if wh.on_death is None:
            key = self._worker_key(wh)

            def died():
                self.head.cast("worker_gone", wkey=key, wid=wh.wid)

            wh.on_death = died

    # -- worker → daemon ops -----------------------------------------------

    _LOCAL_STORE_OPS = frozenset({"get_raw"})

    def handle_worker_op(self, chan: MsgChannel, msg: Dict[str, Any]) -> Any:
        op = msg["op"]
        if op == "ping":
            return "pong"
        if op == "get_raw":
            return self._get_raw(msg)
        if op == "mark_shm_local":
            # A direct-transport task reply sealed bytes into this
            # node's arena; index them here so peer pulls + local reads
            # resolve (the proxy path did this from the wcall reply).
            self.store.mark_shm_sealed(ObjectID(msg["oid"]), msg["size"])
            return None
        if op == "mark_shm":
            # Worker sealed bytes into THIS node's arena: track them in
            # the local store, then tell the head where they live.
            oid = ObjectID(msg["oid"])
            self.store.mark_shm_sealed(oid, msg["size"])
            return self._forward(chan, msg)
        if op == "seal_value":
            kind, payload = msg["entry"]
            if kind == "shm":
                self.store.mark_shm_sealed(ObjectID(msg["oid"]), payload)
            return self._forward(chan, msg)
        if op == "submit_task":
            # Local fast path over the synced resource view (parity:
            # raylet-local scheduling — core/local_dispatch.py); falls
            # through to the head when ineligible.
            rep = self.local.maybe_submit(msg, chan)
            if rep is not None:
                return rep
            return self._forward(chan, msg)
        if op == "available_resources":
            view = self.local.cluster_available()
            if view is not None:
                return view  # served from the synced view, no head RPC
            return self._forward(chan, msg)
        # Everything else is control-plane: forward to the head with
        # this worker's borrower key attached.
        return self._forward(chan, msg)

    def _forward(self, chan: MsgChannel, msg: Dict[str, Any]) -> Any:
        payload = {k: v for k, v in msg.items()
                   if k not in ("mid", "kind", "op")}
        from ray_tpu.core.worker_pool import _wkey

        payload["wkey"] = self._key_prefix + _wkey(chan)
        # No restart-replay for worker control ops: a mutating op (task
        # submit, actor create) may have executed + persisted before the
        # head died — replay would double-execute it.  The worker gets
        # the channel error; previous-epoch workers are killed on rejoin.
        return self.head.call(msg["op"], **payload)

    def _get_raw(self, msg: Dict[str, Any]) -> List[Tuple[str, Any]]:
        no_shm = bool(msg.get("no_shm"))
        entries = []
        for b in msg["oids"]:
            entries.append(self._fetch_entry(b, msg.get("timeout"), no_shm))
        return entries

    def _fetch_entry(self, oid_bin: bytes, timeout: Optional[float],
                     no_shm: bool) -> Tuple[str, Any]:
        """One object's wire entry for a local worker: local store hit,
        else resolve the location at the head and pull the bytes into
        the local arena (dedup'd across concurrent pulls — parity:
        pull_manager.h in-flight dedup)."""
        oid = ObjectID(oid_bin)
        for attempt in range(5):
            if self.store.contains(oid):
                try:
                    entry = self.store.get_wire(oid, timeout)
                except Exception:
                    break  # fall through to head resolution
                return self._maybe_inline(oid_bin, entry, no_shm)
            # In-flight pull?  Wait for it instead of double-pulling.
            with self._pull_lock:
                ev = self._pulls.get(oid_bin)
            if ev is not None:
                ev.wait(300.0)
                continue
            (entry,) = self._head_call("get_wire", oids=[oid_bin],
                                       timeout=timeout)
            kind = entry[0]
            if kind in ("b", "err"):
                return entry
            if kind == "shm":
                # Head materialized it locally after all (race with a
                # concurrent local reader at the head) — re-ask as
                # bytes via a pull from the head.
                entry = ("at", ("", None, entry[1]))
            node_hex, addr, size = entry[1]
            if node_hex == self.node_hex:
                # Head thinks it's here but the local copy is gone
                # (arena eviction): report and retry — the head
                # invalidates + reconstructs.
                self._head_call("report_lost", oid=oid_bin)
                time.sleep(0.2 * (attempt + 1))
                continue
            try:
                self._pull_into_store(oid_bin, node_hex, addr, size)
            except Exception:
                # Source vanished mid-pull (node death): tell the head
                # and retry; reconstruction reseals elsewhere.
                time.sleep(0.2 * (attempt + 1))
                continue
        # Give the head one final authoritative try (it may have an
        # error sealed by now, which is the right thing to raise).
        (entry,) = self._head_call("get_wire", oids=[oid_bin],
                                   timeout=timeout)
        if entry[0] in ("b", "err"):
            return entry
        raise OSError(f"object {oid.hex()}: unfetchable after retries")

    def _maybe_inline(self, oid_bin: bytes, entry, no_shm: bool):
        if no_shm and entry[0] == "shm":
            shm = self.store._shm_store()
            return ("b", shm.get_bytes(oid_bin))
        return entry

    def _pull_into_store(self, oid_bin: bytes, node_hex: str,
                         addr, size: int) -> None:
        with self._pull_lock:
            if self._pulls.get(oid_bin) is not None:
                return  # racer started it; caller loops and waits
            ev = self._pulls[oid_bin] = threading.Event()
        try:
            if node_hex == "" or addr is None:
                data = _pull_bytes(self._head_call, oid_bin, size)
            else:
                peer = self._peer_channel(tuple(addr))
                data = _pull_bytes(peer.call, oid_bin, size)
            self.store.put_serialized(ObjectID(oid_bin), data)
        finally:
            with self._pull_lock:
                self._pulls.pop(oid_bin, None)
            ev.set()

    # -- peer plane --------------------------------------------------------

    def _peer_channel(self, addr: Tuple[str, int]) -> MsgChannel:
        from ray_tpu.util.client.common import client_handshake

        with self._peer_lock:
            ch = self._peer_chans.get(addr)
            if ch is not None and not ch.closed:
                return ch
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.settimeout(10.0)
        sock.connect(addr)
        client_handshake(sock, self._token or None)
        sock.settimeout(None)
        ch = MsgChannel(sock, self._handle_peer_op,
                        name=f"peer-{addr[0]}:{addr[1]}").start()
        with self._peer_lock:
            old = self._peer_chans.get(addr)
            if old is not None and not old.closed:
                ch.close()
                return old
            self._peer_chans[addr] = ch
        return ch

    def _peer_accept_loop(self) -> None:
        from ray_tpu.util.client.common import server_handshake

        while not self._exit.is_set():
            try:
                conn, peer = self._peer_listener.accept()
            except OSError:
                return

            def serve(conn=conn, peer=peer):
                conn.settimeout(10.0)
                if not server_handshake(conn, self._token or None):
                    conn.close()
                    return
                conn.settimeout(None)
                MsgChannel(conn, self._handle_peer_op,
                           name=f"peer-in-{peer[0]}").start()

            threading.Thread(target=serve, daemon=True,
                             name="peer-serve").start()

    def _handle_peer_op(self, chan: MsgChannel, msg: Dict[str, Any]) -> Any:
        op = msg["op"]
        if op == "pull":
            return self.store.read_range(ObjectID(msg["oid"]), msg["off"],
                                         msg["len"])
        if op == "ping":
            return "pong"
        raise ValueError(f"unknown peer op {op!r}")


# ---------------------------------------------------------------------------
# Daemon process entry point
# ---------------------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="ray_tpu.core.node_daemon",
        description="join a ray_tpu cluster as a worker node",
    )
    ap.add_argument("--address", required=True,
                    help="head node address HOST:PORT")
    ap.add_argument("--num-cpus", type=float, default=None)
    ap.add_argument("--num-tpus", type=float, default=None)
    ap.add_argument("--resources", default="{}",
                    help="extra resources as JSON")
    ap.add_argument("--labels", default="{}", help="node labels as JSON")
    ap.add_argument("--port", type=int, default=0,
                    help="peer object-transfer port (0 = ephemeral)")
    ap.add_argument("--advertise-host", default="",
                    help="address other nodes reach this machine at")
    args = ap.parse_args(argv)

    host, _, port = args.address.rpartition(":")
    resources = dict(json.loads(args.resources))
    if args.num_cpus is not None:
        resources["CPU"] = float(args.num_cpus)
    elif "CPU" not in resources:
        resources["CPU"] = float(os.cpu_count() or 8)
    labels = dict(json.loads(args.labels))
    if args.num_tpus is not None and args.num_tpus > 0:
        resources["TPU"] = float(args.num_tpus)
    elif "TPU" not in resources:
        # Chip detection is opt-in for daemons: on a shared test
        # machine the chip belongs to the head process.
        pass
    resources.setdefault("memory", 16 * 1024**3)

    daemon = NodeDaemon(
        (host or "127.0.0.1", int(port)),
        resources=resources, labels=labels,
        peer_port=args.port, advertise_host=args.advertise_host,
    )
    print(f"[ray_tpu node {daemon.node_hex[:12]}] joined "
          f"{args.address}; peer port {daemon.peer_port}",
          flush=True)
    try:
        daemon.wait()
    except KeyboardInterrupt:
        pass
    daemon.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
