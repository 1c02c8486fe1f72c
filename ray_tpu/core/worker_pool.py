"""Pooled OS worker processes — the driver side.

Parity: the raylet's WorkerPool (ray: src/ray/raylet/worker_pool.h:156
— fork/pool/reuse language workers, startup tokens, registration
handshake) plus the driver half of the CoreWorkerService push-task plane
(src/ray/protobuf/core_worker.proto:417).  Workers are real OS
processes spawned with ``python -m ray_tpu.core.worker_main``; each
registers back over an AF_UNIX socket identified by a one-time spawn
token, then tasks/actor methods are pushed over that channel
(ray_tpu/core/wire.py) and large values ride the C++ shared-memory
arena (ray_tpu/_native/shm_store.cc) that every worker attaches to —
the plasma-equivalent shared object plane.

Nested API calls (a task submitting sub-tasks, a worker-side
``ray.get``) arrive as reverse-direction requests and are served
against the driver's runtime by ``WorkerPool.handle_request`` — the
GCS/owner role in the reference.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import tempfile
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, TYPE_CHECKING

import cloudpickle

from ray_tpu.core.wire import ChannelClosedError, MsgChannel
from ray_tpu.utils.ids import ObjectID

if TYPE_CHECKING:
    from ray_tpu.core.runtime import LocalRuntime


def _wkey(chan) -> str:
    """Borrower identity of a worker = its channel object (stable for
    the worker's lifetime; all borrows drop together on close)."""
    return f"w{id(chan):x}"


class WorkerHandle:
    """One registered worker process."""

    def __init__(self, pool: "WorkerPool", proc: subprocess.Popen,
                 chan: MsgChannel, wid: str):
        self.pool = pool
        self.proc = proc
        self.chan = chan
        self.wid = wid
        self.pid = proc.pid
        self.wport = getattr(chan, "wport", None)  # direct listener port
        self.dead = False
        self.dedicated = False  # actor hosts never return to the idle set
        # TPU chips bound to this process at spawn ([] = kept off them).
        self.chip_ids: List[int] = []
        # Actor shells hook this to learn about crashes while idle.
        self.on_death = None
        chan.on_close = self._on_close

    def _on_close(self) -> None:
        self.dead = True
        self.pool._discard(self)
        # A dead borrower's references evaporate (parity: the owner
        # clears borrows when the borrower disconnects).
        try:
            self.pool._rt.refs.drop_worker(_wkey(self.chan))
        except Exception:
            pass
        cb = self.on_death
        if cb is not None:
            try:
                cb()
            except Exception:
                pass

    def call(self, op: str, rpc_timeout: Optional[float] = None,
             **payload):
        try:
            return self.chan.call(op, rpc_timeout=rpc_timeout, **payload)
        except ChannelClosedError as e:
            from ray_tpu.core.exceptions import WorkerDiedError

            # Mark dead NOW: the caller's finally-release must not race
            # the reader thread's on_close and re-pool a dead worker.
            self.dead = True
            raise WorkerDiedError(f"pid {self.pid}: {e}") from None

    def terminate(self, graceful: bool = True) -> None:
        self.dead = True
        if graceful and not self.chan.closed:
            try:
                self.chan._send({"mid": 0, "kind": "req", "op": "exit"})
            except Exception:
                pass
        # Signal BEFORE closing the channel: the close runs _discard,
        # which waits for a chip-holding process to be gone.
        try:
            if graceful:
                self.proc.terminate()
            else:
                # SIGKILL: delivered even to a SIGSTOP'd process (a
                # pending SIGTERM would wait for SIGCONT forever).
                self.proc.kill()
        except Exception:
            pass
        self.chan.close()


class WorkerPool:
    def __init__(self, runtime: "LocalRuntime"):
        self._rt = runtime
        self._lock = threading.Lock()
        self._idle: List[WorkerHandle] = []
        self._all: Dict[str, WorkerHandle] = {}
        self._spawn_waiters: Dict[str, Any] = {}  # token → [Event, handle]
        self._closed = False
        # Soft worker-count cap (parity: the raylet bounding worker
        # processes — num_workers_soft_limit / maximum_startup_
        # concurrency).  Without it, a burst of tiny-resource tasks
        # turns into one OS process per in-flight lease and the node
        # dies in a fork/OOM storm (observed: a 500-noop burst at
        # num_cpus=0.001 silently killing a node daemon).  Non-dedicated
        # leases wait for a release instead of spawning past the cap;
        # dedicated (actor) leases may exceed it — they are long-lived
        # allocations already admitted by the resource ledger.
        self._capacity = threading.Condition(self._lock)
        self._spawning = 0
        # One owner per chip: a chip id leaves this list when a worker
        # is spawned bound to it and returns when that process is gone.
        from ray_tpu.utils import accelerator

        self._host_chips = accelerator.num_tpu_chips()
        self._free_chips: List[int] = list(range(self._host_chips))
        from ray_tpu.utils.config import get_config as _gc

        self._max_workers = (_gc().num_workers_soft_limit
                             or max(os.cpu_count() or 8, 8))
        self._sock_dir = tempfile.mkdtemp(prefix="raytpu-ipc-")
        self._sock_path = os.path.join(self._sock_dir, "driver.sock")
        self._listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._listener.bind(self._sock_path)
        self._listener.listen(128)
        threading.Thread(target=self._accept_loop, name="worker-accept",
                         daemon=True).start()
        # Welcome payload pieces, computed once.
        self._shm_name = runtime.store.shm_name()
        self._shm_threshold = runtime.store.shm_threshold
        from ray_tpu.utils.config import get_config

        for _ in range(get_config().worker_prestart):
            threading.Thread(target=self._prestart_one, daemon=True,
                             name="worker-prestart").start()
        # Active liveness probing (parity: GcsHealthCheckManager's
        # periodic gRPC health probes per node,
        # gcs/gcs_server/gcs_health_check_manager.h:55,87-106): a worker
        # that stops answering pings — SIGSTOP'd, deadlocked socket,
        # livelocked — is declared dead WITHOUT anyone calling kill.
        if get_config().health_check_period_s > 0:
            threading.Thread(target=self._health_loop, daemon=True,
                             name="worker-health").start()

    def _prestart_one(self) -> None:
        try:
            self.release(self.spawn())
        except Exception:
            pass

    # -- health checking ---------------------------------------------------

    def _health_loop(self) -> None:
        from ray_tpu.utils.config import get_config

        cfg = get_config()
        period = cfg.health_check_period_s
        window = period * max(1, cfg.health_check_failure_threshold)
        while not self._closed:
            time.sleep(period)
            with self._lock:
                workers = list(self._all.values())
            for wh in workers:
                if wh.dead or getattr(wh, "_probe_inflight", False):
                    continue
                wh._probe_inflight = True
                threading.Thread(
                    target=self._probe, args=(wh, window), daemon=True,
                    name=f"health-probe-{wh.pid}",
                ).start()

    def _probe(self, wh: WorkerHandle, window: float) -> None:
        try:
            try:
                wh.chan.call("ping", rpc_timeout=window)
            except TimeoutError:
                # Unresponsive for the whole failure window → dead
                # (parity: failure_threshold missed probes).  terminate
                # closes the channel, which fires _on_close → actor
                # death / in-flight call failure / borrow drop.
                if not wh.dead:
                    wh.terminate(graceful=False)
            except Exception:
                pass  # channel already closing — death path owns it
        finally:
            wh._probe_inflight = False

    # -- registration ------------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._closed:
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return
            threading.Thread(target=self._register, args=(conn,),
                             daemon=True, name="worker-register").start()

    def _register(self, conn: socket.socket) -> None:
        from ray_tpu.util.client.common import (
            exchange_versions,
            recv_msg,
            send_msg,
        )

        try:
            exchange_versions(conn)
            hello = recv_msg(conn)
            token = hello.get("token", "")
        except Exception:
            conn.close()
            return
        with self._lock:
            waiter = self._spawn_waiters.get(token)
        if waiter is None:  # unknown peer — not one of our spawns
            conn.close()
            return
        try:
            from ray_tpu.utils.config import get_config

            send_msg(conn, {
                "kind": "rep", "mid": hello.get("mid"), "ok": True,
                "value": {
                    "config": get_config().snapshot(),
                    "shm_name": self._shm_name,
                    "shm_threshold": self._shm_threshold,
                    "job_id": self._rt.job_id.hex(),
                    # Functions pickled by reference (driver-side
                    # modules) must be importable in the worker (parity:
                    # same-node workers share the driver's module
                    # environment; cross-node shipping is runtime_env's
                    # job).
                    "sys_path": list(sys.path),
                    "cwd": os.getcwd(),
                },
            })
        except Exception:
            conn.close()
            return
        chan = MsgChannel(conn, self._handle, name=f"worker-{token[:8]}")
        chan.wport = hello.get("wport")  # direct-transport listener
        with self._lock:
            if self._spawn_waiters.get(token) is not waiter:
                # spawn() already timed out and withdrew the token.
                chan.close()
                return
            waiter[1] = chan
            waiter[0].set()

    def spawn(self, chip_ids: Optional[List[int]] = None) -> WorkerHandle:
        """Start one worker process.  ``chip_ids`` None keeps it off
        the TPU (JAX_PLATFORMS=cpu); a list binds it to those chips."""
        from ray_tpu.utils import accelerator
        from ray_tpu.utils.config import get_config

        token = uuid.uuid4().hex
        env = dict(os.environ)
        env["RAYTPU_WORKER_SOCKET"] = self._sock_path
        env["RAYTPU_WORKER_TOKEN"] = token
        # The worker hosts no runtime of its own — never recurse.
        env.pop("RAYTPU_WORKERS", None)
        env.update(accelerator.chip_worker_env(chip_ids, self._host_chips))
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env["PYTHONPATH"] = repo_root + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
        )
        ev = threading.Event()
        waiter = [ev, None]
        with self._lock:
            if self._closed:
                raise RuntimeError("worker pool is shut down")
            self._spawn_waiters[token] = waiter
        registered = False
        # Per-worker log files (parity: worker stdout/stderr redirection
        # at spawn, services.py start_ray_process); a LogMonitor tails
        # the directory and ships lines to the head's LogBuffer.
        log_dir = getattr(self._rt, "log_dir", None)
        out_f = err_f = None
        if log_dir:
            from ray_tpu.util.log_monitor import open_worker_logs

            try:
                out_f, err_f = open_worker_logs(log_dir, token[:8])
            except OSError:
                out_f = err_f = None
        try:
            proc = subprocess.Popen(
                [sys.executable, "-m", "ray_tpu.core.worker_main"],
                env=env,
                stdout=out_f if out_f is not None else None,
                stderr=err_f if err_f is not None else None,
            )
            timeout = get_config().worker_register_timeout_s
            if not ev.wait(timeout):
                proc.terminate()
                raise TimeoutError(
                    f"worker pid {proc.pid} failed to register within "
                    f"{timeout}s"
                )
            registered = True
        finally:
            for f in (out_f, err_f):
                if f is not None:
                    try:
                        f.close()  # the child owns its copy of the fd
                    except OSError:
                        pass
            with self._lock:
                self._spawn_waiters.pop(token, None)
            if not registered and waiter[1] is not None:
                # _register raced our timeout and produced a channel
                # nobody will ever read — close the orphaned socket.
                waiter[1].close()
        chan = waiter[1]
        wh = WorkerHandle(self, proc, chan, token)
        wh.chip_ids = list(chip_ids or ())
        chan.start()
        with self._lock:
            self._all[token] = wh
        return wh

    # -- leasing -----------------------------------------------------------

    def lease(self, dedicated: bool = False, block: bool = True,
              tpu_chips: int = 0) -> Optional[WorkerHandle]:
        """Pop an idle worker or spawn one (parity: PopWorker with
        on-demand StartWorkerProcess).  At the soft cap, non-dedicated
        leases wait for a released worker; the wait is bounded by
        worker_lease_timeout_s, after which the cap yields (it is a
        soft limit, matching the reference's).  ``block=False`` returns
        None at the cap instead (lease rejection — a remote head parks
        the task for worker handoff rather than pinning a daemon
        handler thread; parity: PopWorker's no-worker reply).

        ``tpu_chips`` > 0 (the task or actor asked for ``TPU``) never
        reuses a pooled worker: pooled workers were started off the
        chip.  It gets a fresh process bound to that many free chips,
        which ends when the lease does."""
        from ray_tpu.utils.config import get_config

        deadline = (time.monotonic()
                    + get_config().worker_lease_timeout_s)
        if tpu_chips:
            wh = self._spawn_on_chips(tpu_chips, deadline)
            wh.dedicated = dedicated
            return wh
        with self._lock:
            while True:
                while self._idle:
                    wh = self._idle.pop()
                    if not wh.dead:
                        wh.dedicated = dedicated
                        return wh
                live = len(self._all) + self._spawning
                if (dedicated or live < self._max_workers
                        or (block and time.monotonic() >= deadline)):
                    self._spawning += 1
                    break
                if not block:
                    return None
                self._capacity.wait(2.0)
        try:
            wh = self.spawn()
        finally:
            with self._lock:
                self._spawning -= 1
                self._capacity.notify_all()
        wh.dedicated = dedicated
        return wh

    def _spawn_on_chips(self, n: int, deadline: float) -> WorkerHandle:
        if n > self._host_chips:
            raise ValueError(
                f"asked for {n} TPU chips; this host exposes "
                f"{self._host_chips}")
        with self._lock:
            # The ledger admitted the demand, so the chips are free or
            # their last owner is on its way out (_discard).
            while len(self._free_chips) < n:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"{n} TPU chips not released in time (free: "
                        f"{self._free_chips})")
                self._capacity.wait(remaining)
            chips = [self._free_chips.pop(0) for _ in range(n)]
        try:
            return self.spawn(chip_ids=chips)
        except BaseException:
            with self._lock:
                self._free_chips.extend(chips)
                self._capacity.notify_all()
            raise

    def release(self, wh: WorkerHandle) -> None:
        if wh.dead or wh.dedicated:
            return
        if wh.chip_ids:
            wh.terminate()  # the chips go back when the process is gone
            return
        with self._lock:
            if not self._closed:
                self._idle.append(wh)
                # ONE released worker serves ONE waiter — notify_all
                # here is a thundering herd at burst queue depths.
                self._capacity.notify(1)

    def _discard(self, wh: WorkerHandle) -> None:
        if wh.chip_ids:
            # A chip is free only once the process that held it is gone.
            try:
                wh.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                wh.proc.kill()
                wh.proc.wait()
        with self._lock:
            self._all.pop(wh.wid, None)
            if wh in self._idle:
                self._idle.remove(wh)
            chips, wh.chip_ids = wh.chip_ids, []
            self._free_chips.extend(chips)
            if chips:
                self._capacity.notify_all()
            else:
                self._capacity.notify(1)

    def kill_all(self, graceful: bool = True) -> List[WorkerHandle]:
        """Terminate every worker without closing the pool — the pool
        keeps spawning fresh workers afterwards (used by a node daemon
        discarding its previous epoch after a head restart)."""
        with self._lock:
            workers = list(self._all.values())
            self._all.clear()
            self._idle.clear()
        for wh in workers:
            try:
                wh.terminate(graceful=graceful)
            except Exception:
                pass
        return workers

    def shutdown(self) -> None:
        with self._lock:
            self._closed = True
        workers = self.kill_all()
        try:
            self._listener.close()
        except OSError:
            pass
        try:
            os.unlink(self._sock_path)
            os.rmdir(self._sock_dir)
        except OSError:
            pass
        for wh in workers:
            try:
                wh.proc.wait(timeout=2)
            except Exception:
                try:
                    wh.proc.kill()
                except Exception:
                    pass

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"workers": len(self._all), "idle": len(self._idle)}

    def all_workers(self) -> List[WorkerHandle]:
        """Snapshot of every live worker, idle or busy — the fan-out
        set for cluster-wide control ops (xprof's distributed profiler
        capture)."""
        with self._lock:
            return [wh for wh in self._all.values() if not wh.dead]

    # -- nested-API dispatch (worker → driver) -----------------------------

    def _handle(self, chan: MsgChannel, msg: Dict[str, Any]) -> Any:
        """Serve a worker's control-plane request against the runtime
        (parity: the owner/GCS RPC surface a core worker talks to)."""
        return handle_control_op(self._rt, _wkey(chan), msg)


def _register_nested(rt, oid: ObjectID, msg: Dict[str, Any]) -> None:
    nested = msg.get("nested")
    if nested:
        rt.refs.add_nested(oid, [ObjectID(b) for b in nested])


def handle_control_op(rt, key: str, msg: Dict[str, Any],
                      node_hex: Optional[str] = None) -> Any:
    """The owner/GCS op surface serving workers AND node daemons.

    ``key`` is the borrower identity for reference counting (one per
    worker process; daemons forward their workers' keys prefixed with
    the node id).  ``node_hex`` is set when the caller is a remote node
    daemon: seals of arena-resident values then record a remote
    location instead of a local arena entry (the bytes stayed in the
    daemon's arena — parity: a remote plasma seal updating the owner's
    object directory)."""
    op = msg["op"]
    if op == "get_raw":
        entries = [rt.store.get_wire(ObjectID(b), msg.get("timeout"))
                   for b in msg["oids"]]
        if msg.get("no_shm"):
            # Shm-less worker (arena attach failed): materialize the
            # bytes driver-side instead of handing out arena refs.
            shm = rt.store._shm_store()
            entries = [
                ("b", shm.get_bytes(ObjectID(b).binary()))
                if kind == "shm" else (kind, payload)
                for b, (kind, payload) in zip(msg["oids"], entries)
            ]
        return entries
    if op == "get_wire":
        # Daemon-side fetch: never materializes remote copies at the
        # head — returns ("at", (node_hex, addr, size)) locations so
        # the consuming node pulls directly from the owning node.
        # Head arena copies are ("at", ("", None, size)): pull over
        # the head channel.
        out = []
        for b in msg["oids"]:
            kind, payload = rt.store.get_wire_loc(
                ObjectID(b), msg.get("timeout"))
            if kind == "shm":
                out.append(("at", ("", None, payload)))
            elif kind == "at":
                nh, size = payload
                node = rt.node_by_hex(nh)
                out.append(("at", (nh, node.addr if node else None, size)))
            else:
                out.append((kind, payload))
        return out
    if op == "pull":
        return rt.store.read_range(ObjectID(msg["oid"]), msg["off"],
                                   msg["len"])
    if op == "report_lost":
        # A node daemon discovered its supposed-local copy is gone
        # (arena eviction): invalidate so readers reconstruct.
        oid = ObjectID(msg["oid"])
        if rt.store.remote_location(oid) == node_hex:
            rt.store.invalidate(oid)
            rt._reconstruct_object(oid)
        return None
    if op == "put_val":
        oid = rt.alloc_put_oid()
        # Pre-register the putting worker's borrow (the worker
        # adopts): a put whose handle dies before the batched flush
        # must still be freeable, not leaked untracked.
        rt.refs.add_borrow(key, oid)
        _register_nested(rt, oid, msg)
        rt.store.put_serialized(oid, msg["data"])
        return oid.binary()
    if op == "alloc_put_oid":
        oid = rt.alloc_put_oid()
        rt.refs.add_borrow(key, oid)
        return oid.binary()
    if op == "mark_shm":
        oid = ObjectID(msg["oid"])
        _register_nested(rt, oid, msg)
        if node_hex:
            rt.seal_remote_at(oid, node_hex, msg["size"])
        else:
            rt.store.mark_shm_sealed(oid, msg["size"])
        return None
    if op == "seal_value":
        kind, payload = msg["entry"]
        oid = ObjectID(msg["oid"])
        _register_nested(rt, oid, msg)
        if kind == "shm":
            if node_hex:
                rt.seal_remote_at(oid, node_hex, payload)
            else:
                rt.store.mark_shm_sealed(oid, payload)
        else:
            rt.store.put_serialized(oid, payload)
        return None
    if op == "ref":
        for b in msg.get("add") or []:
            rt.refs.add_borrow(key, ObjectID(b))
        for b in msg.get("rem") or []:
            rt.refs.remove_borrow(key, ObjectID(b))
        return None
    if op == "worker_gone":
        # A daemon-side worker process died: its borrows evaporate
        # (the daemon forwards the dead worker's borrower key).
        rt.refs.drop_worker(msg["wkey"])
        return None
    if op == "release_stream":
        from ray_tpu.utils.ids import TaskID

        rt.release_stream(TaskID(msg["task"]), msg["index"])
        return None
    if op == "seal_error":
        oid = ObjectID(msg["oid"])
        if msg.get("if_pending"):
            rt.store.put_error_if_pending(oid, msg["error"])
        else:
            rt.store.put_error(oid, msg["error"])
        return None
    if op == "wait":
        ready, pending = rt.store.wait(
            [ObjectID(b) for b in msg["oids"]], msg["num_returns"],
            msg.get("timeout"),
        )
        return ([o.binary() for o in ready],
                [o.binary() for o in pending])
    if op == "peek_error":
        return rt.store.peek_error(ObjectID(msg["oid"]))
    if op == "contains":
        return rt.store.contains(ObjectID(msg["oid"]))
    if op == "submit_task":
        fn, args, kwargs = cloudpickle.loads(msg["spec"])
        options = msg["options"]
        deps = msg.get("deps")
        out = rt.submit_task(
            fn, args, kwargs, options, trace_ctx=msg.get("trace_ctx"),
            # Wire-form specs (WireRef args) carry explicit dep ids the
            # dependency index parks on in place of live handles, plus
            # pin-only inner refs.
            arg_oids=(None if deps is None
                      else [ObjectID(b) for b in deps]),
            pin_oids=[ObjectID(b) for b in msg.get("pins") or ()])
        if options.num_returns == "streaming":
            return {"stream": out.task_id.binary()}
        # Pre-register the caller's borrows: the worker constructs
        # handles from these bins (and adopts them without
        # re-reporting), so a fast-finishing task can't be freed
        # between seal and the worker's batched add.
        for r in out:
            rt.refs.add_borrow(key, r.id)
        return {"oids": [r.id.binary() for r in out]}
    if op == "create_actor":
        cls, args, kwargs = cloudpickle.loads(msg["spec"])
        shell, ref = rt.create_actor(cls, args, kwargs, msg["options"])
        from ray_tpu.core.actor import collect_method_num_returns

        return {"actor_id": shell.actor_id.binary(),
                "cls_name": cls.__name__,
                "table": collect_method_num_returns(cls),
                "creation_oid": ref.id.binary()}
    if op == "submit_actor_task":
        from ray_tpu.utils.ids import ActorID

        args, kwargs = cloudpickle.loads(msg["spec"])
        out = rt.submit_actor_task(
            ActorID(msg["actor_id"]), msg["method"], args, kwargs,
            num_returns=msg["num_returns"],
            trace_ctx=msg.get("trace_ctx"),
            concurrency_group=msg.get("cgroup"),
        )
        if msg["num_returns"] == "streaming":
            return {"stream": out.task_id.binary()}
        for r in out:
            rt.refs.add_borrow(key, r.id)
        return {"oids": [r.id.binary() for r in out]}
    if op == "cancel_task":
        rt.cancel(ObjectID(msg["oid"]), force=msg.get("force", False))
        return None
    if op == "kill_actor":
        from ray_tpu.utils.ids import ActorID

        rt.kill_actor(ActorID(msg["actor_id"]),
                      msg.get("no_restart", True))
        return None
    if op == "ps_pull":
        # Long-poll bounded server-side so a handler thread can't park
        # past the worker's rpc timeout (explicit 0 stays non-blocking).
        to = msg.get("timeout")
        to = 10.0 if to is None else float(to)
        return rt.pubsub.pull(msg["channel"], msg.get("cursor", 0),
                              min(to, 25.0))
    if op == "named_actor":
        aid, cls_name, table, cgroups = rt.named_actor_handle(msg["name"])
        return {"actor_id": aid.binary(), "cls_name": cls_name,
                "table": table, "cgroups": cgroups}
    if op == "create_pg":
        pg = rt.create_placement_group(
            msg["bundles"], msg["strategy"], msg["name"],
            msg.get("lifetime"),
        )
        return pg.id.binary()
    if op == "remove_pg":
        from ray_tpu.utils.ids import PlacementGroupID

        rt.remove_placement_group(PlacementGroupID(msg["pg_id"]))
        return None
    if op == "pg_ready":
        from ray_tpu.utils.ids import PlacementGroupID

        return rt.pg_ready_ref(
            PlacementGroupID(msg["pg_id"])).id.binary()
    if op == "named_pg":
        pg = rt.get_named_placement_group(msg["name"])
        return {"pg_id": pg.id.binary(), "bundles": pg.bundle_specs,
                "strategy": pg.strategy, "name": pg.name}
    if op == "pg_table":
        return rt.placement_group_table()
    if op == "cluster_resources":
        return rt.cluster_resources()
    if op == "available_resources":
        return rt.available_resources()
    if op == "nodes":
        return rt.nodes()
    if op == "kv_put":
        return rt.kv.put(msg["key"], msg["value"],
                         overwrite=msg.get("overwrite", True),
                         namespace=msg.get("namespace"))
    if op == "kv_get":
        return rt.kv.get(msg["key"], namespace=msg.get("namespace"))
    if op == "kv_del":
        return rt.kv.delete(msg["key"], namespace=msg.get("namespace"))
    if op == "kv_keys":
        return rt.kv.keys(msg.get("prefix", b""),
                          namespace=msg.get("namespace"))
    if op == "kv_exists":
        return rt.kv.exists(msg["key"], namespace=msg.get("namespace"))
    if op == "ping":
        return "pong"
    raise ValueError(f"unknown worker op {op!r}")
