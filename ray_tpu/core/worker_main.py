"""Worker process entry point + the worker-side runtime proxy.

Parity: the per-process core worker (ray:
src/ray/core_worker/core_worker.cc — ExecuteTask:2565, HandlePushTask:
3072) and its Python task-execution callback (python/ray/_raylet.pyx:
1448 execute_task).  A worker process:

1. connects back to the driver's AF_UNIX socket using the one-time
   spawn token (parity: worker registration with the raylet,
   node_manager.cc:1292),
2. receives the welcome payload (config snapshot, shared-memory arena
   name, job id),
3. installs a ``WorkerRuntime`` as the process-global runtime so that
   any ``ray_tpu`` API call made by user code inside a task — nested
   tasks, ``get``/``put``, actor creation — proxies to the driver's
   control plane (parity: CoreWorker SubmitTask from within a worker),
4. serves pushed work: plain tasks, actor construction, actor method
   calls, until told to exit or its driver hangs up.

Large values move through the C++ shared-memory store that the worker
attaches by name — reads are zero-copy (pinned views over the mapped
arena), writes land directly under the destination ObjectID so the
driver only learns ("shm", size), never the bytes.
"""

from __future__ import annotations

import collections
import contextlib
import os
import socket
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import cloudpickle

from ray_tpu.core.object_ref import ObjectRef
from ray_tpu.core.wire import ChannelClosedError, MsgChannel, WireRef
from ray_tpu.utils.ids import ActorID, ObjectID, TaskID
from ray_tpu.utils.serialization import (
    deserialize_object,
    framed_size,
    serialize_parts,
    try_shm_put,
    write_framed,
)


class _RefClient:
    """Borrower-side reference reporting (parity: the borrower half of
    the ownership protocol, reference_count.h AddBorrowedObject /
    removing borrows on WaitForRefRemoved).  Every live ObjectRef in
    this worker counts one local ref; transitions 0→1 / 1→0 are batched
    and flushed to the owner as a single ``ref`` message.  Flush points:
    end of every task / actor method (synchronous — the add must land
    before the driver releases the task's argument pins) and a periodic
    background sweep for handles dropped by long-lived actor state."""

    def __init__(self, chan: MsgChannel):
        self._chan = chan
        # RLock: on_create/on_delete run from ObjectRef __init__/__del__;
        # cyclic GC triggered inside the critical section can re-enter
        # on the same thread (see ReferenceCounter._lock).
        self._lock = threading.RLock()
        # Serializes whole flushes (snapshot + send): without it the 1s
        # sweep and a task-end flush can deliver batches out of snapshot
        # order — an add overtaken by its del leaks the borrow forever.
        self._flush_lock = threading.Lock()
        self._local: Dict[bytes, int] = {}
        self._adds: set = set()
        self._dels: set = set()
        self._adopted: set = set()
        # (task_id_bin, from_index) stream releases deferred from
        # generator __del__ — sent by flush, never from GC context.
        self._stream_releases: "collections.deque" = collections.deque()

    def adopt(self, oid_bin: bytes) -> None:
        """The owner already registered our borrow (e.g. in the
        submit-task reply) — the first handle must not re-report it."""
        with self._lock:
            self._adopted.add(oid_bin)

    def on_create(self, oid) -> None:
        b = oid.binary()
        with self._lock:
            n = self._local.get(b, 0)
            self._local[b] = n + 1
            if n == 0:
                if b in self._adopted:
                    self._adopted.discard(b)  # owner-side count exists
                elif b in self._dels:
                    self._dels.discard(b)  # cancel the unsent del
                else:
                    self._adds.add(b)

    def on_delete(self, oid) -> None:
        b = oid.binary()
        with self._lock:
            n = self._local.get(b, 0)
            if n <= 1:
                self._local.pop(b, None)
                if b in self._adds:
                    self._adds.discard(b)  # never told the owner
                else:
                    self._dels.add(b)
            else:
                self._local[b] = n - 1

    def defer_stream_release(self, task_bin: bytes, index: int) -> None:
        self._stream_releases.append((task_bin, index))

    def drain_batches(self):
        """Snapshot pending add/del batches for piggybacking on a task
        reply — the owner applies adds BEFORE sealing/pinning the
        reply's results and dels AFTER, so a del of a ref that rides in
        the returned value can never beat its nested pin."""
        with self._flush_lock:
            with self._lock:
                adds, self._adds = self._adds, set()
                dels, self._dels = self._dels, set()
        return list(adds), list(dels)

    def flush(self) -> None:
        with self._flush_lock:
            with self._lock:
                adds, self._adds = self._adds, set()
                dels, self._dels = self._dels, set()
            streams = []
            while self._stream_releases:
                streams.append(self._stream_releases.popleft())
            try:
                if adds or dels:
                    self._chan.call("ref", add=list(adds), rem=list(dels))
                for task_bin, index in streams:
                    self._chan.call("release_stream", task=task_bin,
                                    index=index)
            except Exception:
                pass  # channel down → owner drops this worker's borrows


class _StoreProxy:
    """The subset of LocalObjectStore the generator/consumer paths use,
    proxied to the driver."""

    def __init__(self, wr: "WorkerRuntime"):
        self._wr = wr

    def wait(self, oids: List[ObjectID], num_returns: int,
             timeout: Optional[float]):
        ready, pending = self._wr._chan.call(
            "wait", oids=[o.binary() for o in oids],
            num_returns=num_returns, timeout=timeout,
        )
        return [ObjectID(b) for b in ready], [ObjectID(b) for b in pending]

    def peek_error(self, oid: ObjectID):
        return self._wr._chan.call("peek_error", oid=oid.binary())

    def contains(self, oid: ObjectID) -> bool:
        return self._wr._chan.call("contains", oid=oid.binary())

    def get(self, oid: ObjectID, timeout: Optional[float] = None):
        return self._wr._fetch([oid.binary()], timeout)[0]


class _KvProxy:
    def __init__(self, wr: "WorkerRuntime"):
        self._wr = wr

    def put(self, key, value, *, overwrite: bool = True, namespace=None):
        return self._wr._chan.call("kv_put", key=key, value=value,
                                   overwrite=overwrite, namespace=namespace)

    def get(self, key, *, namespace=None):
        return self._wr._chan.call("kv_get", key=key, namespace=namespace)

    def delete(self, key, *, namespace=None):
        return self._wr._chan.call("kv_del", key=key, namespace=namespace)

    def exists(self, key, *, namespace=None):
        return self._wr._chan.call("kv_exists", key=key,
                                   namespace=namespace)

    def keys(self, prefix=b"", *, namespace=None):
        return self._wr._chan.call("kv_keys", prefix=prefix,
                                   namespace=namespace)


class WorkerRuntime:
    """Driver-API facade inside a worker process (parity: the worker's
    CoreWorker — same surface as LocalRuntime for everything user code
    can reach, implemented as RPCs to the owner/driver)."""

    def __init__(self, chan: MsgChannel, shm, shm_threshold: int):
        self._chan = chan
        self._shm = shm
        self._shm_threshold = shm_threshold
        self.store = _StoreProxy(self)
        self.kv = _KvProxy(self)
        # Borrower-side ref reporting: every ObjectRef built in this
        # process registers with the owner so borrowed values stay
        # alive while we hold them.
        from ray_tpu.core import object_ref as _object_ref

        self.refs = _RefClient(chan)
        _object_ref.install_ref_hooks(self.refs.on_create,
                                      self.refs.on_delete)

    # -- objects -----------------------------------------------------------

    def _read_shm(self, oid_bin: bytes):
        """Deserialize one shared-arena object — zero-copy when this
        worker attached the arena (views stay pinned until GC'd).  An
        arena miss (object lives on another node, or was evicted) falls
        back to a get_raw through the host, which pulls/materializes it
        into the local arena; attach-failed workers always go through
        the host with inline bytes."""
        if self._shm is not None:
            try:
                pb = self._shm.get(oid_bin, timeout=0.05)
                return deserialize_object(pb.view)
            except OSError:
                pass  # not local (yet) — ask the host to make it so
        no_shm = self._shm is None
        (kind, payload), = self._chan.call("get_raw", oids=[oid_bin],
                                           no_shm=no_shm)
        if kind == "err":
            raise payload
        if kind == "shm":
            pb = self._shm.get(oid_bin, timeout=5.0)
            return deserialize_object(pb.view)
        return deserialize_object(payload)

    def _fetch(self, oid_bins: List[bytes],
               timeout: Optional[float] = None) -> List[Any]:
        entries = self._chan.call("get_raw", oids=oid_bins,
                                  timeout=timeout,
                                  no_shm=self._shm is None)
        out = []
        for b, (kind, payload) in zip(oid_bins, entries):
            if kind == "err":
                raise payload
            if kind == "shm":
                out.append(self._read_shm(b))
            else:
                out.append(deserialize_object(payload))
        return out

    def get(self, refs, timeout: Optional[float] = None):
        single = isinstance(refs, ObjectRef)
        ref_list = [refs] if single else list(refs)
        out = self._fetch([r.id.binary() for r in ref_list], timeout)
        return out[0] if single else out

    def put(self, value: Any) -> ObjectRef:
        from ray_tpu.core.object_ref import collect_nested_refs

        with collect_nested_refs() as nested:
            meta, buffers = serialize_parts(value)
        nested_bins = [o.binary() for o in nested]
        size = framed_size(meta, buffers)
        if self._shm is not None and size >= self._shm_threshold:
            oid_bin = self._chan.call("alloc_put_oid")
            self.refs.adopt(oid_bin)  # owner pre-registered our borrow
            sealed = try_shm_put(self._shm, oid_bin, meta, buffers, size)
            if sealed:
                # Outside the try: a ChannelClosedError here is a real
                # failure (the value IS in the arena), not arena-full.
                self._chan.call("mark_shm", oid=oid_bin, size=size,
                                nested=nested_bins)
                return ObjectRef(ObjectID(oid_bin))
            out = bytearray(size)
            write_framed(memoryview(out), meta, buffers)
            self._chan.call("seal_value", oid=oid_bin,
                            entry=("b", bytes(out)), nested=nested_bins)
            return ObjectRef(ObjectID(oid_bin))
        out = bytearray(size)
        write_framed(memoryview(out), meta, buffers)
        oid_bin = self._chan.call("put_val", data=bytes(out),
                                  nested=nested_bins)
        self.refs.adopt(oid_bin)
        return ObjectRef(ObjectID(oid_bin))

    def wait(self, refs, num_returns: int, timeout: Optional[float],
             fetch_local: bool = True):
        ids = [r.id for r in refs]
        ready_ids, pending_ids = self.store.wait(ids, num_returns, timeout)
        by_id = {r.id: r for r in refs}
        return ([by_id[i] for i in ready_ids],
                [by_id[i] for i in pending_ids])

    def release_stream_async(self, task_id: TaskID, from_index: int) -> None:
        # Called from generator __del__ (possibly inside a GC pause) —
        # never RPC here; the next flush (task end or 1 s sweep) sends it.
        self.refs.defer_stream_release(task_id.binary(), from_index)

    # -- tasks / actors ----------------------------------------------------

    def submit_task(self, fn, args, kwargs, options):
        from ray_tpu.util import tracing
        from ray_tpu.core.object_ref import collect_nested_refs

        # Ship the spec in wire form: top-level ObjectRef args become
        # location-agnostic WireRef("fetch") markers the EXECUTING
        # worker resolves through its own daemon, and the dependency
        # ids travel explicitly (parity: TaskSpec's dependency list).
        # This is what lets the host daemon dispatch the task locally
        # without unpickling anything (core/local_dispatch.py), and
        # the head park it on deps without live handles.
        def wire(v):
            if isinstance(v, ObjectRef):
                return WireRef("fetch", None, v.id.binary())
            return v

        top = [v.id.binary() for v in list(args) + list(kwargs.values())
               if isinstance(v, ObjectRef)]
        wargs = tuple(wire(a) for a in args)
        wkwargs = {k: wire(v) for k, v in kwargs.items()}
        with collect_nested_refs() as inner:
            spec = cloudpickle.dumps((fn, wargs, wkwargs))
        deps = list(dict.fromkeys(top))
        # Refs nested INSIDE container args are pinned by the owner but
        # are NOT scheduling dependencies (the task may never get()
        # them) — same top-level-only parking contract as the driver
        # path.
        pins = [b for b in dict.fromkeys(o.binary() for o in inner)
                if b not in set(deps)]
        rep = self._chan.call(
            "submit_task", spec=spec, options=options, deps=deps,
            pins=pins, trace_ctx=tracing.capture_context(),
        )
        if "stream" in rep:
            from ray_tpu.core.generator import ObjectRefGenerator

            return ObjectRefGenerator(TaskID(rep["stream"]))
        for b in rep["oids"]:
            self.refs.adopt(b)  # owner pre-registered our borrow
        return [ObjectRef(ObjectID(b)) for b in rep["oids"]]

    def create_actor(self, cls, args, kwargs, options):
        rep = self._chan.call(
            "create_actor", spec=cloudpickle.dumps((cls, args, kwargs)),
            options=options,
        )
        import types

        shell = types.SimpleNamespace(
            actor_id=ActorID(rep["actor_id"]),
            _creation_oid=ObjectID(rep["creation_oid"]),
        )
        return shell, ObjectRef(shell._creation_oid)

    def submit_actor_task(self, actor_id: ActorID, method_name: str,
                          args, kwargs, num_returns: Any = 1,
                          concurrency_group: Optional[str] = None):
        from ray_tpu.util import tracing

        rep = self._chan.call(
            "submit_actor_task", actor_id=actor_id.binary(),
            method=method_name, spec=cloudpickle.dumps((args, kwargs)),
            num_returns=num_returns, trace_ctx=tracing.capture_context(),
            cgroup=concurrency_group,
        )
        if "stream" in rep:
            from ray_tpu.core.generator import ObjectRefGenerator

            return ObjectRefGenerator(TaskID(rep["stream"]))
        for b in rep["oids"]:
            self.refs.adopt(b)
        return [ObjectRef(ObjectID(b)) for b in rep["oids"]]

    def cancel(self, oid: ObjectID, force: bool = False) -> None:
        self._chan.call("cancel_task", oid=oid.binary(), force=force)

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True):
        self._chan.call("kill_actor", actor_id=actor_id.binary(),
                        no_restart=no_restart)

    def ps_pull(self, channel: str, cursor: int = 0,
                timeout: float = 10.0):
        """Long-poll a head pubsub channel (core/pubsub.py) through
        the control plane; from a daemon's worker this forwards to the
        head like every other control op."""
        return tuple(self._chan.call(
            "ps_pull", rpc_timeout=timeout + 30.0,
            channel=channel, cursor=cursor, timeout=timeout))

    def get_named_actor(self, name: str) -> ActorID:
        return ActorID(self._chan.call("named_actor", name=name)
                       ["actor_id"])

    def named_actor_handle(self, name: str):
        rep = self._chan.call("named_actor", name=name)
        return (ActorID(rep["actor_id"]), rep["cls_name"], rep["table"],
                rep.get("cgroups") or {})

    # -- placement groups --------------------------------------------------

    def create_placement_group(self, bundles, strategy, name, lifetime):
        from ray_tpu.core.placement_group import PlacementGroup
        from ray_tpu.utils.ids import PlacementGroupID

        pg_id = self._chan.call(
            "create_pg", bundles=bundles, strategy=strategy, name=name,
            lifetime=lifetime,
        )
        return PlacementGroup(PlacementGroupID(pg_id), bundles, strategy,
                              name)

    def remove_placement_group(self, pg_id):
        self._chan.call("remove_pg", pg_id=pg_id.binary())

    def pg_ready_ref(self, pg_id):
        return ObjectRef(ObjectID(
            self._chan.call("pg_ready", pg_id=pg_id.binary())
        ))

    def get_named_placement_group(self, name: str):
        from ray_tpu.core.placement_group import PlacementGroup
        from ray_tpu.utils.ids import PlacementGroupID

        rep = self._chan.call("named_pg", name=name)
        return PlacementGroup(PlacementGroupID(rep["pg_id"]),
                              rep["bundles"], rep["strategy"],
                              rep["name"])

    def placement_group_table(self):
        return self._chan.call("pg_table")

    # -- cluster info ------------------------------------------------------

    def cluster_resources(self):
        return self._chan.call("cluster_resources")

    def available_resources(self):
        return self._chan.call("available_resources")

    def nodes(self):
        return self._chan.call("nodes")


# -- execution --------------------------------------------------------------


class _ActorExecutor:
    """Fixed thread pool that runs all of an actor's work, so a method
    sees the SAME thread across calls when max_concurrency == 1 —
    matching reference actor semantics (one scheduling-queue thread per
    actor; thread-locals like collective group contexts survive between
    method invocations)."""

    def __init__(self, n: int):
        import queue as _q

        self._q: "_q.Queue" = _q.Queue()
        for i in range(max(1, n)):
            threading.Thread(target=self._loop, daemon=True,
                             name=f"actor-exec-{i}").start()

    def _loop(self) -> None:
        while True:
            fn, box, ev = self._q.get()
            try:
                box.append(("ok", fn()))
            except BaseException as e:
                box.append(("err", e))
            ev.set()

    def run(self, fn):
        box: list = []
        ev = threading.Event()
        self._q.put((fn, box, ev))
        ev.wait()
        kind, val = box[0]
        if kind == "err":
            raise val
        return val


class _WorkerServer:
    def __init__(self):
        self._chan: Optional[MsgChannel] = None
        self._wr: Optional[WorkerRuntime] = None
        self._shm = None
        self._shm_threshold = 1 << 30
        self._actor_instance: Any = None
        self._actor_env = None
        self._actor_env_plugins = None
        self._actor_exec: Optional[_ActorExecutor] = None
        self._actor_group_execs: Dict[str, _ActorExecutor] = {}
        self._fn_cache: Dict[str, Any] = {}  # ship-once task functions
        # ALL plain tasks run on one persistent executor thread — the
        # reference's model (a worker's main loop executes tasks one at
        # a time), and load-bearing here: native extensions imported in
        # a transient thread can corrupt their TLS when that thread
        # exits (observed: pyarrow 25 segfaults on second use when first
        # imported in a short-lived thread).  A thread that never exits
        # sidesteps the entire class of bug.
        self._task_exec = _ActorExecutor(1)
        self._exit = threading.Event()
        self._chips_claimed = False
        # In-flight pushed work: the 1s ref sweep only flushes when
        # idle, so a sweep-sent del can't overtake a reply-attached add.
        self._busy = 0
        self._booted = False
        self._busy_lock = threading.Lock()
        # Cancellation registry: task_bin → ("thread", ident) while a
        # sync body runs, ("async", fut) while a coroutine is in flight
        # (parity: the executing-tasks map HandleCancelTask consults).
        self._running: Dict[bytes, Any] = {}
        self._running_lock = threading.Lock()
        # Shared event loop for async actor methods: concurrent calls
        # interleave their awaits on it instead of each getting a
        # private asyncio.run (parity: fiber.h async actors).
        self._loop = None

    # -- value encoding ----------------------------------------------------

    def _encode_result(self, value: Any, dest_oid: Optional[bytes]):
        """Wire entry for one produced value: written straight into the
        shared arena under its destination ObjectID when large, inline
        bytes otherwise.  Returns (entry, nested_oid_bins) — refs
        serialized inside the value, which the owner pins under the
        result oid (nested ownership)."""
        from ray_tpu.core.object_ref import collect_nested_refs

        with collect_nested_refs() as nested:
            meta, buffers = serialize_parts(value)
        nested_bins = [o.binary() for o in nested]
        size = framed_size(meta, buffers)
        if (self._shm is not None and dest_oid is not None
                and size >= self._shm_threshold):
            if try_shm_put(self._shm, dest_oid, meta, buffers, size):
                return ("shm", size), nested_bins
        out = bytearray(size)
        write_framed(memoryview(out), meta, buffers)
        return ("b", bytes(out)), nested_bins

    def _decode_args(self, args, kwargs) -> Tuple[tuple, dict]:
        def dec(v):
            if isinstance(v, WireRef):
                if v.kind in ("shm", "fetch"):
                    # "fetch": the bytes live on another node — the
                    # host daemon pulls them into the local arena on
                    # the get_raw fallback inside _read_shm.
                    return self._wr._read_shm(v.oid)
                return deserialize_object(v.data)
            return v

        return (tuple(dec(a) for a in args),
                {k: dec(v) for k, v in kwargs.items()})

    def _env_context(self, env, plugins_blob=None):
        if plugins_blob:
            from ray_tpu.runtime_env import register_plugin

            for plugin in cloudpickle.loads(plugins_blob).values():
                register_plugin(plugin)
        if env:
            from ray_tpu.runtime_env import materialize

            return materialize(env).applied()
        return contextlib.nullcontext()

    @staticmethod
    def _trace(ctx):
        from ray_tpu.util import tracing

        # The driver sends a context iff tracing is on over there —
        # mirror the flag so spans opened by user/library code in this
        # worker actually record (they ride the reply back via
        # drain_finished in _run_op).  A ctx-less call while enabled
        # means the driver turned tracing off; follow it down so the
        # is_enabled() fast path goes back to zero overhead.
        if ctx is not None:
            if not tracing.is_enabled():
                tracing.enable_tracing()
        elif tracing.is_enabled():
            tracing.disable_tracing()
        return tracing.activate(ctx)

    # -- request handling --------------------------------------------------

    def _claim_chips(self) -> None:
        """A worker spawned for a ``TPU`` lease (worker_pool.spawn pinned
        JAX_PLATFORMS=tpu) owns its chips before it runs anything: the
        backend is initialised here, so a chip it cannot get fails the
        task or the actor's construction instead of a CPU run."""
        if os.environ.get("JAX_PLATFORMS") != "tpu" or self._chips_claimed:
            return
        from ray_tpu.utils import accelerator

        report = accelerator.claim_tpu()
        self._chips_claimed = True
        print(f"[ray_tpu worker {os.getpid()}] holds "
              + " ".join(f"{k}={v}" for k, v in report.items()),
              file=sys.stderr)

    def handle(self, chan: MsgChannel, msg: Dict[str, Any]) -> Any:
        op = msg["op"]
        if op == "task":
            return self._run_op(
                lambda: self._task_exec.run(lambda: self._run_task(msg)))
        if op == "actor_create":
            return self._run_op(lambda: self._actor_create(msg))
        if op == "actor_task":
            return self._run_op(lambda: self._actor_task(msg))
        if op == "cancel":
            return self._cancel(msg["task"])
        if op == "ping":
            return "pong"
        if op == "profile":
            # Blocking is fine: MsgChannel runs handlers on a pooled
            # thread per request, so tasks keep flowing during capture.
            return self._profile(msg)
        if op == "exit":
            self._exit.set()
            return None
        raise ValueError(f"unknown driver op {op!r}")

    @staticmethod
    def _profile(msg: Dict[str, Any]) -> List[str]:
        """One bounded jax.profiler capture in THIS worker (the fan-out
        target of the dashboard's POST /api/v0/profile).  Unavailable
        profiler → empty list, never an error reply."""
        from ray_tpu.util import xprof

        paths = xprof.capture(float(msg.get("duration_s", 1.0)),
                              msg.get("out_dir"))
        return paths or []

    def _cancel(self, task_bin: bytes) -> None:
        from ray_tpu.core.exceptions import TaskCancelledError
        from ray_tpu.utils.interrupt import async_raise

        with self._running_lock:
            entry = self._running.get(task_bin)
            if entry is None:
                return None  # already finished — no-op
            kind, target = entry
            if kind == "thread":
                # Under the lock: the executor thread unregisters (and
                # withdraws pending exceptions) under the same lock, so
                # this cannot hit a later task.
                async_raise(target, TaskCancelledError)
                return None
        target.cancel()  # asyncio future — thread-safe
        return None

    @contextlib.contextmanager
    def _cancellable(self, task_bin: bytes):
        """Register the calling thread as the executor of task_bin for
        the duration of the body."""
        from ray_tpu.utils.interrupt import clear_async_exc

        ident = threading.get_ident()
        if task_bin:
            with self._running_lock:
                self._running[task_bin] = ("thread", ident)
        try:
            yield
        finally:
            if task_bin:
                with self._running_lock:
                    self._running.pop(task_bin, None)
                    clear_async_exc(ident)

    def _run_op(self, body) -> Dict[str, Any]:
        """Run one pushed work item.  On success the pending borrow
        add/del batches ride IN the reply (the driver applies adds
        before pinning/sealing results and dels after); on failure they
        flush as a plain ref message — an error reply carries no values
        to pin, so ordering doesn't matter there."""
        with self._busy_lock:
            self._busy += 1
            booted, self._booted = self._booted, True
        if not booted:
            # the start-up record's ``worker.boot``: this process's own
            # start to its first operation
            from ray_tpu.util import tracing

            born = tracing.process_start()
            if born is not None:
                tracing.startup_event("worker.boot", born, time.time())
        try:
            try:
                rep = body()
            except BaseException:
                self._flush_refs()
                raise
            # Drain while still "busy" so the sweep can't grab (and
            # send out-of-band) a del that belongs after this reply.
            rep = rep if rep is not None else {}
            adds, dels = self._wr.refs.drain_batches()
            if adds:
                rep["ref_add"] = adds
            if dels:
                rep["ref_rem"] = dels
            from ray_tpu.util import tracing

            if tracing.is_enabled():
                # Spans finished in this worker ride the reply home;
                # concurrent calls may drain each other's spans, which
                # is fine — they all land in the same driver buffer.
                spans = tracing.drain_finished()
                if spans:
                    rep["spans"] = spans
            # Metric snapshots ride at most once per second per worker
            # (absolute cumulative state, so skipped replies lose
            # nothing — the next snapshot covers them).
            now = time.monotonic()
            if now - getattr(self, "_metrics_ship_t", 0.0) >= 1.0:
                from ray_tpu.util import metrics

                snap = metrics.snapshot_samples()
                if snap:
                    rep["metrics"] = snap
                    self._metrics_ship_t = now
            # Request-lifecycle rows (serve/request_events) federate
            # the same way — sys.modules guard: a worker that never
            # imported the serve stack must not load it for telemetry.
            reqev = sys.modules.get("ray_tpu.serve.request_events")
            if reqev is not None and \
                    now - getattr(self, "_reqev_ship_t", 0.0) >= 1.0:
                rows = reqev.snapshot_rows(local_only=True)
                if rows:
                    rep["request_events"] = rows
                    self._reqev_ship_t = now
            # Flight-recorder events ship incrementally (ship() moves a
            # cursor, so every event crosses exactly once); unlike the
            # absolute snapshots above there is no cadence gate — a
            # trigger event must reach the driver on the NEXT reply,
            # not up to a second later.
            frec = sys.modules.get("ray_tpu.util.flight_recorder")
            if frec is not None:
                evs = frec.ship()
                if evs:
                    rep["flightrec"] = evs
            # Time-series points ship cursor-style too (util/timeseries
            # drains its outbox, so every point crosses exactly once);
            # the worker's 1 Hz sampler bounds the payload to roughly
            # one tick's points per reply.
            tser = sys.modules.get("ray_tpu.util.timeseries")
            if tser is not None:
                pts = tser.ship()
                if pts:
                    rep["timeseries"] = pts
            return rep
        finally:
            with self._busy_lock:
                self._busy -= 1

    def _flush_refs(self) -> None:
        if self._wr is not None:
            self._wr.refs.flush()

    def _run_task(self, msg: Dict[str, Any]) -> Any:
        self._claim_chips()
        fhash = msg.get("fn_hash")
        if fhash is not None:
            # Ship-once function protocol: the blob rides the first
            # call only (parity: function-manager export by hash).
            fn = self._fn_cache.get(fhash)
            if fn is None:
                blob = msg.get("fn_blob")
                if blob is None:
                    raise RuntimeError(
                        f"unknown function hash {fhash} (no blob shipped)")
                fn = cloudpickle.loads(blob)
                self._fn_cache[fhash] = fn
            args, kwargs = cloudpickle.loads(msg["spec"])
        else:
            fn, args, kwargs = cloudpickle.loads(msg["spec"])
        args, kwargs = self._decode_args(args, kwargs)
        with self._env_context(msg.get("env"), msg.get("env_plugins")), \
                self._trace(msg.get("trace_ctx")), \
                self._cancellable(msg.get("task") or b""):
            result = fn(*args, **kwargs)
            if msg.get("streaming"):
                self._stream(result, TaskID(msg["task"]), msg["name"])
                return {"streamed": True}
        return self._encode_reply(result, msg)

    def _ensure_loop(self):
        with self._running_lock:
            if self._loop is None:
                import asyncio

                self._loop = asyncio.new_event_loop()
                threading.Thread(
                    target=self._loop.run_forever, daemon=True,
                    name="async-actor-loop",
                ).start()
            return self._loop

    def _run_coroutine(self, coro, task_bin: bytes):
        """Run an async actor method on the shared loop so concurrent
        calls interleave their awaits; cancellable via the registry."""
        import asyncio
        import concurrent.futures as _cf

        from ray_tpu.core.exceptions import TaskCancelledError

        loop = self._ensure_loop()
        fut = asyncio.run_coroutine_threadsafe(coro, loop)
        if task_bin:
            with self._running_lock:
                self._running[task_bin] = ("async", fut)
        try:
            return fut.result()
        except (_cf.CancelledError, asyncio.CancelledError):
            raise TaskCancelledError(
                TaskID(task_bin).hex() if task_bin else "")
        finally:
            if task_bin:
                with self._running_lock:
                    self._running.pop(task_bin, None)

    def _encode_reply(self, result, msg: Dict[str, Any]) -> Dict[str, Any]:
        num_returns = msg.get("num_returns", 1)
        returns = msg.get("returns", [])
        if num_returns == 1:
            entry, nested = self._encode_result(
                result, returns[0] if returns else None)
            return {"results": [entry], "nested": [nested]}
        values = list(result)
        if len(values) != num_returns:
            raise ValueError(
                f"task declared num_returns={num_returns} but returned "
                f"{len(values)} values"
            )
        entries, nesteds = [], []
        for i, v in enumerate(values):
            entry, nested = self._encode_result(
                v, returns[i] if i < len(returns) else None)
            entries.append(entry)
            nesteds.append(nested)
        return {"results": entries, "nested": nesteds}

    def _stream(self, result, task_id: TaskID, name: str) -> None:
        """Seal yielded items into the driver's store one by one
        (parity: the streaming-generator executor, _raylet.pyx:918)."""
        from ray_tpu.core.exceptions import TaskError
        from ray_tpu.core.generator import EndOfStream

        i = 0
        try:
            if not hasattr(result, "__iter__"):
                raise TypeError(
                    f"streaming task {name!r} must return an iterable, "
                    f"got {type(result).__name__}"
                )
            for item in result:
                oid = ObjectID.for_task_return(task_id, i)
                entry, nested = self._encode_result(item, oid.binary())
                self._chan.call("seal_value", oid=oid.binary(), entry=entry,
                                nested=nested)
                i += 1
        except BaseException as e:
            err = e if isinstance(e, TaskError) else TaskError(name, e)
            self._chan.call(
                "seal_error",
                oid=ObjectID.for_task_return(task_id, i).binary(),
                error=err, if_pending=False,
            )
            raise
        self._chan.call(
            "seal_error", oid=ObjectID.for_task_return(task_id, i).binary(),
            error=EndOfStream(), if_pending=False,
        )

    def _actor_create(self, msg: Dict[str, Any]) -> None:
        self._claim_chips()
        cls, args, kwargs = cloudpickle.loads(msg["spec"])
        args, kwargs = self._decode_args(args, kwargs)
        self._actor_env = msg.get("env")
        self._actor_env_plugins = msg.get("env_plugins")
        self._actor_exec = _ActorExecutor(msg.get("max_concurrency", 1))
        # One executor pool per named concurrency group (parity:
        # concurrency_group_manager.cc — per-group BoundedExecutor), so
        # a stalled group cannot serialize another group's calls.
        self._actor_group_execs = {
            g: _ActorExecutor(max(1, int(n)))
            for g, n in (msg.get("concurrency_groups") or {}).items()
        }

        def construct():
            with self._env_context(self._actor_env,
                                   self._actor_env_plugins):
                self._actor_instance = cls(*args, **kwargs)

        # __init__ runs on the executor thread too, so instance state
        # bound to the thread (thread-locals, event loops) carries over
        # into method calls.
        self._actor_exec.run(construct)
        return None

    def _actor_task(self, msg: Dict[str, Any]) -> Any:
        if self._actor_instance is None:
            raise RuntimeError("no actor constructed in this worker")
        import inspect as _inspect

        method = getattr(self._actor_instance, msg["method"], None)
        if _inspect.iscoroutinefunction(method):
            # Async methods bypass the executor: each request's handler
            # thread parks on the coroutine's future while the SHARED
            # loop interleaves all of them (parity: fiber.h async
            # actors) — routing through the 1-thread executor would
            # serialize exactly what async actors exist to overlap.
            # (The driver-side shell bounds per-group async concurrency.)
            return self._actor_task_body(msg)
        cgroup = msg.get("cgroup")
        exec_ = (getattr(self, "_actor_group_execs", {}).get(cgroup)
                 if cgroup else None) or self._actor_exec
        return exec_.run(lambda: self._actor_task_body(msg))

    def _actor_task_body(self, msg: Dict[str, Any]) -> Any:
        args, kwargs = cloudpickle.loads(msg["spec"])
        args, kwargs = self._decode_args(args, kwargs)
        method = getattr(self._actor_instance, msg["method"])
        task_bin = msg.get("task") or b""
        with self._env_context(self._actor_env, self._actor_env_plugins), \
                self._trace(msg.get("trace_ctx")):
            import inspect as _inspect

            if _inspect.iscoroutinefunction(method):
                # Shared loop: concurrent calls interleave their awaits
                # (each handler thread blocks, the coroutines don't).
                result = self._run_coroutine(method(*args, **kwargs),
                                             task_bin)
            else:
                with self._cancellable(task_bin):
                    result = method(*args, **kwargs)
                if _inspect.iscoroutine(result):
                    result = self._run_coroutine(result, task_bin)
            if msg.get("num_returns") == "streaming":
                self._stream(result, TaskID(msg["task"]), msg["method"])
                return {"streamed": True}
        return self._encode_reply(result, msg)

    # -- direct transport --------------------------------------------------

    def _direct_accept_loop(self, cluster_token: str) -> None:
        from ray_tpu.util.client.common import server_handshake

        while not self._exit.is_set():
            try:
                conn, peer = self._direct_listener.accept()
            except OSError:
                return

            def serve(conn=conn, peer=peer):
                conn.settimeout(10.0)
                if not server_handshake(conn, cluster_token or None):
                    conn.close()
                    return
                conn.settimeout(None)
                MsgChannel(conn, self._handle_direct,
                           name=f"direct-{peer[0]}").start()

            threading.Thread(target=serve, daemon=True,
                             name="direct-serve").start()

    def _handle_direct(self, chan: MsgChannel, msg: Dict[str, Any]) -> Any:
        """Ops pushed over a direct owner channel.  Results sealed into
        the local arena must ALSO be indexed at this node's daemon (the
        proxy path did that from the reply; direct replies bypass it).
        The index update is SYNCHRONOUS, before the owner sees the
        reply: the owner may immediately direct another node to pull
        from this daemon, and the daemon's spill-ahead-of-eviction
        policy needs to see arena pressure as it builds, not after."""
        rep = self.handle(chan, msg)
        if isinstance(rep, dict) and rep.get("results"):
            for oid_bin, (kind, payload) in zip(msg.get("returns") or (),
                                                rep["results"]):
                if kind == "shm":
                    try:
                        self._chan.call("mark_shm_local", oid=oid_bin,
                                        size=payload)
                    except Exception:
                        pass  # daemon gone: node death owns cleanup
        return rep

    # -- bootstrap ---------------------------------------------------------

    def main(self) -> int:
        import faulthandler

        faulthandler.enable()  # crashing workers leave a stack trace
        sock_path = os.environ.get("RAYTPU_WORKER_SOCKET")
        token = os.environ.get("RAYTPU_WORKER_TOKEN", "")
        if not sock_path:
            print("RAYTPU_WORKER_SOCKET not set", file=sys.stderr)
            return 2
        sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        sock.connect(sock_path)
        from ray_tpu.util.client.common import (
            exchange_versions,
            recv_msg,
            send_msg,
        )

        exchange_versions(sock)

        # Direct task transport (parity: the owner pushing tasks to a
        # leased worker over its own gRPC channel rather than through
        # the raylet, direct_task_transport.cc → PushTask): a TCP
        # listener remote owners dial directly, skipping the daemon's
        # per-task forwarding.  Token-gated beyond loopback (same trust
        # rule as the peer/object plane).
        cluster_token = os.environ.get("RAYTPU_CLUSTER_TOKEN", "")
        self._direct_listener = socket.socket(socket.AF_INET,
                                              socket.SOCK_STREAM)
        self._direct_listener.setsockopt(socket.SOL_SOCKET,
                                         socket.SO_REUSEADDR, 1)
        self._direct_listener.bind(
            ("0.0.0.0" if cluster_token else "127.0.0.1", 0))
        self._direct_listener.listen(16)
        wport = self._direct_listener.getsockname()[1]
        # NOTE: the accept loop starts only after _wr exists — a direct
        # push must never race runtime construction.

        send_msg(sock, {"kind": "req", "mid": 0, "op": "hello",
                        "token": token, "pid": os.getpid(),
                        "wport": wport})
        welcome = recv_msg(sock)
        if not welcome.get("ok"):
            return 3
        info = welcome["value"]
        from ray_tpu.utils.config import get_config

        try:
            get_config().update(info.get("config") or {})
        except Exception:
            pass
        for p in info.get("sys_path") or []:
            if p not in sys.path:
                sys.path.append(p)
        try:
            if info.get("cwd"):
                os.chdir(info["cwd"])
        except OSError:
            pass
        self._shm_threshold = info.get("shm_threshold", 1 << 30)
        if info.get("shm_name"):
            try:
                from ray_tpu.core.shm_store import SharedMemoryStore

                self._shm = SharedMemoryStore.connect(info["shm_name"])
            except Exception as e:
                # Degraded but functional: large values travel as bytes
                # through the driver (see _read_shm / get_raw no_shm).
                print(f"[ray_tpu worker {os.getpid()}] shared-memory "
                      f"attach failed ({e!r}); falling back to inline "
                      f"transfers", file=sys.stderr)
                self._shm = None
        self._chan = MsgChannel(sock, self.handle, name="driver",
                                on_close=lambda: self._exit.set())
        self._wr = WorkerRuntime(self._chan, self._shm,
                                 self._shm_threshold)
        # Install the proxy as THE runtime for this process: any
        # ray_tpu API call in user code now routes to the driver.
        from ray_tpu.core import api

        api._runtime = self._wr
        # Always-on telemetry history: sample this process's metric
        # registry into bounded rings; points ride task replies home
        # (see _run_op's timeseries ship).
        try:
            from ray_tpu.util import timeseries

            timeseries.ensure_started()
        except Exception:
            pass
        threading.Thread(target=self._direct_accept_loop,
                         args=(cluster_token,), daemon=True,
                         name="direct-accept").start()

        def ref_sweep():
            # Handles dropped by long-lived actor state between tasks
            # (reply-attached batches cover everything else).  Only
            # when idle: a sweep del racing an in-flight reply's adds
            # would leak the borrow.
            while not self._exit.wait(1.0):
                with self._busy_lock:
                    busy = self._busy
                if busy:
                    continue
                try:
                    self._wr.refs.flush()
                except Exception:
                    pass

        threading.Thread(target=ref_sweep, name="ref-sweep",
                         daemon=True).start()
        self._chan.start()
        self._exit.wait()
        # Let in-flight replies flush before dying.
        self._chan.close()
        return 0


def main() -> int:
    return _WorkerServer().main()


if __name__ == "__main__":
    sys.exit(main())
