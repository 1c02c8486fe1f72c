"""Cluster runtime: tasks, actors, objects over logical nodes in one process.

Semantics-first parity with the reference's core: dependency-aware task
dispatch (ray: raylet/local_task_manager.cc WaitForTaskArgsRequests /
DispatchScheduledTasksToWorkers), two-phase cluster scheduling with the
hybrid pack-then-spread policy (raylet/scheduling/cluster_task_manager.cc:44,
policy/hybrid_scheduling_policy.h:28-50), logical resource accounting
(common/scheduling/resource_instance_set.cc), per-actor ordered execution
queues (core_worker/transport/actor_scheduling_queue.cc), error capture +
retries (core_worker/task_manager.h max_retries), named actors (gcs actor
directory), placement-group bundle reservation
(gcs/gcs_server/gcs_placement_group_scheduler.cc), and node membership +
death propagation (gcs/gcs_server/gcs_node_manager.cc).

The cluster is simulated as N logical nodes inside one process — the same
trick the reference uses for multi-node tests (python/ray/cluster_utils.py
Cluster runs N raylets locally).  Libraries only ever see the api module,
so they run unchanged when workers move behind a process/RPC boundary.
"""

from __future__ import annotations

import collections as _collections
import contextlib
import dataclasses
import inspect as _inspect
import itertools
import math
import threading
import time
import queue as _queue
import re as _re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ray_tpu.core import events as _ev
from ray_tpu.core.exceptions import (
    ActorDiedError,
    TaskCancelledError,
    TaskError,
)
from ray_tpu.core.object_ref import ObjectRef
from ray_tpu.core.placement_group import (
    Bundle,
    NodeAffinitySchedulingStrategy,
    NodeLabelSchedulingStrategy,
    PlacementGroup,
    PlacementGroupSchedulingStrategy,
)
from ray_tpu.core.store import LocalObjectStore
from ray_tpu.utils.config import get_config
from ray_tpu.utils.ids import (
    ActorID,
    JobID,
    NodeID,
    ObjectID,
    PlacementGroupID,
    TaskID,
)

_tracing_mod = None

# Gloo emits one "[Gloo] Rank N is connected to M peer ranks ..." line
# per rank per rendezvous — O(ranks^2) console spam on multi-process
# CPU dryruns.  Matched lines are kept in the LogBuffer but skipped by
# the driver echo (ingest_logs).
_GLOO_CONNECT_RE = _re.compile(
    r"\[Gloo\]\s+Rank\s+\d+\s+is\s+connected\s+to\s+\d+\s+peer\s+ranks")


def _tracing():
    """Cycle-safe cached import of ray_tpu.util.tracing (ray_tpu.util's
    __init__ imports back into core, so a top-level import here would
    be circular)."""
    global _tracing_mod
    if _tracing_mod is None:
        from ray_tpu.util import tracing

        _tracing_mod = tracing
    return _tracing_mod


def _tpu_chips(demand: Dict[str, float]) -> int:
    """Chips a worker must own for this demand: a chip has one owner,
    so any share of one is the whole chip."""
    return math.ceil(demand.get("TPU", 0))


@dataclasses.dataclass
class TaskOptions:
    num_cpus: float = 1.0
    num_tpus: float = 0.0
    resources: Dict[str, float] = dataclasses.field(default_factory=dict)
    num_returns: int = 1
    max_retries: int = 0
    name: str = ""
    scheduling_strategy: Any = "DEFAULT"
    placement_group: Any = None
    placement_bundle_index: int = -1
    runtime_env: Any = None

    def resource_demand(self) -> Dict[str, float]:
        demand = dict(self.resources)
        if self.num_cpus:
            demand["CPU"] = demand.get("CPU", 0) + self.num_cpus
        if self.num_tpus:
            demand["TPU"] = demand.get("TPU", 0) + self.num_tpus
        return demand

    def effective_strategy(self) -> Any:
        if self.placement_group is not None:
            return PlacementGroupSchedulingStrategy(
                self.placement_group, self.placement_bundle_index
            )
        return self.scheduling_strategy


@dataclasses.dataclass
class ActorOptions:
    num_cpus: float = 1.0
    num_tpus: float = 0.0
    resources: Dict[str, float] = dataclasses.field(default_factory=dict)
    name: Optional[str] = None
    get_if_exists: bool = False
    max_restarts: int = 0
    max_concurrency: int = 1
    # Named concurrency groups: group → max concurrent calls.  Methods
    # route via @method(concurrency_group=...) or per-call .options();
    # each group executes independently, so a slow group cannot starve
    # another (parity: ray concurrency groups,
    # core_worker/transport/concurrency_group_manager.cc).
    concurrency_groups: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    # Out-of-order execution: a queued call whose ObjectRef args are
    # not ready yet does not block later calls (parity:
    # out_of_order_actor_submit_queue.cc).  Ordering guarantees are
    # forfeited, as in the reference.
    execute_out_of_order: bool = False
    lifetime: Optional[str] = None  # None | "detached"
    scheduling_strategy: Any = "DEFAULT"
    placement_group: Any = None
    placement_bundle_index: int = -1
    runtime_env: Any = None

    def resource_demand(self) -> Dict[str, float]:
        demand = dict(self.resources)
        if self.num_cpus:
            demand["CPU"] = demand.get("CPU", 0) + self.num_cpus
        if self.num_tpus:
            demand["TPU"] = demand.get("TPU", 0) + self.num_tpus
        return demand

    def effective_strategy(self) -> Any:
        if self.placement_group is not None:
            return PlacementGroupSchedulingStrategy(
                self.placement_group, self.placement_bundle_index
            )
        return self.scheduling_strategy


class ResourcePool:
    """Logical resource ledger (parity: NodeResourceInstanceSet).

    When the native scheduler built (ray_tpu/_native/scheduler.cc), the
    ledger lives in C++ fixed-point arithmetic — acquire/release/
    utilization forward there (parity: the raylet's C++ resource core).
    Pure-Python fallback when no C++ toolchain is available."""

    def __init__(self, total: Dict[str, float], native=None):
        self._lock = threading.Lock()
        self.total = dict(total)
        self._avail = dict(total)
        # native = (NativeClusterScheduler, node_int_id) or None
        self._native = native

    @property
    def available(self) -> Dict[str, float]:
        if self._native is not None:
            sched, nid = self._native
            return {k: sched.available(nid, k) for k in self.total}
        return self._avail

    def can_fit(self, demand: Dict[str, float]) -> bool:
        return all(self.total.get(k, 0) >= v for k, v in demand.items())

    def try_acquire(self, demand: Dict[str, float]) -> bool:
        if self._native is not None:
            sched, nid = self._native
            return sched.try_acquire(nid, demand)
        with self._lock:
            if all(self._avail.get(k, 0) >= v - 1e-9 for k, v in demand.items()):
                for k, v in demand.items():
                    self._avail[k] = self._avail.get(k, 0) - v
                return True
            return False

    def release(self, demand: Dict[str, float]) -> None:
        if self._native is not None:
            sched, nid = self._native
            sched.release(nid, demand)
            return
        with self._lock:
            for k, v in demand.items():
                self._avail[k] = self._avail.get(k, 0) + v

    def utilization(self) -> float:
        """Max over resource kinds of used/total (0 = idle, 1 = full)."""
        if self._native is not None:
            sched, nid = self._native
            return sched.utilization(nid)
        with self._lock:
            worst = 0.0
            for k, tot in self.total.items():
                if tot > 0:
                    worst = max(worst, (tot - self._avail.get(k, 0)) / tot)
            return worst


class NodeState:
    """One logical node: resources + labels + liveness
    (parity: GcsNodeManager's node table entry + raylet resource view)."""

    def __init__(self, node_id: NodeID, resources: Dict[str, float],
                 labels: Optional[Dict[str, str]] = None,
                 native=None, int_id: int = -1):
        self.node_id = node_id
        self.int_id = int_id  # dense id for the native scheduler
        self.pool = ResourcePool(resources, native=native)
        self.labels = dict(labels or {})
        self.alive = True
        self.actor_ids: set = set()
        # Remote node daemon handle (ray_tpu.core.node_daemon
        # RemoteNodeAgent) — None for the head's local node and for
        # logical test nodes.  When set, tasks/actors allocated here
        # dispatch over the daemon's channel to ITS worker pool, and
        # the daemon's object-plane address is ``addr``.
        self.agent = None
        self.addr: Optional[Tuple[str, int]] = None

    def matches_labels(self, required: Dict[str, str]) -> bool:
        return all(self.labels.get(k) == v for k, v in required.items())


@dataclasses.dataclass
class _Allocation:
    """Where a task/actor's resources came from, for symmetric release."""

    node: Optional[NodeState]
    bundle: Optional[Bundle]
    demand: Dict[str, float]

    def release(self):
        if self.bundle is not None:
            # node_id must be read under the bundle lock so we can't race
            # remove_placement_group between its ledger-zeroing and its
            # node_id reset (which would credit a dead ledger).
            with self.bundle.lock:
                still_ours = (self.node is not None
                              and self.bundle.node_id == self.node.node_id)
                if still_ours:
                    for k, v in self.demand.items():
                        self.bundle.available[k] = \
                            self.bundle.available.get(k, 0) + v
            if still_ours:
                pass
            elif self.node is not None:
                # The bundle moved away (PG removed, or relocated after a
                # node death).  The in-use portion was never returned to
                # the node when that happened — return it now.  If the
                # node is dead its pool is inert, so this is harmless.
                self.node.pool.release(self.demand)
        elif self.node is not None:
            self.node.pool.release(self.demand)


@dataclasses.dataclass
class _PendingTask:
    fn: Callable
    args: tuple
    kwargs: dict
    options: TaskOptions
    return_ids: List[ObjectID]
    retries_left: int
    task_id: TaskID
    function_name: str
    streaming: bool = False
    on_done: Optional[Callable[[], None]] = None
    trace_ctx: Optional[Dict[str, str]] = None
    # Set by ray_tpu.cancel: never (re)dispatch, never retry (parity:
    # TaskSpec cancellation flag checked in _raylet.pyx:1806).
    cancelled: bool = False
    # Unsatisfied dependency oids while parked in the waiting index
    # (parity: DependencyManager's per-task unfulfilled set).
    waiting_on: Optional[set] = None
    # Resource demand, computed once at submission (hot path).
    demand: Optional[Dict[str, float]] = None
    # Explicit dependency list (nested submissions ship WireRef args +
    # a deps list instead of live handles — parity: TaskSpec's
    # dependency ids).  None → collect ObjectRefs from args/kwargs.
    arg_oids: Optional[List[ObjectID]] = None
    # Head-side handles pinning explicit deps (same lifetime as the
    # handles that live inside args on the normal path).
    arg_refs: Optional[list] = None
    # Pickled (fn, args, kwargs) of a daemon-dispatched task; hydrated
    # lazily only if the head must re-run it (retry, reconstruction).
    spec_blob: Optional[bytes] = None


class _CachedThreadPool:
    """Task-execution threads, pooled and reused (parity: the raylet's
    WorkerPool keeping warm workers instead of forking per task,
    worker_pool.h:156 — here for thread mode).  Unbounded on purpose:
    tasks may block arbitrarily long (nested ray.get), so a bounded
    pool would deadlock; idle threads expire instead."""

    def __init__(self, idle_timeout: float = 2.0, name: str = "task-exec"):
        import collections as _c

        self._cv = threading.Condition()
        self._work: "_c.deque" = _c.deque()
        self._idle = 0
        self._timeout = idle_timeout
        self._name = name
        self._seq = itertools.count()
        self._closed = False

    def submit(self, fn: Callable[[], None]) -> None:
        spawn = False
        with self._cv:
            if self._closed:
                return
            self._work.append(fn)
            if self._idle > 0:
                self._cv.notify()
            if len(self._work) > self._idle:
                spawn = True
        if spawn:
            threading.Thread(
                target=self._worker, daemon=True,
                name=f"{self._name}-{next(self._seq)}",
            ).start()

    def _worker(self) -> None:
        import time as _time

        while True:
            with self._cv:
                deadline = _time.monotonic() + self._timeout
                self._idle += 1
                while not self._work:
                    remaining = deadline - _time.monotonic()
                    if remaining <= 0 or self._closed:
                        self._idle -= 1
                        return
                    self._cv.wait(remaining)
                self._idle -= 1
                fn = self._work.popleft()
            try:
                fn()
            except BaseException:
                pass  # task bodies seal their own errors

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._work.clear()
            self._cv.notify_all()


# Returned by _execute_item when completion happens later on the actor's
# event loop (async method): the serve loop must not record FINISHED.
_ASYNC_DEFERRED = object()


def _collect_arg_oids(args: tuple, kwargs: dict) -> List[ObjectID]:
    """Top-level ObjectRef dependencies of one actor call (the same
    top-level contract as resolve_args / the dependency index)."""
    from ray_tpu.core.object_ref import ObjectRef as _OR

    return [v.id for v in list(args) + list(kwargs.values())
            if isinstance(v, _OR)]


from ray_tpu.utils.interrupt import (
    async_raise as _async_raise,
    clear_async_exc as _clear_async_exc,
)


class _ActorShell:
    """Server side of one actor: instance + execution thread(s).

    max_concurrency == 1 (default): one thread drains the queue in
    submission order (parity: ActorSchedulingQueue ordering guarantee).
    max_concurrency > 1: a pool of threads drains the same queue and
    ordering is NOT guaranteed (parity: threaded actors via
    BoundedExecutor, core_worker/transport/thread_pool.cc)."""

    def __init__(self, runtime: "LocalRuntime", actor_id: ActorID, cls: type,
                 args: tuple, kwargs: dict, options: ActorOptions,
                 creation_oid: ObjectID, allocation: _Allocation):
        self.runtime = runtime
        self.actor_id = actor_id
        self.cls = cls
        self.init_args = args
        self.init_kwargs = kwargs
        self.options = options
        self.allocation = allocation
        self.instance: Any = None
        self.dead = False
        self.death_reason = ""
        self.no_restart = False  # set by an explicit kill(no_restart=True)
        self.restarts_left = options.max_restarts
        self.queue: _queue.Queue = _queue.Queue()
        # Named concurrency groups: each gets its own queue + thread
        # pool, so groups execute independently (parity:
        # concurrency_group_manager.cc — one BoundedExecutor per group).
        self._group_queues: Dict[str, _queue.Queue] = {
            g: _queue.Queue() for g in (options.concurrency_groups or ())
        }
        self._creation_oid = creation_oid
        self.thread: Optional[threading.Thread] = None
        # Restart counter for per-attempt task events (parity: each
        # restart is a distinct attempt of the creation task).
        self.creation_attempt = -1
        # Cancellation bookkeeping (parity: actor task cancel via the
        # scheduling queue / asyncio task cancel).
        from ray_tpu.core.refcount import TombstoneSet

        self._cancel_lock = threading.Lock()
        self._cancelled = TombstoneSet(1024)  # cancelled-before-run ids
        self._running_sync: Dict[TaskID, Any] = {}  # id → thread ident
        self._inflight_async: Dict[TaskID, Any] = {}  # id → (fut, oids)
        # Async actors: one event loop thread per actor; N method calls
        # interleave as coroutines on it (parity: boost::fibers async
        # actors, core_worker/transport/fiber.h:55).
        self._loop = None
        self._loop_thread: Optional[threading.Thread] = None
        self._async_sem = None
        self._async_group_sems: Dict[str, Any] = {}
        # Orders "dead/drained check + queue.put" against kill/_drain so
        # a racing submit (esp. a dep-blocked out-of-order call whose
        # wait spans the death) can't land in a queue nothing drains.
        self._submit_gate = threading.Lock()
        self._drained = False
        # Out-of-order mode: dep-blocked calls park here; ONE dispatcher
        # thread enqueues them as their deps seal.
        self._ooo_pending: List[Any] = []
        self._ooo_thread: Optional[threading.Thread] = None

    @property
    def node_id(self) -> Optional[NodeID]:
        return self.allocation.node.node_id if self.allocation.node else None

    def start(self):
        """Called after the runtime has registered the actor, so death
        bookkeeping always sees a registered actor."""
        import time as _time

        # Age for OOM kill policies (reset per (re)start — parity: the
        # policies rank by the running task's start time).
        self._start_ts = _time.monotonic()
        self.thread = threading.Thread(
            target=self._run, name=f"actor-{self.actor_id.hex()[:8]}",
            daemon=True,
        )
        self.thread.start()

    def _construct(self):
        if self.options.runtime_env:
            from ray_tpu.runtime_env import materialize

            self._env_ctx = materialize(self.options.runtime_env)
            with self._env_ctx.applied():
                self.instance = self.cls(*self.init_args, **self.init_kwargs)
        else:
            self._env_ctx = None
            self.instance = self.cls(*self.init_args, **self.init_kwargs)

    def _run(self):
        # Actor creation is the first "task" (parity: actor creation task).
        ev = self.runtime.events
        ctid = getattr(self, "creation_task_id", None)
        self.creation_attempt += 1
        attempt = self.creation_attempt
        if ctid is not None:
            ev.record(ctid.hex(), _ev.RUNNING, attempt=attempt,
                      name=f"{self.cls.__name__}.__init__",
                      type=_ev.ACTOR_CREATION_TASK,
                      actor_id=self.actor_id.hex(),
                      node_id=(self.node_id.hex() if self.node_id else None),
                      worker=threading.current_thread().name)
        try:
            self._construct()
            self.runtime.store.put_value(self._creation_oid, None)
            if ctid is not None:
                ev.record(ctid.hex(), _ev.FINISHED, attempt=attempt)
        except BaseException as e:
            self.dead = True
            self.death_reason = f"creation failed: {e!r}"
            if ctid is not None:
                ev.record(ctid.hex(), _ev.FAILED, attempt=attempt,
                          error_message=repr(e))
            err = ActorDiedError(repr(self.cls), self.death_reason)
            self.runtime.store.put_error(self._creation_oid, err)
            # Methods queued while __init__ was still running must fail,
            # not hang (submissions after death are rejected in submit()).
            self._drain(err)
            self.runtime._on_actor_death(self)
            return
        # max_concurrency > 1: a pool of threads drains the same queue, so
        # blocking calls (long-polls, slow requests) don't serialize
        # (parity: threaded actors via BoundedExecutor,
        # core_worker/transport/thread_pool.cc — ordering is only
        # guaranteed for max_concurrency == 1, as in the reference).
        n = max(1, int(self.options.max_concurrency))
        extra = [
            threading.Thread(
                target=self._serve_loop, daemon=True,
                name=f"actor-{self.actor_id.hex()[:8]}-c{i + 1}",
            )
            for i in range(n - 1)
        ]
        # One pool per named concurrency group, sized by its declared
        # limit — a stalled group never borrows (or blocks) another
        # group's threads.
        for gname, gsize in (self.options.concurrency_groups or {}).items():
            extra += [
                threading.Thread(
                    target=self._serve_loop,
                    args=(self._group_queues[gname],), daemon=True,
                    name=f"actor-{self.actor_id.hex()[:8]}-{gname}{i}",
                )
                for i in range(max(1, int(gsize)))
            ]
        for t in extra:
            t.start()
        self._serve_loop()
        for t in extra:
            t.join()
        self._drain(ActorDiedError(repr(self.cls), self.death_reason or "killed"))
        self.runtime._on_actor_death(self)

    def _serve_loop(self, queue: Optional[_queue.Queue] = None):
        queue = queue if queue is not None else self.queue
        while True:
            item = queue.get()
            if item is None:  # kill signal — re-post so sibling threads stop
                queue.put(None)
                return
            method_name, args, kwargs, return_ids, num_returns = item[:5]
            task_id = item[5] if len(item) > 5 else None
            trace_ctx = item[6] if len(item) > 6 else None
            cgroup = item[7] if len(item) > 7 else None
            task_hex = task_id.hex() if task_id is not None else None
            ev = self.runtime.events
            qname = f"{self.cls.__name__}.{method_name}"
            if task_id is not None:
                with self._cancel_lock:
                    was_cancelled = task_id in self._cancelled
                if was_cancelled:
                    # Cancelled while queued: never runs (parity: the
                    # scheduling queue drops cancelled actor tasks).
                    self.runtime._seal_cancelled(
                        task_id, return_ids, num_returns == "streaming")
                    if task_hex:
                        ev.record(task_hex, _ev.FAILED,
                                  error_message="cancelled")
                    continue
            if task_hex:
                ev.record(task_hex, _ev.RUNNING, name=qname,
                          type=_ev.ACTOR_TASK, actor_id=self.actor_id.hex(),
                          node_id=(self.node_id.hex() if self.node_id
                                   else None),
                          worker=self._worker_label())
            try:
                outcome = self._execute_item(qname, method_name, args, kwargs,
                                             return_ids, num_returns, task_id,
                                             trace_ctx, task_hex,
                                             cgroup=cgroup)
                if task_hex and outcome is not _ASYNC_DEFERRED:
                    ev.record(task_hex, _ev.FINISHED)
            except BaseException as e:
                if task_hex:
                    ev.record(task_hex, _ev.FAILED, error_message=repr(e))
                err = (e if isinstance(e, TaskCancelledError)
                       else self._item_error(qname, e))
                for oid in return_ids:
                    self.runtime.store.put_error(oid, err)
                if num_returns == "streaming" and task_id is not None:
                    # Seal at the first unsealed index (a worker may
                    # already have produced a prefix of the stream) so
                    # the consumer's next() unblocks with the error.
                    self.runtime._seal_stream_failure(task_id, err)
                if self._after_item_error(e):
                    return

    def _worker_label(self) -> str:
        return threading.current_thread().name

    def _execute_item(self, qname, method_name, args, kwargs, return_ids,
                      num_returns, task_id, trace_ctx, task_hex,
                      cgroup=None):
        """Run one dequeued method call; overridden by the process
        shell to push it to the actor's worker process."""
        resolved_args, resolved_kwargs = self.runtime.resolve_args(
            args, kwargs
        )
        method = getattr(self.instance, method_name)
        if _inspect.iscoroutinefunction(method) and num_returns != "streaming":
            # Async actor path: schedule on the actor's event loop and
            # return immediately — the serve loop moves to the next
            # item, so N awaits interleave (parity: fiber.h async
            # actors).  Completion seals results from the callback.
            return self._execute_async(qname, method, resolved_args,
                                       resolved_kwargs, return_ids,
                                       num_returns, task_id, task_hex,
                                       cgroup=cgroup)
        ctx = getattr(self, "_env_ctx", None)
        if task_id is not None:
            with self._cancel_lock:
                self._running_sync[task_id] = threading.get_ident()
        try:
            # Env covers the whole body, including a streaming method's
            # lazy generator execution.
            with (ctx.applied() if ctx is not None
                  else contextlib.nullcontext()), \
                    _tracing().task_span(qname, trace_ctx,
                                         {"task_id": task_hex or ""}):
                result = method(*resolved_args, **resolved_kwargs)
                if _inspect.iscoroutine(result):
                    import asyncio

                    result = asyncio.run(result)
                if num_returns == "streaming":
                    self.runtime._stream_results(result, task_id, qname)
        finally:
            if task_id is not None:
                with self._cancel_lock:
                    self._running_sync.pop(task_id, None)
                    # Withdraw a cancel that arrived too late, so it
                    # cannot hit the next item on this thread.
                    _clear_async_exc(threading.get_ident())
        if num_returns != "streaming":
            self.runtime._store_results(result, return_ids, num_returns)

    def _ensure_loop(self):
        with self._cancel_lock:
            return self._ensure_loop_locked()

    def _ensure_loop_locked(self):
        if self._loop is not None:
            return
        import asyncio

        self._loop = asyncio.new_event_loop()
        # Async actors default to high concurrency when the user left
        # max_concurrency at 1 (parity: ray's async actors default to
        # 1000 concurrent coroutines).
        limit = int(self.options.max_concurrency)
        if limit <= 1:
            limit = 1000
        self._async_sem = asyncio.Semaphore(limit)
        # Named groups bound their coroutines independently (parity:
        # per-group event loops in the reference; one shared loop with
        # per-group semaphores gives the same isolation contract).
        self._async_group_sems = {
            g: asyncio.Semaphore(max(1, int(n)))
            for g, n in (self.options.concurrency_groups or {}).items()
        }
        self._loop_thread = threading.Thread(
            target=self._loop.run_forever, daemon=True,
            name=f"actor-{self.actor_id.hex()[:8]}-loop",
        )
        self._loop_thread.start()

    def _execute_async(self, qname, method, args, kwargs, return_ids,
                       num_returns, task_id, task_hex, cgroup=None):
        import asyncio
        import concurrent.futures as _cf

        self._ensure_loop()
        sem = (self._async_group_sems.get(cgroup, self._async_sem)
               if cgroup else self._async_sem)

        async def body():
            async with sem:
                return await method(*args, **kwargs)

        fut = asyncio.run_coroutine_threadsafe(body(), self._loop)
        if task_id is not None:
            with self._cancel_lock:
                self._inflight_async[task_id] = (fut, return_ids)
        ev = self.runtime.events

        def done(f):
            if task_id is not None:
                with self._cancel_lock:
                    self._inflight_async.pop(task_id, None)
            try:
                result = f.result()
            except BaseException as e:
                if isinstance(e, (asyncio.CancelledError, _cf.CancelledError)):
                    err: BaseException = TaskCancelledError(task_hex or "")
                elif isinstance(e, TaskCancelledError):
                    err = e
                else:
                    err = self._item_error(qname, e)
                for oid in return_ids:
                    self.runtime.store.put_error_if_pending(oid, err)
                if task_hex:
                    ev.record(task_hex, _ev.FAILED, error_message=repr(err))
                return
            try:
                self.runtime._store_results(result, return_ids, num_returns)
                if task_hex:
                    ev.record(task_hex, _ev.FINISHED)
            except BaseException as e:
                err = self._item_error(qname, e)
                for oid in return_ids:
                    self.runtime.store.put_error_if_pending(oid, err)
                if task_hex:
                    ev.record(task_hex, _ev.FAILED, error_message=repr(err))

        fut.add_done_callback(done)
        return _ASYNC_DEFERRED

    def cancel_task(self, task_id: TaskID, force: bool = False) -> None:
        """Cancel one submitted actor task: drop it if queued, cancel
        the coroutine if in-flight async, async-raise into the thread
        if running sync (parity: CancelActorTask semantics — force has
        no stronger meaning for actor tasks)."""
        with self._cancel_lock:
            entry = self._inflight_async.get(task_id)
            tid = self._running_sync.get(task_id)
            if entry is None and tid is None:
                self._cancelled.add(task_id)
                return
            if entry is None:
                # Deliver UNDER the lock: _execute_item's finally
                # unregisters + withdraws pending exceptions under the
                # same lock, so this can never poison a later item on
                # the thread.
                _async_raise(tid, TaskCancelledError)
                return
        # Future.cancel outside the lock: a not-yet-started coroutine
        # cancels synchronously, invoking done() which takes the lock.
        entry[0].cancel()

    def _item_error(self, qname: str, e: BaseException) -> BaseException:
        return TaskError(qname, e)

    def _after_item_error(self, e: BaseException) -> bool:
        """True → stop serving (the loop returns)."""
        if not isinstance(e, Exception):
            # actor dies on SystemExit et al
            self.dead = True
            self.death_reason = repr(e)
            self._post_kill()
            return True
        return False

    def _post_kill(self) -> None:
        """Wake every serve pool (default + named groups) for exit."""
        self.queue.put(None)
        for q in self._group_queues.values():
            q.put(None)

    def _drain(self, err: BaseException):
        # Close the submit gate FIRST: anything enqueued before this
        # point is swept below; anything after seals directly.
        with self._submit_gate:
            self._drained = True
        # In-flight async calls: seal the death error (so consumers
        # can't hang on a stopped loop) and cancel the coroutines.
        with self._cancel_lock:
            inflight = list(self._inflight_async.values())
            self._inflight_async.clear()
        for fut, oids in inflight:
            for oid in oids:
                self.runtime.store.put_error_if_pending(oid, err)
            fut.cancel()
        if self._loop is not None:
            self._loop.call_soon_threadsafe(lambda: None)  # wake the loop
        for q in [self.queue, *self._group_queues.values()]:
            while True:
                try:
                    item = q.get_nowait()
                except _queue.Empty:
                    break
                if item is None:
                    continue
                for oid in item[3]:
                    self.runtime.store.put_error(oid, err)
                if item[4] == "streaming" and len(item) > 5 and item[5]:
                    # Queued-but-never-started stream: index 0 unsealed.
                    self.runtime.store.put_error(
                        ObjectID.for_task_return(item[5], 0), err
                    )
                if len(item) > 5 and item[5]:
                    self.runtime.events.record(item[5].hex(), _ev.FAILED,
                                               error_message=repr(err))

    def _seal_item_error(self, err: BaseException, return_ids, num_returns,
                         task_id) -> None:
        for oid in return_ids:
            self.runtime.store.put_error(oid, err)
        if num_returns == "streaming" and task_id is not None:
            self.runtime.store.put_error(
                ObjectID.for_task_return(task_id, 0), err
            )
        if task_id is not None:
            self.runtime.events.record(task_id.hex(), _ev.FAILED,
                                       error_message=repr(err))

    def _seal_item_dead(self, return_ids, num_returns, task_id) -> None:
        self._seal_item_error(
            ActorDiedError(repr(self.cls), self.death_reason or "dead"),
            return_ids, num_returns, task_id)

    def submit(self, method_name: str, args, kwargs, return_ids, num_returns,
               task_id: Optional[TaskID] = None, trace_ctx=None,
               concurrency_group: Optional[str] = None):
        if self.dead:
            self._seal_item_dead(return_ids, num_returns, task_id)
            return
        if concurrency_group and concurrency_group not in self._group_queues:
            self._seal_item_error(
                TaskError(
                    f"{self.cls.__name__}.{method_name}",
                    ValueError(f"unknown concurrency group "
                               f"{concurrency_group!r}; declared: "
                               f"{sorted(self._group_queues)}")),
                return_ids, num_returns, task_id)
            return
        queue = (self._group_queues[concurrency_group]
                 if concurrency_group else self.queue)
        item = (method_name, args, kwargs, return_ids, num_returns,
                task_id, trace_ctx, concurrency_group)
        if self.options.execute_out_of_order:
            # A call whose ObjectRef args are not sealed yet must not
            # block later calls (parity: OutOfOrderActorSubmitQueue —
            # dependency-ready tasks dispatch immediately).
            deps = [oid for oid in _collect_arg_oids(args, kwargs)
                    if not self.runtime.store.contains(oid)]
            if deps:
                self._ooo_add(queue, item, deps)
                return
        with self._submit_gate:
            if self._drained:
                self._seal_item_dead(return_ids, num_returns, task_id)
                return
            queue.put(item)

    def _ooo_add(self, queue: _queue.Queue, item, deps) -> None:
        """Park a dep-blocked out-of-order call on the shell's single
        dispatcher thread (bounded: O(1) threads regardless of how many
        calls are blocked, unlike a thread per call)."""
        with self._submit_gate:
            if self.dead:
                self._seal_item_dead(item[3], item[4], item[5])
                return
            self._ooo_pending.append((queue, item, deps))
            if self._ooo_thread is None:
                self._ooo_thread = threading.Thread(
                    target=self._ooo_loop, daemon=True,
                    name=f"actor-{self.actor_id.hex()[:8]}-ooo",
                )
                self._ooo_thread.start()

    def _ooo_loop(self) -> None:
        store = self.runtime.store
        while True:
            with self._submit_gate:
                if self.dead:
                    pending, self._ooo_pending = self._ooo_pending, []
                    self._ooo_thread = None
                    break
                if not self._ooo_pending:
                    self._ooo_thread = None
                    return
                snapshot = list(self._ooo_pending)
            ready = [(q, it, deps) for q, it, deps in snapshot
                     if all(store.contains(d) for d in deps)]
            with self._submit_gate:
                for entry in ready:
                    if entry in self._ooo_pending:
                        self._ooo_pending.remove(entry)
                        if not self._drained:
                            entry[0].put(entry[1])
                        else:
                            it = entry[1]
                            self._seal_item_dead(it[3], it[4], it[5])
                remaining = [d for _, _, deps in self._ooo_pending
                             for d in deps if not store.contains(d)]
            if remaining:
                # Woken by ANY dep sealing; bounded timeout re-checks
                # death so a killed actor can't strand the loop.
                store.wait(remaining, 1, 0.5)
        for _, it, _ in pending:
            self._seal_item_dead(it[3], it[4], it[5])

    def kill(self, no_restart: bool = True):
        self.dead = True
        self.no_restart = no_restart
        self.death_reason = "killed via ray_tpu.kill"
        self._post_kill()


class _RemoteInstance:
    """Truthy sentinel: the actor's real instance lives in a worker
    process; drivers only know it was constructed."""

    def __repr__(self):
        return "<instance in worker process>"


_REMOTE_INSTANCE = _RemoteInstance()


class _ProcessActorShell(_ActorShell):
    """Actor hosted in a dedicated OS worker process (parity: each actor
    is its own worker process, gcs_actor_scheduler.cc LeaseWorkerFromNode
    → the actor owns that worker for life).  The driver side keeps the
    same queue/ordering/restart machinery as the in-process shell; only
    construction and method execution cross the process boundary.

    Crash semantics the thread shell cannot give: kill -9 of the worker
    → in-flight calls fail with ActorDiedError and the restart FSM
    re-leases a fresh process; ray_tpu.kill() preemptively terminates
    the process, interrupting even a stuck method."""

    def _construct(self):
        import cloudpickle as _cp

        pool = self.runtime._pool_for(self.allocation)
        wh = pool.lease(dedicated=True,
                        tpu_chips=_tpu_chips(self.allocation.demand))
        try:
            # Init args ship raw — ObjectRefs stay refs, matching the
            # thread shell (the instance resolves them itself if/when
            # it wants the values).
            rep = wh.call(
                "actor_create",
                spec=_cp.dumps((self.cls, self.init_args,
                                self.init_kwargs)),
                env=self.options.runtime_env,
                env_plugins=self.runtime._ship_env(
                    self.options.runtime_env),
                max_concurrency=self.options.max_concurrency,
                concurrency_groups=dict(
                    self.options.concurrency_groups or {}),
            )
            if isinstance(rep, dict):
                self.runtime.apply_ref_batches(
                    rep, self.runtime._worker_ref_key(wh))
        except BaseException:
            # A half-constructed worker may hold broken state — never
            # return it to the pool.
            wh.terminate(graceful=False)
            raise
        self._worker = wh
        wh.on_death = self._worker_died
        self._env_ctx = None  # env is applied worker-side
        self.instance = _REMOTE_INSTANCE

    def _worker_died(self):
        if self.dead:
            return
        self.dead = True
        self.death_reason = "worker process died"
        self._post_kill()

    def _worker_label(self) -> str:
        return f"pid-{getattr(self._worker, 'pid', '?')}"

    def _execute_item(self, qname, method_name, args, kwargs, return_ids,
                      num_returns, task_id, trace_ctx, task_hex,
                      cgroup=None):
        import cloudpickle as _cp

        method = getattr(self.cls, method_name, None)
        if (_inspect.iscoroutinefunction(method)
                and num_returns != "streaming"):
            # Async actor method: dispatch WITHOUT blocking the serve
            # loop, so N calls are in flight to the worker together and
            # interleave on its shared event loop (parity: fiber.h
            # async actors — the thread shell's _execute_async
            # equivalent across the process boundary).
            return self._execute_async_remote(
                qname, method_name, args, kwargs, return_ids,
                num_returns, task_id, trace_ctx, task_hex, cgroup=cgroup)
        wire_args, wire_kwargs = self.runtime._wire_args(args, kwargs)
        if task_id is not None:
            with self._cancel_lock:
                self._running_sync[task_id] = True  # in-flight marker
        try:
            with _tracing().task_span(qname, trace_ctx,
                                      {"task_id": task_hex or ""}):
                rep = self._worker.call(
                    "actor_task", method=method_name,
                    spec=_cp.dumps((wire_args, wire_kwargs)),
                    num_returns=num_returns,
                    returns=[oid.binary() for oid in return_ids],
                    task=(task_id.binary() if task_id is not None else b""),
                    trace_ctx=_tracing().capture_context(),
                    cgroup=cgroup,
                )
        finally:
            if task_id is not None:
                with self._cancel_lock:
                    self._running_sync.pop(task_id, None)
        wkey = self.runtime._worker_ref_key(self._worker)
        if num_returns != "streaming":
            self.runtime.seal_remote_results(
                return_ids, rep, wkey,
                node_hex=getattr(self._worker, "node_hex", None))
        else:
            self.runtime.apply_ref_batches(rep, wkey)

    def _execute_async_remote(self, qname, method_name, args, kwargs,
                              return_ids, num_returns, task_id, trace_ctx,
                              task_hex, cgroup=None):
        import cloudpickle as _cp

        from ray_tpu.core.exceptions import WorkerDiedError

        with self._cancel_lock:
            if self._async_sem is None:
                limit = int(self.options.max_concurrency)
                self._async_sem = threading.Semaphore(
                    limit if limit > 1 else 1000)
                self._async_group_sems = {
                    g: threading.Semaphore(max(1, int(n)))
                    for g, n in
                    (self.options.concurrency_groups or {}).items()
                }
        wire_args, wire_kwargs = self.runtime._wire_args(args, kwargs)
        spec = _cp.dumps((wire_args, wire_kwargs))
        wh = self._worker
        # At the concurrency cap the serve loop blocks here — the same
        # bound the thread shell's asyncio.Semaphore enforces (named
        # groups bound independently).
        sem = (self._async_group_sems.get(cgroup, self._async_sem)
               if cgroup else self._async_sem)
        sem.acquire()
        if task_id is not None:
            with self._cancel_lock:
                self._running_sync[task_id] = True
        ev = self.runtime.events
        ctx = _tracing().capture_context()

        def run():
            try:
                try:
                    rep = wh.call(
                        "actor_task", method=method_name, spec=spec,
                        num_returns=num_returns,
                        returns=[oid.binary() for oid in return_ids],
                        task=(task_id.binary() if task_id is not None
                              else b""),
                        trace_ctx=ctx,
                        cgroup=cgroup,
                    )
                finally:
                    if task_id is not None:
                        with self._cancel_lock:
                            self._running_sync.pop(task_id, None)
                self.runtime.seal_remote_results(
                    return_ids, rep,
                    self.runtime._worker_ref_key(wh),
                    node_hex=getattr(wh, "node_hex", None))
                if task_hex:
                    ev.record(task_hex, _ev.FINISHED)
            except BaseException as e:
                if isinstance(e, WorkerDiedError):
                    err: BaseException = ActorDiedError(
                        repr(self.cls), "worker process died")
                    self._worker_died()
                elif isinstance(e, TaskCancelledError):
                    err = e
                else:
                    err = TaskError(qname, e)
                for oid in return_ids:
                    self.runtime.store.put_error_if_pending(oid, err)
                if task_hex:
                    ev.record(task_hex, _ev.FAILED, error_message=repr(err))
            finally:
                sem.release()

        threading.Thread(target=run, daemon=True,
                         name=f"{qname}-async").start()
        return _ASYNC_DEFERRED

    def _item_error(self, qname: str, e: BaseException) -> BaseException:
        from ray_tpu.core.exceptions import WorkerDiedError

        if isinstance(e, WorkerDiedError):
            return ActorDiedError(repr(self.cls), "worker process died")
        return TaskError(qname, e)

    def _after_item_error(self, e: BaseException) -> bool:
        from ray_tpu.core.exceptions import WorkerDiedError

        if isinstance(e, WorkerDiedError):
            self._worker_died()
            return False  # drain remaining items fast via dead calls
        # SystemExit et al raised worker-side and transported here —
        # mirror the thread shell.
        return super()._after_item_error(e)

    def _drain(self, err: BaseException):
        wh = getattr(self, "_worker", None)
        if wh is not None:
            wh.on_death = None
            wh.terminate(graceful=not wh.dead)
            self._worker = None
        super()._drain(err)

    def cancel_task(self, task_id: TaskID, force: bool = False) -> None:
        with self._cancel_lock:
            running = task_id in self._running_sync
            if not running:
                self._cancelled.add(task_id)
                return
        wh = getattr(self, "_worker", None)
        if wh is not None:
            try:
                wh.call("cancel", task=task_id.binary())
            except Exception:
                pass  # worker gone — death semantics already apply

    def kill(self, no_restart: bool = True):
        super().kill(no_restart)
        # Preemptive: a stuck or long-running method dies with the
        # process (the thread shell can only ask nicely).
        wh = getattr(self, "_worker", None)
        if wh is not None:
            wh.terminate(graceful=False)


@dataclasses.dataclass
class _PGState:
    pg: PlacementGroup
    bundles: List[Bundle]
    ready_oid: ObjectID
    lifetime: Optional[str] = None
    removed: bool = False


class LocalRuntime:
    def __init__(self, *, resources: Optional[Dict[str, float]] = None,
                 labels: Optional[Dict[str, str]] = None,
                 job_id: Optional[JobID] = None):
        cfg = get_config()
        total = dict(resources or {})
        if "CPU" not in total:
            total["CPU"] = float(cfg.num_workers_soft_limit or 8)
        total.setdefault("memory", 64 * 1024**3)
        self.store = LocalObjectStore()
        # Cluster KV (parity: GcsKvManager — function table, job info,
        # runtime envs and usage stats live here).
        from ray_tpu.core.kv import KvStore

        self.kv = KvStore()
        # GCS-side task-event ring (parity: GcsTaskManager, see events.py).
        self.events = _ev.TaskEventBuffer(
            max_tasks=getattr(cfg, "task_events_max_num", 16384)
        )
        self.job_id = job_id or JobID.next()
        self.driver_task_id = TaskID.for_driver(self.job_id)
        self._put_counter = itertools.count(1)
        self._lock = threading.Lock()
        # Ready queue (deps satisfied, awaiting resources) + the
        # dependency-wakeup index: missing oid → tasks parked on it
        # (parity: DependencyManager, raylet/dependency_manager.h:51 —
        # tasks wake when their deps become local, no polling).  Deque:
        # the dispatcher pops the head O(1) — a list's pop(0) would be
        # O(n) per dispatch with 100k tasks queued.
        self._pending: "_collections.deque[_PendingTask]" = \
            _collections.deque()
        self._waiting_deps: Dict[ObjectID, List[_PendingTask]] = {}
        self._dispatch_cv = threading.Condition()
        # Pooled executor threads for thread-mode task bodies.
        self._exec_pool = _CachedThreadPool()
        # Feasibility memo for (demand, string-strategy) pairs —
        # submit-path hot cache, cleared on any topology change.  The
        # epoch guards against caching a verdict computed against
        # pre-change topology (compute is not under the topology lock).
        self._feasible_cache: Dict[Any, bool] = {}
        self._topology_epoch = 0
        self._shutdown = False
        self._actors: Dict[ActorID, _ActorShell] = {}
        self._named_actors: Dict[str, ActorID] = {}
        self._nodes: Dict[NodeID, NodeState] = {}
        self._node_order: List[NodeID] = []  # stable order for hybrid packing
        # Native C++ scheduler core (parity: the raylet's C++
        # ClusterResourceScheduler); None → pure-Python ledgers.
        try:
            from ray_tpu.core.native_scheduler import NativeClusterScheduler

            self._native_sched = NativeClusterScheduler(
                spread_threshold=cfg.scheduler_spread_threshold
            )
        except Exception:
            self._native_sched = None
        self._node_int_ids = itertools.count(1)
        self._nodes_by_int: Dict[int, NodeState] = {}
        self._pgs: Dict[PlacementGroupID, _PGState] = {}
        self._named_pgs: Dict[str, PlacementGroupID] = {}
        # Tombstones for the actor state table, bounded (parity: GCS keeps
        # DEAD actors queryable up to
        # RAY_maximum_gcs_destroyed_actor_cached_count).
        self._dead_actors: Any = _collections.deque(maxlen=1024)
        # Lineage for object reconstruction (parity: TaskManager keeps
        # specs of finished tasks while their outputs are referenced,
        # reference_count lineage pinning; bounded like
        # RAY_max_lineage_bytes).  Keyed by return ObjectID → task spec.
        self._lineage: "_collections.OrderedDict[ObjectID, _PendingTask]" = \
            _collections.OrderedDict()
        self._lineage_cap = 10000
        # Where each task output's primary copy lives (parity: the
        # object directory's location view).
        self._object_locations: Dict[ObjectID, NodeID] = {}
        # Reconstruction bookkeeping: in-flight task specs (by identity)
        # and attempts per spec, bounded by max_retries (parity: the
        # reference counts reconstruction against the retry budget).
        self._reconstructing: set = set()
        self._recon_attempts: Dict[int, int] = {}
        # Daemon-dispatched (external) tasks in flight: task_bin →
        # {"pt", "node_hex", "acquired"} (see register_external_task).
        self._external: Dict[bytes, Dict[str, Any]] = {}
        # Completion casts with no matching register: same-epoch
        # reordering CANNOT happen (local_task/done/failed ride the
        # node channel's serial FIFO lane — wire.py serial_ops, which
        # is load-bearing, do not remove it); what lands here is
        # stale-epoch garbage after a head restart, absorbed bounded
        # and consumed by a register only in pathological replays.
        self._external_early: Dict[bytes, Dict[str, Any]] = {}
        # Running normal tasks, for cancellation: task_id → {"pt", and
        # "thread" (thread mode) or "worker" (process mode)} (parity:
        # the executing-tasks map HandleCancelTask consults).
        self._running_tasks: Dict[TaskID, Dict[str, Any]] = {}
        # Serializes all bundle (re-)reservation: concurrent node events
        # must not double-place the same pending bundle.
        self._pg_reserve_lock = threading.Lock()
        # Readers hitting a lost object trigger lazy lineage
        # reconstruction (parity: recovery on fetch failure).
        self.store.lost_object_callback = self._reconstruct_object
        # Ownership / reference counting (parity: ReferenceCounter,
        # reference_count.h:61): local handles via ObjectRef hooks,
        # seal pins for in-flight task returns, borrows from worker
        # processes, nested pins from sealed values.  Zero → the free
        # thread releases the store copy and this object's lineage
        # entry (which in turn drops the task spec's argument handles —
        # lineage bounded by the ref count).
        from ray_tpu.core import object_ref as _object_ref
        from ray_tpu.core.refcount import ReferenceCounter

        self.refs = ReferenceCounter(self._on_refs_zero)
        # RLock: release_stream (reachable from generator __del__ via
        # the defer path, and directly in tests) takes it while seal
        # callbacks may be on the same stack.
        self._seal_pin_lock = threading.RLock()
        self._seal_pinned: set = set()
        # Streams whose consumer generator was dropped: items the
        # producer seals afterwards are released on arrival instead of
        # leaking (bounded tombstone ring).
        from ray_tpu.core.refcount import TombstoneSet

        self._dropped_streams = TombstoneSet(4096)
        self.store.on_sealed = self._on_object_sealed
        self.store.on_nested = self.refs.add_nested
        # Cross-node object plane: pull remote primary copies through
        # the owning daemon's channel; free them when refs hit zero.
        self.store.fetch_remote = self._fetch_remote_bytes
        self.store.release_remote = self._release_remote
        self._ref_hooks = (self.refs.add_local, self.refs.remove_local)
        _object_ref.install_ref_hooks(*self._ref_hooks)
        # Execution backend: thread (in-process) or pooled OS worker
        # processes over the shared-memory object plane (parity: the
        # raylet's WorkerPool of forked language workers,
        # raylet/worker_pool.h:156).  RAYTPU_WORKERS=process.
        self.worker_mode = cfg.workers
        self.worker_pool = None
        # Cluster log plane (parity: per-node log files + log_monitor.py
        # tailing them + dashboard log views): this node's workers write
        # to log_dir; the monitor ships complete lines to the LogBuffer;
        # remote daemons ship theirs over the head channel.
        from ray_tpu.core.pubsub import Publisher
        from ray_tpu.util.log_monitor import LogBuffer

        # General pubsub channels (parity: GCS pubsub, publisher.h:307
        # — node/actor/logs/error channels, long-poll subscribers).
        self.pubsub = Publisher()
        self.logs = LogBuffer(cfg.log_buffer_lines)
        self.log_dir = None
        self._log_monitor = None
        if self.worker_mode == "process":
            from ray_tpu.core.worker_pool import WorkerPool
            from ray_tpu.util.log_monitor import (
                LogMonitor,
                resolve_log_dir,
            )

            self.log_dir = resolve_log_dir()
            self.worker_pool = WorkerPool(self)
            self._log_monitor = LogMonitor(
                self.log_dir, self._publish_local_logs,
                cfg.log_monitor_period_s)
        # Control-plane persistence (parity: Redis-backed GCS storage —
        # KV + detached-actor specs + detached PG specs survive a
        # driver restart, gcs/store_client/redis_store_client.h:33).
        self._detached_specs: Dict[str, bytes] = {}
        # Restored detached-actor specs that could not place yet (no
        # capacity at restart — e.g. the head came back before its
        # daemons rejoined).  add_node retries them (parity: pending
        # GCS actor-table entries placed on node add).
        self._pending_restores: Dict[str, bytes] = {}
        self._rejoin_lock = threading.Lock()
        self._persist = None
        self._restored_tables = None
        if cfg.gcs_persist_path:
            from ray_tpu.core.gcs_persistence import GcsPersistence

            self._persist = GcsPersistence(
                cfg.gcs_persist_path, cfg.gcs_flush_period_s,
                mirror_paths=[p.strip() for p in
                              cfg.gcs_persist_mirrors.split(",")
                              if p.strip()],
            )
            self._restored_tables = self._persist.load()
            if self._restored_tables:
                self.kv.restore(self._restored_tables.get("kv") or {})
            self.kv.on_mutate = self._persist.mark_dirty
        self.head_node_id = self.add_node(total, labels)
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, name="dispatcher", daemon=True
        )
        self._dispatcher.start()
        # Detached actors re-create AFTER the dispatcher is live (their
        # constructors may submit work).  Parity: GCS restart replays
        # the actor table and reschedules detached actors
        # (gcs_init_data.cc + GcsActorManager::Initialize).
        if self._restored_tables:
            self._restore_detached(self._restored_tables)
        self._restored_tables = None  # only needed during init
        if self._persist is not None:
            self._persist.start_flusher(self._gcs_tables)

    # -- cluster membership ------------------------------------------------

    def add_node(self, resources: Dict[str, float],
                 labels: Optional[Dict[str, str]] = None,
                 node_id: Optional[NodeID] = None) -> NodeID:
        if node_id is None:
            node_id = NodeID.from_random()
        int_id = next(self._node_int_ids)
        native = ((self._native_sched, int_id)
                  if self._native_sched is not None else None)
        node = NodeState(node_id, dict(resources), labels,
                         native=native, int_id=int_id)
        with self._lock:
            self._nodes[node_id] = node
            self._node_order.append(node_id)
            self._nodes_by_int[int_id] = node
            pending_pgs = [st for st in self._pgs.values()
                           if not st.removed
                           and any(b.node_id is None for b in st.bundles)]
        self._topology_epoch += 1
        self._feasible_cache.clear()  # new capacity changes feasibility
        # Register with the native scheduler LAST: the node must not be
        # natively pickable before the Python tables can map it back.
        if self._native_sched is not None:
            self._native_sched.add_node(int_id, dict(resources))
        # New capacity may satisfy pending placement groups
        # (parity: GcsPlacementGroupManager::OnNodeAdd retry).
        for st in pending_pgs:
            self._reserve_bundles(
                st, [b for b in st.bundles if b.node_id is None]
            )
        if getattr(self, "_pending_restores", None):
            threading.Thread(target=self._retry_detached_restores,
                             daemon=True, name="detached-restore").start()
        self.pubsub.publish("node", {
            "event": "added", "node_id": node_id.hex(),
            "resources": dict(resources),
        })
        self._notify()
        return node_id

    def register_remote_node(self, agent, resources: Dict[str, float],
                             labels: Optional[Dict[str, str]],
                             addr: Tuple[str, int]) -> NodeID:
        """Register a node daemon that joined over TCP (parity: raylet
        registration with the GCS, gcs_node_manager.cc RegisterNode).
        The daemon owns its local worker pool + shm arena; the head
        schedules onto it like any node and dispatches over ``agent``."""
        node_id = self.add_node(resources, labels)
        with self._lock:
            node = self._nodes[node_id]
            node.agent = agent
            node.addr = tuple(addr)
        agent.bind(self, node)
        return node_id

    def rejoin_remote_node(self, agent, node_id_bin: bytes,
                           resources: Dict[str, float],
                           labels: Optional[Dict[str, str]],
                           addr: Tuple[str, int],
                           objects: List[Tuple[bytes, int]]):
        """A daemon that was already a cluster member reconnects —
        either this head restarted (its node table is empty) or the
        daemon's channel blipped.  Returns ``(node_id, accepted)``:
        ``accepted=False`` tells the daemon its previous identity is
        stale (the head declared it dead and rescheduled its work) and
        it must re-register fresh.  On acceptance the daemon keeps its
        node id and its advertised objects are re-pinned as locations
        (parity: raylets re-registering with a Redis-recovered GCS,
        gcs/gcs_server/gcs_server.cc:517-518 + gcs_node_manager
        re-registration; object locations re-reported by the owner)."""
        want = NodeID(node_id_bin)
        # One rejoin admitted per node id: a daemon that redialed while
        # its first attempt was still registering must not double-insert
        # the id into the node tables (add_node takes _lock repeatedly,
        # so the exists-check alone is not atomic with the insert).
        with self._rejoin_lock:
            with self._lock:
                existing = self._nodes.get(want)
            if existing is not None:
                # The head never restarted: it has already declared this
                # node dead (channel close → kill_node) and recovered
                # its actors/objects elsewhere — or a concurrent rejoin
                # already won.  Resurrecting the id would race that.
                return want, False
            node_id = self.add_node(resources, labels, node_id=want)
        with self._lock:
            node = self._nodes[node_id]
            node.agent = agent
            node.addr = tuple(addr)
        agent.bind(self, node)
        # Re-pin the daemon's surviving objects: location table + store
        # remote-seal marks + a borrow keyed under the node so the pins
        # evaporate if the node later dies.
        node_hex = node_id.hex()
        restore_key = node_hex[:12] + "/restored"
        for oid_bin, size in objects:
            oid = ObjectID(oid_bin)
            if self.store.is_freed(oid):
                continue
            self.seal_remote_at(oid, node_hex, size)
            self.refs.add_borrow(restore_key, oid)
        return node_id, True

    def seal_remote_at(self, oid: ObjectID, node_hex: str,
                       size: int) -> None:
        """Record a seal whose bytes live in a remote daemon's arena:
        store marks the location; the location table feeds node-death
        recovery (parity: object directory location update)."""
        self.store.mark_remote_sealed(oid, node_hex, size)
        with self._lock:
            node = next((n for n in self._nodes.values()
                         if n.node_id.hex() == node_hex), None)
            if node is not None:
                self._object_locations[oid] = node.node_id

    def node_by_hex(self, node_hex: str) -> Optional[NodeState]:
        with self._lock:
            for n in self._nodes.values():
                if n.node_id.hex() == node_hex:
                    return n
        return None

    def kill_node(self, node_id: NodeID) -> None:
        """Mark a node dead; its actors die (restartable ones restart
        elsewhere), its PG bundles are re-reserved on surviving nodes
        (parity: GcsNodeManager death → actor fate + bundle reschedule)."""
        with self._lock:
            node = self._nodes.get(node_id)
            if node is None or not node.alive:
                return
            node.alive = False
            self._topology_epoch += 1
            self._feasible_cache.clear()
            if self._native_sched is not None:
                self._native_sched.kill_node(node.int_id)
            doomed = [self._actors[a] for a in list(node.actor_ids)
                      if a in self._actors]
        if node.agent is not None:
            # Borrows held by the dead node's workers evaporate (their
            # keys are namespaced under the node id), and the channel
            # closes (idempotent if the close is what killed the node).
            self.refs.drop_worker_prefix(node_id.hex()[:12] + "/")
            node.agent.close()
        for shell in doomed:
            shell.death_reason = "node died"
            shell.dead = True
            shell._post_kill()
        # Re-reserve PG bundles that lived on this node.
        with self._lock:
            pgs = list(self._pgs.values())
        for st in pgs:
            lost = [b for b in st.bundles
                    if b.node_id == node_id and not st.removed]
            for b in lost:
                with b.lock:
                    b.node_id = None
                    b.available = {}
            if lost:
                self._reserve_bundles(st, lost)
        self._recover_lost_objects(node_id)
        self._reroute_external_on_node_death(node_id.hex())
        self.pubsub.publish("node", {"event": "died",
                                     "node_id": node_id.hex()})
        self._notify()

    def _recover_lost_objects(self, node_id: NodeID) -> None:
        """Objects whose primary copy lived on the dead node are
        invalidated.  Retriable outputs stay in the "lost" state until a
        reader fetches them, which triggers lazy lineage reconstruction
        (parity: ObjectRecoveryManager recovers on fetch, not on node
        death — no eager replay of side effects for outputs nobody
        reads).  Non-retriable outputs are sealed with ObjectLostError.
        ray.put objects have no lineage and live on the driver node, so
        they are never in the location map (parity: put objects are not
        reconstructable)."""
        from ray_tpu.core.exceptions import ObjectLostError

        with self._lock:
            lost = [oid for oid, nid in self._object_locations.items()
                    if nid == node_id]
            for oid in lost:
                del self._object_locations[oid]
            unrecoverable = [
                oid for oid in lost
                if (pt := self._lineage.get(oid)) is None
                or pt.options.max_retries == 0
            ]
        for oid in lost:
            invalidated = self.store.invalidate(oid)
            if invalidated and oid in unrecoverable:
                self.store.put_error(oid, ObjectLostError(oid.hex()))
        # Tasks parked on a just-lost dep would otherwise wait for a
        # reconstruction nobody triggers (recovery is fetch-lazy, and a
        # parked task never fetches) — kick it for them now.
        with self._dispatch_cv:
            parked_lost = [oid for oid in lost if oid in self._waiting_deps]
        for oid in parked_lost:
            self._reconstruct_object(oid)

    def _reconstruct_object(self, oid: ObjectID) -> None:
        """Resubmit the creating task of a lost object (parity:
        ObjectRecoveryManager::ReconstructObject via
        TaskManager::ResubmitTask).  Idempotent while a rebuild is in
        flight; attempts are bounded by the task's max_retries."""
        from ray_tpu.core.exceptions import ObjectLostError

        with self._lock:
            pt = self._lineage.get(oid)
            if pt is None:
                pt_missing = True
            elif pt.task_id.binary() in self._external:
                # Still running on its daemon: the node-death reroute
                # owns re-enqueue; a fetch-triggered rebuild here would
                # double-run it.
                return
            else:
                pt_missing = False
                key = id(pt)
                if key in self._reconstructing:
                    return
                attempts = self._recon_attempts.get(key, 0)
                if attempts >= max(1, pt.options.max_retries):
                    exhausted = True
                else:
                    exhausted = False
                    self._hydrate_external(pt)  # no-op for normal tasks
                    self._recon_attempts[key] = attempts + 1
                    self._reconstructing.add(key)
                    options = pt.options
                    strategy = options.effective_strategy()
                    if isinstance(strategy, NodeAffinitySchedulingStrategy):
                        want = (strategy.node_id.hex()
                                if isinstance(strategy.node_id, NodeID)
                                else str(strategy.node_id))
                        alive = any(n.alive and n.node_id.hex() == want
                                    for n in self._nodes.values())
                        if not alive:
                            # Pinned node is gone; rebuild anywhere.
                            options = dataclasses.replace(
                                options, scheduling_strategy="DEFAULT"
                            )
                    fresh = dataclasses.replace(
                        pt, options=options,
                        retries_left=options.max_retries,
                        on_done=lambda k=key: self._reconstructing.discard(k),
                    )
        if pt_missing:
            self.store.put_error_if_pending(oid, ObjectLostError(oid.hex()))
            return
        if exhausted:
            for roid in pt.return_ids:
                self.store.put_error_if_pending(
                    roid, ObjectLostError(roid.hex())
                )
            return
        self._enqueue_task(fresh)

    def _alive_nodes(self) -> List[NodeState]:
        return [self._nodes[i] for i in self._node_order
                if self._nodes[i].alive]

    # -- cross-node object plane -------------------------------------------

    def _fetch_remote_bytes(self, node_hex: str, oid: ObjectID,
                            size: int) -> bytes:
        """Pull one object's framed bytes from the node daemon that
        holds its primary copy (parity: PullManager → remote object
        manager chunk transfer)."""
        node = self.node_by_hex(node_hex)
        if node is None or not node.alive or node.agent is None:
            raise OSError(f"object {oid.hex()}: node {node_hex} is gone")
        return node.agent.pull(oid, size)

    def _release_remote(self, node_hex: Optional[str],
                        oid: ObjectID) -> None:
        """Free node-side copies of a released object.  Broadcast to
        every joined daemon: replicas pulled by consumer nodes are not
        location-tracked at the head (parity trade-off vs the
        reference's per-copy object directory), and the cast is a
        fire-and-forget socket write — cheap at this scale."""
        with self._lock:
            agents = [n.agent for n in self._nodes.values()
                      if n.agent is not None and n.alive]
        for agent in agents:
            agent.free([oid.binary()])

    # -- control-plane persistence -----------------------------------------

    def _gcs_tables(self) -> Dict[str, Any]:
        """Durable control-plane snapshot (parity: the GCS tables Redis
        holds: KV, actor specs for detached actors, PG specs)."""
        with self._lock:
            detached = dict(self._detached_specs)
            pgs = [
                {"bundles": [dict(b.resources) for b in st.bundles],
                 "strategy": st.pg.strategy, "name": st.pg.name}
                for st in self._pgs.values()
                if st.lifetime == "detached" and st.pg.name
                and not st.removed
            ]
        return {"kv": self.kv.dump(), "detached_actors": detached,
                "detached_pgs": pgs}

    def _mark_gcs_dirty(self) -> None:
        if self._persist is not None:
            self._persist.mark_dirty()

    def _restore_detached(self, tables: Dict[str, Any]) -> None:
        """Re-create persisted detached actors/PGs.  Actor memory state
        is NOT recovered — same contract as the reference restarting a
        detached actor after its process died (checkpoint in the actor
        if its state matters)."""
        import cloudpickle as _cp

        for spec in tables.get("detached_pgs") or ():
            try:
                self.create_placement_group(
                    spec["bundles"], spec["strategy"], spec["name"],
                    "detached",
                )
            except Exception:
                pass  # e.g. name re-taken; best-effort replay
        for name, blob in (tables.get("detached_actors") or {}).items():
            try:
                cls, args, kwargs, options = _cp.loads(blob)
                # Bounded wait: a cluster that shrank since the snapshot
                # must skip unplaceable actors, not hang init forever.
                self.create_actor(cls, args, kwargs, options,
                                  alloc_timeout=5.0)
            except Exception:
                # Unplaceable/unreplayable NOW ≠ gone: keep the spec in
                # the durable table so a later restart with capacity can
                # still recover it (parity: an unplaceable detached
                # actor stays pending in the GCS actor table), and queue
                # it for retry when capacity joins (daemons rejoin a
                # restarted head AFTER its init).
                with self._lock:
                    self._detached_specs.setdefault(name, blob)
                    self._pending_restores.setdefault(name, blob)

    def _retry_detached_restores(self) -> None:
        """Retry restored-but-unplaced detached actors after a node
        joined.  Every queued spec gets one attempt per round — a spec
        that still cannot place must not strand later specs that can."""
        import cloudpickle as _cp

        with self._lock:
            pending = dict(self._pending_restores)
            self._pending_restores.clear()
        failed: Dict[str, bytes] = {}
        for name, blob in pending.items():
            try:
                cls, args, kwargs, options = _cp.loads(blob)
            except Exception:
                continue  # unreplayable spec; durable table keeps it
            with self._lock:
                taken = bool(options.name
                             and options.name in self._named_actors)
            if taken:
                continue  # someone already (re)created it
            try:
                self.create_actor(cls, args, kwargs, options,
                                  alloc_timeout=5.0)
            except Exception:
                # Still unplaceable (or lost a create race): back in
                # the queue; the next node join retries.
                failed[name] = blob
        if failed:
            with self._lock:
                for name, blob in failed.items():
                    self._pending_restores.setdefault(name, blob)

    # -- objects -----------------------------------------------------------

    def put(self, value: Any) -> ObjectRef:
        oid = self.alloc_put_oid()
        self.store.put_value(oid, value)
        return ObjectRef(oid)

    def alloc_put_oid(self) -> ObjectID:
        """Fresh put-object id (also used for worker-side puts that
        write the bytes directly into the shared arena)."""
        return ObjectID.from_put(self.driver_task_id,
                                 next(self._put_counter))

    # -- ownership / GC ----------------------------------------------------

    def _record_lineage_locked(self, return_ids: Sequence[ObjectID],
                               pt: _PendingTask) -> None:
        """Insert into the lineage table with cap eviction.  Evicting
        lineage also drops the location entry and reconstruction
        counters — the three tables stay bounded together.  Caller
        holds _lock."""
        for oid in return_ids:
            self._lineage[oid] = pt
        while len(self._lineage) > self._lineage_cap:
            old_oid, old_pt = self._lineage.popitem(last=False)
            self._object_locations.pop(old_oid, None)
            self._recon_attempts.pop(id(old_pt), None)

    def _pin_returns(self, return_ids: Sequence[ObjectID]) -> None:
        """Pin task-return oids from submission until seal, so dropping
        the future before the task finishes can't free the slot under
        the executor (parity: submitted-task return refs)."""
        with self._seal_pin_lock:
            for oid in return_ids:
                self.refs.add_seal_pin(oid)
                self._seal_pinned.add(oid)

    def _on_object_sealed(self, oid: ObjectID) -> None:
        with self._seal_pin_lock:
            pinned = oid in self._seal_pinned
            if pinned:
                self._seal_pinned.discard(oid)
            dropped_stream = (self._dropped_streams
                              and oid.task_id() in self._dropped_streams)
        if pinned:
            self.refs.remove_seal_pin(oid)
        if dropped_stream:
            # Item sealed into an abandoned stream — nobody can ever
            # consume it (the generator is gone); release on arrival.
            self.store.release(oid)
        # Dependency wakeup (parity: DependencyManager::HandleObjectLocal
        # moving tasks to ready) — tasks parked on this oid whose last
        # missing dep just sealed go to the ready queue.
        if self._waiting_deps:
            with self._dispatch_cv:
                waiters = self._waiting_deps.pop(oid, None)
                if waiters:
                    woke = False
                    for pt in waiters:
                        if pt.waiting_on is not None:
                            pt.waiting_on.discard(oid)
                        if not pt.waiting_on:
                            pt.waiting_on = None
                            self._pending.append(pt)
                            woke = True
                    if woke:
                        self._dispatch_cv.notify_all()

    def _on_refs_zero(self, oid: ObjectID) -> None:
        """Free thread: last reference to ``oid`` dropped.  Release the
        store copy and this object's lineage/location entries; dropping
        the lineage task spec releases its argument handles, cascading
        the collection upstream (parity: lineage_ref_count_)."""
        with self._lock:
            self._lineage.pop(oid, None)
            self._object_locations.pop(oid, None)
        self.store.release(oid, tombstone=True)

    def release_stream_async(self, task_id: TaskID, from_index: int) -> None:
        """GC-safe entry for generator __del__: defers the release to
        the free thread (release_stream takes store/runtime locks that
        may already be held by the thread a GC pause interrupted)."""
        self.refs.defer(lambda: self.release_stream(task_id, from_index))

    def release_stream(self, task_id: TaskID, from_index: int) -> None:
        """A dropped ObjectRefGenerator releases sealed-but-unconsumed
        stream items (consumed items have their own counted handles).
        The stream is also marked dropped FIRST, so items a still-running
        producer seals after this scan are released on arrival
        (_on_object_sealed) instead of leaking."""
        with self._seal_pin_lock:
            self._dropped_streams.add(task_id)
        i = from_index
        while True:
            oid = ObjectID.for_task_return(task_id, i)
            if not self.store.contains(oid):
                return
            self.store.release(oid)
            i += 1

    def _wire_args(self, args: tuple, kwargs: dict):
        """Replace top-level ObjectRef args with their WIRE
        representation for shipping to a worker process — shared-arena
        pointers for large objects, framed bytes otherwise.  Never
        deserializes here (the worker does the one decode); sealed
        errors re-raise, matching resolve_args semantics."""
        from ray_tpu.core.wire import WireRef

        def enc(v):
            if not isinstance(v, ObjectRef):
                return v
            kind, payload = self.store.get_wire_loc(v.id)
            if kind == "err":
                raise payload
            if kind == "at":
                # Remote primary copy: ship the location marker; the
                # executing worker fetches through its node daemon
                # (local-arena hit when the task landed on the owning
                # node — the common consumer-follows-producer case).
                return WireRef("fetch", payload[1], v.id.binary())
            return WireRef(kind, payload, v.id.binary())

        return (tuple(enc(a) for a in args),
                {k: enc(v) for k, v in kwargs.items()})

    def get(self, refs, timeout: Optional[float] = None):
        single = isinstance(refs, ObjectRef)
        ref_list = [refs] if single else list(refs)
        out = [self.store.get(r.id, timeout) for r in ref_list]
        return out[0] if single else out

    def wait(self, refs: Sequence[ObjectRef], num_returns: int,
             timeout: Optional[float], fetch_local: bool = True):
        ids = [r.id for r in refs]
        ready_ids, pending_ids = self.store.wait(ids, num_returns, timeout)
        by_id = {r.id: r for r in refs}
        return [by_id[i] for i in ready_ids], [by_id[i] for i in pending_ids]

    def resolve_args(self, args: tuple, kwargs: dict) -> Tuple[tuple, dict]:
        """Replace top-level ObjectRef args with their values
        (parity: LocalDependencyResolver inlining).  Wire-form specs
        (nested submissions) carry WireRef("fetch") markers instead of
        handles — resolve those too so a re-enqueued external task can
        execute in-process."""
        from ray_tpu.core.wire import WireRef

        def res(v):
            if isinstance(v, ObjectRef):
                return self.get(v)
            if isinstance(v, WireRef) and v.kind == "fetch":
                return self.get(ObjectRef(ObjectID(v.oid)))
            return v

        return tuple(res(a) for a in args), {k: res(v) for k, v in kwargs.items()}

    def _task_arg_oids(self, pt: _PendingTask) -> List[ObjectID]:
        if pt.arg_oids is not None:
            return pt.arg_oids
        return [v.id for v in list(pt.args) + list(pt.kwargs.values())
                if isinstance(v, ObjectRef)]

    def _enqueue_task(self, pt: _PendingTask) -> None:
        """Queue for dispatch: straight to the ready queue when every
        ObjectRef arg is local, else parked in the dependency index to
        be woken by the seal callback (parity: DependencyManager
        subscribe → wake, no polling).  The registration and the seal
        callback's resolution both run under _dispatch_cv, so a seal
        racing this enqueue either makes contains() true here or finds
        the parked entry there — never neither."""
        with self._dispatch_cv:
            missing = []
            for oid in self._task_arg_oids(pt):
                if not self.store.contains(oid):
                    missing.append(oid)
                    if self.store._state(oid).lost:
                        # Parked fetcher triggers recovery (parity: the
                        # dependency resolver's recovery path).
                        self._reconstruct_object(oid)
            if missing:
                self._park_locked(pt, missing)
                return
            pt.waiting_on = None
            self._pending.append(pt)
            self._dispatch_cv.notify_all()

    def _park_locked(self, pt: _PendingTask,
                     missing: List[ObjectID]) -> None:
        """Park in the dependency index; caller holds _dispatch_cv.
        After registering, re-check each dep: the seal callback's
        UNLOCKED emptiness fast-path may have skipped a wakeup while we
        were parking — the locked contains() re-check closes that race."""
        pt.waiting_on = set(missing)
        for oid in pt.waiting_on:
            self._waiting_deps.setdefault(oid, []).append(pt)
        for oid in list(pt.waiting_on):
            if self.store.contains(oid):
                pt.waiting_on.discard(oid)
                lst = self._waiting_deps.get(oid)
                if lst is not None:
                    try:
                        lst.remove(pt)
                    except ValueError:
                        pass
                    if not lst:
                        del self._waiting_deps[oid]
        if not pt.waiting_on:
            pt.waiting_on = None
            self._pending.append(pt)
            self._dispatch_cv.notify_all()

    def _deps_still_ready_locked(self, pt: _PendingTask) -> bool:
        """Cheap pre-dispatch re-check: a dep sealed at enqueue time may
        have been invalidated since (node death).  Re-parks the task and
        kicks reconstruction if so.  Caller holds _dispatch_cv."""
        missing = []
        for oid in self._task_arg_oids(pt):
            if not self.store.contains(oid):
                missing.append(oid)
                if self.store._state(oid).lost:
                    self._reconstruct_object(oid)
        if not missing:
            return True
        self._park_locked(pt, missing)
        return False

    def _store_results(self, result: Any, return_ids: List[ObjectID],
                       num_returns: int):
        if num_returns == 1:
            self.store.put_value(return_ids[0], result)
        else:
            values = list(result)
            if len(values) != num_returns:
                raise ValueError(
                    f"task declared num_returns={num_returns} but returned "
                    f"{len(values)} values"
                )
            for oid, v in zip(return_ids, values):
                self.store.put_value(oid, v)

    def _stream_results(self, result: Any, task_id: TaskID,
                        function_name: str) -> None:
        """Seal each yielded item at its return index as it is produced,
        then the end-of-stream sentinel (parity: the streaming-generator
        executor in _raylet.pyx:918).  Mid-stream errors are sealed at
        the failing index and re-raised."""
        from ray_tpu.core.generator import EndOfStream

        i = 0
        try:
            if not hasattr(result, "__iter__"):
                raise TypeError(
                    f"streaming task {function_name!r} must return an "
                    f"iterable/generator, got {type(result).__name__}"
                )
            for item in result:
                self.store.put_value(
                    ObjectID.for_task_return(task_id, i), item
                )
                i += 1
        except BaseException as e:
            # Seal the error at the failing index so the consumer's
            # next() unblocks with an error ref instead of hanging.
            self.store.put_error(
                ObjectID.for_task_return(task_id, i),
                e if isinstance(e, TaskError) else TaskError(
                    function_name, e
                ),
            )
            raise
        self.store.put_error(
            ObjectID.for_task_return(task_id, i), EndOfStream()
        )

    def _seal_stream_failure(self, task_id: TaskID,
                             err: BaseException) -> None:
        """Seal ``err`` at the first UNSEALED stream index.  A worker
        process that dies mid-stream leaves a sealed prefix [0, k);
        sealing only index 0 would leave a consumer already past it
        blocked forever on index k."""
        i = 0
        while True:
            oid = ObjectID.for_task_return(task_id, i)
            if self.store.is_freed(oid):
                # Consumed-and-dropped index (refcount freed it): not
                # the first unsealed — keep scanning, or the consumer
                # hangs at the real one.
                i += 1
                continue
            if self.store.put_error_if_pending(oid, err):
                return
            if self.store.peek_error(oid) is not None:
                # Already ended (error or EndOfStream sentinel) — the
                # consumer can't hang; don't clobber.
                return
            i += 1

    # -- scheduling --------------------------------------------------------

    def _feasible(self, demand: Dict[str, float], strategy: Any) -> bool:
        """Memoized _cluster_can_fit for hashable (string) strategies;
        the cache clears whenever cluster topology changes."""
        if not isinstance(strategy, str):
            return self._cluster_can_fit(demand, strategy)
        key = (tuple(sorted(demand.items())), strategy)
        cached = self._feasible_cache.get(key)
        if cached is not None:
            return cached
        epoch = self._topology_epoch
        ok = self._cluster_can_fit(demand, strategy)
        if epoch == self._topology_epoch and len(self._feasible_cache) < 1024:
            self._feasible_cache[key] = ok
        return ok

    def _cluster_can_fit(self, demand: Dict[str, float],
                         strategy: Any = "DEFAULT") -> bool:
        """Strategy-aware feasibility: a hard affinity/label constraint
        that no live node can ever satisfy must fail at submission, not
        hang (parity: Ray's unschedulable-task error)."""
        nodes = self._alive_nodes()
        if (isinstance(strategy, NodeAffinitySchedulingStrategy)
                and not strategy.soft):
            want = (strategy.node_id.hex()
                    if isinstance(strategy.node_id, NodeID)
                    else str(strategy.node_id))
            nodes = [n for n in nodes if n.node_id.hex() == want]
        elif isinstance(strategy, NodeLabelSchedulingStrategy):
            nodes = [n for n in nodes if n.matches_labels(strategy.hard)]
        return any(n.pool.can_fit(demand) for n in nodes)

    def _try_allocate(self, demand: Dict[str, float],
                      strategy: Any) -> Optional[_Allocation]:
        """Cluster phase of the two-phase scheduler: pick a node (or PG
        bundle) and acquire resources.  Returns None when nothing fits
        right now (parity: ClusterTaskManager::QueueAndScheduleTask +
        HybridSchedulingPolicy)."""
        if isinstance(strategy, PlacementGroupSchedulingStrategy):
            st = self._pgs.get(strategy.placement_group.id)
            if st is None or st.removed:
                raise ValueError("placement group removed or unknown")
            idx = strategy.placement_group_bundle_index
            if idx >= len(st.bundles):
                raise ValueError(
                    f"bundle index {idx} out of range for a "
                    f"{len(st.bundles)}-bundle placement group"
                )
            candidates = (st.bundles if idx < 0 else [st.bundles[idx]])
            if not any(all(b.resources.get(k, 0) >= v
                           for k, v in demand.items())
                       for b in candidates):
                raise ValueError(
                    f"demand {demand} exceeds every candidate bundle's "
                    f"reservation — infeasible"
                )
            for b in candidates:
                if b.node_id is not None and b.try_acquire(demand):
                    node = self._nodes.get(b.node_id)
                    return _Allocation(node, b, demand)
            return None

        nodes = self._alive_nodes()
        if isinstance(strategy, NodeAffinitySchedulingStrategy):
            want = (strategy.node_id.hex()
                    if isinstance(strategy.node_id, NodeID)
                    else str(strategy.node_id))
            exact = [n for n in nodes if n.node_id.hex() == want]
            if exact and exact[0].pool.try_acquire(demand):
                return _Allocation(exact[0], None, demand)
            if not strategy.soft:
                return None
            nodes = [n for n in nodes if n.node_id.hex() != want] or nodes
            strategy = "DEFAULT"

        if isinstance(strategy, NodeLabelSchedulingStrategy):
            hard = [n for n in nodes if n.matches_labels(strategy.hard)]
            soft = [n for n in hard if n.matches_labels(strategy.soft)]
            for n in soft + [n for n in hard if n not in soft]:
                if n.pool.try_acquire(demand):
                    return _Allocation(n, None, demand)
            return None

        if self._native_sched is not None and strategy in ("SPREAD",
                                                           "DEFAULT"):
            # Atomic pick+acquire in the C++ core (one lock, no Python
            # loop races; parity: ClusterResourceScheduler picking under
            # the raylet's single-threaded executor).
            from ray_tpu.core import native_scheduler as _ns

            with self._lock:
                all_alive = len(nodes) == sum(
                    1 for nd in self._nodes.values() if nd.alive
                )
            cands = None if all_alive else [n.int_id for n in nodes]
            chosen = self._native_sched.pick_and_acquire(
                demand,
                _ns.SPREAD if strategy == "SPREAD" else _ns.HYBRID,
                candidates=cands,
            )
            if chosen is None:
                return None
            node = self._nodes_by_int.get(chosen)
            if node is None:  # can't happen post-registration ordering
                self._native_sched.release(chosen, demand)
                return None
            return _Allocation(node, None, demand)

        if strategy == "SPREAD":
            for n in sorted(nodes, key=lambda n: n.pool.utilization()):
                if n.pool.try_acquire(demand):
                    return _Allocation(n, None, demand)
            return None

        # DEFAULT hybrid: pack onto the first (stable-order) node below the
        # utilization threshold, else fall back to least-utilized
        # (parity: policy/hybrid_scheduling_policy.h:28-46, threshold 0.5).
        threshold = 0.5
        for n in nodes:
            if n.pool.utilization() < threshold and n.pool.try_acquire(demand):
                return _Allocation(n, None, demand)
        for n in sorted(nodes, key=lambda n: n.pool.utilization()):
            if n.pool.try_acquire(demand):
                return _Allocation(n, None, demand)
        return None

    # -- tasks -------------------------------------------------------------

    def _ship_env(self, renv):
        """Worker-bound runtime-env payload: plugins named by the env
        ship by value so the worker can materialize them (parity: the
        reference distributes plugin setup through the per-node
        runtime-env agent).  The pickled blob is memoized per
        (plugin set, registry version) — NOT re-pickled per dispatch."""
        if not renv:
            return None
        try:
            names = frozenset(renv.keys())
        except AttributeError:
            return None
        from ray_tpu import runtime_env as _re

        used = frozenset(k for k in _re._plugins if k in names)
        if not used:
            return None
        key = (used, _re._plugins_version)
        cache = getattr(self, "_env_plugin_cache", None)
        if cache is None or cache[0] != key:
            import cloudpickle

            cache = (key, cloudpickle.dumps(
                {k: _re._plugins[k] for k in used}))
            self._env_plugin_cache = cache
        return cache[1]

    def submit_task(self, fn: Callable, args: tuple, kwargs: dict,
                    options: TaskOptions,
                    trace_ctx: Optional[Dict[str, str]] = None,
                    arg_oids: Optional[List[ObjectID]] = None,
                    pin_oids: Optional[List[ObjectID]] = None,
                    ) -> List[ObjectRef]:
        demand = options.resource_demand()
        strategy = options.effective_strategy()
        if (not isinstance(strategy, PlacementGroupSchedulingStrategy)
                and not self._feasible(demand, strategy)):
            raise ValueError(
                f"task {getattr(fn, '__name__', fn)!r} demands {demand} "
                f"under {strategy!r}, which no node can ever satisfy — "
                f"infeasible"
            )
        task_id = TaskID.of(ActorID.nil_for_job(self.job_id))
        streaming = options.num_returns == "streaming"
        return_ids = [] if streaming else [
            ObjectID.for_task_return(task_id, i)
            for i in range(options.num_returns)
        ]
        self._pin_returns(return_ids)
        pt = _PendingTask(
            fn=fn, args=args, kwargs=kwargs, options=options,
            return_ids=return_ids,
            # Streaming tasks never retry: the consumer may already have
            # observed a prefix of the stream (see generator.py).
            retries_left=0 if streaming else options.max_retries,
            task_id=task_id, function_name=getattr(fn, "__name__", repr(fn)),
            streaming=streaming,
            trace_ctx=(trace_ctx if trace_ctx is not None
                       else _tracing().capture_context()),
        )
        pt.demand = demand  # computed once; dispatch + events reuse it
        if arg_oids is not None:
            # Nested submission with wire-form args: pin the explicit
            # deps AND the pin-only inner refs with head-side handles
            # (the normal path pins both via the ObjectRef instances
            # living inside pt.args).  Only arg_oids park the task.
            pt.arg_oids = arg_oids
            pt.arg_refs = [ObjectRef(o)
                           for o in arg_oids + list(pin_oids or ())]
        self.events.record(
            task_id.hex(), _ev.PENDING_NODE_ASSIGNMENT,
            name=pt.function_name, type=_ev.NORMAL_TASK,
            job_id=self.job_id.hex(), required_resources=demand,
        )
        if not streaming:
            with self._lock:
                self._record_lineage_locked(return_ids, pt)
        self._enqueue_task(pt)
        if streaming:
            from ray_tpu.core.generator import ObjectRefGenerator

            return ObjectRefGenerator(task_id)
        return [ObjectRef(oid) for oid in return_ids]

    def _dispatch_loop(self):
        """Event-driven dispatcher: sleeps until woken by a new ready
        task, a dependency seal, or a resource release (parity: the
        raylet scheduling on events, not a poll — the 1 s timeout is
        only a lost-wakeup safety net; round 1 polled every 20 ms)."""
        while True:
            with self._dispatch_cv:
                while not self._shutdown:
                    runnable = self._next_runnable_locked()
                    if runnable is not None:
                        break
                    self._dispatch_cv.wait(1.0)
                if self._shutdown:
                    return
            self._start_task(*runnable)

    def _next_runnable_locked(self):
        """Pop the first dispatchable ready task.  Head-pop is O(1) on
        the hot path (homogeneous tasks: the head either fits or
        nothing does); skipped tasks are restored in order."""
        skipped: List[_PendingTask] = []
        runnable = None
        try:
            while self._pending:
                pt = self._pending.popleft()
                if pt.cancelled:
                    continue  # cancel() already sealed its outputs
                # Dep liveness re-check: sealed-at-enqueue deps may have
                # been invalidated by a node death since.
                if not self._deps_still_ready_locked(pt):
                    continue  # re-parked (or re-appended, if it resolved)
                try:
                    alloc = self._try_allocate(
                        pt.demand if pt.demand is not None
                        else pt.options.resource_demand(),
                        pt.options.effective_strategy(),
                    )
                except ValueError as e:
                    err = TaskError(pt.function_name, e)
                    for oid in pt.return_ids:
                        self.store.put_error(oid, err)
                    if pt.streaming:
                        self.store.put_error(
                            ObjectID.for_task_return(pt.task_id, 0), err
                        )
                    self.events.record(
                        pt.task_id.hex(), _ev.FAILED, name=pt.function_name,
                        attempt=pt.options.max_retries - pt.retries_left,
                        error_message=str(e),
                    )
                    # Keep scanning: with no poll, returning here would
                    # stall runnable tasks behind a poisoned head for a
                    # full safety-net wait.
                    continue
                if alloc is not None:
                    runnable = (pt, alloc)
                    return runnable
                skipped.append(pt)
            return None
        finally:
            # Restore skipped tasks at the front, original order first.
            self._pending.extendleft(reversed(skipped))

    def _start_task(self, pt: _PendingTask, alloc: _Allocation):
        # Streaming tasks force retries_left=0, so derive their attempt
        # as 0 rather than max_retries - 0.
        attempt = (0 if pt.streaming
                   else pt.options.max_retries - pt.retries_left)

        def run():
            requeued = False
            if pt.cancelled:
                # Cancelled between scheduling and start: never run.
                self._seal_cancelled(pt.task_id, pt.return_ids,
                                     pt.streaming)
                if pt.on_done is not None:
                    pt.on_done()
                alloc.release()
                self._notify()
                return
            with self._lock:
                self._running_tasks[pt.task_id] = {
                    "pt": pt, "thread": threading.get_ident(),
                }
            self.events.record(
                pt.task_id.hex(), _ev.RUNNING, name=pt.function_name,
                attempt=attempt, job_id=self.job_id.hex(),
                node_id=(alloc.node.node_id.hex() if alloc.node else None),
                worker=threading.current_thread().name,
                required_resources=(pt.demand if pt.demand is not None
                                    else pt.options.resource_demand()),
            )
            try:
                pool = self._pool_for(alloc)
                if pool is not None:
                    with _tracing().task_span(
                        pt.function_name, pt.trace_ctx,
                        {"task_id": pt.task_id.hex(), "attempt": attempt},
                    ):
                        self._execute_task_remote(pt, pool)
                else:
                    args, kwargs = self.resolve_args(pt.args, pt.kwargs)
                    if pt.options.runtime_env:
                        from ray_tpu.runtime_env import materialize

                        env_cm = materialize(
                            pt.options.runtime_env).applied()
                    else:
                        env_cm = contextlib.nullcontext()
                    # The env must cover the whole body — for a
                    # streaming task the generator body runs inside
                    # _stream_results.
                    with env_cm, _tracing().task_span(
                        pt.function_name, pt.trace_ctx,
                        {"task_id": pt.task_id.hex(), "attempt": attempt},
                    ):
                        result = pt.fn(*args, **kwargs)
                        if pt.streaming:
                            self._stream_results(result, pt.task_id,
                                                 pt.function_name)
                    if not pt.streaming:
                        self._store_results(result, pt.return_ids,
                                            pt.options.num_returns)
                if not pt.streaming:
                    if alloc.node is not None:
                        with self._lock:
                            for oid in pt.return_ids:
                                self._object_locations[oid] = \
                                    alloc.node.node_id
                self.events.record(pt.task_id.hex(), _ev.FINISHED,
                                   attempt=attempt)
            except Exception as e:
                self.events.record(pt.task_id.hex(), _ev.FAILED,
                                   attempt=attempt, error_message=repr(e))
                cancelled = pt.cancelled or isinstance(e, TaskCancelledError)
                if cancelled:
                    # Cancelled tasks seal TaskCancelledError and NEVER
                    # retry (parity: cancellation beats max_retries).
                    self._seal_cancelled(
                        pt.task_id, pt.return_ids, pt.streaming,
                        err=e if isinstance(e, TaskCancelledError) else None,
                    )
                elif pt.streaming:
                    # Failures before/inside the stream must unblock the
                    # consumer at the first unsealed index (a worker
                    # process may have died after producing a prefix;
                    # in-process failures already sealed the failing
                    # index, making this a no-op there).
                    self._seal_stream_failure(
                        pt.task_id,
                        e if isinstance(e, TaskError)
                        else TaskError(pt.function_name, e),
                    )
                if not cancelled and pt.retries_left > 0:
                    pt.retries_left -= 1
                    requeued = True
                    self._enqueue_task(pt)
                elif not cancelled and not pt.streaming:
                    err = e if isinstance(e, TaskError) else TaskError(
                        pt.function_name, e
                    )
                    for oid in pt.return_ids:
                        self.store.put_error(oid, err)
                    # Retries exhausted: surface cluster-wide (parity:
                    # the GCS error-info channel).
                    self.pubsub.publish("error", {
                        "source": pt.function_name,
                        "task_id": pt.task_id.hex(),
                        "message": repr(e)[:500],
                    })
            finally:
                with self._lock:
                    self._running_tasks.pop(pt.task_id, None)
                    # Withdraw a too-late cancel UNDER the lock (cancel
                    # delivers under it too), so it can't hit an
                    # unrelated future task on this thread.
                    _clear_async_exc(threading.get_ident())
                # on_done (the reconstruction in-flight guard) must NOT
                # fire when the task was re-queued for retry — the work
                # is still in flight.
                if pt.on_done is not None and not requeued:
                    pt.on_done()
                alloc.release()
                self._notify()

        self._exec_pool.submit(run)

    def _pool_for(self, alloc: _Allocation):
        """Execution backend for an allocation: the remote node's daemon
        agent when the task landed on a joined node, else the head's
        local worker pool (None → thread-mode in-process execution)."""
        if alloc.node is not None and alloc.node.agent is not None:
            return alloc.node.agent
        return self.worker_pool

    def _execute_task_remote(self, pt: _PendingTask, pool=None) -> None:
        """Run one task on a leased worker process (parity: OnWorkerIdle
        pushing onto a leased worker, direct_task_transport.cc:191 →
        HandlePushTask, core_worker.cc:3072).  ``pool`` is the head's
        WorkerPool or a remote node's agent (same lease/release
        surface).  Raises the worker-side exception (or WorkerDiedError
        on a crash) so the caller's retry path treats remote failures
        exactly like local ones."""
        import cloudpickle

        if pool is None:
            pool = self.worker_pool
        wire_args, wire_kwargs = self._wire_args(pt.args, pt.kwargs)
        spec = cloudpickle.dumps((wire_args, wire_kwargs))
        fhash, fblob = self._export_fn(pt.fn)
        wh = pool.lease(tpu_chips=_tpu_chips(
            pt.demand if pt.demand is not None
            else pt.options.resource_demand()))
        with self._lock:
            entry = self._running_tasks.get(pt.task_id)
            if entry is not None:
                entry["worker"] = wh  # cancellation targets the process
        try:
            # Function ship-once (parity: the function manager exporting
            # a remote function to each worker once, keyed by hash —
            # python/ray/_private/function_manager.py): the pickled fn
            # rides only the worker's FIRST call; later calls send the
            # hash + args, which is most of the per-task pickle cost.
            shipped = getattr(wh, "shipped_fns", None)
            if shipped is None:
                shipped = wh.shipped_fns = set()
            rep = wh.call(
                "task", spec=spec, name=pt.function_name,
                fn_hash=fhash,
                fn_blob=(None if fhash in shipped else fblob),
                streaming=pt.streaming, task=pt.task_id.binary(),
                num_returns=pt.options.num_returns,
                returns=[oid.binary() for oid in pt.return_ids],
                env=pt.options.runtime_env,
                env_plugins=self._ship_env(pt.options.runtime_env),
                # Capture INSIDE the driver-side task span so nested
                # submissions from the worker parent to this task.
                trace_ctx=_tracing().capture_context(),
            )
            shipped.add(fhash)
        finally:
            pool.release(wh)
        wkey = self._worker_ref_key(wh)
        if pt.streaming:
            # The worker sealed every index + the sentinel.
            self.apply_ref_batches(rep, wkey)
            return
        self.seal_remote_results(pt.return_ids, rep, wkey,
                                 node_hex=getattr(wh, "node_hex", None))

    def _export_fn(self, fn) -> Tuple[str, bytes]:
        """(hash, pickled blob) of a task function, pickled once per fn
        object (parity: function-manager export; closure mutations
        after decoration do not re-export, as in the reference)."""
        cache = getattr(self, "_fn_blob_cache", None)
        if cache is None:
            import weakref

            cache = self._fn_blob_cache = weakref.WeakKeyDictionary()
            self._fn_blob_lock = threading.Lock()
        try:
            with self._fn_blob_lock:
                hit = cache.get(fn)
            if hit is not None:
                return hit
        except TypeError:
            hit = None  # unhashable/unweakrefable callable
        import hashlib

        import cloudpickle

        blob = cloudpickle.dumps(fn)
        fhash = hashlib.sha1(blob).hexdigest()[:16]
        try:
            with self._fn_blob_lock:
                cache[fn] = (fhash, blob)
        except TypeError:
            pass
        return fhash, blob

    @staticmethod
    def _worker_ref_key(wh) -> str:
        rk = getattr(wh, "ref_key", None)
        if rk is not None:
            return rk
        from ray_tpu.core.worker_pool import _wkey

        return _wkey(wh.chan)

    def apply_ref_batches(self, rep: Dict[str, Any], worker_key: str,
                          which: str = "both") -> None:
        """Apply borrow add/del batches piggybacked on a worker reply."""
        # Worker-finished spans and metric snapshots also ride the
        # reply (pop: this runs twice per reply on the sealing path —
        # add then rem).
        if isinstance(rep, dict):
            spans = rep.pop("spans", None)
            if spans and _tracing().is_enabled():
                _tracing().ingest(spans)
            snap = rep.pop("metrics", None)
            if snap:
                from ray_tpu.util import metrics as _metrics

                _metrics.merge_remote(worker_key, snap)
            reqev_rows = rep.pop("request_events", None)
            if reqev_rows:
                from ray_tpu.serve import request_events as _request_events

                _request_events.merge_remote(worker_key, reqev_rows)
            frec_events = rep.pop("flightrec", None)
            if frec_events:
                from ray_tpu.util import flight_recorder as _frec

                _frec.ingest(worker_key, frec_events)
            ts_points = rep.pop("timeseries", None)
            if ts_points:
                from ray_tpu.util import timeseries as _timeseries

                _timeseries.ingest(worker_key, ts_points)
        if which in ("both", "add"):
            for b in rep.get("ref_add") or ():
                self.refs.add_borrow(worker_key, ObjectID(b))
        if which in ("both", "rem"):
            for b in rep.get("ref_rem") or ():
                self.refs.remove_borrow(worker_key, ObjectID(b))

    def seal_remote_results(self, return_ids: Sequence[ObjectID],
                            rep: Dict[str, Any],
                            worker_key: Optional[str] = None,
                            node_hex: Optional[str] = None) -> None:
        """Seal a worker task reply's results.  Order matters: borrow
        ADDS first (they may cover refs inside the returned values),
        then nested pins, then the seal, then borrow DELS — so a del of
        a ref riding in the reply can never free it before its pin.
        ``node_hex`` set → the executing worker lives on a remote node
        daemon; "shm" entries stayed in THAT node's arena and seal as
        remote locations."""
        if worker_key is not None:
            self.apply_ref_batches(rep, worker_key, which="add")
        nested = rep.get("nested") or [()] * len(return_ids)
        for oid, (kind, payload), inner in zip(return_ids,
                                               rep["results"], nested):
            if inner:
                self.refs.add_nested(oid, [ObjectID(b) for b in inner])
            if kind == "shm":
                if node_hex:
                    self.seal_remote_at(oid, node_hex, payload)
                else:
                    self.store.mark_shm_sealed(oid, payload)
            else:
                self.store.put_serialized(oid, payload)
        if worker_key is not None:
            self.apply_ref_batches(rep, worker_key, which="rem")

    # -- daemon-dispatched (external) tasks --------------------------------
    #
    # Parity: raylet-local scheduling over the Ray Syncer's resource
    # view — a daemon dispatches its workers' nested submissions onto
    # its own pool and the head only does the owner-side bookkeeping,
    # off the submit critical path (see core/local_dispatch.py).

    def register_external_task(self, task_bin: bytes,
                               return_bins: List[bytes], spec: bytes,
                               options: TaskOptions,
                               deps: List[bytes],
                               demand: Dict[str, float],
                               submit_wkey: str, node_hex: str,
                               pins: Optional[List[bytes]] = None,
                               ) -> None:
        """Owner-side bookkeeping for a task a daemon dispatched
        locally: return-oid pins + submitter borrows, explicit-dep
        pins, lineage (lazily hydratable from ``spec``), events, and
        the cached-ledger debit.  Applied from the daemon's ordered
        cast, so it lands before any later ref-drop or get that could
        mention these ids."""
        task_id = TaskID(task_bin)
        return_ids = [ObjectID(b) for b in return_bins]
        self._pin_returns(return_ids)
        pt = _PendingTask(
            fn=None, args=(), kwargs={}, options=options,
            return_ids=return_ids, retries_left=options.max_retries,
            task_id=task_id,
            function_name=options.name or "nested",
            spec_blob=spec,
            arg_oids=[ObjectID(b) for b in deps],
        )
        pt.arg_refs = [ObjectRef(ObjectID(b))
                       for b in list(deps) + list(pins or ())]
        pt.demand = demand
        node = self.node_by_hex(node_hex)
        if node is None or not node.alive:
            # The daemon died between sending this cast and its
            # processing — the node-death reroute already ran (and
            # found nothing), and no completion cast will ever come.
            # Re-run through the normal scheduler instead of
            # registering an orphan (reconstruction explicitly skips
            # in-flight external tasks).  Safe double-run-wise: the
            # dead daemon's workers are killed on rejoin.
            self._hydrate_external(pt)
            with self._lock:
                self._record_lineage_locked(return_ids, pt)
            for b in return_bins:
                self.refs.add_borrow(submit_wkey, ObjectID(b))
            self._enqueue_task(pt)
            return
        acquired = bool(node.pool.try_acquire(demand))
        with self._lock:
            self._record_lineage_locked(return_ids, pt)
            self._external[task_bin] = {
                "pt": pt, "node_hex": node_hex, "acquired": acquired,
            }
        for b in return_bins:
            self.refs.add_borrow(submit_wkey, ObjectID(b))
        self.events.record(
            task_id.hex(), _ev.PENDING_NODE_ASSIGNMENT,
            name=pt.function_name, type=_ev.NORMAL_TASK,
            job_id=self.job_id.hex(), required_resources=demand,
        )
        self.events.record(task_id.hex(), _ev.RUNNING,
                           node_id=node_hex)
        # Defensive only: the serial lane orders register before its
        # completion within an epoch, so a hit here means a replayed
        # stale completion — applying it beats orphaning the task.
        with self._lock:
            early = self._external_early.pop(task_bin, None)
        if early is not None:
            self.finish_external_task(task_bin, return_bins, **early)

    def _hydrate_external(self, pt: _PendingTask) -> None:
        """Materialize fn/args/kwargs from the cast's spec — only when
        the head itself must re-run the task (retry after a local
        worker crash, reconstruction after node loss).  Args hold
        WireRef("fetch") markers, so a re-dispatch executes on any
        node."""
        if pt.fn is not None or pt.spec_blob is None:
            return
        import cloudpickle

        pt.fn, pt.args, pt.kwargs = cloudpickle.loads(pt.spec_blob)

    def _release_external(self, rec: Dict[str, Any]) -> None:
        if rec.get("acquired"):
            node = self.node_by_hex(rec["node_hex"])
            if node is not None:
                node.pool.release(rec["pt"].demand or {})
            rec["acquired"] = False

    def finish_external_task(self, task_bin: bytes,
                             return_bins: List[bytes],
                             rep: Optional[Dict[str, Any]],
                             exec_wkey: Optional[str],
                             node_hex: str,
                             error: Optional[BaseException] = None,
                             retryable: bool = False) -> None:
        """Completion of a daemon-dispatched task.  Success seals the
        results (shm entries as locations on the executing node);
        an app failure seals the error; an infra failure (local worker
        crash) re-enqueues through the normal scheduler while retries
        remain — the same retry semantics the head path has."""
        with self._lock:
            rec = self._external.pop(task_bin, None)
            if rec is None:
                # Unknown epoch (head restart) — or a register that
                # re-routed at a dead node.  Park bounded; mostly
                # garbage that ages out of the cap.
                self._external_early[task_bin] = {
                    "rep": rep, "exec_wkey": exec_wkey,
                    "node_hex": node_hex, "error": error,
                    "retryable": retryable,
                }
                while len(self._external_early) > 10000:
                    self._external_early.pop(
                        next(iter(self._external_early)))
                return
        pt: _PendingTask = rec["pt"]
        self._release_external(rec)
        task_id = pt.task_id
        if rep is not None:
            self.seal_remote_results(pt.return_ids, rep, exec_wkey,
                                     node_hex=node_hex)
            self.events.record(task_id.hex(), _ev.FINISHED)
            self._notify()
            return
        if retryable and not pt.cancelled and pt.retries_left > 0:
            pt.retries_left -= 1
            self._hydrate_external(pt)
            self.events.record(task_id.hex(), _ev.PENDING_NODE_ASSIGNMENT,
                               name=pt.function_name)
            self._enqueue_task(pt)
            return
        from ray_tpu.core.exceptions import TaskError

        if pt.cancelled:
            self._seal_cancelled(task_id, pt.return_ids, pt.streaming)
            self.events.record(task_id.hex(), _ev.FAILED,
                               error_message="cancelled")
        else:
            err = error if error is not None else TaskError(
                f"task {task_id.hex()[:12]} failed on node "
                f"{node_hex[:12]}")
            for oid in pt.return_ids:
                self.store.put_error(oid, err)
            self.events.record(task_id.hex(), _ev.FAILED,
                               error_message=repr(err))
        self._notify()

    def _reroute_external_on_node_death(self, node_hex: str) -> None:
        """Daemon died with local tasks in flight: re-enqueue each one
        through the normal scheduler (retries permitting) — the cast
        gave the head everything it needs to re-run them elsewhere."""
        with self._lock:
            doomed = [(b, rec) for b, rec in self._external.items()
                      if rec["node_hex"] == node_hex]
        from ray_tpu.core.exceptions import WorkerDiedError

        for task_bin, rec in doomed:
            self.finish_external_task(
                task_bin, [o.binary() for o in rec["pt"].return_ids],
                None, None, node_hex,
                error=WorkerDiedError(f"node {node_hex[:12]} died"),
                retryable=True)

    def resource_view(self) -> Dict[str, Dict[str, Dict[str, float]]]:
        """Seq-free per-node availability snapshot for the view sync
        (parity: the Ray Syncer's NodeResourceInfo broadcast)."""
        out: Dict[str, Dict[str, Dict[str, float]]] = {}
        with self._lock:
            nodes = list(self._nodes.values())
        for n in nodes:
            if not n.alive:
                continue
            out[n.node_id.hex()] = {
                "available": dict(n.pool.available),
                "total": dict(n.pool.total),
            }
        return out

    def _notify(self):
        with self._dispatch_cv:
            self._dispatch_cv.notify_all()

    # -- cancellation ------------------------------------------------------

    def _seal_cancelled(self, task_id: TaskID,
                        return_ids: Sequence[ObjectID], streaming: bool,
                        err: Optional[BaseException] = None
                        ) -> BaseException:
        """Seal TaskCancelledError on a task's outputs — the single
        sealing path for every cancellation site (queued, pre-start,
        failed-running, queued-actor)."""
        err = err or TaskCancelledError(task_id.hex())
        for roid in return_ids:
            self.store.put_error_if_pending(roid, err)
        if streaming:
            self._seal_stream_failure(task_id, err)
        return err

    def cancel(self, oid: ObjectID, force: bool = False) -> None:
        """Cancel the task that produces ``oid`` (parity: ray.cancel —
        core_worker.cc HandleCancelTask + _raylet.pyx:1806).  Pending
        tasks are dropped; running tasks get a cooperative async
        exception (thread mode) or a cancel RPC / process kill
        (process mode, force=True).  A finished task is a no-op."""
        task_id = oid.task_id()
        # 1. Queued (not yet dispatched) normal task — ready queue or
        # parked in the dependency index.
        target = None
        with self._dispatch_cv:
            for pt in self._pending:
                if pt.task_id == task_id:
                    target = pt
                    pt.cancelled = True
                    self._pending.remove(pt)
                    break
            if target is None:
                for lst in self._waiting_deps.values():
                    for pt in lst:
                        if pt.task_id == task_id:
                            target = pt
                            pt.cancelled = True
                            break
                    if target is not None:
                        break
                if target is not None:
                    # Unpark from every dep list it sits in.
                    for dep in list(target.waiting_on or ()):
                        lst = self._waiting_deps.get(dep)
                        if lst is not None:
                            try:
                                lst.remove(target)
                            except ValueError:
                                pass
                            if not lst:
                                del self._waiting_deps[dep]
                    target.waiting_on = None
        if target is not None:
            self._seal_cancelled(task_id, target.return_ids,
                                 target.streaming)
            if target.on_done is not None:
                target.on_done()
            self.events.record(task_id.hex(), _ev.FAILED,
                               error_message="cancelled")
            return
        # 1b. Running on a node daemon's local fast path: mark, then
        # ask THAT daemon (the head never held the worker lease).
        with self._lock:
            rec = self._external.get(task_id.binary())
            if rec is not None:
                rec["pt"].cancelled = True
                node = self._nodes.get(
                    NodeID(bytes.fromhex(rec["node_hex"])))
        if rec is not None:
            if node is not None and node.agent is not None:
                node.agent.chan.cast("cancel_local",
                                     task=task_id.binary(), force=force)
            return
        # 2. Running normal task.
        wh = None
        with self._lock:
            info = self._running_tasks.get(task_id)
            if info is not None:
                info["pt"].cancelled = True
                wh = info.get("worker")
                if wh is None:
                    # Deliver UNDER the lock — run()'s finally withdraws
                    # pending exceptions under the same lock, so a
                    # too-late cancel can't poison the thread's next task.
                    _async_raise(info["thread"], TaskCancelledError)
        if info is not None:
            if wh is not None:
                if force:
                    # Hard kill: the lease-holder sees WorkerDiedError,
                    # which the cancelled flag converts to
                    # TaskCancelledError with no retry.
                    wh.terminate(graceful=False)
                else:
                    try:
                        wh.call("cancel", task=task_id.binary())
                    except Exception:
                        pass  # worker died — death semantics apply
            return
        # 3. Actor task (the task id embeds its actor).
        with self._lock:
            shell = self._actors.get(task_id.actor_id())
        if shell is not None:
            shell.cancel_task(task_id, force)
        # 4. Already finished or unknown: no-op (parity: cancelling a
        # completed task has no effect).

    # -- actors ------------------------------------------------------------

    def create_actor(self, cls: type, args: tuple, kwargs: dict,
                     options: ActorOptions,
                     alloc_timeout: Optional[float] = None):
        if options.name:
            with self._lock:
                existing = self._named_actors.get(options.name)
                shell = self._actors.get(existing) if existing else None
            if shell is not None:
                if options.get_if_exists:
                    return shell, ObjectRef(shell._creation_oid)
                raise ValueError(f"actor name {options.name!r} already taken")
        demand = options.resource_demand()
        strategy = options.effective_strategy()
        if (not isinstance(strategy, PlacementGroupSchedulingStrategy)
                and not self._cluster_can_fit(demand, strategy)):
            raise ValueError(
                f"actor {cls.__name__!r} demands {demand} under "
                f"{strategy!r}, which no node can ever satisfy — infeasible"
            )
        # Actors hold their resources for their lifetime; block until
        # capacity frees up (woken by _notify on every release).
        # alloc_timeout bounds the wait (used by detached-actor replay,
        # where a shrunken cluster must not hang init forever).
        deadline = (None if alloc_timeout is None
                    else time.monotonic() + alloc_timeout)
        while True:
            alloc = self._try_allocate(demand, strategy)
            if alloc is not None:
                break
            if deadline is not None and time.monotonic() >= deadline:
                raise ValueError(
                    f"actor {cls.__name__!r}: no capacity for {demand} "
                    f"within {alloc_timeout}s"
                )
            with self._dispatch_cv:
                self._dispatch_cv.wait(0.05)
        actor_id = ActorID.of(self.job_id)
        creation_task_id = TaskID.of(actor_id)
        creation_oid = ObjectID.for_task_return(creation_task_id, 0)
        # Permanent pin (not seal-cleared): restarts RE-seal this oid,
        # so it must never be freed/tombstoned while the actor lives;
        # _finish_actor_removal drops the pin and the store entry.
        self.refs.add_seal_pin(creation_oid)
        shell_cls = (_ProcessActorShell
                     if (self.worker_pool is not None
                         or (alloc.node is not None
                             and alloc.node.agent is not None))
                     else _ActorShell)
        shell = shell_cls(self, actor_id, cls, args, kwargs, options,
                          creation_oid, alloc)
        shell.creation_task_id = creation_task_id
        self.events.record(
            creation_task_id.hex(), _ev.PENDING_NODE_ASSIGNMENT,
            name=f"{cls.__name__}.__init__", type=_ev.ACTOR_CREATION_TASK,
            job_id=self.job_id.hex(), actor_id=actor_id.hex(),
            node_id=(alloc.node.node_id.hex() if alloc.node else None),
            required_resources=demand,
        )
        # Persist the creation spec so a restarted driver can replay it
        # (parity: detached actors in the GCS actor table).  Serialized
        # BEFORE registration: an unpicklable constructor arg must not
        # leave a ghost registration behind (thread-mode actors never
        # pickle their args otherwise) — it just isn't persisted.
        spec_blob = None
        if (options.lifetime == "detached" and options.name
                and self._persist is not None):
            import cloudpickle as _cp

            try:
                spec_blob = _cp.dumps((cls, args, kwargs, options))
            except Exception:
                spec_blob = None
        # Register before starting: if __init__ fails instantly, the death
        # path must find (and unregister) the actor, or its name leaks.
        with self._lock:
            self._actors[actor_id] = shell
            if options.name:
                self._named_actors[options.name] = actor_id
            if alloc.node is not None:
                alloc.node.actor_ids.add(actor_id)
            if spec_blob is not None:
                self._detached_specs[options.name] = spec_blob
        if spec_blob is not None:
            self._mark_gcs_dirty()
        self.pubsub.publish("actor", {
            "event": "created", "actor_id": actor_id.hex(),
            "name": options.name or "", "class": cls.__name__,
        })
        shell.start()
        return shell, ObjectRef(creation_oid)

    def submit_actor_task(self, actor_id: ActorID, method_name: str,
                          args: tuple, kwargs: dict,
                          num_returns: Any = 1,
                          trace_ctx: Optional[Dict[str, str]] = None,
                          concurrency_group: Optional[str] = None):
        with self._lock:
            shell = self._actors.get(actor_id)
        task_id = TaskID.of(actor_id)
        streaming = num_returns == "streaming"
        return_ids = [] if streaming else [
            ObjectID.for_task_return(task_id, i) for i in range(num_returns)
        ]
        self._pin_returns(return_ids)
        if shell is None:
            err = ActorDiedError(actor_id.hex(), "no such actor")
            for oid in return_ids:
                self.store.put_error(oid, err)
            if streaming:
                self.store.put_error(
                    ObjectID.for_task_return(task_id, 0), err
                )
        else:
            self.events.record(
                task_id.hex(), _ev.SUBMITTED_TO_WORKER,
                name=f"{shell.cls.__name__}.{method_name}",
                type=_ev.ACTOR_TASK, job_id=self.job_id.hex(),
                actor_id=actor_id.hex(),
            )
            shell.submit(method_name, args, kwargs, return_ids, num_returns,
                         task_id,
                         trace_ctx if trace_ctx is not None
                         else _tracing().capture_context(),
                         concurrency_group=concurrency_group)
        if streaming:
            from ray_tpu.core.generator import ObjectRefGenerator

            return ObjectRefGenerator(task_id)
        return [ObjectRef(oid) for oid in return_ids]

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True):
        with self._lock:
            shell = self._actors.get(actor_id)
        if shell is not None:
            if no_restart:
                shell.restarts_left = 0
            shell.kill(no_restart)

    def get_named_actor(self, name: str) -> ActorID:
        with self._lock:
            actor_id = self._named_actors.get(name)
        if actor_id is None:
            raise ValueError(f"no actor named {name!r}")
        return actor_id

    def named_actor_handle(self, name: str):
        """(actor_id, class name, @method num_returns table, @method
        concurrency-group table) for handle re-hydration — the same
        lookup worker processes do over RPC."""
        from ray_tpu.core.actor import (
            collect_method_cgroups,
            collect_method_num_returns,
        )

        actor_id = self.get_named_actor(name)
        with self._lock:
            shell = self._actors.get(actor_id)
        return (
            actor_id,
            shell.cls.__name__ if shell else "unknown",
            collect_method_num_returns(shell.cls) if shell else {},
            collect_method_cgroups(shell.cls) if shell else {},
        )

    def _on_actor_death(self, shell: _ActorShell):
        # Restart (parity: GCS actor FSM RESTARTING→ALIVE, gcs.proto actor
        # states): keep id + queue, re-construct the instance on a fresh
        # thread.  If the actor's node died, re-place it on a live node.
        # Explicit kills and creation failures don't restart.
        restartable = (
            shell.restarts_left > 0
            and not shell.no_restart
            and not shell.death_reason.startswith("creation")
        )
        node_died = shell.death_reason == "node died"
        strategy = shell.options.effective_strategy()
        if restartable and node_died:
            # Hard affinity to a dead node can never be satisfied
            # (parity: NodeAffinitySchedulingStrategy hard + node death
            # → actor unschedulable, fails permanently).
            if (isinstance(strategy, NodeAffinitySchedulingStrategy)
                    and not strategy.soft):
                want = (strategy.node_id.hex()
                        if isinstance(strategy.node_id, NodeID)
                        else str(strategy.node_id))
                with self._lock:
                    target = next((n for n in self._nodes.values()
                                   if n.node_id.hex() == want), None)
                if target is None or not target.alive:
                    restartable = False
        if restartable:
            shell.restarts_left -= 1
            if node_died:
                try:
                    alloc = self._try_allocate(
                        shell.options.resource_demand(), strategy
                    )
                except ValueError:
                    alloc = None
                    restartable = False  # e.g. PG was removed
                if restartable and alloc is None:
                    # Stay in RESTARTING until capacity appears (parity:
                    # GCS keeps the actor pending-recreation).
                    self._await_restart_capacity(shell, strategy)
                    return
                if restartable:
                    shell.allocation = alloc
                    with self._lock:
                        if alloc.node is not None:
                            alloc.node.actor_ids.add(shell.actor_id)
            if restartable:
                with shell._submit_gate:
                    shell.dead = False
                    shell.death_reason = ""
                    shell._drained = False
                shell.start()
                return
        if not node_died:
            shell.allocation.release()
        self._finish_actor_removal(shell)

    def _await_restart_capacity(self, shell: _ActorShell, strategy: Any):
        """Background wait for cluster capacity to restart a displaced
        actor; the handle keeps working once it comes back."""

        def poll():
            import time

            while not self._shutdown:
                try:
                    alloc = self._try_allocate(
                        shell.options.resource_demand(), strategy
                    )
                except ValueError:
                    self._finish_actor_removal(shell)
                    return
                if alloc is not None:
                    shell.allocation = alloc
                    with self._lock:
                        if alloc.node is not None:
                            alloc.node.actor_ids.add(shell.actor_id)
                    with shell._submit_gate:
                        shell.dead = False
                        shell.death_reason = ""
                        shell._drained = False
                    shell.start()
                    return
                time.sleep(0.05)

        threading.Thread(target=poll, daemon=True,
                         name=f"restart-{shell.actor_id.hex()[:8]}").start()

    def _actor_row(self, shell: _ActorShell, state: str) -> Dict[str, Any]:
        return {
            "actor_id": shell.actor_id.hex(),
            "class_name": shell.cls.__name__,
            "state": state,
            "name": shell.options.name or "",
            "node_id": (shell.node_id.hex() if shell.node_id else None),
            "death_cause": shell.death_reason or None,
            "job_id": self.job_id.hex(),
        }

    def _finish_actor_removal(self, shell: _ActorShell):
        self.pubsub.publish("actor", {
            "event": "died", "actor_id": shell.actor_id.hex(),
            "name": shell.options.name or "",
            "class": shell.cls.__name__,
            "reason": shell.death_reason or "",
        })
        # Drop the creation oid's permanent pin (its error/None value
        # stays readable through any still-held handles; the pin removal
        # lets it free once those drop).
        self.refs.remove_seal_pin(shell._creation_oid)
        # Stop a dead async actor's event loop thread (queued callbacks
        # — including cancellation dones — run before the stop lands).
        loop = getattr(shell, "_loop", None)
        if loop is not None:
            try:
                loop.call_soon_threadsafe(loop.stop)
            except RuntimeError:
                pass  # already stopped/closed
        with self._lock:
            self._dead_actors.append(self._actor_row(shell, "DEAD"))
            self._actors.pop(shell.actor_id, None)
            if shell.allocation.node is not None:
                shell.allocation.node.actor_ids.discard(shell.actor_id)
            dropped_spec = False
            for name, aid in list(self._named_actors.items()):
                if aid == shell.actor_id:
                    del self._named_actors[name]
                    # A detached actor that truly died (kill/crash out
                    # of restarts) leaves the durable table too — but a
                    # driver SHUTDOWN must keep the spec so the next
                    # driver can replay it.
                    if not self._shutdown and name in self._detached_specs:
                        del self._detached_specs[name]
                        dropped_spec = True
        if dropped_spec:
            self._mark_gcs_dirty()
        self._retry_pending_pgs()
        self._notify()

    # -- placement groups --------------------------------------------------

    def create_placement_group(self, bundles: List[Dict[str, float]],
                               strategy: str, name: str,
                               lifetime: Optional[str]) -> PlacementGroup:
        pg_id = PlacementGroupID.of(self.job_id)
        pg = PlacementGroup(pg_id, bundles, strategy, name)
        ready_task = TaskID(pg_id.binary() + b"\x00" * 8)
        ready_oid = ObjectID.for_task_return(ready_task, 0)
        st = _PGState(
            pg=pg,
            bundles=[Bundle(i, dict(spec)) for i, spec in enumerate(bundles)],
            ready_oid=ready_oid,
            lifetime=lifetime,
        )
        # Permanent pin: ready() can be called repeatedly for the PG's
        # lifetime; remove_placement_group drops pin + store entry.
        self.refs.add_seal_pin(ready_oid)
        with self._lock:
            self._pgs[pg_id] = st
            if name:
                if name in self._named_pgs:
                    raise ValueError(f"placement group name {name!r} taken")
                self._named_pgs[name] = pg_id
        self._reserve_bundles(st, st.bundles)
        if lifetime == "detached" and name:
            self._mark_gcs_dirty()
        return pg

    def _retry_pending_pgs(self) -> None:
        """Capacity freed (PG/actor removal): pending placement groups
        get another shot (parity: GcsPlacementGroupManager retrying on
        resource updates, not just node adds)."""
        with self._lock:
            pending = [s for s in self._pgs.values()
                       if not s.removed
                       and any(b.node_id is None for b in s.bundles)]
        for s in pending:
            self._reserve_bundles(
                s, [b for b in s.bundles if b.node_id is None]
            )

    def _reserve_bundles(self, st: _PGState, bundles: List[Bundle]) -> bool:
        """Reserve bundles on nodes per the PG strategy.  All-or-nothing
        with rollback (parity: the 2-phase commit in
        gcs_placement_group_scheduler.cc, simplified to one process)."""
        with self._pg_reserve_lock:
            if st.removed:  # raced with remove_placement_group
                return False
            bundles = [b for b in bundles if b.node_id is None]
            if not bundles:
                return True
            return self._reserve_bundles_locked(st, bundles)

    def _reserve_bundles_locked(self, st: _PGState,
                                bundles: List[Bundle]) -> bool:
        strategy = st.pg.strategy
        nodes = self._alive_nodes()
        # Nodes already holding this PG's surviving bundles — STRICT_SPREAD
        # re-reservation must not collapse onto them.
        occupied = {b.node_id for b in st.bundles if b.node_id is not None}
        # ICI-aware ordering: nodes labeled with an integer "ici_index"
        # are considered in coordinate order so PACKed bundles land on a
        # contiguous slice block.
        def ici_key(n: NodeState):
            try:
                return (0, int(n.labels.get("ici_index", "")))
            except ValueError:
                return (1, 0)

        nodes = sorted(nodes, key=ici_key)
        reserved: List[Tuple[Bundle, NodeState]] = []

        def rollback():
            for b, n in reserved:
                n.pool.release(b.resources)
                with b.lock:
                    b.node_id = None
                    b.available = {}
            reserved.clear()

        def place_on(b: Bundle, n: NodeState) -> bool:
            if n.pool.try_acquire(b.resources):
                with b.lock:
                    b.node_id = n.node_id
                    b.available = dict(b.resources)
                reserved.append((b, n))
                return True
            return False

        if strategy == "ICI_CONTIGUOUS":
            # Gang placement on a contiguous axis-aligned sub-grid of
            # ONE slice's ICI torus (SURVEY.md §7 hard part 4; extends
            # the reference's bundle policies
            # raylet/scheduling/policy/bundle_scheduling_policy.h:31-98
            # with slice topology — the reference only sketches TPU
            # head resources in _private/accelerator.py:176-191).
            # Fragmented placements are REJECTED: the group stays
            # pending until a whole rectangle frees up.  Node death
            # voids the whole gang (re-reservation re-places every
            # bundle so adjacency is preserved).
            requested = {id(b) for b in bundles}
            voided = [b for b in st.bundles
                      if b.node_id is not None and id(b) not in requested]
            if voided:
                for b in voided:
                    node = self._nodes.get(b.node_id)
                    with b.lock:
                        avail = dict(b.available)
                        b.available = {}
                        b.node_id = None
                    if node is not None and node.alive:
                        node.pool.release(avail)
                bundles = list(st.bundles)
            return self._reserve_ici_contiguous(st, bundles, nodes,
                                                place_on, rollback)

        if strategy in ("PACK", "STRICT_PACK"):
            # Try to land everything on a single node first.
            for n in nodes:
                ok = True
                for b in bundles:
                    if not place_on(b, n):
                        ok = False
                        break
                if ok:
                    self._pg_maybe_ready(st)
                    return True
                rollback()
                reserved.clear()
            if strategy == "STRICT_PACK":
                return False  # stays pending; bundles unreserved
            # soft PACK: greedy first-fit across nodes
            for b in bundles:
                if not any(place_on(b, n) for n in nodes):
                    rollback()
                    return False
            self._pg_maybe_ready(st)
            return True

        # SPREAD / STRICT_SPREAD: distinct nodes (best-effort for SPREAD).
        used: set = set(occupied)
        for b in bundles:
            placed = False
            for n in nodes:
                if n.node_id in used:
                    continue
                if place_on(b, n):
                    used.add(n.node_id)
                    placed = True
                    break
            if not placed and strategy == "SPREAD":
                for n in nodes:
                    if place_on(b, n):
                        placed = True
                        break
            if not placed:
                rollback()
                return False
        self._pg_maybe_ready(st)
        return True

    def _reserve_ici_contiguous(self, st: _PGState, bundles: List[Bundle],
                                nodes: List[NodeState], place_on,
                                rollback) -> bool:
        """Place n bundles on an h×w rectangle of ici_coord-labeled
        nodes within one slice, row-major bundle order (bundle index →
        mesh position is deterministic, so callers can map coordinates
        to mesh axes).  All-or-nothing."""
        n = len(bundles)
        # Slice name → {(x, y): node}
        slices: Dict[str, Dict[Tuple[int, int], NodeState]] = {}
        for node in nodes:
            coord = node.labels.get("ici_coord")
            if not coord:
                continue
            try:
                x, y = (int(c) for c in coord.split(","))
            except ValueError:
                continue
            key = node.labels.get("raytpu.io/tpu-slice",
                                  node.labels.get("raytpu.io/tpu-pod", ""))
            slices.setdefault(key, {})[(x, y)] = node

        def shapes():
            # Prefer squares, then squat rectangles (less ICI hop
            # diameter); 1×n last.
            out = []
            for h in range(int(n ** 0.5), 0, -1):
                if n % h == 0:
                    out.append((h, n // h))
                    if h != n // h:
                        out.append((n // h, h))
            return out

        for grid in slices.values():
            if len(grid) < n:
                continue
            xs = [c[0] for c in grid]
            ys = [c[1] for c in grid]
            for h, w in shapes():
                for x0 in range(min(xs), max(xs) - h + 2):
                    for y0 in range(min(ys), max(ys) - w + 2):
                        cells = [(x0 + i, y0 + j)
                                 for i in range(h) for j in range(w)]
                        if any(c not in grid for c in cells):
                            continue
                        ok = True
                        for b, c in zip(bundles, cells):
                            if not place_on(b, grid[c]):
                                ok = False
                                break
                        if ok:
                            self._pg_maybe_ready(st)
                            return True
                        rollback()
        return False  # no contiguous window — stays pending

    def _pg_maybe_ready(self, st: _PGState):
        if all(b.node_id is not None for b in st.bundles):
            if not self.store.contains(st.ready_oid):
                self.store.put_value(st.ready_oid, None)

    def pg_ready_ref(self, pg_id: PlacementGroupID) -> ObjectRef:
        with self._lock:
            st = self._pgs.get(pg_id)
        if st is None:
            raise ValueError("unknown placement group")
        return ObjectRef(st.ready_oid)

    def remove_placement_group(self, pg_id: PlacementGroupID) -> None:
        with self._pg_reserve_lock:
            with self._lock:
                st = self._pgs.get(pg_id)
                if st is None or st.removed:
                    return
                st.removed = True
                if st.pg.name:
                    self._named_pgs.pop(st.pg.name, None)
            # Return only the *unused* part of each reservation now; the
            # in-use part comes back when each holder finishes (see
            # _Allocation.release) — never oversubscribe the node.
            bundle_set = set(map(id, st.bundles))
            for b in st.bundles:
                if b.node_id is not None:
                    node = self._nodes.get(b.node_id)
                    with b.lock:
                        unused = dict(b.available)
                        b.available = {}
                        b.node_id = None  # atomic with the ledger zeroing
                    if node is not None and node.alive:
                        node.pool.release(unused)
        # Kill actors living inside the group (parity: PG removal kills
        # the actors/tasks scheduled into it).
        with self._lock:
            doomed = [s for s in self._actors.values()
                      if s.allocation.bundle is not None
                      and id(s.allocation.bundle) in bundle_set]
        for shell in doomed:
            shell.restarts_left = 0
            shell.kill(no_restart=True)
        # Drop the ready marker's permanent pin + store entry.  The
        # tombstone turns a get on a still-held pg.ready() ref into
        # ObjectFreedError instead of an unseal-forever hang.
        self.refs.remove_seal_pin(st.ready_oid)
        self.store.release(st.ready_oid, tombstone=True)
        self._mark_gcs_dirty()
        self._retry_pending_pgs()
        self._notify()

    def get_named_placement_group(self, name: str) -> PlacementGroup:
        with self._lock:
            pg_id = self._named_pgs.get(name)
            if pg_id is None:
                raise ValueError(f"no placement group named {name!r}")
            return self._pgs[pg_id].pg

    def placement_group_table(self) -> Dict[str, Any]:
        with self._lock:
            out = {}
            for pg_id, st in self._pgs.items():
                out[pg_id.hex()] = {
                    "strategy": st.pg.strategy,
                    "name": st.pg.name,
                    "state": ("REMOVED" if st.removed else
                              "CREATED" if all(b.node_id is not None
                                               for b in st.bundles)
                              else "PENDING"),
                    "bundles": {
                        b.index: (b.node_id.hex() if b.node_id else None)
                        for b in st.bundles
                    },
                }
            return out

    # -- cluster info ------------------------------------------------------

    def actor_table(self) -> List[Dict[str, Any]]:
        """Live + dead actor entries (parity: GCS ActorTableData rows
        behind `ray list actors`, gcs.proto actor FSM states)."""
        with self._lock:
            live = []
            for shell in self._actors.values():
                if not shell.dead:
                    state = "ALIVE" if shell.instance is not None \
                        else "PENDING_CREATION"
                else:
                    state = "RESTARTING"
                live.append(self._actor_row(shell, state))
            return live + list(self._dead_actors)

    def cluster_resources(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for n in self._alive_nodes():
            for k, v in n.pool.total.items():
                out[k] = out.get(k, 0) + v
        return out

    def available_resources(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for n in self._alive_nodes():
            with n.pool._lock:
                for k, v in n.pool.available.items():
                    out[k] = out.get(k, 0) + v
        return out

    def nodes(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [{
                "NodeID": nid.hex(),
                "Alive": self._nodes[nid].alive,
                "Resources": dict(self._nodes[nid].pool.total),
                "Labels": dict(self._nodes[nid].labels),
            } for nid in self._node_order]

    # -- log plane ---------------------------------------------------------

    def _publish_local_logs(self, file: str, lines: List[str],
                            truncated: bool = False) -> None:
        self.ingest_logs("head", file, lines, truncated=truncated)

    def ingest_logs(self, node: str, file: str,
                    lines: List[str], truncated: bool = False) -> None:
        """One batch of worker log lines into the head buffer (+ echo
        to the driver console — parity: ray's log_to_driver prefixing
        lines with their producing worker/node).  ``truncated`` marks a
        stream whose file was rotated/truncated mid-tail (these lines
        are a readable suffix)."""
        self.logs.ingest(node, file, lines, truncated=truncated)
        # Publish only once someone has pulled the channel: with no
        # subscriber the ring would duplicate LogBuffer's retention and
        # every batch would wake all other channels' waiters for nothing.
        if self.pubsub.has_consumers("logs"):
            self.pubsub.publish("logs", {"node": node, "file": file,
                                         "lines": list(lines)})
        from ray_tpu.utils.config import get_config

        if get_config().log_to_driver:
            tag = file.rsplit(".", 1)[0]
            where = f"{tag}" if node == "head" else f"{tag}, node={node[:8]}"
            for ln in lines:
                # Gloo's per-rank connection chatter ("[Gloo] Rank N is
                # connected to M peer ranks...") floods the driver
                # console quadratically on multi-process dryruns; keep
                # it out of the echo only — LogBuffer retains every
                # line for `raytpu logs`.
                if _GLOO_CONNECT_RE.search(ln):
                    continue
                print(f"({where}) {ln}", flush=True)

    def shutdown(self):
        from ray_tpu.core import object_ref as _object_ref

        # Stop counting first: mass ref destruction during teardown must
        # not trigger frees against a closing store.
        self.refs.close()
        if _object_ref._ref_hooks == self._ref_hooks:
            _object_ref.clear_ref_hooks()
        with self._dispatch_cv:
            self._shutdown = True
            self._dispatch_cv.notify_all()
        with self._lock:
            actors = list(self._actors.values())
        for shell in actors:
            shell.restarts_left = 0
            shell.kill()
        # Ask joined node daemons to exit (best-effort cast), then drop
        # their channels.
        with self._lock:
            agents = [n.agent for n in self._nodes.values()
                      if n.agent is not None]
        for agent in agents:
            agent.shutdown_daemon()
        if self.worker_pool is not None:
            self.worker_pool.shutdown()
        # Drop the federated per-process metric snapshots (they ride
        # worker replies, see apply_ref_batches): those processes are
        # gone, so their series would otherwise show up as stale
        # samples in the NEXT cluster's /metrics scrape forever.
        from ray_tpu.util import metrics as _metrics

        _metrics.clear_remote()
        # Same for the telemetry history plane: stop the driver's
        # sampler and drop every ring (local + federated) so the next
        # runtime in this process starts from an empty plane.
        from ray_tpu.util import timeseries as _timeseries

        _timeseries.shutdown()
        if self._log_monitor is not None:
            # AFTER the pool: stop()'s final sweep then sees everything
            # the dying workers flushed.
            self._log_monitor.stop()
        if self._persist is not None:
            # Final snapshot AFTER actor teardown (specs were kept —
            # _finish_actor_removal skips spec removal once _shutdown).
            self._persist.close(final_flush=True)
        self._exec_pool.close()
        self.store.close()
