"""Fault-injection helpers for tests.

Parity: ray: python/ray/_private/test_utils.py —
``get_and_run_node_killer`` (:1391-1401) randomly SIGKILLs raylets
during chaos tests (python/ray/tests/test_chaos.py, release
nightly_tests/chaos_test/).  Here the killer targets logical nodes of
the in-process cluster; the failure semantics exercised (actor restart
elsewhere, task retry, object reconstruction, bundle rescheduling) are
the same paths real node death takes.
"""

from __future__ import annotations

import os
import random
import threading
from typing import List, Optional

from ray_tpu.core.exceptions import PreemptedError

# How long a serving test waits for a replica to be RUNNING or routable.
# A replica is a process that imports JAX and compiles its engine:
# seconds alone, minutes beside five other test workers.  A wait ends
# when the controller or the route table shows the event, so the bound
# only has to be past anything a loaded machine takes.
REPLICA_READY_S = 300.0


class NodeKiller:
    """Kills a random non-head alive node every ``interval_s`` until
    stopped (parity: NodeKillerActor's kill loop)."""

    def __init__(self, runtime, *, interval_s: float = 0.2,
                 max_kills: Optional[int] = None, seed: int = 0,
                 spare_labels: Optional[dict] = None):
        self.runtime = runtime
        self.interval_s = interval_s
        self.max_kills = max_kills
        self.spare_labels = spare_labels or {}
        self.killed: List[str] = []
        self._rng = random.Random(seed)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _victims(self):
        rt = self.runtime
        with rt._lock:
            out = []
            for node in rt._nodes.values():
                if not node.alive or node.node_id == rt.head_node_id:
                    continue
                if any(node.labels.get(k) == v
                       for k, v in self.spare_labels.items()):
                    continue
                out.append(node.node_id)
            return out

    def start(self) -> "NodeKiller":
        self._thread = threading.Thread(
            target=self._loop, name="node-killer", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            if self.max_kills is not None \
                    and len(self.killed) >= self.max_kills:
                return
            victims = self._victims()
            if not victims:
                continue
            victim = self._rng.choice(victims)
            self.runtime.kill_node(victim)
            self.killed.append(victim.hex())


class HardKillInterrupt(BaseException):
    """Delivered into an actor's running task threads to emulate
    SIGKILL for in-process (thread-mode) actors.  Deliberately a
    BaseException: the actor serve loop treats a non-Exception escaping
    user code as process death (seals the in-flight results, marks the
    actor dead, fails everything queued with ActorDiedError) — the same
    observable contract a real SIGKILL of a worker process has."""


def kill_actor_hard(runtime, actor_id) -> None:
    """SIGKILL semantics for a thread-mode actor: a plain
    ``ray_tpu.kill`` cannot interrupt a method that is already running
    (threads are not preemptible), so mark the actor dead first, then
    deliver HardKillInterrupt into every thread currently executing one
    of its tasks.  In-flight calls seal TaskError(HardKillInterrupt),
    in-flight streams seal it mid-stream, queued calls seal
    ActorDiedError — exactly what callers of a SIGKILLed process-mode
    actor observe."""
    from ray_tpu.utils.interrupt import async_raise

    with runtime._lock:
        shell = runtime._actors.get(actor_id)
    if shell is None:
        return
    runtime.kill_actor(actor_id, no_restart=True)
    with shell._cancel_lock:
        tids = {t for t in shell._running_sync.values()
                if isinstance(t, int)}
    for tid in tids:
        async_raise(tid, HardKillInterrupt)


class ReplicaKiller:
    """Chaos helper targeting serve replicas (parity: the reference's
    chaos suite kills serve actors out from under live traffic).  Picks
    a seeded victim among alive actors of the given class and hard-kills
    it mid-request via kill_actor_hard."""

    def __init__(self, runtime, *, seed: int = 0,
                 class_name: str = "ReplicaActor"):
        self.runtime = runtime
        self.class_name = class_name
        self.killed: List[str] = []
        self._rng = random.Random(seed)

    def victims(self) -> list:
        with self.runtime._lock:
            return sorted(
                (a for a, s in self.runtime._actors.items()
                 if not s.dead and s.cls.__name__ == self.class_name),
                key=lambda a: a.hex(),
            )

    def kill_one(self, actor_id=None):
        """Hard-kill one victim (seeded choice when not given).
        Returns the killed actor id, or None when no victim exists."""
        if actor_id is None:
            victims = self.victims()
            if not victims:
                return None
            actor_id = self._rng.choice(victims)
        kill_actor_hard(self.runtime, actor_id)
        self.killed.append(actor_id.hex())
        return actor_id


# -- env-gated fail points ---------------------------------------------------

class FailPointError(PreemptedError):
    """Raised by an armed fail point.  Subclasses PreemptedError so the
    serve failover path treats injected faults exactly like a real
    preemption (retriable, empty continuation)."""

    def __init__(self, point: str = "", continuation: Optional[dict] = None):
        self.point = point
        super().__init__(f"fail point {point!r} fired", continuation)

    def __reduce__(self):
        return (type(self), (self.point, self.continuation))


_fail_lock = threading.Lock()
_fail_env: Optional[str] = None
_fail_armed: dict = {}


def fail_point(name: str) -> None:
    """Fire an injected fault at a named point.  Armed via the
    RAYTPU_FAILPOINTS env var — a comma list of ``point[:count]``
    entries (count = number of firings, default 1).  Unarmed points are
    a near-free no-op, so production code can call this unconditionally
    at interesting boundaries (e.g. ``replica.stream``)."""
    global _fail_env
    env = os.environ.get("RAYTPU_FAILPOINTS", "")
    if not env and _fail_env in (None, ""):
        return
    with _fail_lock:
        if env != _fail_env:
            _fail_env = env
            _fail_armed.clear()
            for entry in env.split(","):
                entry = entry.strip()
                if not entry:
                    continue
                point, _, count = entry.partition(":")
                _fail_armed[point] = int(count) if count else 1
        remaining = _fail_armed.get(name, 0)
        if remaining <= 0:
            return
        _fail_armed[name] = remaining - 1
    raise FailPointError(name)
