"""User-facing error types.

Parity with the reference's exception hierarchy
(ray: python/ray/exceptions.py): task failures are captured where they
happen, serialized, and re-raised at every ``get`` of the poisoned ref,
with the remote traceback attached.
"""

from __future__ import annotations

import traceback
from typing import Optional


class RayTpuError(Exception):
    pass


class TaskError(RayTpuError):
    """A task raised; re-raised at ray_tpu.get (parity: RayTaskError)."""

    def __init__(self, function_name: str, cause: BaseException,
                 remote_tb: Optional[str] = None):
        self.function_name = function_name
        self.cause = cause
        self.remote_tb = remote_tb or "".join(
            traceback.format_exception(type(cause), cause, cause.__traceback__)
        )
        super().__init__(
            f"task {function_name!r} failed: {type(cause).__name__}: {cause}\n"
            f"--- remote traceback ---\n{self.remote_tb}"
        )

    def __reduce__(self):
        # Exception pickling replays __init__ with self.args (the
        # formatted message) — rebuild from the real fields instead so
        # TaskError survives the client-mode wire (parity: RayTaskError
        # is serializable).
        return (type(self),
                (self.function_name, self.cause, self.remote_tb))


class ActorError(RayTpuError):
    pass


class ActorDiedError(ActorError):
    def __init__(self, actor_repr: str, reason: str = "actor died"):
        self.actor_repr = actor_repr
        super().__init__(f"{actor_repr}: {reason}")


class ActorUnavailableError(ActorError):
    pass


class PreemptedError(RayTpuError):
    """The replica serving this request was preempted (drain, SIGTERM,
    maintenance event) before the request finished.  Carries the
    continuation payload — everything a surviving replica needs to
    resume generation with one re-prefill and no token loss:

        {"prompt": [...], "tokens": [... generated so far ...],
         "temperature": float, "request_id": str}

    The serve router treats this as retriable; it is NOT a failure of
    the request itself."""

    def __init__(self, reason: str = "replica preempted",
                 continuation: Optional[dict] = None):
        self.reason = reason
        self.continuation = continuation or {}
        generated = len(self.continuation.get("tokens", ()))
        super().__init__(
            f"{reason} (continuation: {generated} generated tokens)"
        )

    def __reduce__(self):
        return (type(self), (self.reason, self.continuation))


class ShedError(RayTpuError):
    """The serving engine refused to ADMIT this request: its admission
    queue is already older than the SLO budget, so queuing the request
    could only produce a guaranteed-late answer or a silent client
    timeout.  Clean backpressure, not a failure of the request — no
    work was started, so the caller may retry immediately (ideally
    after easing off).  The serve handle does NOT transparently retry
    it: shedding that gets re-enqueued sheds nothing."""

    def __init__(self, reason: str = "request shed: admission queue over "
                 "SLO budget", queue_age_s: float = 0.0):
        self.reason = reason
        self.queue_age_s = float(queue_age_s)
        super().__init__(f"{reason} (queue age {self.queue_age_s:.3f}s)")

    def __reduce__(self):
        return (type(self), (self.reason, self.queue_age_s))


class GetTimeoutError(RayTpuError, TimeoutError):
    pass


class ObjectLostError(RayTpuError):
    def __init__(self, object_id_hex: str):
        super().__init__(f"object {object_id_hex} was lost and could not be "
                         f"reconstructed")


class TaskCancelledError(RayTpuError):
    """The task was cancelled via ray_tpu.cancel (parity:
    ray.exceptions.TaskCancelledError) — raised at every get of the
    cancelled ref.  Cancelled tasks never retry."""

    def __init__(self, task_id_hex: str = ""):
        self.task_id_hex = task_id_hex
        super().__init__(
            f"task {task_id_hex or '<unknown>'} was cancelled"
        )

    def __reduce__(self):
        return (type(self), (self.task_id_hex,))


class ObjectFreedError(RayTpuError):
    """Fetch of an object the owner already freed — every reference went
    out of scope, so the value was garbage-collected (parity:
    ReferenceCountingAssertionError on get-after-free)."""

    def __init__(self, object_id_hex: str):
        super().__init__(
            f"object {object_id_hex} was freed: all references to it went "
            f"out of scope and its value was garbage-collected"
        )


class WorkerDiedError(RayTpuError):
    """The OS worker process executing a task died (crash, kill -9, OOM
    kill).  Retriable: the task is resubmitted per max_retries (parity:
    WorkerCrashedError, python/ray/exceptions.py)."""

    def __init__(self, detail: str = ""):
        super().__init__(
            f"the worker process executing the task died unexpectedly"
            f"{': ' + detail if detail else ''}"
        )


class RuntimeNotInitializedError(RayTpuError):
    def __init__(self):
        super().__init__(
            "ray_tpu runtime is not initialized — call ray_tpu.init() first"
        )
