"""DeploymentHandle / DeploymentResponse — the composition API.

Parity with the reference (ray: python/ray/serve/handle.py —
DeploymentHandle:297, DeploymentResponse:795): ``handle.remote(...)``
returns a response future; responses can be passed straight into other
handles' ``.remote(...)`` calls (the downstream replica receives the
resolved value), mirroring model-composition graphs.
"""

from __future__ import annotations

import random
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.core import api
from ray_tpu.core.object_ref import ObjectRef
from ray_tpu.serve import request_events as _reqev

_routers_lock = threading.Lock()
_routers: Dict[Tuple[str, str], Any] = {}


def _is_death(err: BaseException) -> bool:
    """The replica process is gone: ActorDiedError directly (queued
    calls sealed on death), or a TaskError whose cause is NOT an
    Exception — the serve loop seals the in-flight call with the raw
    BaseException that killed the actor (see _after_item_error), so a
    non-Exception cause is the in-flight face of the same death."""
    from ray_tpu.core.exceptions import ActorDiedError, TaskError

    if isinstance(err, ActorDiedError):
        return True
    return (isinstance(err, TaskError)
            and not isinstance(getattr(err, "cause", None), Exception))


def _migration_handoff(err: BaseException):
    """The MigrationHandoff inside an attempt's outcome, if any —
    raised directly (local engine) or riding a TaskError from the
    replica.  A handoff is a SUCCESSFUL prefill attempt whose KV pages
    landed on a decode replica; the stream resumes there."""
    from ray_tpu.core.exceptions import TaskError
    from ray_tpu.serve.kv_transfer import MigrationHandoff

    if isinstance(err, MigrationHandoff):
        return err
    if (isinstance(err, TaskError)
            and isinstance(getattr(err, "cause", None), MigrationHandoff)):
        return err.cause
    return None


def _shed_error(err: BaseException):
    """The ShedError inside an attempt's outcome, if any — raised
    directly (local engine) or riding a TaskError from the replica.
    A shed is clean admission-control backpressure: no attempt ran, so
    the handle fails fast with the unwrapped error instead of burning
    its retry budget re-enqueueing onto the same overloaded queue."""
    from ray_tpu.core.exceptions import ShedError, TaskError

    if isinstance(err, ShedError):
        return err
    if (isinstance(err, TaskError)
            and isinstance(getattr(err, "cause", None), ShedError)):
        return err.cause
    return None


def _is_retriable(err: BaseException) -> bool:
    """Safe to re-enqueue the request on a surviving replica: the
    replica died (the work is lost, not duplicated) or it preempted the
    request cooperatively (PreemptedError — raised locally by a
    draining engine, or riding a TaskError from the replica)."""
    from ray_tpu.core.exceptions import PreemptedError, TaskError

    if _is_death(err):
        return True
    if isinstance(err, PreemptedError):
        return True
    return (isinstance(err, TaskError)
            and isinstance(getattr(err, "cause", None), PreemptedError))


def _router_for(app_name: str, deployment_name: str):
    from ray_tpu.serve.router import Router

    key = (app_name, deployment_name)
    with _routers_lock:
        r = _routers.get(key)
        if r is None:
            r = _routers[key] = Router(app_name, deployment_name)
        return r


def _shutdown_routers() -> None:
    with _routers_lock:
        for r in _routers.values():
            r.stop()
        _routers.clear()


class DeploymentResponse:
    """Future for one request (parity: serve DeploymentResponse)."""

    def __init__(self, ref: ObjectRef, resubmit=None):
        self._ref = ref
        self._resubmit = resubmit

    def result(self, timeout_s: Optional[float] = None) -> Any:
        from ray_tpu.core.exceptions import (ActorDiedError, PreemptedError,
                                             TaskError)

        # A replica can die between assignment and execution (downscale,
        # health replacement) or preempt the request cooperatively while
        # draining.  Either way the work is lost, not duplicated, so
        # resubmitting on a live replica is safe (parity: serve router
        # replica retries).  The resubmit closure excludes every replica
        # already observed dead, so retries can't land on the same one.
        # ``timeout_s`` is ONE deadline shared across every attempt —
        # not a per-attempt allowance — and attempts are spaced by
        # capped exponential backoff with jitter so a fleet of callers
        # doesn't stampede the surviving replicas in lockstep.
        deadline = (None if timeout_s is None
                    else time.monotonic() + timeout_s)
        attempts = 3 if self._resubmit is not None else 1
        backoff = 0.05
        for attempt in range(attempts):
            remaining = (None if deadline is None
                         else max(0.0, deadline - time.monotonic()))
            try:
                return api.get(self._ref, timeout=remaining)
            except (ActorDiedError, PreemptedError, TaskError) as err:
                retriable = (
                    isinstance(err, (ActorDiedError, PreemptedError))
                    or isinstance(getattr(err, "cause", None),
                                  PreemptedError))
                if (not retriable or attempt == attempts - 1
                        or (deadline is not None
                            and time.monotonic() >= deadline)):
                    raise
                # Half-fixed + half-jitter: spreads a stampede of
                # retrying callers without ever collapsing the spacing
                # to ~0 (a replacement replica needs real time to start).
                delay = backoff / 2.0 + random.uniform(0.0, backoff / 2.0)
                backoff = min(backoff * 2.0, 1.0)
                if deadline is not None:
                    delay = min(delay,
                                max(0.0, deadline - time.monotonic()))
                time.sleep(delay)
                self._ref = self._resubmit()

    def __await__(self):
        """Awaitable inside async replicas (parity: serve
        DeploymentResponse.__await__): the blocking get runs on the
        loop's default executor, so concurrent requests on one async
        replica interleave while awaiting downstream deployments."""
        import asyncio

        loop = asyncio.get_event_loop()
        fut = loop.run_in_executor(None, self.result)
        return fut.__await__()

    def _to_object_ref(self) -> ObjectRef:
        return self._ref

    def __reduce__(self):
        # A response travels as its underlying ref; the runtime resolves
        # refs in task args, so downstream replicas see the value.
        return (DeploymentResponse, (self._ref,))


class DeploymentResponseGenerator:
    """Streaming response with mid-stream failover (parity: serve's
    DeploymentResponseGenerator, plus the failover the reference leaves
    to the application).  Iterating yields items as the replica
    generates them.  When the current attempt dies (replica hard-killed)
    or is preempted (replica draining), the request is re-enqueued on a
    surviving replica under a per-request retry budget and the shared
    deadline, with capped-exponential jittered backoff between attempts.

    For LLM payloads (first positional arg a dict with a ``tokens``
    prompt) the retry resumes from ``prompt + generated_prefix`` — one
    re-prefill of the continuation, no token re-generated, no token
    lost: the replica seals every generated token before the failure
    surfaces, so the delivered prefix IS the generated prefix, and
    greedy decoding makes the continuation bit-identical to the
    uninterrupted stream.  For any other payload the retry replays the
    stream and skips the already-delivered prefix (deterministic
    streams only), so consumers still see each item exactly once."""

    def __init__(self, router, method_name: str, args: tuple, kwargs: dict,
                 *, assign_timeout_s: Optional[float] = None,
                 model_id: str = "", max_retries: int = 3,
                 total_timeout_s: Optional[float] = None):
        self._router = router
        self._method_name = method_name
        self._args = args
        self._kwargs = kwargs
        self._assign_timeout_s = assign_timeout_s
        self._model_id = model_id
        self._max_retries = max_retries
        self._total_timeout_s = total_timeout_s
        # One identity for every attempt: the id is minted once and
        # re-sent on retries, so the engine rings, the router ring,
        # spans and log lines all tell one request's story.
        self.request_id = _reqev.get_request_id() or _reqev.new_request_id()
        self._delivered: List[Any] = []
        self._iter = None
        # Disaggregated-serving handoff state: once a prefill replica
        # migrates this stream's KV pages, resumed attempts carry
        # ``_disagg_resumed`` (so prefill replicas serve them instead
        # of handing off again) and prefer the decode replica the
        # pages landed on.
        self._migrated = False
        self._prefer_replica: Optional[str] = None

    @property
    def delivered(self) -> List[Any]:
        """Items yielded so far (the generated prefix for LLM streams)."""
        return list(self._delivered)

    def __iter__(self):
        return self

    def __next__(self):
        if self._iter is None:
            self._iter = self._run()
        return next(self._iter)

    def result(self, timeout_s: Optional[float] = None) -> List[Any]:
        """Drain the stream and return every item (LLM: the full list
        of generated tokens).  ``timeout_s`` installs the shared
        cross-attempt deadline if none was set at creation."""
        if timeout_s is not None and self._total_timeout_s is None:
            self._total_timeout_s = timeout_s
        for _ in self:
            pass
        return list(self._delivered)

    # -- attempt loop ------------------------------------------------------

    def _continuation_args(self):
        """Args for a resumed attempt.  Returns (args, skip): LLM dict
        payloads get prompt+prefix spliced in (skip 0); anything else
        replays verbatim and skips the delivered prefix.  args=None
        means the continuation has nothing left to generate.

        Prefix-resumed failover: the spliced payload re-enters the
        router's cache-aware selection (assign_streaming matches its
        ``tokens`` against replica prefix summaries), so with
        EngineConfig.prefix_cache the retry lands on a survivor
        holding the shared prefix and re-prefills only the cold tail —
        the replay's full re-prefill collapses to the uncached suffix
        plus the delivered tokens."""
        if not self._delivered:
            return self._args, 0
        first = self._args[0] if self._args else None
        if isinstance(first, dict) and "tokens" in first:
            payload = dict(first)
            payload["tokens"] = list(first["tokens"]) + \
                [t for t in self._delivered]
            if payload.get("max_new_tokens") is not None:
                remaining = (int(payload["max_new_tokens"])
                             - len(self._delivered))
                if remaining <= 0:
                    return None, 0
                payload["max_new_tokens"] = remaining
            payload["request_id"] = self.request_id
            if self._migrated:
                payload["_disagg_resumed"] = True
            return (payload,) + self._args[1:], 0
        return self._args, len(self._delivered)

    def _run(self):
        deadline = (None if self._total_timeout_s is None
                    else time.monotonic() + self._total_timeout_s)
        first = (self._args[0]
                 if self._args and isinstance(self._args[0], dict)
                 else {})
        self._router.note_queued(
            self.request_id, prompt_tokens=len(first.get("tokens", ())),
            adapter_id=first.get("adapter_id", ""))
        attempt = 0
        # Replicas this request is never sent to again: one that died,
        # and one that preempted it (a draining replica refuses every
        # later attempt too, and each refusal would be charged to the
        # retry budget).  With every replica tried the router holds the
        # request for the replacement, under the same deadline.
        tried: set = set()
        rng = random.Random(self.request_id)
        backoff = 0.05
        while True:
            call_args, skip = self._continuation_args()
            if call_args is None:
                break  # prefix already covers max_new_tokens
            assign_timeout = self._assign_timeout_s
            if deadline is not None:
                left = max(0.0, deadline - time.monotonic())
                assign_timeout = (left if assign_timeout is None
                                  else min(assign_timeout, left))
            gen, replica_id, _ = self._router.assign_streaming(
                self._method_name, call_args, self._kwargs,
                timeout=assign_timeout, exclude=tried,
                model_id=self._model_id, request_id=self.request_id,
                prefer_replica=self._prefer_replica)
            try:
                for ref in gen:
                    item = api.get(ref)
                    if skip > 0:
                        skip -= 1
                        continue
                    self._delivered.append(item)
                    yield item
            except GeneratorExit:
                # Consumer abandoned the stream: release the slot, no
                # retry, no terminal verdict (the request was dropped,
                # not failed).
                self._router.finish_streaming(replica_id)
                raise
            except Exception as err:
                died = _is_death(err)
                self._router.finish_streaming(replica_id, died=died)
                shed = _shed_error(err)
                if shed is not None:
                    # Admission-control shed: terminal immediately —
                    # SHED in the ring (distinct from FAILED: nothing
                    # ran), the unwrapped error to the caller so it can
                    # retry on its own schedule.
                    self._router.note_terminal(
                        self.request_id, _reqev.SHED, cause="ShedError",
                        generated_tokens=len(self._delivered))
                    raise shed from None
                handoff = _migration_handoff(err)
                if handoff is not None and (
                        deadline is None or time.monotonic() < deadline):
                    # Planned prefill→decode handoff, not a failure:
                    # resume immediately (no backoff — the pages are
                    # already waiting on the target) and do not charge
                    # the retry budget.  If the target died in the
                    # meantime, the next attempt's continuation replay
                    # recomputes locally like any other failover.
                    attempt += 1
                    self._migrated = True
                    self._prefer_replica = (handoff.target_replica_id
                                            or None)
                    self._router.note_migrating(
                        self.request_id, attempt, replica_id,
                        handoff.target_replica_id)
                    continue
                budget_left = (
                    _is_retriable(err)
                    and attempt < self._max_retries
                    and (deadline is None or time.monotonic() < deadline))
                if not budget_left:
                    self._router.note_terminal(
                        self.request_id, _reqev.FAILED,
                        cause=type(err).__name__,
                        generated_tokens=len(self._delivered))
                    raise
                tried.add(replica_id)
                attempt += 1
                self._router.note_retry(self.request_id, attempt,
                                        replica_id,
                                        reason=type(err).__name__)
                # Half-fixed + half-jitter (see DeploymentResponse
                # .result): spacing never collapses to ~0.  What makes a
                # bounced request outlast its replacement's start-up is
                # ``tried``: the router holds it until a replica it has
                # not tried is routable.
                delay = backoff / 2.0 + rng.uniform(0.0, backoff / 2.0)
                backoff = min(backoff * 2.0, 1.0)
                if deadline is not None:
                    delay = min(delay,
                                max(0.0, deadline - time.monotonic()))
                time.sleep(delay)
                continue
            else:
                self._router.finish_streaming(replica_id)
                break
        self._router.note_terminal(
            self.request_id, _reqev.FINISHED,
            generated_tokens=len(self._delivered))


class DeploymentHandle:
    """Client-side handle to a deployment (one router per process per
    deployment, shared across handle copies)."""

    def __init__(self, deployment_name: str, app_name: str,
                 method_name: str = "__call__",
                 assign_timeout_s: Optional[float] = None,
                 multiplexed_model_id: str = "",
                 stream: bool = False,
                 max_retries: int = 3):
        self.deployment_name = deployment_name
        self.app_name = app_name
        self._method_name = method_name
        # None = wait for a free replica slot indefinitely (backpressure,
        # the reference's behavior); a number bounds the wait.
        self._assign_timeout_s = assign_timeout_s
        self._multiplexed_model_id = multiplexed_model_id
        self._stream = stream
        self._max_retries = max_retries

    def options(self, *, method_name: Optional[str] = None,
                assign_timeout_s: Optional[float] = None,
                multiplexed_model_id: Optional[str] = None,
                stream: Optional[bool] = None,
                max_retries: Optional[int] = None
                ) -> "DeploymentHandle":
        return DeploymentHandle(
            self.deployment_name, self.app_name,
            method_name or self._method_name,
            (assign_timeout_s if assign_timeout_s is not None
             else self._assign_timeout_s),
            (multiplexed_model_id if multiplexed_model_id is not None
             else self._multiplexed_model_id),
            (stream if stream is not None else self._stream),
            (max_retries if max_retries is not None
             else self._max_retries),
        )

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        # handle.method.remote(...) sugar (parity: handle method access)
        return DeploymentHandle(self.deployment_name, self.app_name, name,
                                self._assign_timeout_s,
                                self._multiplexed_model_id,
                                self._stream, self._max_retries)

    def remote(self, *args, **kwargs):
        args = tuple(self._unwrap(a) for a in args)
        kwargs = {k: self._unwrap(v) for k, v in kwargs.items()}
        router = _router_for(self.app_name, self.deployment_name)
        if self._stream:
            # stream=True handles return a failover-aware generator; the
            # target method (default "stream" when the handle's method
            # was left at __call__) must be @serve-streaming on the
            # replica (LLMServer.stream is).
            method = ("stream" if self._method_name == "__call__"
                      else self._method_name)
            return DeploymentResponseGenerator(
                router, method, args, kwargs,
                assign_timeout_s=self._assign_timeout_s,
                model_id=self._multiplexed_model_id,
                max_retries=self._max_retries,
            )
        method = self._method_name
        timeout = self._assign_timeout_s
        model_id = self._multiplexed_model_id
        dead: set = set()
        last = [None]

        def submit() -> ObjectRef:
            if last[0] is not None:
                dead.add(last[0])
            ref, replica_id = router.assign(
                method, args, kwargs, timeout=timeout, exclude=dead,
                model_id=model_id,
            )
            last[0] = replica_id
            return ref

        return DeploymentResponse(submit(), resubmit=submit)

    @staticmethod
    def _unwrap(value: Any) -> Any:
        # Pass the underlying ref; the actor runtime resolves refs in args
        # before execution (parity: response-to-upstream-arg resolution).
        if isinstance(value, DeploymentResponse):
            return value._to_object_ref()
        return value

    def __repr__(self):
        return (f"DeploymentHandle({self.app_name}/{self.deployment_name}"
                f".{self._method_name})")

    def __reduce__(self):
        return (
            DeploymentHandle,
            (self.deployment_name, self.app_name, self._method_name,
             self._assign_timeout_s, self._multiplexed_model_id,
             self._stream, self._max_retries),
        )
