"""Replica actor: hosts one copy of a deployment's user callable.

Parity with the reference (ray: python/ray/serve/_private/replica.py —
RayServeReplica:494): constructs the user class, counts ongoing
requests, pushes autoscaling metrics to the controller, supports
``reconfigure(user_config)`` and user-defined ``check_health``.
"""

from __future__ import annotations

import inspect
import threading
import time
from typing import Any, Dict, Optional

from ray_tpu.core.actor import method
from ray_tpu.core.exceptions import PreemptedError
from ray_tpu.serve.deployment import _HandlePlaceholder
from ray_tpu.util import tracing

_TELEMETRY = None


def _telemetry():
    """Replica metric singletons (re-registered on refetch — see
    llm_engine._telemetry for the registry-clear rationale)."""
    global _TELEMETRY
    from ray_tpu.util import metrics

    if _TELEMETRY is None:
        _TELEMETRY = {
            "latency": metrics.Histogram(
                "raytpu_serve_request_latency_seconds",
                "End-to-end user-code latency inside the replica, by "
                "deployment.",
                boundaries=[0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0,
                            5.0, 10.0, 60.0],
                tag_keys=("deployment",),
            ),
            "ongoing": metrics.Gauge(
                "raytpu_serve_replica_ongoing",
                "Requests currently executing, by replica.",
                tag_keys=("deployment", "replica"),
            ),
        }
    else:
        reg = metrics.registry()
        for m in _TELEMETRY.values():
            reg.register(m)
    return _TELEMETRY


def _resolve_placeholders(value: Any) -> Any:
    from ray_tpu.serve.handle import DeploymentHandle

    if isinstance(value, _HandlePlaceholder):
        return DeploymentHandle(value.deployment_name, value.app_name)
    if isinstance(value, (list, tuple)):
        return type(value)(_resolve_placeholders(v) for v in value)
    if isinstance(value, dict):
        return {k: _resolve_placeholders(v) for k, v in value.items()}
    return value


class ReplicaActor:
    """The actor class every deployment replica runs as."""

    def __init__(self, app_name: str, deployment_name: str, replica_id: str,
                 func_or_class: Any, init_args: tuple, init_kwargs: dict,
                 user_config: Any, metrics_interval_s: float = 0.0,
                 shard_group: Optional[dict] = None,
                 disagg: Optional[dict] = None):
        self.app_name = app_name
        self.deployment_name = deployment_name
        self.replica_id = replica_id
        self._ongoing = 0
        self._total = 0
        self._lock = threading.Lock()
        self._tm = _telemetry()
        self._tags = {"deployment": deployment_name, "replica": replica_id}
        init_args = _resolve_placeholders(init_args)
        init_kwargs = _resolve_placeholders(init_kwargs)
        if shard_group is not None:
            # Rank 0 of a multi-host shard group: install the ambient
            # context BEFORE the user callable constructs, so an
            # engine-hosting callable builds its hybrid serving mesh
            # (serve/shard_group.py; LLMServer reads it).
            from ray_tpu.serve.shard_group import (
                ShardGroupContext,
                set_shard_group,
            )

            set_shard_group(ShardGroupContext(**shard_group))
        if disagg is not None:
            # Disaggregated prefill/decode role (config.disagg):
            # install the ambient context BEFORE the user callable
            # constructs, same pattern as the shard group — LLMServer
            # reads it to run the KV-migration handoff protocol.
            from ray_tpu.serve.kv_transfer import DisaggContext, set_disagg

            set_disagg(DisaggContext(**disagg))
        if inspect.isclass(func_or_class):
            # the start-up record's span of the user's own constructor
            with tracing.span("serve.replica_init", startup=True,
                              attributes={"deployment": deployment_name,
                                          "replica": replica_id}):
                self._callable = func_or_class(*init_args, **init_kwargs)
        else:
            if init_args or init_kwargs:
                raise ValueError(
                    "function deployments take no bind() arguments"
                )
            self._callable = func_or_class
        if user_config is not None:
            self.reconfigure(user_config)
        # Preemption-aware drain: once flipped the replica rejects new
        # data-plane requests with PreemptedError (the router retries
        # them on a surviving replica) and reports DRAINING from
        # check_health so the controller starts a replacement.
        self._draining = False
        self._install_sigterm_drain()
        self._metrics_stop = threading.Event()
        # Prefix-cache routing: a callable exposing prefix_summary()
        # (LLMServer over a prefix-cached engine) gets the push loop
        # even without an autoscaling metrics interval — the summary
        # rides the same thread, pushed only on change.
        self._last_prefix_summary = None
        _summary_fn = getattr(self._callable, "prefix_summary", None)
        try:
            # None at probe time = the cache is off for good (the flag
            # is construction-time config), so stay off the push path.
            self._pushes_summary = (callable(_summary_fn)
                                    and _summary_fn() is not None)
        except Exception:
            self._pushes_summary = False
        # LoRA multiplexing rides the same push thread: a callable
        # exposing adapter_summary() publishes its resident-adapter set
        # for adapter-affinity routing, pushed only on change.
        self._last_adapter_summary = None
        _adapter_fn = getattr(self._callable, "adapter_summary", None)
        try:
            self._pushes_adapters = (callable(_adapter_fn)
                                     and _adapter_fn() is not None)
        except Exception:
            self._pushes_adapters = False
        # SLO pressure signals for the autoscaler: a callable exposing
        # pressure() (LLMServer) reports its admission-queue age and
        # goodput ratio with every metrics push.
        _pressure_fn = getattr(self._callable, "pressure", None)
        self._pressure_fn = _pressure_fn if callable(_pressure_fn) else None
        if (metrics_interval_s > 0 or self._pushes_summary
                or self._pushes_adapters):
            threading.Thread(
                target=self._push_metrics_loop,
                args=(metrics_interval_s or 0.25,),
                daemon=True, name=f"metrics-{replica_id}",
            ).start()

    def _install_sigterm_drain(self) -> None:
        """Best-effort preemption notice: a SIGTERM (cloud preemption
        warning) drains the replica instead of letting it die hot with
        every stream attached.  Only installable from a process main
        thread (process-mode replicas); thread-mode replicas get the
        same behavior through the controller's drain_replica RPC."""
        import signal

        try:
            prev = signal.getsignal(signal.SIGTERM)

            def _on_sigterm(signum, frame):
                threading.Thread(target=self.drain, daemon=True,
                                 name=f"drain-{self.replica_id}").start()
                if callable(prev):
                    prev(signum, frame)

            signal.signal(signal.SIGTERM, _on_sigterm)
        except (ValueError, OSError):
            pass

    def _reject_if_draining(self) -> None:
        if self._draining:
            raise PreemptedError(
                f"replica {self.replica_id} is draining: not accepting "
                f"new requests")

    # -- data plane --------------------------------------------------------

    def _target(self, method_name: str):
        if method_name == "__call__":
            if not callable(self._callable):
                raise TypeError(
                    f"deployment {self.deployment_name!r} is not "
                    f"callable — define __call__ or route to a named "
                    f"method"
                )
            return self._callable
        return getattr(self._callable, method_name)

    def handle_request(self, method_name: str, args: tuple, kwargs: dict,
                       metadata: dict = None):
        from ray_tpu.core import api
        from ray_tpu.core.object_ref import ObjectRef
        from ray_tpu.serve import multiplex as _mux
        from ray_tpu.serve import request_events as _reqev

        self._reject_if_draining()
        # Upstream DeploymentResponses arrive as refs nested inside the
        # args tuple — resolve them here (parity: the reference resolves
        # response args before invoking the user method).
        args = tuple(
            api.get(a) if isinstance(a, ObjectRef) else a for a in args
        )
        kwargs = {
            k: api.get(v) if isinstance(v, ObjectRef) else v
            for k, v in kwargs.items()
        }
        t0 = time.perf_counter()
        with self._lock:
            self._ongoing += 1
            self._total += 1
            self._tm["ongoing"].set(self._ongoing, tags=self._tags)
        mux_token = _mux._set_model_id(
            (metadata or {}).get("multiplexed_model_id", "")
        )
        # The router-minted request id becomes ambient context for the
        # user callable (same token pattern as the mux model id) —
        # LLMEngine.submit and any downstream handle call inherit it.
        rid_token = _reqev.set_request_id(
            (metadata or {}).get("request_id", "")
        )
        try:
            with tracing.span(
                    "serve.replica",
                    attributes={"deployment": self.deployment_name,
                                "replica": self.replica_id,
                                "method": method_name,
                                "request_id":
                                    (metadata or {}).get("request_id")}):
                result = self._target(method_name)(*args, **kwargs)
                if inspect.iscoroutine(result):
                    import asyncio

                    result = asyncio.run(result)
                return result
        finally:
            _reqev.reset_request_id(rid_token)
            _mux._reset_model_id(mux_token)
            self._tm["latency"].observe(
                time.perf_counter() - t0,
                tags={"deployment": self.deployment_name})
            with self._lock:
                self._ongoing -= 1
                self._tm["ongoing"].set(self._ongoing, tags=self._tags)

    async def handle_request_async(self, method_name: str, args: tuple,
                                   kwargs: dict, metadata: dict = None):
        """Async data plane: runs as a coroutine on the replica actor's
        event loop, so max_ongoing_requests requests interleave their
        awaits on ONE loop instead of one thread each (parity: the
        reference's replica is natively asyncio, replica.py:494)."""
        from ray_tpu.core.object_ref import ObjectRef
        from ray_tpu.serve import multiplex as _mux
        from ray_tpu.serve import request_events as _reqev

        self._reject_if_draining()
        # List comp, not genexp: a generator expression containing
        # ``await`` is an async generator, which tuple() rejects.
        args = tuple(
            [(await a) if isinstance(a, ObjectRef) else a for a in args]
        )
        kwargs = {
            k: (await v) if isinstance(v, ObjectRef) else v
            for k, v in kwargs.items()
        }
        t0 = time.perf_counter()
        with self._lock:
            self._ongoing += 1
            self._total += 1
            self._tm["ongoing"].set(self._ongoing, tags=self._tags)
        mux_token = _mux._set_model_id(
            (metadata or {}).get("multiplexed_model_id", "")
        )
        rid_token = _reqev.set_request_id(
            (metadata or {}).get("request_id", "")
        )
        try:
            # Metrics only on the async plane: a span context manager
            # around an await would leak its thread-local ctx across
            # every coroutine interleaved on the loop.
            target = self._target(method_name)
            # Per-METHOD dispatch: the deployment is announced async off
            # its __call__, but a sync named method must not run inline
            # on the shared event loop (it would freeze every
            # interleaved request, or deadlock if it blocks on another
            # coroutine's output) — push it to a thread.
            fn = (target if inspect.isroutine(target)
                  else getattr(target, "__call__", target))
            if inspect.iscoroutinefunction(fn):
                return await target(*args, **kwargs)
            import asyncio
            import contextvars
            import functools

            loop = asyncio.get_running_loop()
            # copy_context(): run_in_executor does not carry
            # contextvars to the worker thread — the request id (and
            # mux model id) must follow the sync target there.
            result = await loop.run_in_executor(
                None,
                functools.partial(contextvars.copy_context().run,
                                  functools.partial(target, *args,
                                                    **kwargs)))
            if inspect.iscoroutine(result):
                result = await result
            return result
        finally:
            _reqev.reset_request_id(rid_token)
            _mux._reset_model_id(mux_token)
            self._tm["latency"].observe(
                time.perf_counter() - t0,
                tags={"deployment": self.deployment_name})
            with self._lock:
                self._ongoing -= 1
                self._tm["ongoing"].set(self._ongoing, tags=self._tags)

    @method(num_returns="streaming")
    def handle_request_streaming(self, method_name: str, args: tuple,
                                 kwargs: dict, metadata: dict = None):
        """Streaming data plane: the user target returns an iterable
        (e.g. ``LLMServer.stream``) and each item rides back as one
        stream element.  A replica death or preemption seals the error
        AFTER every already-yielded item, so the consumer-side failover
        (handle.DeploymentResponseGenerator) resumes from exactly the
        delivered prefix."""
        from ray_tpu.core import api
        from ray_tpu.core.object_ref import ObjectRef
        from ray_tpu.serve import multiplex as _mux
        from ray_tpu.serve import request_events as _reqev
        from ray_tpu.utils.test_utils import fail_point

        self._reject_if_draining()
        fail_point("replica.stream")
        args = tuple(
            api.get(a) if isinstance(a, ObjectRef) else a for a in args
        )
        kwargs = {
            k: api.get(v) if isinstance(v, ObjectRef) else v
            for k, v in kwargs.items()
        }
        t0 = time.perf_counter()
        with self._lock:
            self._ongoing += 1
            self._total += 1
            self._tm["ongoing"].set(self._ongoing, tags=self._tags)
        mux_token = _mux._set_model_id(
            (metadata or {}).get("multiplexed_model_id", "")
        )
        rid_token = _reqev.set_request_id(
            (metadata or {}).get("request_id", "")
        )
        try:
            with tracing.span(
                    "serve.replica",
                    attributes={"deployment": self.deployment_name,
                                "replica": self.replica_id,
                                "method": method_name,
                                "streaming": True,
                                "request_id":
                                    (metadata or {}).get("request_id")}):
                for item in self._target(method_name)(*args, **kwargs):
                    yield item
        finally:
            _reqev.reset_request_id(rid_token)
            _mux._reset_model_id(mux_token)
            self._tm["latency"].observe(
                time.perf_counter() - t0,
                tags={"deployment": self.deployment_name})
            with self._lock:
                self._ongoing -= 1
                self._tm["ongoing"].set(self._ongoing, tags=self._tags)

    # -- control plane -----------------------------------------------------

    def drain(self, grace_s: float = 5.0) -> str:
        """Preemption notice (controller drain_replica RPC, SIGTERM, or
        a node-daemon maintenance event): stop accepting new requests
        and hand the notice down to the user callable's ``drain`` hook
        when it has one (LLMServer drains its engine — short requests
        finish, long ones are evicted with continuations).  Idempotent;
        returns the DRAINING health state."""
        with self._lock:
            already = self._draining
            self._draining = True
        if not already:
            fn = getattr(self._callable, "drain", None)
            if fn is not None:
                fn(grace_s)
        return "DRAINING"

    def get_metadata(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "replica_id": self.replica_id,
                "ongoing": self._ongoing,
                "total": self._total,
            }

    def num_ongoing_requests(self) -> int:
        with self._lock:
            return self._ongoing

    def reconfigure(self, user_config: Any) -> None:
        fn = getattr(self._callable, "reconfigure", None)
        if fn is None:
            raise ValueError(
                f"deployment {self.deployment_name!r} got user_config but "
                f"defines no reconfigure(config) method"
            )
        fn(user_config)

    def check_health(self):
        """True = healthy; the string "DRAINING" = alive but draining
        (the controller starts a replacement without tearing this
        replica out of the route table first); raises = unhealthy."""
        with tracing.span("serve.health_probe", record=False):
            if self._draining:
                return "DRAINING"
            fn = getattr(self._callable, "check_health", None)
            if fn is not None:
                fn()  # raises on unhealthy (parity: serve health-check contract)
            return True

    def doctor(self, deep: bool = True) -> Optional[Dict[str, Any]]:
        """Run the invariant doctor on the user callable's engine
        (LLMServer.doctor → LLMEngine.doctor) and return its report;
        None when the callable has no doctor surface."""
        fn = getattr(self._callable, "doctor", None)
        if fn is None:
            return None
        return fn(deep=deep)

    def prepare_for_shutdown(self, timeout_s: float) -> None:
        """Drain: wait for ongoing requests to finish (parity:
        graceful_shutdown_timeout_s)."""
        self._metrics_stop.set()
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._lock:
                if self._ongoing == 0:
                    return
            time.sleep(0.01)

    def _push_metrics_loop(self, interval_s: float) -> None:
        from ray_tpu.core import api
        from ray_tpu.serve.controller import CONTROLLER_NAME

        # Controller-outage tolerance: a failed push backs off
        # (capped-exponential) and RETRIES instead of killing the loop
        # — a controller crash would otherwise permanently silence this
        # replica's autoscaling signal and routing summaries even after
        # recovery.  The latest summary IS the buffer: on reconnect the
        # change-detection baselines reset so the new controller epoch
        # (whose adopted record may predate recent changes) gets a
        # fresh push of both summaries.
        backoff = interval_s or 0.05
        failing = False
        while not self._metrics_stop.wait(
                backoff if failing else interval_s):
            try:
                with tracing.span("serve.push_pressure",
                                  record=False):
                    controller = api.get_actor(CONTROLLER_NAME)
                    if failing:
                        failing = False
                        backoff = interval_s or 0.05
                        self._last_prefix_summary = None
                        self._last_adapter_summary = None
                    qage, goodput, arrivals = 0.0, None, None
                    if self._pressure_fn is not None:
                        try:
                            p = self._pressure_fn()
                            qage = float(p.get("queue_age_s") or 0.0)
                            goodput = p.get("goodput")
                            arrivals = p.get("arrivals")
                        except Exception:
                            pass
                    controller.record_autoscaling_metric.remote(
                        self.app_name, self.deployment_name, self.replica_id,
                        self.num_ongoing_requests(), time.monotonic(),
                        qage, goodput, arrivals,
                    )
                    if self._pushes_summary:
                        try:
                            summary = self._callable.prefix_summary()
                        except Exception:
                            summary = None
                        if (summary is not None
                                and summary != self._last_prefix_summary):
                            self._last_prefix_summary = summary
                            controller.record_prefix_summary.remote(
                                self.app_name, self.deployment_name,
                                self.replica_id, summary,
                            )
                    if self._pushes_adapters:
                        try:
                            asum = self._callable.adapter_summary()
                        except Exception:
                            asum = None
                        if (asum is not None
                                and asum != self._last_adapter_summary):
                            self._last_adapter_summary = asum
                            controller.record_adapter_summary.remote(
                                self.app_name, self.deployment_name,
                                self.replica_id, asum,
                            )
            except Exception:
                failing = True
                backoff = min(max(backoff, 0.05) * 2.0, 2.0)


class ShardMemberActor:
    """Rank >= 1 of a multi-host shard-group replica.

    Holds one placement-group bundle (one host's worth of chips) and
    answers health pings; its DEATH is the group's failure signal —
    the controller treats any member loss as whole-replica failure and
    routes the group through the PR-5 drain/failover path.  On real
    multi-host TPU this process additionally joins the group's
    jax.distributed runtime so rank 0's hybrid mesh spans its chips;
    on the CPU test backend the mesh lives over rank 0's virtual
    devices and this actor is purely the membership/fault unit."""

    def __init__(self, group_id: str, rank: int, size: int):
        self.group_id = group_id
        self.rank = rank
        self.size = size

    def ping(self) -> str:
        return f"{self.group_id}/{self.rank}"

    def get_metadata(self) -> Dict[str, Any]:
        return {"group_id": self.group_id, "rank": self.rank,
                "size": self.size}
