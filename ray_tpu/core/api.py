"""Public runtime API — init / remote / get / put / wait.

Parity with the reference's driver API
(ray: python/ray/_private/worker.py — init:1139, get:2481, put:2590,
wait:2653, remote:3027, shutdown:1716, kill, get_actor).
"""

from __future__ import annotations

import atexit
import inspect
import threading
from typing import Any, Dict, List, Optional, Sequence, Union

from ray_tpu.core.actor import ActorClass, ActorHandle, method  # noqa: F401
from ray_tpu.core.exceptions import RuntimeNotInitializedError
from ray_tpu.core.object_ref import ObjectRef
from ray_tpu.core.remote_function import RemoteFunction
from ray_tpu.core.runtime import LocalRuntime
from ray_tpu.util import tracing
from ray_tpu.utils.config import get_config

_runtime: Optional[LocalRuntime] = None
_runtime_lock = threading.Lock()


def runtime() -> LocalRuntime:
    global _runtime
    rt = _runtime
    if rt is None:
        raise RuntimeNotInitializedError()
    return rt


def is_initialized() -> bool:
    return _runtime is not None


def init(
    *,
    resources: Optional[Dict[str, float]] = None,
    num_cpus: Optional[float] = None,
    num_tpus: Optional[float] = None,
    system_config: Optional[Dict[str, Any]] = None,
    ignore_reinit_error: bool = False,
) -> LocalRuntime:
    """Start (or connect to) the runtime.

    Currently single-node: one in-process runtime hosting tasks/actors
    with logical resources.  TPU chips are auto-detected into the "TPU"
    resource (parity: _private/accelerator.py TPU detection).
    """
    global _runtime
    with _runtime_lock:
        if _runtime is not None:
            if ignore_reinit_error:
                return _runtime
            raise RuntimeError("ray_tpu.init() called twice — pass "
                               "ignore_reinit_error=True to allow")
        with tracing.span("runtime.init", startup=True):
            _start_runtime(resources, num_cpus, num_tpus, system_config)
        return _runtime


def _start_runtime(resources, num_cpus, num_tpus, system_config) -> None:
    global _runtime
    if system_config:
        get_config().update(system_config)
    total = dict(resources or {})
    labels = None
    if num_cpus is not None:
        total["CPU"] = float(num_cpus)
    if num_tpus is not None:
        total["TPU"] = float(num_tpus)
    elif "TPU" not in total:
        # Full detection path (parity: _private/accelerator.py):
        # chip count, version resource, slice-head resource, ICI
        # topology labels.
        from ray_tpu.utils.accelerator import node_resources_and_labels

        extra, labels = node_resources_and_labels()
        for k, v in extra.items():
            total.setdefault(k, v)
        labels = labels or None
    _runtime = LocalRuntime(resources=total, labels=labels)
    # Always-on telemetry history plane: the driver samples its own
    # registry; worker points arrive via reply piggyback
    # (runtime.apply_ref_batches → timeseries.ingest).
    from ray_tpu.util import timeseries

    timeseries.ensure_started()
    atexit.register(shutdown)


def shutdown() -> None:
    global _runtime
    with _runtime_lock:
        rt = _runtime
        _runtime = None
    if rt is not None:
        rt.shutdown()


def remote(*args, **kwargs):
    """@remote decorator for functions and classes (parity: ray.remote)."""

    def make(target):
        if inspect.isclass(target):
            return ActorClass(target, **kwargs)
        return RemoteFunction(target, **kwargs)

    if len(args) == 1 and not kwargs and (inspect.isfunction(args[0])
                                          or inspect.isclass(args[0])):
        return make(args[0])
    if args:
        raise TypeError("@remote takes only keyword options, e.g. "
                        "@remote(num_cpus=2)")
    return make


def get(refs: Union[ObjectRef, Sequence[ObjectRef]], *,
        timeout: Optional[float] = None):
    _check_refs(refs)
    return runtime().get(refs, timeout)


def put(value: Any) -> ObjectRef:
    if isinstance(value, ObjectRef):
        raise TypeError("put of an ObjectRef is not allowed")
    return runtime().put(value)


def wait(refs: Sequence[ObjectRef], *, num_returns: int = 1,
         timeout: Optional[float] = None, fetch_local: bool = True):
    if isinstance(refs, ObjectRef):
        raise TypeError("wait expects a list of ObjectRefs")
    _check_refs(refs)
    if num_returns > len(refs):
        raise ValueError("num_returns exceeds the number of refs")
    return runtime().wait(refs, num_returns, timeout, fetch_local)


def _check_refs(refs):
    if isinstance(refs, ObjectRef):
        return
    for r in refs:
        if not isinstance(r, ObjectRef):
            raise TypeError(f"expected ObjectRef, got {type(r).__name__}")


def kill(actor: ActorHandle, *, no_restart: bool = True) -> None:
    runtime().kill_actor(actor._actor_id, no_restart)


def get_actor(name: str) -> ActorHandle:
    actor_id, cls_name, table, cgroups = runtime().named_actor_handle(name)
    return ActorHandle(actor_id, cls_name, table,
                       method_cgroups=cgroups)


def cancel(ref: ObjectRef, *, force: bool = False) -> None:
    """Cancel the task producing ``ref`` (parity: ray.cancel).  Pending
    tasks never run; running tasks are interrupted cooperatively, or
    hard-killed with force=True in process mode.  get() of a cancelled
    ref raises TaskCancelledError; cancelled tasks never retry."""
    if not isinstance(ref, ObjectRef):
        raise TypeError(f"cancel expects an ObjectRef, got "
                        f"{type(ref).__name__}")
    runtime().cancel(ref.id, force=force)


def nodes() -> List[Dict[str, Any]]:
    return runtime().nodes()


def cluster_resources() -> Dict[str, float]:
    return runtime().cluster_resources()


def available_resources() -> Dict[str, float]:
    return runtime().available_resources()
