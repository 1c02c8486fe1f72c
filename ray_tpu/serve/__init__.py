"""ray_tpu.serve — model serving on the actor runtime.

Parity with the reference (ray: python/ray/serve/api.py — serve.run:479,
serve.start, serve.shutdown, @serve.deployment, @serve.batch,
get_deployment_handle/get_app_handle).  TPU-specific addition: the
continuous-batching LLM engine (ray_tpu.serve.llm_engine) — the
reference delegates model inference entirely to user code.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

from ray_tpu.util import tracing

_importing = tracing.import_span(__name__)

from ray_tpu.core import api as _api  # noqa: E402
from ray_tpu.serve.batching import batch
from ray_tpu.serve.config import AutoscalingConfig, DeploymentConfig
from ray_tpu.serve.deployment import (
    Application,
    Deployment,
    build_application,
    deployment,
)
from ray_tpu.serve.handle import (
    DeploymentHandle,
    DeploymentResponse,
    _shutdown_routers,
)
from ray_tpu.serve.graph import (
    DAGDriver,
    InputNode,
    build_graph_app,
)
from ray_tpu.serve.multiplex import get_multiplexed_model_id, multiplexed

_importing.__exit__(None, None, None)

__all__ = [
    "Application", "AutoscalingConfig", "Deployment", "DeploymentConfig",
    "DAGDriver", "DeploymentHandle", "DeploymentResponse",
    "InputNode", "batch", "build_graph_app", "deployment",
    "delete", "get_app_handle", "get_deployment_handle",
    "get_multiplexed_model_id", "multiplexed", "run", "shutdown",
    "start", "status",
]

_proxy = None


def _get_or_create_controller():
    from ray_tpu.serve.controller import CONTROLLER_NAME, ServeController

    if not _api.is_initialized():
        _api.init(ignore_reinit_error=True)
    cls = _api.remote(ServeController)
    # Crash-recoverable control plane: max_restarts covers in-place
    # actor restarts, and a controller that died outright (hard kill,
    # restarts exhausted) is recreated HERE as a fresh actor — either
    # way __init__ reloads the persisted checkpoint, re-censuses the
    # fleet and rebroadcasts before serving, so callers of this
    # function always get a controller that reflects reality.
    return cls.options(
        name=CONTROLLER_NAME, get_if_exists=True, lifetime="detached",
        num_cpus=0, max_concurrency=32, max_restarts=3,
    ).remote()


def start(http_port: Optional[int] = None, http_host: str = "127.0.0.1"):
    """Start the Serve control plane (and optionally the HTTP proxy).
    Parity: serve.start (ray serve/api.py)."""
    global _proxy
    _get_or_create_controller()
    if http_port is not None and _proxy is None:
        from ray_tpu.serve.http import AsyncHTTPProxy

        _proxy = AsyncHTTPProxy(http_host, http_port)
    return _proxy


def run(app: Application, *, name: str = "default",
        route_prefix: Optional[str] = "/", wait_for_ready: bool = True,
        timeout_s: float = 60.0) -> DeploymentHandle:
    """Deploy an application; returns a handle to its ingress deployment
    (parity: ray serve.run api.py:479)."""
    with tracing.span("serve.run", startup=True):
        with tracing.span("serve.deploy", startup=True):
            controller = _get_or_create_controller()
            infos = build_application(app, name)
            _api.get(controller.deploy_application.remote(
                name, infos, route_prefix))
        if wait_for_ready:
            with tracing.span("serve.wait_ready", startup=True):
                _wait_healthy(controller, name, timeout_s)
        ingress = _api.get(controller.get_ingress.remote(name))
    return DeploymentHandle(ingress, name)


def _wait_healthy(controller, name: str, timeout_s: float) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        st = _api.get(controller.status.remote())
        deps = st["applications"].get(name, {}).get("deployments", {})
        if deps and all(d["status"] == "HEALTHY" for d in deps.values()):
            return
        time.sleep(0.02)
    raise TimeoutError(
        f"application {name!r} not healthy after {timeout_s}s: "
        f"{_api.get(controller.status.remote())}"
    )


def get_app_handle(name: str = "default") -> DeploymentHandle:
    controller = _get_or_create_controller()
    ingress = _api.get(controller.get_ingress.remote(name))
    return DeploymentHandle(ingress, name)


def get_deployment_handle(deployment_name: str, app_name: str = "default"
                          ) -> DeploymentHandle:
    return DeploymentHandle(deployment_name, app_name)


def status() -> Dict[str, Any]:
    controller = _get_or_create_controller()
    return _api.get(controller.status.remote())


def delete(name: str, *, wait: bool = True, timeout_s: float = 10.0) -> None:
    controller = _get_or_create_controller()
    _api.get(controller.delete_application.remote(name))
    if wait:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            st = _api.get(controller.status.remote())
            if name not in st["applications"] or not st["applications"][
                name
            ]["deployments"]:
                return
            time.sleep(0.02)


def shutdown(timeout_s: float = 10.0) -> None:
    """Tear down all applications, replicas, proxy and the controller
    (parity: serve.shutdown)."""
    global _proxy
    from ray_tpu.serve.controller import CONTROLLER_NAME

    if _proxy is not None:
        _proxy.shutdown()
        _proxy = None
    _shutdown_routers()
    if not _api.is_initialized():
        return
    try:
        controller = _api.get_actor(CONTROLLER_NAME)
    except ValueError:
        return
    try:
        _api.get(controller.graceful_shutdown.remote())
        _api.get(controller.wait_for_drained.remote(timeout_s))
    finally:
        try:
            _api.get(controller.stop_reconcile.remote(), timeout=5.0)
        except Exception:
            pass
        _api.kill(controller, no_restart=True)
