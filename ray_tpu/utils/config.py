"""Central runtime configuration registry.

Parity with the reference's ``RAY_CONFIG`` macro table
(ray: src/ray/common/ray_config_def.h — 208 env-overridable knobs with
priority env > _system_config > default).  We keep the same three-level
priority but as a typed Python dataclass-like registry: every knob is
declared once with a type and default, is overridable via a
``RAYTPU_<NAME>`` environment variable, and can be overridden
programmatically via ``init(system_config={...})``.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict


def _parse_bool(v: str) -> bool:
    return v.strip().lower() in ("1", "true", "yes", "on")


_PARSERS: Dict[type, Callable[[str], Any]] = {
    bool: _parse_bool,
    int: int,
    float: float,
    str: str,
}


class _Knob:
    __slots__ = ("name", "type", "default", "doc")

    def __init__(self, name: str, type_: type, default: Any, doc: str = ""):
        self.name = name
        self.type = type_
        self.default = default
        self.doc = doc


class Config:
    """Process-wide config. Priority: env RAYTPU_<NAME> > overrides > default."""

    _KNOBS: Dict[str, _Knob] = {}

    def __init__(self):
        self._lock = threading.Lock()
        self._overrides: Dict[str, Any] = {}

    @classmethod
    def declare(cls, name: str, type_: type, default: Any, doc: str = "") -> None:
        cls._KNOBS[name] = _Knob(name, type_, default, doc)

    def get(self, name: str) -> Any:
        knob = self._KNOBS[name]
        env = os.environ.get(f"RAYTPU_{name.upper()}")
        if env is not None:
            return _PARSERS[knob.type](env)
        with self._lock:
            if name in self._overrides:
                return self._overrides[name]
        return knob.default

    def set(self, name: str, value: Any) -> None:
        knob = self._KNOBS[name]
        if not isinstance(value, knob.type):
            # strings go through the same parsers as env vars, so
            # set('some_bool', 'false') is False, not bool('false')
            if isinstance(value, str):
                value = _PARSERS[knob.type](value)
            else:
                value = knob.type(value)
        with self._lock:
            self._overrides[name] = value

    def update(self, overrides: Dict[str, Any]) -> None:
        for k, v in overrides.items():
            self.set(k, v)

    def snapshot(self) -> Dict[str, Any]:
        """Everything, resolved — shipped to spawned workers at startup."""
        return {name: self.get(name) for name in self._KNOBS}

    def __getattr__(self, name: str) -> Any:
        if name.startswith("_"):
            raise AttributeError(name)
        try:
            return self.get(name)
        except KeyError:
            raise AttributeError(name) from None


D = Config.declare

# --- Object store ---------------------------------------------------------
D("object_store_memory_bytes", int, 2 * 1024**3, "Shared-memory arena size per node.")
D("object_store_min_alloc", int, 64, "Minimum allocation granularity (bytes).")
D("object_inline_max_bytes", int, 100 * 1024,
  "Objects at or below this size travel inline in RPCs instead of the store.")
D("object_spill_threshold", float, 0.8,
  "Store fullness fraction that triggers spilling to disk.")
D("object_spill_dir", str, "", "Directory for spilled objects ('' = <session>/spill).")
D("object_store_inproc_cap_bytes", int, 512 * 1024**2,
  "In-process tier size that triggers spilling of cold sealed objects.")

# --- Scheduler ------------------------------------------------------------
D("scheduler_spread_threshold", float, 0.5,
  "Hybrid policy: pack onto a node until this utilization, then spread.")
D("scheduler_top_k_fraction", float, 0.2,
  "Hybrid policy: random choice among the top k fraction of candidate nodes.")
D("worker_lease_timeout_s", float, 30.0, "Worker lease request timeout.")
D("max_pending_lease_requests_per_scheduling_class", int, 10,
  "Pipelined lease requests per distinct (fn, resources) class.")
D("resource_view_sync_period_s", float, 0.25,
  "Head→daemon resource-view broadcast period (parity: the Ray "
  "Syncer's resource gossip).  Daemons schedule their workers' nested "
  "submissions locally against this view — bounded overcommit within "
  "one period; 0 disables the sync AND the daemon-local fast path.")
D("remote_lease_idle_s", float, 10.0,
  "Head-side cached worker leases idle this long return to their node "
  "daemon (lease pipelining parity: OnWorkerIdle keeps leased workers "
  "hot between tasks, direct_task_transport.cc:191).")

# --- Workers --------------------------------------------------------------
D("workers", str, "process",
  "Execution backend: 'process' (default — pooled OS worker processes "
  "over the shared-memory object plane: real parallelism and crash "
  "isolation, like the reference, which never runs user code in the "
  "driver: ray src/ray/raylet/worker_pool.h:156) or 'thread' "
  "(in-process, fast start, GIL-bound — the annotated exception for "
  "latency-critical embedded uses and tests).  Env: RAYTPU_WORKERS.")
D("worker_prestart", int, 0,
  "Spawn this many workers in the background at init (hides cold-start).")
D("num_workers_soft_limit", int, 0, "0 = num_cpus workers per node.")
D("worker_register_timeout_s", float, 30.0, "Startup handshake deadline.")
D("worker_idle_timeout_s", float, 300.0, "Idle worker reap time.")

# --- Control plane --------------------------------------------------------
D("health_check_period_s", float, 5.0,
  "Worker liveness probe period (0 disables).  The probe shares the "
  "worker's GIL, so the failure window (period x threshold) must exceed "
  "any single GIL-holding C call a healthy task might make.")
D("health_check_failure_threshold", int, 6,
  "Unresponsive for period x threshold (default 30 s) = dead.")
D("task_event_buffer_size", int, 10000, "Ring buffer of task state events.")
D("pubsub_poll_timeout_s", float, 30.0, "Long-poll timeout for subscribers.")

# --- Control-plane persistence (GCS fault tolerance) ----------------------
D("gcs_persist_path", str, "",
  "File the control plane snapshots to (KV, detached-actor specs, "
  "placement-group specs).  '' disables persistence; a driver restart "
  "pointed at the same path recovers the state (parity: the Redis-backed "
  "GCS storage, gcs/store_client/redis_store_client.h:33).  "
  "Env: RAYTPU_GCS_PERSIST_PATH.")
D("gcs_flush_period_s", float, 0.2,
  "Dirty-snapshot flush period (crash loses at most this window, like "
  "Redis AOF everysec).")
D("gcs_persist_mirrors", str, "",
  "Comma-separated replica snapshot paths mirrored best-effort on "
  "every flush (a peer machine's export / NFS / bucket mount).  Head "
  "bootstrap loads the NEWEST readable snapshot across primary + "
  "mirrors, so the control plane survives head MACHINE loss — the "
  "external-Redis deployment's role (gcs_server.cc:517-518).  "
  "Env: RAYTPU_GCS_PERSIST_MIRRORS.")
D("head_reconnect_window_s", float, 60.0,
  "How long a node daemon keeps retrying to rejoin the head after its "
  "channel drops before giving up and exiting (parity: raylets "
  "reconnecting to a restarted GCS, gcs/gcs_client reconnect + "
  "gcs_rpc_server_reconnect_timeout_s).  0 = exit immediately on head "
  "loss (pre-FT behavior).")
D("head_reconnect_retry_s", float, 0.5,
  "Delay between daemon rejoin attempts while the head is unreachable.")
D("serve_checkpoint_flush_period_s", float, 0.05,
  "Serve-controller checkpoint flush period: a controller crash loses "
  "at most this window of control-state mutations (the recovery "
  "re-census covers the gap).  The checkpoint persists through the "
  "cluster KV, so it survives the controller ACTOR's death and "
  "inherits disk durability whenever gcs_persist_path is set.  "
  "Env: RAYTPU_SERVE_CHECKPOINT_FLUSH_PERIOD_S.")
D("serve_checkpoint_mirrors", str, "",
  "Comma-separated file paths mirrored best-effort on every serve "
  "controller checkpoint flush (same MirroredStore semantics as "
  "gcs_persist_mirrors): recovery loads the NEWEST readable copy "
  "across KV + mirrors.  Env: RAYTPU_SERVE_CHECKPOINT_MIRRORS.")

# --- Fault tolerance ------------------------------------------------------
D("task_max_retries_default", int, 3, "Default retries for idempotent tasks.")
D("actor_max_restarts_default", int, 0, "Default actor restarts.")
D("lineage_max_bytes", int, 256 * 1024**2, "Lineage table cap per owner.")

# --- TPU / mesh -----------------------------------------------------------
D("tpu_topology", str, "", "Override detected topology, e.g. 'v5p-64'.")
D("mesh_allow_cpu_fallback", bool, True,
  "Build meshes over the CPU backend when no TPU is present (tests).")
D("ici_contiguous_placement", bool, True,
  "Placement groups prefer ICI-contiguous chips within a slice.")

# --- Logging --------------------------------------------------------------
D("log_dir", str, "",
  "Worker stdout/stderr log directory ('' = fresh temp dir per node).")
D("log_to_driver", bool, True,
  "Echo worker log lines at the head console, prefixed with their "
  "worker/node (parity: ray's log_to_driver).")
D("log_monitor_period_s", float, 0.3, "Log tail/publish period.")
D("log_buffer_lines", int, 10000,
  "Head-side bounded window of cluster worker log lines.")

# --- Metrics / events -----------------------------------------------------
D("metrics_export_interval_s", float, 10.0, "Metrics flush period.")
D("event_log_dir", str, "", "Structured event log dir ('' = <session>/events).")


GLOBAL_CONFIG = Config()


def get_config() -> Config:
    return GLOBAL_CONFIG
