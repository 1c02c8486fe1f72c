"""Serve controller: deployment reconciliation, health, autoscaling.

Parity with the reference (ray: python/ray/serve/controller.py —
ServeController:80; serve/_private/deployment_state.py —
DeploymentState:1155, DeploymentStateManager:2258; application
lifecycle serve/_private/application_state.py; autoscaling
serve/_private/autoscaling_policy.py).  A single named actor owns all
target state and runs a reconcile loop: start/stop/replace replica
actors until the running set matches the target, health-check them,
and broadcast routing tables over long-poll.
"""

from __future__ import annotations

import logging
import math
import threading
import time
import traceback
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.core import api
from ray_tpu.serve.config import AutoscalingConfig, DeploymentConfig
from ray_tpu.serve.deployment import DeploymentInfo
from ray_tpu.serve.long_poll import LongPollHost
from ray_tpu.serve.replica import ReplicaActor

log = logging.getLogger(__name__)

CONTROLLER_NAME = "serve::controller"
ROUTES_KEY = "routes"

RECONCILE_PERIOD_S = 0.05

# Controller-checkpoint blob: layout version INSIDE the GCS snapshot
# envelope (which carries its own format version + monotonic seq), and
# the cluster-KV slot it persists through.  The KV lives on the driver
# runtime, so it survives the controller ACTOR's death — and inherits
# disk durability when gcs_persist_path is configured.
CKPT_VERSION = 1
CKPT_NAMESPACE = "serve"
CKPT_KEY = b"controller::checkpoint"

_TELEMETRY = None


def _telemetry():
    """Controller metric singletons (re-registered on refetch — see
    llm_engine._telemetry for the registry-clear rationale)."""
    global _TELEMETRY
    from ray_tpu.util import metrics

    if _TELEMETRY is None:
        _TELEMETRY = {
            "drains": metrics.Counter(
                "raytpu_serve_replica_drains_total",
                "Replica drains begun (preemption notices, SIGTERM, "
                "drain_replica RPCs), by deployment.",
                tag_keys=("deployment",),
            ),
            "reconcile_errors": metrics.Counter(
                "raytpu_serve_reconcile_errors_total",
                "Exceptions swallowed by the controller reconcile "
                "loop — nonzero means the control plane is limping.",
            ),
            "shard_members": metrics.Gauge(
                "raytpu_serve_shard_group_members",
                "Member processes of a multi-host shard-group replica "
                "(rank 0 + shard members; 0 once the group is torn "
                "down), by deployment and replica.",
                tag_keys=("deployment", "replica"),
            ),
            "autoscale_decisions": metrics.Counter(
                "raytpu_serve_autoscale_decisions_total",
                "Applied autoscaling decisions, by deployment, "
                "direction (up = capacity added; down = retirement "
                "through the DRAINING path) and reason (ongoing / "
                "queue_age / goodput / arrival_slope — the last is the "
                "predictive path: scaled on arrival-rate slope before "
                "any queue formed).",
                tag_keys=("deployment", "direction", "reason"),
            ),
            "autoscale_target": metrics.Gauge(
                "raytpu_serve_autoscale_target_groups",
                "Shard groups (replicas) the reconciler is currently "
                "driving the deployment toward.",
                tag_keys=("deployment",),
            ),
            "autoscale_actual": metrics.Gauge(
                "raytpu_serve_autoscale_actual_groups",
                "Shard groups (replicas) currently RUNNING, by "
                "deployment — lags the target while replicas start "
                "or drain.",
                tag_keys=("deployment",),
            ),
            "restarts": metrics.Counter(
                "raytpu_serve_controller_restarts_total",
                "Controller recoveries: a replacement controller "
                "adopted a previous epoch's state from the persisted "
                "checkpoint after the controller actor died.",
            ),
            "ckpt_seq": metrics.Gauge(
                "raytpu_serve_controller_checkpoint_seq",
                "Monotonic save counter of the controller checkpoint "
                "(resumed across controller generations, so it never "
                "regresses).",
            ),
            "ckpt_age": metrics.Gauge(
                "raytpu_serve_controller_checkpoint_age_seconds",
                "Seconds since the controller checkpoint was last "
                "persisted — climbing under traffic means the "
                "checkpointer is wedged and a crash would lose state.",
            ),
            "orphans_adopted": metrics.Counter(
                "raytpu_serve_orphans_adopted_total",
                "Checkpointed replicas found alive at controller "
                "recovery and adopted back into the census.",
            ),
            "orphans_killed": metrics.Counter(
                "raytpu_serve_orphans_killed_total",
                "Live replica actors from a previous controller epoch "
                "with no checkpoint record, hard-killed at recovery "
                "(they are invisible to reconciliation and would leak "
                "forever).",
            ),
        }
    else:
        reg = metrics.registry()
        for m in _TELEMETRY.values():
            reg.register(m)
    return _TELEMETRY


def replica_set_key(app_name: str, deployment_name: str) -> str:
    return f"replicas::{app_name}::{deployment_name}"


class _Replica:
    def __init__(self, replica_id: str, handle, creation_ref):
        self.replica_id = replica_id
        self.handle = handle
        self.creation_ref = creation_ref
        # STARTING | RUNNING | DRAINING | STOPPING.  DRAINING = alive
        # and still routable (it finishes what it has, rejects new
        # work) while a replacement starts; it leaves the broadcast
        # table only once RUNNING capacity is back at target.
        self.state = "STARTING"
        self.health_ref = None
        self.last_health_check = time.monotonic()
        # Drain bookkeeping: retirement waits for in-flight work to
        # settle (ongoing_ref polls the replica) up to drain_deadline.
        self.drain_deadline = None
        self.ongoing_ref = None
        # Latest prefix-cache routing summary the replica pushed
        # ({"page": …, "hashes": […]}), re-broadcast on the route
        # table so routers can prefer the replica holding the longest
        # cached prefix.  None = no cache / nothing cached yet.
        self.prefix_summary = None
        # Latest resident-adapter routing summary the replica pushed
        # ({"adapters": [ids…]}), re-broadcast the same way so routers
        # can prefer the replica already holding a request's LoRA
        # adapter.  None = multiplexing off / nothing resident yet.
        self.adapter_summary = None
        # Multi-host shard group (config.shard_group): rank 0 IS this
        # replica's handle (the streaming endpoint the router
        # addresses); members holds the rank >= 1 ShardMemberActor
        # handles whose death fails the whole group.
        self.members: List[Tuple[int, Any]] = []
        self.pg = None
        self.mesh_shape = ""
        self.member_ping_refs = None
        # Disaggregated serving role (config.disagg): "prefill" |
        # "decode" | "unified".  Assigned at start by live-role census
        # so a killed prefill replica's replacement is prefill again.
        self.role = "unified"
        # Ongoing-request count carried on the last broadcast row for
        # this replica — metric pushes rebroadcast only when the live
        # count moved a whole request away from it (live-load routing
        # without a 20 Hz broadcast storm).
        self.bcast_ongoing = 0.0


class _DeploymentState:
    """Target + running state for one deployment (parity:
    serve/_private/deployment_state.py DeploymentState)."""

    def __init__(self, app_name: str, info: DeploymentInfo):
        self.app_name = app_name
        self.info = info
        self.target_replicas = info.config.initial_target_replicas()
        self.replicas: Dict[str, _Replica] = {}
        self.next_replica_idx = 0
        self.deleting = False
        # autoscaling bookkeeping: id -> (ts, ongoing, queue_age, goodput)
        self.metrics: Dict[str, Tuple[float, float, float,
                                      Optional[float]]] = {}
        # Arrival-rate signal (predictive scale-up): per-replica
        # cumulative arrival counts fold reset-tolerantly into one
        # deployment-wide total that feeds an EWMA rate + slope
        # (serve/signals.ArrivalSignal).  Lazy: only built when the
        # config enables upscale_slope_threshold, so the reactive-only
        # path stays byte-for-byte what it was.
        self._arrival_prev: Dict[str, float] = {}
        self._arrival_total = 0.0
        self._arrival_signal = None
        self._scale_intent: Optional[Tuple[int, float]] = None
        # Last APPLIED scale decision ({direction, from, to, reason,
        # ts}) — surfaced on list_replicas rows for `raytpu list
        # replicas`.  None until the policy first moves the target.
        self.last_decision: Optional[Dict[str, Any]] = None
        # What the last routing-table broadcast actually announced:
        # [(replica_id, draining)] — the doctor's census_broadcast
        # check recomputes the expected table from the replica census
        # and diffs it against this.
        self.last_broadcast: List[Tuple[str, bool]] = []

    @property
    def config(self) -> DeploymentConfig:
        return self.info.config

    def apply_new_info(self, info: DeploymentInfo) -> None:
        """Code or config update: lightweight path for user_config-only
        changes, full rolling replace otherwise."""
        old = self.info
        self.info = info
        auto = info.config.autoscaling_config
        if auto is not None:
            # Preserve the autoscaled target across idempotent redeploys —
            # only clamp into the (possibly new) bounds.
            self.target_replicas = max(
                auto.min_replicas, min(auto.max_replicas, self.target_replicas)
            )
        else:
            self.target_replicas = info.config.initial_target_replicas()
        same_code = (
            old.func_or_class is info.func_or_class
            and old.init_args == info.init_args
            and old.init_kwargs == info.init_kwargs
        )
        if same_code and old.config.user_config != info.config.user_config:
            for r in self.replicas.values():
                if r.state == "RUNNING":
                    r.handle.reconfigure.remote(info.config.user_config)
        elif not same_code:
            # Replace everything; reconcile restarts at the new version.
            for r in self.replicas.values():
                r.state = "STOPPING"

    # -- autoscaling -------------------------------------------------------

    def _signal(self):
        cfg = self.config.autoscaling_config
        if cfg is None or cfg.upscale_slope_threshold is None:
            return None
        if self._arrival_signal is None:
            from ray_tpu.serve.signals import ArrivalSignal

            self._arrival_signal = ArrivalSignal(
                half_life_s=cfg.arrival_half_life_s,
                window_s=cfg.arrival_slope_window_s)
        return self._arrival_signal

    def record_metric(self, replica_id: str, ongoing: float, ts: float,
                      queue_age: float = 0.0,
                      goodput: Optional[float] = None,
                      arrivals: Optional[float] = None):
        self.metrics[replica_id] = (ts, ongoing, queue_age, goodput)
        if arrivals is None:
            return
        # Fold the replica's cumulative arrival count into the
        # deployment total: first push baselines (a fresh replica's
        # history is unknown), a count that went backwards means the
        # replica restarted (the new count IS the delta).
        prev = self._arrival_prev.get(replica_id)
        self._arrival_prev[replica_id] = arrivals
        if prev is None:
            delta = 0.0
        else:
            delta = arrivals if arrivals < prev else arrivals - prev
        self._arrival_total += delta
        sig = self._signal()
        if sig is not None:
            sig.observe(ts, self._arrival_total)

    def autoscale(self, now: float) -> Optional[Dict[str, Any]]:
        """One reconciliation pass of the scaling policy.  Four
        signals, pushed by the replicas: the averaged ongoing-request
        count (the sizing signal — desired = ceil(total/target)), the
        worst admission-queue age (leading SLO pressure: it climbs
        before any latency bound blows), the worst goodput ratio
        (trailing guard: a fleet already missing its objectives must
        not shrink), and — when upscale_slope_threshold is set — the
        arrival-rate slope (predictive: it moves before any queue even
        forms).  Pressure from any of them forces at least one step up
        from the current target and vetoes any scale-down this pass.
        Returns the applied decision dict, or None."""
        cfg = self.config.autoscaling_config
        if cfg is None or self.deleting:
            return None
        running = [r for r in self.replicas.values() if r.state == "RUNNING"]
        if not running:
            return None
        cutoff = now - cfg.look_back_period_s
        total = 0.0
        fresh = 0
        worst_age = 0.0
        worst_goodput: Optional[float] = None
        for r in running:
            m = self.metrics.get(r.replica_id)
            if m is not None and m[0] >= cutoff:
                fresh += 1
                total += m[1]
                if len(m) > 2 and m[2]:
                    worst_age = max(worst_age, m[2])
                if len(m) > 3 and m[3] is not None:
                    worst_goodput = (m[3] if worst_goodput is None
                                     else min(worst_goodput, m[3]))
        if fresh == 0:
            # No live signal at all — e.g. right after a controller
            # recovery, before the adopted fleet's first metric push.
            # Make NO decision (and leave any restored intent armed)
            # rather than sizing a busy fleet from an empty window,
            # which would read as "scale to min".
            return None
        desired = math.ceil(total / cfg.target_ongoing_requests)
        reason = "ongoing"
        pressure = False
        if (cfg.target_queue_age_s is not None
                and worst_age > cfg.target_queue_age_s):
            pressure, reason = True, "queue_age"
        elif (cfg.target_goodput is not None
              and worst_goodput is not None
              and worst_goodput < cfg.target_goodput):
            pressure, reason = True, "goodput"
        elif (cfg.upscale_slope_threshold is not None
              and self._arrival_signal is not None
              and self._arrival_signal.slope()
              > cfg.upscale_slope_threshold):
            # Predictive scale-up: the arrival RATE is still climbing,
            # so today's fleet will be undersized by the time a queue
            # forms — step up now, while queue age and goodput are
            # still clean.  Reactive reasons keep precedence: once a
            # queue exists it is the more honest signal.
            pressure, reason = True, "arrival_slope"
        current = self.target_replicas
        if pressure:
            desired = max(desired, current + 1)
        desired = max(cfg.min_replicas, min(cfg.max_replicas, desired))
        if pressure and desired < current:
            # Scale-down vetoed while overloaded: the fleet is pinned
            # at max_replicas under pressure — exactly the incident the
            # flight recorder exists for.
            desired = current
            try:
                from ray_tpu.util import flight_recorder
                flight_recorder.trigger("autoscale_veto",
                                        reason_detail=reason,
                                        replicas=current)
            except Exception:
                pass
        if desired == current:
            self._scale_intent = None
            return None
        delay = (cfg.upscale_delay_s if desired > current
                 else cfg.downscale_delay_s)
        if self._scale_intent is None or (
            (self._scale_intent[0] > current) != (desired > current)
        ):
            self._scale_intent = (desired, now)
            return None
        if now - self._scale_intent[1] >= delay:
            self.target_replicas = desired
            self._scale_intent = None
            self.last_decision = {
                "direction": "up" if desired > current else "down",
                "from": current,
                "to": desired,
                "reason": reason,
                "ts": time.time(),
            }
            return self.last_decision
        return None


class ServeController:
    """The singleton control-plane actor."""

    def __init__(self):
        self._lock = threading.RLock()
        self._host = LongPollHost()
        self._deployments: Dict[Tuple[str, str], _DeploymentState] = {}
        self._routes: Dict[str, Tuple[str, str]] = {}  # prefix -> (app, ingress)
        self._app_ingress: Dict[str, str] = {}
        self._tm = _telemetry()
        self._reconcile_errors_seen: set = set()
        self._shutdown = threading.Event()
        # Crash recovery (the paper's durable-GCS keystone applied to
        # the serve control plane): every state mutation checkpoints
        # through the GCS StoreClient machinery, and a replacement
        # controller rebuilds itself from that checkpoint — re-census,
        # adoption, orphan sweep, rebroadcast — BEFORE the reconcile
        # loop starts, so routers only ever see tables that reflect a
        # verified fleet.  The epoch increments per generation; it
        # rides on every long_poll response so clients detect the
        # replacement and full-resync their snapshot ids.
        self._epoch = 1
        self._last_recovery = 0.0  # wall ts of last recovery (0 = never)
        self._last_ckpt_wall = 0.0
        self._self_actor_id = None  # resolved lazily by _fenced()
        self._ckpt = self._make_checkpointer()
        self._recover()
        # Persist the adopted state SYNCHRONOUSLY before serving: a
        # second crash inside the first debounce window would otherwise
        # recover from the previous generation's blob and reuse its
        # epoch — and an epoch collision means long-poll clients never
        # detect the replacement.
        try:
            with self._ckpt._save_lock:
                self._ckpt.save(self._checkpoint_tables())
        except Exception:
            pass
        self._ckpt.start_flusher(self._checkpoint_tables,
                                 span_name="serve.ckpt_flush")
        threading.Thread(
            target=self._reconcile_loop, daemon=True, name="serve-reconcile"
        ).start()

    # -- checkpointing -----------------------------------------------------

    def _fenced(self) -> bool:
        """True once this instance's actor shell has died.  A hard kill
        on a thread-mode actor cannot stop the instance's OWN daemon
        threads (reconcile loop, checkpoint flusher), so they check
        this fence and stand down — without it a SIGKILLed controller
        generation would keep mutating replicas and overwrite its
        successor's checkpoint.  Local (non-actor) instances never find
        a shell and never fence."""
        try:
            rt = api.runtime()
            if self._self_actor_id is None:
                for aid, shell in list(rt._actors.items()):
                    if shell.instance is self:
                        self._self_actor_id = aid
                        return False
                return False
            shell = rt._actors.get(self._self_actor_id)
            return shell is None or shell.dead
        except Exception:
            return False

    def _make_checkpointer(self):
        from ray_tpu.core.gcs_persistence import (
            FileStore,
            GcsPersistence,
            KvStoreClient,
            MirroredStore,
        )
        from ray_tpu.utils.config import get_config

        cfg = get_config()
        primary = KvStoreClient(api.runtime().kv, namespace=CKPT_NAMESPACE,
                                key=CKPT_KEY)
        mirrors = [FileStore(p.strip())
                   for p in cfg.serve_checkpoint_mirrors.split(",")
                   if p.strip()]
        store = MirroredStore(primary, mirrors) if mirrors else primary
        return GcsPersistence("", cfg.serve_checkpoint_flush_period_s,
                              store=store)

    def _checkpoint_tables(self) -> Dict[str, Any]:
        """Collect one checkpoint under the lock.  Plain-picklable end
        to end: DeploymentInfo (arbitrary user callables) rides as a
        cloudpickle sub-blob; actor handles, object refs and placement
        groups reduce to their ids.  Replica metrics are deliberately
        NOT persisted — a recovered autoscaler must size from live
        pushes, never from a dead generation's window."""
        import cloudpickle as _cp

        from ray_tpu.serve import audit as _audit

        if self._fenced():
            # Dead generation: refuse to collect, so the (best-effort)
            # flusher can never clobber the replacement controller's
            # checkpoint with this epoch's stale tables.
            raise RuntimeError("controller generation is fenced")
        with self._lock:
            deployments = []
            for (app, dep), st in sorted(self._deployments.items()):
                reps = []
                for rid in sorted(st.replicas):
                    r = st.replicas[rid]
                    reps.append({
                        "replica_id": rid,
                        "state": r.state,
                        "role": r.role,
                        "mesh_shape": r.mesh_shape,
                        "prefix_summary": r.prefix_summary,
                        "adapter_summary": r.adapter_summary,
                        "handle": r.handle,
                        # Only STARTING replicas need their creation
                        # ref back (recovery re-polls it); dropping the
                        # rest keeps resolved results out of the blob.
                        "creation_ref": (r.creation_ref
                                         if r.state == "STARTING"
                                         else None),
                        "members": list(r.members),
                        "pg": r.pg,
                    })
                if reps and _audit.corrupt(_audit.INJECT_STALE_CHECKPOINT):
                    reps = reps[:-1]  # checkpoint↔census drift
                intent = st._scale_intent
                deployments.append({
                    "app": app,
                    "name": dep,
                    "info": _cp.dumps(st.info),
                    "target_replicas": st.target_replicas,
                    "next_replica_idx": st.next_replica_idx,
                    "deleting": st.deleting,
                    "scale_intent_desired": (intent[0]
                                             if intent is not None
                                             else None),
                    "last_decision": (dict(st.last_decision)
                                      if st.last_decision else None),
                    "replicas": reps,
                })
            tables = {
                "ckpt_version": CKPT_VERSION,
                "epoch": self._epoch,
                "saved_at": time.time(),
                "deployments": deployments,
                "routes": dict(self._routes),
                "app_ingress": dict(self._app_ingress),
            }
        self._last_ckpt_wall = tables["saved_at"]
        return tables

    def _recover(self) -> None:
        """Rebuild state from the persisted checkpoint, if any: ping
        every checkpointed replica, adopt the live ones (DRAINING ones
        resume draining), drop unreachable ones onto the existing
        replacement path, hard-kill live replica actors the checkpoint
        has no record of, then rebroadcast routes + tables."""
        try:
            tables = self._ckpt.load()
        except Exception as e:
            log.warning("controller checkpoint unreadable (%r) — "
                        "starting fresh", e)
            return
        if not tables:
            return
        if tables.get("ckpt_version") != CKPT_VERSION:
            log.warning("controller checkpoint has unknown layout "
                        "version %r — starting fresh",
                        tables.get("ckpt_version"))
            return
        if tables.get("clean_shutdown"):
            # The previous generation exited deliberately (serve
            # shutdown): nothing to recover, keep only epoch continuity.
            self._epoch = int(tables.get("epoch", 0)) + 1
            return
        import cloudpickle as _cp

        self._epoch = int(tables.get("epoch", 0)) + 1
        self._last_recovery = time.time()
        now = time.monotonic()
        self._routes = dict(tables.get("routes") or {})
        self._app_ingress = dict(tables.get("app_ingress") or {})
        pings = []
        for d in tables.get("deployments") or ():
            try:
                info = _cp.loads(d["info"])
            except Exception as e:
                log.error("checkpointed deployment %s/%s is "
                          "unrecoverable (%r) — dropping it",
                          d.get("app"), d.get("name"), e)
                continue
            st = _DeploymentState(d["app"], info)
            st.target_replicas = int(d["target_replicas"])
            st.next_replica_idx = int(d["next_replica_idx"])
            st.deleting = bool(d["deleting"])
            if d.get("last_decision"):
                st.last_decision = dict(d["last_decision"])
            desired = d.get("scale_intent_desired")
            if (desired is not None
                    and st.config.autoscaling_config is not None):
                # Restart the intent timer from NOW: the fleet was just
                # re-censused, so letting a pre-crash countdown expire
                # immediately would fire a spurious scale event off a
                # dead generation's signals.
                st._scale_intent = (int(desired), now)
            self._deployments[(d["app"], d["name"])] = st
            for rd in d.get("replicas") or ():
                if rd.get("handle") is None:
                    continue
                ref = None
                # STARTING replicas may still be in __init__ (a ping
                # would queue behind it) — adopt them unpinged; their
                # creation ref resolves through _check_started exactly
                # as before the crash.  STOPPING ones are adopted
                # unpinged too: the stop path is idempotent.
                if rd["state"] in ("RUNNING", "DRAINING"):
                    try:
                        ref = rd["handle"].check_health.remote()
                    except Exception:
                        ref = None
                pings.append((st, rd, ref))
        adopted = 0
        adopted_ids = set()
        # Resolve the census pings only after ALL were fired — they
        # settle concurrently on the replicas' own actor threads.
        for st, rd, ref in pings:
            rid = rd["replica_id"]
            if rd["state"] in ("RUNNING", "DRAINING"):
                alive = False
                if ref is not None:
                    try:
                        api.get(ref, timeout=5.0)
                        alive = True
                    except Exception:
                        alive = False
                if not alive:
                    # Not adopted: the reconcile loop sees live <
                    # target and starts a replacement — the existing
                    # replica-death path.
                    log.warning("recovery: checkpointed replica %s is "
                                "unreachable — replacing it", rid)
                    continue
            r = _Replica(rid, rd["handle"], rd.get("creation_ref"))
            r.state = rd["state"]
            r.role = rd.get("role", "unified")
            r.mesh_shape = rd.get("mesh_shape", "")
            r.prefix_summary = rd.get("prefix_summary")
            r.adapter_summary = rd.get("adapter_summary")
            r.members = list(rd.get("members") or ())
            r.pg = rd.get("pg")
            r.last_health_check = now
            if r.state == "DRAINING":
                # Resume draining with a re-armed deadline (the drain
                # RPC was already delivered by the previous epoch).
                r.drain_deadline = (
                    now + st.config.graceful_shutdown_timeout_s + 30.0)
            st.replicas[rid] = r
            adopted_ids.add(r.handle._actor_id)
            for _rank, m in r.members:
                adopted_ids.add(m._actor_id)
            if r.state in ("RUNNING", "DRAINING", "STARTING"):
                adopted += 1
        killed = self._kill_stale_orphans(adopted_ids)
        # Rebuild + rebroadcast the full routing surface BEFORE the
        # reconcile loop starts: a router that resyncs against this
        # epoch must never observe an empty table.
        self._host.notify_changed(ROUTES_KEY, dict(self._routes))
        for st in self._deployments.values():
            self._broadcast(st)
        self._tm["restarts"].inc()
        if adopted:
            self._tm["orphans_adopted"].inc(adopted)
        if killed:
            self._tm["orphans_killed"].inc(killed)
        log.warning(
            "serve controller recovered from checkpoint: epoch=%d, "
            "%d deployment(s), %d replica(s) adopted, %d orphan(s) "
            "killed", self._epoch, len(self._deployments), adopted,
            killed)
        try:
            from ray_tpu.util import flight_recorder

            flight_recorder.trigger(
                "controller_recovery", detail=f"epoch={self._epoch}",
                adopted=adopted, orphans_killed=killed)
        except Exception:
            pass

    def _kill_stale_orphans(self, adopted_ids) -> int:
        """Hard-kill live replica/shard-member actors from the previous
        controller generation that the checkpoint has no record of
        (started inside the last flush window, or rows lost to a stale
        checkpoint copy).  They are invisible to reconciliation — left
        alone they would hold chips forever."""
        from ray_tpu.utils.test_utils import kill_actor_hard

        rt = api.runtime()
        killed = 0
        try:
            shells = list(rt._actors.items())
        except Exception:
            return 0
        for actor_id, shell in shells:
            try:
                if shell.dead or shell.cls.__name__ not in (
                        "ReplicaActor", "ShardMemberActor"):
                    continue
            except Exception:
                continue
            if actor_id in adopted_ids:
                continue
            try:
                kill_actor_hard(rt, actor_id)
                killed += 1
            except Exception:
                pass
        return killed

    # -- API ---------------------------------------------------------------

    def deploy_application(self, app_name: str, infos: List[DeploymentInfo],
                           route_prefix: Optional[str]) -> None:
        with self._lock:
            new_names = {i.name for i in infos}
            for (app, dep), st in list(self._deployments.items()):
                if app == app_name and dep not in new_names:
                    st.deleting = True
                    st.target_replicas = 0
            for info in infos:
                key = (app_name, info.name)
                st = self._deployments.get(key)
                if st is None or st.deleting:
                    self._deployments[key] = _DeploymentState(app_name, info)
                else:
                    st.apply_new_info(info)
                if info.is_ingress:
                    self._app_ingress[app_name] = info.name
            if route_prefix is not None:
                self._routes = {
                    p: t for p, t in self._routes.items() if t[0] != app_name
                }
                self._routes[route_prefix] = (
                    app_name, self._app_ingress[app_name]
                )
                self._host.notify_changed(ROUTES_KEY, dict(self._routes))
            self._ckpt.mark_dirty()

    def delete_application(self, app_name: str) -> None:
        with self._lock:
            for (app, _), st in self._deployments.items():
                if app == app_name:
                    st.deleting = True
                    st.target_replicas = 0
            self._routes = {
                p: t for p, t in self._routes.items() if t[0] != app_name
            }
            self._host.notify_changed(ROUTES_KEY, dict(self._routes))
            self._ckpt.mark_dirty()

    def get_ingress(self, app_name: str) -> str:
        with self._lock:
            name = self._app_ingress.get(app_name)
        if name is None:
            raise ValueError(f"no application named {app_name!r}")
        return name

    def long_poll(self, keys_to_ids: Dict[str, int]):
        # Non-blocking snapshot check: clients poll on a short cadence.
        # (The reference blocks in an asyncio handler, which holds no
        # thread; here a blocking listen would pin one controller pool
        # thread per subscriber, starving control RPCs at scale.)
        # The epoch rides on every response: a replacement controller's
        # snapshot ids restart at 1, so a client holding the previous
        # generation's large `seen` values would filter every update
        # forever — seeing the epoch move tells it to full-resync.
        return {"epoch": self._epoch,
                "updates": self._host.listen(keys_to_ids, timeout=0.0)}

    def record_autoscaling_metric(self, app_name: str, deployment_name: str,
                                  replica_id: str, ongoing: float,
                                  ts: float, queue_age: float = 0.0,
                                  goodput: Optional[float] = None,
                                  arrivals: Optional[float] = None) -> None:
        with self._lock:
            st = self._deployments.get((app_name, deployment_name))
            if st is None:
                return
            st.record_metric(replica_id, ongoing, ts, queue_age, goodput,
                             arrivals)
            # Live-load routing: broadcast rows carry each replica's
            # last-pushed ongoing count, so rebroadcast when the count
            # moved a whole request away from the broadcast one —
            # routers' p2c arm tracks real load without the controller
            # re-notifying every push.
            r = st.replicas.get(replica_id)
            if (r is not None and r.state in ("RUNNING", "DRAINING")
                    and abs(ongoing - r.bcast_ongoing) >= 1.0):
                self._broadcast(st)

    def record_prefix_summary(self, app_name: str, deployment_name: str,
                              replica_id: str, summary) -> None:
        """Replica push: its engine's prefix-cache routing summary
        changed.  Stored on the replica record and re-broadcast so
        every router's table row carries the fresh summary (the same
        long-poll channel that delivers membership changes)."""
        with self._lock:
            st = self._deployments.get((app_name, deployment_name))
            if st is None:
                return
            r = st.replicas.get(replica_id)
            if r is None or r.prefix_summary == summary:
                return
            r.prefix_summary = summary
            self._broadcast(st)

    def record_adapter_summary(self, app_name: str, deployment_name: str,
                               replica_id: str, summary) -> None:
        """Replica push: its engine's resident-adapter set changed.
        Same store-and-rebroadcast contract as record_prefix_summary —
        routers read the summary off their table row for
        adapter-affinity routing."""
        with self._lock:
            st = self._deployments.get((app_name, deployment_name))
            if st is None:
                return
            r = st.replicas.get(replica_id)
            if r is None or r.adapter_summary == summary:
                return
            r.adapter_summary = summary
            self._broadcast(st)

    def list_replicas(self) -> List[Dict[str, Any]]:
        """Replica inventory for `raytpu list replicas` (util/state.py):
        one row per replica, deterministic order (app, deployment,
        replica id).  Shard-group replicas carry their mesh shape
        ("dcn_tp=S x tp=T") and group membership (rank:actor pairs,
        rank 0 = the replica actor itself).  Every row carries the
        controller epoch + last-recovery wall time so an operator can
        see at a glance whether this fleet survived a control-plane
        crash (stable across calls — the determinism tests pin it)."""
        rows: List[Dict[str, Any]] = []
        with self._lock:
            last_recovery = (round(self._last_recovery, 3)
                             if self._last_recovery else "")
            for (app, dep), st in sorted(self._deployments.items()):
                actual = sum(1 for r in st.replicas.values()
                             if r.state == "RUNNING")
                last = st.last_decision
                autoscale = (
                    f"{last['direction']} {last['from']}->{last['to']} "
                    f"({last['reason']})" if last is not None else ""
                )
                for rid in sorted(st.replicas):
                    r = st.replicas[rid]
                    sg = st.config.shard_group
                    membership = ""
                    if sg is not None:
                        # hex[8:16]: the leading 4 bytes are the job id,
                        # identical for every actor — show the
                        # distinguishing slice.
                        parts = [f"0:{r.handle._actor_id.hex()[8:16]}"]
                        parts += [f"{rank}:{m._actor_id.hex()[8:16]}"
                                  for rank, m in r.members]
                        membership = ",".join(parts)
                    rows.append({
                        "app": app,
                        "deployment": dep,
                        "replica_id": rid,
                        "state": r.state,
                        "shard_group": sg.size if sg is not None else 0,
                        "mesh_shape": r.mesh_shape,
                        "members": membership,
                        "role": r.role,
                        "target_groups": st.target_replicas,
                        "actual_groups": actual,
                        "autoscale": autoscale,
                        "ctl_epoch": self._epoch,
                        "last_recovery": last_recovery,
                    })
        return rows

    def migration_targets(self, app_name: str, deployment_name: str,
                          role: Optional[str] = "decode",
                          exclude: Optional[List[str]] = None,
                          with_summary: bool = False,
                          with_load: bool = False) -> List[Tuple]:
        """RUNNING replicas of one deployment, for the KV-migration
        plane: a prefill replica asks here for its decode handoff
        target, a cold replica for warm peers to pull prefixes from.
        Deterministic (sorted by replica id).  Rows are
        ``(replica_id, handle)`` — plus the replica's latest prefix
        summary when ``with_summary`` (prefix migration picks the
        warmest peer by published hash count), or its last-pushed
        ongoing-request count when ``with_load`` (prefill→decode
        handoff picks the least-loaded decode replica)."""
        excluded = set(exclude or ())
        out: List[Tuple] = []
        with self._lock:
            st = self._deployments.get((app_name, deployment_name))
            if st is None:
                return []
            for rid in sorted(st.replicas):
                r = st.replicas[rid]
                if r.state != "RUNNING" or rid in excluded:
                    continue
                if role is not None and r.role != role:
                    continue
                if with_summary:
                    out.append((rid, r.handle, r.prefix_summary))
                elif with_load:
                    m = st.metrics.get(rid)
                    out.append((rid, r.handle,
                                float(m[1]) if m is not None else 0.0))
                else:
                    out.append((rid, r.handle))
        return out

    def drain_replica(self, app_name: str, deployment_name: str,
                      replica_id: str,
                      grace_s: Optional[float] = None) -> bool:
        """Deliver a preemption notice to one replica (the node-daemon
        maintenance-event path): flip it to DRAINING and send the drain
        RPC.  A replacement starts on the next reconcile pass while the
        draining replica stays in the route table.  Returns False for
        unknown or non-RUNNING replicas."""
        with self._lock:
            st = self._deployments.get((app_name, deployment_name))
            if st is None:
                raise ValueError(
                    f"no deployment {deployment_name!r} in app "
                    f"{app_name!r}")
            r = st.replicas.get(replica_id)
            if r is None:
                return False
            return self._mark_draining(st, r, grace_s=grace_s)

    def _mark_draining(self, st: _DeploymentState, r: _Replica, *,
                       grace_s: Optional[float] = None,
                       notify: bool = True) -> bool:
        if r.state != "RUNNING":
            return False
        r.state = "DRAINING"
        grace = (grace_s if grace_s is not None
                 else st.config.graceful_shutdown_timeout_s)
        # After the engine's grace expires it evicts what's left, so
        # in-flight work settles shortly after; the margin only bounds
        # a wedged replica.
        r.drain_deadline = time.monotonic() + grace + 30.0
        self._tm["drains"].inc(tags={"deployment": st.info.name})
        if notify:
            try:
                r.handle.drain.remote(grace)
            except Exception:
                r.state = "STOPPING"  # can't even reach it — replace
        # Routers read the draining flag off their table row (they
        # deprioritise draining replicas for NEW requests while keeping
        # them routable for retries) — tell them now, not at retirement.
        self._broadcast(st)
        return True

    def doctor(self, deep: bool = False,
               replica_id: Optional[str] = None) -> Dict[str, Any]:
        """Cluster invariant audit (the `raytpu doctor` backend): run
        the controller's own census↔broadcast consistency checks, fan
        the doctor RPC out to every RUNNING/DRAINING replica (or just
        ``replica_id``), and merge the per-process reports.  The
        merged report additionally carries ``census`` —
        {"app/deployment": [replica ids]} — so the caller can diff its
        local routers' tables against the same census snapshot."""
        from ray_tpu.serve import audit as _audit
        from ray_tpu.util import doctor as _doctor

        fns = []
        work: List[Tuple[str, Any]] = []
        census_by_key: Dict[str, List[str]] = {}
        with self._lock:
            # checkpoint↔census: flush the pending state synchronously,
            # read the persisted copy back through the store, and diff
            # it against the live census — catching a wedged or
            # corrupted checkpointer (the doctor.stale_checkpoint
            # injector drops a row to prove detection).  Under the same
            # lock as the census snapshot so the reconcile loop can't
            # move the fleet between the two reads.
            ckpt_rows: Dict[str, Dict[str, str]] = {}
            ckpt_err: Optional[str] = None
            try:
                with self._ckpt._save_lock:
                    self._ckpt.save(self._checkpoint_tables())
                blob = self._ckpt.store.load_blob()
                tables = (blob or {}).get("tables") or {}
                for d in tables.get("deployments") or ():
                    ckpt_rows[f"{d['app']}/{d['name']}"] = {
                        rd["replica_id"]: rd["state"]
                        for rd in d.get("replicas") or ()
                        if rd["state"] in ("RUNNING", "DRAINING")}
            except Exception as e:
                ckpt_err = repr(e)
            for (app, dep), st in sorted(self._deployments.items()):
                key = f"{app}/{dep}"
                census = [(rid, st.replicas[rid].state == "DRAINING")
                          for rid in sorted(st.replicas)
                          if st.replicas[rid].state
                          in ("RUNNING", "DRAINING")]
                census_by_key[key] = [rid for rid, _ in census]
                last = list(st.last_broadcast)
                fns.append((_audit.CENSUS_BROADCAST,
                            lambda k=key, c=census, t=last:
                            _audit.census_broadcast_checks(k, c, t)))
                fns.append((_audit.CHECKPOINT_CENSUS,
                            lambda k=key, c=census,
                            p=ckpt_rows.get(key), e=ckpt_err:
                            _audit.checkpoint_census_checks(k, c, p, e)))
                for rid, _draining in census:
                    if replica_id is not None and rid != replica_id:
                        continue
                    work.append((rid, st.replicas[rid].handle))
        reports = [_doctor.run_audit("controller", fns, deep=True)]
        for rid, handle in work:
            try:
                rep = api.get(handle.doctor.remote(deep))
            except Exception as e:
                rep = {"proc": rid, "checks_run": 0, "violations": 0,
                       "audit_seconds": 0.0, "checks": [],
                       "error": repr(e)}
            if rep is not None:  # None = callable has no doctor surface
                rep.setdefault("replica_id", rid)
                reports.append(rep)
        out = _doctor.merge_reports(reports, deep=deep)
        out["census"] = census_by_key
        return out

    def status(self) -> Dict[str, Any]:
        with self._lock:
            out: Dict[str, Any] = {"applications": {}}
            for (app, dep), st in self._deployments.items():
                a = out["applications"].setdefault(
                    app, {"deployments": {}, "ingress": self._app_ingress.get(app)}
                )
                running = sum(
                    1 for r in st.replicas.values() if r.state == "RUNNING"
                )
                a["deployments"][dep] = {
                    "target_replicas": st.target_replicas,
                    "running_replicas": running,
                    "status": (
                        "DELETING" if st.deleting
                        else "HEALTHY" if running >= st.target_replicas
                        else "UPDATING"
                    ),
                }
            return out

    def get_routes(self) -> Dict[str, Tuple[str, str]]:
        with self._lock:
            return dict(self._routes)

    def graceful_shutdown(self) -> None:
        with self._lock:
            for st in self._deployments.values():
                st.deleting = True
                st.target_replicas = 0
            self._ckpt.mark_dirty()

    def _num_live(self) -> int:
        with self._lock:
            return sum(len(st.replicas) for st in self._deployments.values())

    def wait_for_drained(self, timeout_s: float = 10.0) -> bool:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self._num_live() == 0:
                return True
            time.sleep(0.02)
        return self._num_live() == 0

    def stop_reconcile(self) -> None:
        """Stop the reconcile thread; called right before the controller
        actor is killed so no orphan loop keeps mutating state.  Also
        writes a clean-shutdown tombstone over the checkpoint: a
        DELIBERATE teardown must not be recovered from — the next
        controller generation starts fresh (keeping only epoch
        continuity) instead of resurrecting the torn-down app."""
        self._shutdown.set()
        try:
            self._ckpt.close(final_flush=False)
            with self._ckpt._save_lock:
                self._ckpt.save({
                    "ckpt_version": CKPT_VERSION,
                    "epoch": self._epoch,
                    "clean_shutdown": True,
                    "deployments": [],
                    "routes": {},
                    "app_ingress": {},
                })
        except Exception:
            pass

    # -- reconcile ---------------------------------------------------------

    def _reconcile_loop(self):
        while not self._shutdown.wait(RECONCILE_PERIOD_S):
            if self._fenced():
                # This generation's actor was hard-killed: stop
                # reconciling (a replacement controller owns the fleet
                # now) and stop the checkpoint flusher, WITHOUT the
                # clean-shutdown tombstone — the successor must
                # recover, not start fresh.
                self._shutdown.set()
                try:
                    self._ckpt.close(final_flush=False)
                except Exception:
                    pass
                return
            try:
                self._reconcile_once()
            except Exception:
                # A wedged reconcile loop must be visible, not silent:
                # count every swallowed error and log the traceback the
                # first time each distinct error appears (distinct =
                # the final exception line, so a repeating failure
                # doesn't flood the log at 20 Hz).
                self._tm["reconcile_errors"].inc()
                tb = traceback.format_exc()
                key = tb.strip().splitlines()[-1]
                if key not in self._reconcile_errors_seen:
                    self._reconcile_errors_seen.add(key)
                    log.error(
                        "serve reconcile loop error (repeats of this "
                        "error are counted in "
                        "raytpu_serve_reconcile_errors_total but not "
                        "re-logged):\n%s", tb)

    def _reconcile_once(self):
        now = time.monotonic()
        self._tm["ckpt_seq"].set(self._ckpt._seq)
        self._tm["ckpt_age"].set(
            max(0.0, time.time() - self._last_ckpt_wall)
            if self._last_ckpt_wall else 0.0)
        with self._lock:
            states = list(self._deployments.items())
        for key, st in states:
            with self._lock:
                intent_before = st._scale_intent
                decision = st.autoscale(now)
                if decision is not None:
                    self._tm["autoscale_decisions"].inc(
                        tags={"deployment": st.info.name,
                              "direction": decision["direction"],
                              "reason": decision.get("reason",
                                                     "ongoing")})
                if (st.config.autoscaling_config is not None
                        and not st.deleting):
                    self._tm["autoscale_target"].set(
                        st.target_replicas,
                        tags={"deployment": st.info.name})
                    self._tm["autoscale_actual"].set(
                        sum(1 for r in st.replicas.values()
                            if r.state == "RUNNING"),
                        tags={"deployment": st.info.name})
                if (decision is not None
                        or st._scale_intent is not intent_before):
                    # Intent state (armed/cleared/target moved) is part
                    # of the checkpoint — broadcast won't catch it.
                    self._ckpt.mark_dirty()
                self._check_started(st)
                self._check_health(st, now)
                changed = self._scale(st)
                if st.deleting and not st.replicas:
                    self._deployments.pop(key, None)
                    self._host.drop_key(replica_set_key(st.app_name, st.info.name))
                    self._ckpt.mark_dirty()
                    changed = False
            if changed:
                self._broadcast(st)

    def _check_started(self, st: _DeploymentState):
        rt = api.runtime()
        for r in st.replicas.values():
            if r.state == "STARTING" and rt.store.contains(r.creation_ref.id):
                try:
                    api.get(r.creation_ref)
                    r.state = "RUNNING"
                    self._maybe_warm_start(st, r)
                except Exception as err:
                    # constructor failed → replace; say why, or a
                    # deployment whose every replica fails to build sits
                    # in UPDATING with nothing to read
                    log.warning("replica %s failed to start (%r): "
                                "replacing it", r.replica_id, err)
                    r.state = "STOPPING"

    def _maybe_warm_start(self, st: _DeploymentState, r: _Replica) -> None:
        """A freshly RUNNING replica of an autoscaled deployment starts
        with a cold prefix cache — every request it absorbs pays full
        prefill until the cache warms, exactly when the fleet is under
        the pressure that triggered the scale-up.  Kick off a one-shot
        pull_prefix_cache against the warmest surviving peer
        (kv_transfer's cold-start path) so the new capacity is useful
        immediately.  Fire-and-forget: a non-LLM callable ignores the
        method, a failed pull just means a cold start."""
        if st.config.autoscaling_config is None:
            return
        warm = any(
            p.prefix_summary for p in st.replicas.values()
            if p is not r and p.state in ("RUNNING", "DRAINING")
        )
        if not warm:
            return
        try:
            r.handle.handle_request.remote(
                "pull_prefix_cache", (),
                {"app_name": st.app_name,
                 "deployment_name": st.info.name,
                 "replica_id": r.replica_id},
                None,
            )
        except Exception:
            pass

    def _check_health(self, st: _DeploymentState, now: float):
        rt = api.runtime()
        for r in st.replicas.values():
            if r.state not in ("RUNNING", "DRAINING"):
                continue
            if r.health_ref is not None and rt.store.contains(r.health_ref.id):
                try:
                    verdict = api.get(r.health_ref)
                    if verdict == "DRAINING" and r.state == "RUNNING":
                        # Self-reported preemption notice (SIGTERM /
                        # node maintenance): the replica already began
                        # draining itself, so track it without sending
                        # another drain RPC.
                        self._mark_draining(st, r, notify=False)
                except Exception:
                    r.state = "STOPPING"  # unhealthy → replace
                r.health_ref = None
            elif (r.health_ref is None
                  and now - r.last_health_check
                  >= st.config.health_check_period_s):
                r.last_health_check = now
                r.health_ref = r.handle.check_health.remote()
                if r.members:
                    r.member_ping_refs = [
                        (rank, m.ping.remote()) for rank, m in r.members
                    ]
            if r.member_ping_refs and r.state in ("RUNNING", "DRAINING"):
                self._check_shard_members(st, r, rt)

    def _check_shard_members(self, st: _DeploymentState, r: _Replica, rt):
        """Resolve outstanding shard-member pings.  ANY member death is
        whole-replica failure: the group's mesh spans every member, so
        a lost member means lost collectives — rank 0 is hard-killed
        (sealing ActorDiedError into its live streams exactly as the
        lost link would on real hardware, which is what routes every
        in-flight request through the router's failover/replay path)
        and the group is replaced as one unit."""
        pending = []
        dead = False
        for rank, ref in r.member_ping_refs:
            if not rt.store.contains(ref.id):
                pending.append((rank, ref))
                continue
            try:
                api.get(ref)
            except Exception:
                dead = True
        r.member_ping_refs = pending
        if dead:
            from ray_tpu.utils.test_utils import kill_actor_hard

            log.warning(
                "shard group %s lost a member — failing the whole "
                "replica", r.replica_id)
            try:
                kill_actor_hard(rt, r.handle._actor_id)
            except Exception:
                pass
            r.state = "STOPPING"
            r.member_ping_refs = None

    def _scale(self, st: _DeploymentState) -> bool:
        changed = False
        running = [r for r in st.replicas.values() if r.state == "RUNNING"]
        # Retire draining replicas only once RUNNING capacity is back
        # at target AND their in-flight requests have settled: until
        # then they stay in the broadcast table, so a drain never dips
        # routable capacity, and killing the replica can't seal
        # ActorDiedError into a live stream.  The broadcast that drops
        # them is the same one that announces their replacement.
        if st.deleting or len(running) >= st.target_replicas:
            for r in st.replicas.values():
                if r.state != "DRAINING":
                    continue
                if st.deleting or self._drain_settled(r):
                    r.state = "STOPPING"
                    changed = True
        # Stop replicas marked STOPPING, and excess RUNNING ones.
        excess = len(running) + sum(
            1 for r in st.replicas.values() if r.state == "STARTING"
        ) - st.target_replicas
        auto_down = (st.config.autoscaling_config is not None
                     and not st.deleting)
        for r in sorted(running, key=lambda r: r.replica_id, reverse=True):
            if excess <= 0:
                break
            if auto_down:
                # Policy scale-down retires through the DRAINING path:
                # the replica finishes its in-flight streams (zero
                # router retries) and leaves the broadcast table only
                # once it has settled, so routable capacity never dips
                # below the new target mid-decision.
                if self._mark_draining(st, r):
                    changed = True
            else:
                r.state = "STOPPING"
            excess -= 1
        for r in list(st.replicas.values()):
            if r.state == "STOPPING":
                self._stop_replica(st, r)
                changed = True
        # Start missing replicas.
        live = [r for r in st.replicas.values()
                if r.state in ("STARTING", "RUNNING")]
        missing = st.target_replicas - len(live)
        for _ in range(max(0, missing)):
            self._start_replica(st)
            changed = True
        # Newly RUNNING replicas also need a broadcast.
        if any(r.state == "RUNNING" and not getattr(r, "_announced", False)
               for r in st.replicas.values()):
            changed = True
        return changed

    def _drain_settled(self, r: _Replica) -> bool:
        """True once a DRAINING replica has no in-flight requests, or
        its drain deadline passed (a wedged drain must not pin the
        replica forever).  Polled without blocking the reconcile loop:
        one outstanding num_ongoing_requests RPC at a time."""
        if (r.drain_deadline is not None
                and time.monotonic() >= r.drain_deadline):
            return True
        if r.ongoing_ref is None:
            try:
                r.ongoing_ref = r.handle.num_ongoing_requests.remote()
            except Exception:
                return True  # unreachable — nothing left to protect
            return False
        if not api.runtime().store.contains(r.ongoing_ref.id):
            return False
        ref, r.ongoing_ref = r.ongoing_ref, None
        try:
            return api.get(ref) == 0
        except Exception:
            return True

    def _start_replica(self, st: _DeploymentState):
        idx = st.next_replica_idx
        st.next_replica_idx += 1
        replica_id = f"{st.app_name}#{st.info.name}#{idx}"
        opts = dict(st.config.ray_actor_options)
        opts.setdefault("num_cpus", 0.1)
        cfg = st.config
        metrics_interval = (
            cfg.autoscaling_config.metrics_interval_s
            if cfg.autoscaling_config else 0.0
        )
        sg = cfg.shard_group
        members: List[Tuple[int, Any]] = []
        pg = None
        shard_kwarg = {}
        if sg is not None:
            # One placement group gang-reserves the whole group (one
            # bundle per member — on TPU each bundle is one host's
            # chips, ICI_CONTIGUOUS keeps the group on one slice
            # block); members rank 1..size-1 are ShardMemberActors,
            # rank 0 is the ReplicaActor itself so the router's
            # broadcast table naturally addresses the group's rank 0.
            from ray_tpu.core.placement_group import (
                PlacementGroupSchedulingStrategy,
                placement_group,
            )
            from ray_tpu.serve.replica import ShardMemberActor

            pg = placement_group(
                [dict(sg.bundle_resources) for _ in range(sg.size)],
                strategy=sg.placement_strategy,
                name=f"sg::{replica_id}",
            )
            member_cls = api.remote(ShardMemberActor)
            for rank in range(1, sg.size):
                m = member_cls.options(
                    num_cpus=0.1,
                    scheduling_strategy=PlacementGroupSchedulingStrategy(
                        placement_group=pg,
                        placement_group_bundle_index=rank,
                    ),
                ).remote(replica_id, rank, sg.size)
                members.append((rank, m))
            opts["scheduling_strategy"] = PlacementGroupSchedulingStrategy(
                placement_group=pg, placement_group_bundle_index=0,
            )
            shard_kwarg = {"shard_group": {
                "group_id": replica_id,
                "rank": 0,
                "size": sg.size,
                "tensor_parallel": sg.tensor_parallel,
                "dcn_collective": sg.dcn_collective,
                "member_ids": [m._actor_id.hex() for _, m in members],
            }}
        disagg_kwarg = {}
        role = "unified"
        dis = cfg.disagg
        if dis is not None:
            # Role by CENSUS of live prefill replicas, not by replica
            # index: a killed prefill replica's replacement takes the
            # prefill role again, so the split stays at target across
            # failovers.  (DRAINING replicas are not counted — their
            # replacement inherits the role immediately.)
            live_prefill = sum(
                1 for rep in st.replicas.values()
                if rep.role == "prefill"
                and rep.state in ("STARTING", "RUNNING"))
            role = ("prefill" if live_prefill < dis.prefill_replicas
                    else "decode")
            disagg_kwarg = {"disagg": {
                "role": role,
                "transfer": dis.transfer,
                "handoff_after_tokens": dis.handoff_after_tokens,
                "migration_timeout_s": dis.migration_timeout_s,
                "app_name": st.app_name,
                "deployment_name": st.info.name,
                "replica_id": replica_id,
            }}
        actor_cls = api.remote(ReplicaActor)
        handle = actor_cls.options(
            max_concurrency=cfg.max_ongoing_requests + 4, **opts
        ).remote(
            st.app_name, st.info.name, replica_id, st.info.func_or_class,
            st.info.init_args, st.info.init_kwargs, cfg.user_config,
            metrics_interval, **shard_kwarg, **disagg_kwarg,
        )
        r = _Replica(replica_id, handle, handle._creation_ref)
        r.members = members
        r.pg = pg
        r.role = role
        if sg is not None:
            r.mesh_shape = f"dcn_tp={sg.size} x tp={sg.tensor_parallel}"
            self._tm["shard_members"].set(
                sg.size, tags={"deployment": st.info.name,
                               "replica": replica_id})
        st.replicas[replica_id] = r

    def _stop_replica(self, st: _DeploymentState, r: _Replica):
        try:
            r.handle.prepare_for_shutdown.remote(
                st.config.graceful_shutdown_timeout_s
            )
            api.kill(r.handle, no_restart=True)
        except Exception:
            pass
        # Shard group: tear down the whole gang — surviving members
        # and the placement-group reservation go with rank 0.
        for _rank, m in r.members:
            try:
                api.kill(m, no_restart=True)
            except Exception:
                pass
        if r.pg is not None:
            from ray_tpu.core.placement_group import remove_placement_group

            try:
                remove_placement_group(r.pg)
            except Exception:
                pass
        if r.members or r.pg is not None:
            self._tm["shard_members"].set(
                0, tags={"deployment": st.info.name,
                         "replica": r.replica_id})
        st.replicas.pop(r.replica_id, None)
        st.metrics.pop(r.replica_id, None)

    def _broadcast(self, st: _DeploymentState):
        import inspect as _inspect

        # Async deployments route to handle_request_async (loop
        # interleaving on the replica); sync ones to handle_request
        # (thread pool) — see replica.py.
        target = st.info.func_or_class
        call = (getattr(target, "__call__", None)
                if _inspect.isclass(target) else target)
        is_async = (_inspect.iscoroutinefunction(call)
                    or _inspect.isasyncgenfunction(call))
        table = []
        for r in st.replicas.values():
            # DRAINING replicas stay routable (they finish in-flight
            # work and bounce new requests with PreemptedError, which
            # the router retries) until _scale retires them.
            if r.state in ("RUNNING", "DRAINING"):
                r._announced = True
                m = st.metrics.get(r.replica_id)
                ongoing = float(m[1]) if m is not None else 0.0
                r.bcast_ongoing = ongoing
                table.append(
                    (r.replica_id, r.handle, st.config.max_ongoing_requests,
                     is_async, r.prefix_summary, r.role, r.adapter_summary,
                     ongoing, r.state == "DRAINING")
                )
        from ray_tpu.serve import audit as _audit

        if table and _audit.corrupt(_audit.INJECT_BROADCAST):
            table = table[:-1]  # drop one row: census/broadcast desync
        # Record what was ACTUALLY announced (post-injection), so the
        # doctor's census_broadcast check diffs the real table against
        # the census rather than our intent.
        st.last_broadcast = [(row[0], bool(row[8])) for row in table]
        self._host.notify_changed(
            replica_set_key(st.app_name, st.info.name), table
        )
        # Anything worth telling the routers is worth persisting:
        # membership, drain flags, summaries and load all flow through
        # here, so the broadcast doubles as the checkpoint dirty edge.
        self._ckpt.mark_dirty()
