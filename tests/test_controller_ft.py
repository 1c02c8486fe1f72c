"""Serve control-plane fault tolerance (ISSUE 20 tentpole).

The paper's durable-GCS keystone applied to the serve control plane:
with the controller's state checkpointed through the GCS StoreClient
machinery, everything else is recoverable — so SIGKILLing the
controller mid-traffic must cost nothing but control-plane latency.

- Controller kill under chaos: an autoscaled fleet takes bursty
  streaming waves; once it scales past one group the controller actor
  is hard-killed.  Traffic keeps flowing on the routers' last-known
  tables, a replica is killed DURING the outage, and the data plane
  itself resurrects the control plane (the router's long-poll
  reconnect re-resolves CONTROLLER_NAME through
  _get_or_create_controller).  The replacement recovers from the
  checkpoint (epoch 2 on `raytpu list replicas` rows), replaces the
  outage victim, and a SECOND kill immediately after recovery
  converges too (epoch 3).  Every stream finishes byte-identical to
  the greedy recompute oracle, the routing table never goes empty,
  and the post-recovery deep doctor — including the
  controller.checkpoint_census check — reports zero violations.

- Router ghost purge: a new-epoch authoritative table releases the
  outstanding entries of replicas that died during the outage (their
  in-flight charges must not pin the inflight gauge until the reaper
  happens to poll one of their refs).

- Checkpoint round trip: a mid-chaos controller state (armed scale
  intent, DRAINING replica, disagg roles, adapter/prefix summaries)
  reloads into an equivalent _DeploymentState; unreachable replicas
  drop onto the replacement path; the restored autoscaler makes no
  decision from an empty metrics window (no spurious scale events).

- Store durability: MirroredStore survives primary loss/corruption
  (newest-by-seq wins, saves proceed through the mirror); a corrupt
  or version-skewed checkpoint is rejected LOUDLY (ray_tpu.gcs /
  controller log warning) and the controller starts fresh; the
  clean-shutdown tombstone keeps epoch continuity without
  resurrecting a deliberately torn-down app.

- Fault injection: RAYTPU_FAILPOINTS="doctor.stale_checkpoint:N"
  drops a checkpoint row, and the deep doctor's
  controller.checkpoint_census check must catch the drift.

Deterministic where it matters: greedy (temperature=0) decoding,
seeded victim choice, bounded waits everywhere.
"""

import functools
import logging
import re
import threading
import time

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.core import api
from ray_tpu.models import llama
from ray_tpu.serve.config import (
    AutoscalingConfig,
    DeploymentConfig,
    DisaggConfig,
)
from ray_tpu.serve.controller import (
    CKPT_KEY,
    CKPT_NAMESPACE,
    CKPT_VERSION,
    CONTROLLER_NAME,
    ROUTES_KEY,
    ServeController,
    _DeploymentState,
    _Replica,
    _telemetry,
    replica_set_key,
)
from ray_tpu.serve.deployment import DeploymentInfo
from ray_tpu.serve.llm_engine import EngineConfig, LLMServer
from ray_tpu.serve.long_poll import LongPollHost
from ray_tpu.utils.test_utils import ReplicaKiller, kill_actor_hard
from tests import midstream_kill, oracle
from tests.midstream_kill import hold  # noqa: F401 (fixture)

CFG = llama.LlamaConfig(
    vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, max_seq_len=128, remat=False, dtype=jnp.float32,
    param_dtype=jnp.float32,
)

DEP = "LLMServer"

# Same bounds as the autoscale chaos suite: 12 new tokens keeps every
# resumed continuation's re-prefill inside the 16-token prefill bucket,
# the one the recompute oracle is exact against for this tiny config.
N_STREAMS = 8
N_NEW = 12
PROMPTS = [[i + 1, i + 2, i + 3] for i in range(N_STREAMS)]

ENG = EngineConfig(max_slots=8, max_seq_len=128, min_prefill_bucket=16,
                   page_size=16, ragged_batching=True, token_budget=64,
                   prefix_cache=True)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.key(0), CFG)


def _greedy_reference(params, prompt, n_tokens):
    return oracle.greedy_tokens(params, CFG, prompt, n_tokens)


@pytest.fixture(scope="module")
def references(params):
    """Oracle token sequences: greedy decoding by full-prefix recompute."""
    return [_greedy_reference(params, p, N_NEW) for p in PROMPTS]


def _slow_paged_adapter_factory(cfg, hold):
    """Paged adapter with a throttled ragged step so a 12-token stream
    spans an observable window and the controller kills land
    mid-decode (see test_autoscale_chaos), and one that stands still
    while the file ``hold`` exists, so that the replica kill does."""
    import dataclasses

    from ray_tpu.serve.llm_engine import llama_paged_adapter

    base = llama_paged_adapter(cfg)
    throttle = midstream_kill.throttle(hold, 0.03)

    def slow_step(*args, **kwargs):
        jax.debug.callback(throttle, ordered=True)
        return base.ragged_step(*args, **kwargs)

    return dataclasses.replace(base, ragged_step=slow_step)


def _metric(family: str, tag_re: str = "") -> float:
    """Sum of every exported sample of `family` whose tag block matches
    tag_re (untagged families export without braces)."""
    from ray_tpu.util import metrics

    total = 0.0
    pat = re.compile(
        rf'^{family}(?:{{[^}}]*{tag_re}[^}}]*}})? (\S+)$')
    for line in metrics.export_prometheus().splitlines():
        m = pat.match(line)
        if m:
            total += float(m.group(1))
    return total


def _metric_max(family: str, tag_re: str = "") -> float:
    from ray_tpu.util import metrics

    best = 0.0
    pat = re.compile(
        rf'^{family}(?:{{[^}}]*{tag_re}[^}}]*}})? (\S+)$')
    for line in metrics.export_prometheus().splitlines():
        m = pat.match(line)
        if m:
            best = max(best, float(m.group(1)))
    return best


def _wait(pred, timeout_s=60.0, nudge=None, interval=0.2):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        if nudge is not None:
            try:
                nudge()
            except Exception:
                pass
        time.sleep(interval)
    return pred()


def _groups(app_name):
    from ray_tpu.util import state

    rows = [r for r in state.list_replicas() if r["app"] == app_name]
    if not rows:
        return (0, 0)
    return (rows[0]["target_groups"], rows[0]["actual_groups"])


def _router(app, dep=DEP):
    from ray_tpu.serve.handle import _routers

    return _routers[(app, dep)]


def _serve_autoscaled(params, app_name, hold, **auto_kw):
    ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
    serve.start()
    auto = dict(min_replicas=1, target_ongoing_requests=2.0,
                metrics_interval_s=0.05, look_back_period_s=0.5,
                upscale_delay_s=0.1, downscale_delay_s=0.3,
                target_queue_age_s=1.0, target_goodput=0.5)
    auto.update(auto_kw)
    app = serve.deployment(
        max_ongoing_requests=8, health_check_period_s=0.1,
        autoscaling_config=auto,
    )(LLMServer).bind(CFG, ENG, lambda: params,
                      adapter_factory=functools.partial(
                          _slow_paged_adapter_factory, hold=str(hold)))
    return serve.run(app, name=app_name, route_prefix=None)


def _launch_stream(shandle, prompt_idx, recs, n_new=N_NEW):
    gen = shandle.remote({
        "tokens": list(PROMPTS[prompt_idx]),
        "max_new_tokens": n_new, "temperature": 0.0})
    rec = {"i": prompt_idx, "gen": gen, "out": [], "err": None,
           "done_at": None}

    def consume():
        try:
            for tok in gen:
                rec["out"].append(tok)
        except BaseException as e:  # recorded, asserted on below
            rec["err"] = e
        rec["done_at"] = time.monotonic()

    th = threading.Thread(target=consume, daemon=True)
    th.start()
    rec["thread"] = th
    recs.append(rec)
    return rec


@pytest.fixture
def ft_app(params, hold, monkeypatch):
    # THREAD worker mode (the annotated exception; process is the
    # default): kill_actor_hard / ReplicaKiller semantics, the driver
    # metric registry, and the post-kill generation fence all assume
    # the controller shares the driver process (see test_doctor.py).
    monkeypatch.setenv("RAYTPU_WORKERS", "thread")
    ray_tpu.shutdown()
    handle = _serve_autoscaled(params, "ft", hold, max_replicas=3)
    yield handle
    serve.shutdown()
    ray_tpu.shutdown()


class Echo:
    def __call__(self, x):
        return x


@pytest.fixture
def mini_app(monkeypatch):
    """Tiny non-LLM app for router/doctor plumbing tests.

    THREAD worker mode: the stale-checkpoint injector is armed via the
    driver's RAYTPU_FAILPOINTS env, which only reaches a controller
    that shares the driver process."""
    monkeypatch.setenv("RAYTPU_WORKERS", "thread")
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    serve.start()
    app = serve.deployment(num_replicas=1)(Echo).bind()
    handle = serve.run(app, name="mini", route_prefix=None)
    yield handle
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.fixture
def bare_runtime(monkeypatch):
    """Runtime without serve: checkpoint unit tests drive bare
    ServeController instances (never registered as actors, so the
    generation fence never trips) against fake replica actors — which
    must live in the driver process (thread mode) for the orphan sweep
    to see them in rt._actors."""
    monkeypatch.setenv("RAYTPU_WORKERS", "thread")
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


# -- the acceptance chaos test ----------------------------------------------


def test_controller_kill_recovery_byte_exact(ft_app, references, hold):
    """SIGKILL the controller mid-traffic with autoscaling and the
    replica killer active: streams keep flowing on the last-known
    routing table, the data plane resurrects the control plane from
    its checkpoint, a replica killed during the outage is replaced
    post-recovery, a second kill immediately after recovery converges
    too — and every stream is byte-identical to the greedy oracle."""
    from ray_tpu.util import state

    restarts0 = _metric("raytpu_serve_controller_restarts_total")
    adopted0 = _metric("raytpu_serve_orphans_adopted_total")
    trig0 = _metric("raytpu_flightrec_triggers_total",
                    'reason="controller_recovery"')

    # Warm the compiled paths off the clock (also primes the router).
    ft_app.remote({"tokens": [1, 2, 3], "max_new_tokens": 1,
                   "temperature": 0.0}).result(timeout_s=300)
    router = _router("ft")
    shandle = ft_app.options(stream=True, max_retries=8)
    killer = ReplicaKiller(api.runtime(), seed=0)

    # Routing-table capacity watcher: from first service through both
    # recoveries the router's table must never go empty — degraded
    # mode serves on the last-known table, and a recovery resync swaps
    # the table atomically, never through an empty intermediate.
    with router._lock:
        min_cap = [len(router._replicas)]
    stop_cap = threading.Event()

    def watch_cap():
        while not stop_cap.is_set():
            with router._lock:
                n = len(router._replicas)
            min_cap[0] = min(min_cap[0], n)
            time.sleep(0.005)

    capt = threading.Thread(target=watch_cap, daemon=True)
    capt.start()

    # Ramp until the fleet actually scaled beyond one group.
    recs = []
    max_groups = 0
    for wave in range(16):
        for i in range(N_STREAMS):
            _launch_stream(shandle, i, recs)
        time.sleep(0.4)
        max_groups = max(max_groups, _groups("ft")[1])
        if max_groups >= 2 and len(killer.victims()) >= 2:
            break
    assert max_groups >= 2, f"never scaled up: max {max_groups} group(s)"

    def rows():
        return [r for r in state.list_replicas() if r["app"] == "ft"]

    ids0 = {r["replica_id"] for r in rows()}
    assert ids0, "no census rows before the controller kill"

    # -- outage 1: SIGKILL the controller out from under live waves --
    old_id = api.get_actor(CONTROLLER_NAME)._actor_id
    kill_actor_hard(api.runtime(), old_id)

    # Traffic keeps flowing on the last-known table…
    for i in range(N_STREAMS):
        _launch_stream(shandle, i, recs)
    # …and a replica dies DURING the outage, with no controller alive
    # to see it — the router's per-request eviction carries the load
    # until the recovered controller replaces it.
    # It is one that holds a stream of this wave: the steps stand still
    # while the victim is chosen, so none can end before the kill.
    midstream_kill.kill_a_replica_mid_stream("ft", DEP, hold, arrive_s=30)
    for i in range(N_STREAMS):
        _launch_stream(shandle, i, recs)

    def new_controller(prev_id):
        def check():
            try:
                return api.get_actor(CONTROLLER_NAME)._actor_id != prev_id
            except Exception:
                return False
        return check

    # The data plane resurrects the control plane: the router's
    # long-poll reconnect goes through _get_or_create_controller.
    assert _wait(new_controller(old_id), timeout_s=60), \
        "controller never recovered after the kill"
    assert _wait(lambda: rows()
                 and all(r["ctl_epoch"] == 2 for r in rows())
                 and all(r["last_recovery"] != "" for r in rows()),
                 timeout_s=60), \
        "recovered controller never reached epoch 2 on list_replicas"

    # -- outage 2: kill the replacement immediately after recovery ---
    ctl2_id = api.get_actor(CONTROLLER_NAME)._actor_id
    kill_actor_hard(api.runtime(), ctl2_id)
    for i in range(N_STREAMS):
        _launch_stream(shandle, i, recs)
    assert _wait(new_controller(ctl2_id), timeout_s=60), \
        "second controller kill never recovered"
    assert _wait(lambda: rows()
                 and all(r["ctl_epoch"] == 3 for r in rows()),
                 timeout_s=60), "second recovery never reached epoch 3"

    # The replica killed during the outage is replaced post-recovery:
    # replica ids are unique forever, so the replacement is a NEW id.
    assert _wait(lambda: {r["replica_id"] for r in rows()} - ids0,
                 timeout_s=120), \
        "no replacement replica appeared after the outage kill"
    assert _wait(lambda: rows() and rows()[0]["actual_groups"]
                 == rows()[0]["target_groups"], timeout_s=120), \
        "fleet never converged back to target after recovery"

    for rec in recs:
        rec["thread"].join(timeout=300)
    hung = [rec["i"] for rec in recs if rec["thread"].is_alive()]
    assert not hung, f"streams hung across controller kills: {hung}"
    errs = [rec["err"] for rec in recs if rec["err"] is not None]
    assert not errs, f"streams failed across controller kills: {errs}"
    # Byte-exact goodput: two control-plane outages and a replica kill
    # cost latency, never tokens.
    for rec in recs:
        assert rec["out"] == references[rec["i"]], rec["i"]

    stop_cap.set()
    capt.join(timeout=5)
    assert min_cap[0] >= 1, \
        "routing table dipped to zero during the outages"

    # Recovery telemetry: restart counter, checkpoint seq (monotonic,
    # resumed across generations), adoption census, flight-recorder
    # trigger per recovery.
    assert _wait(lambda: _metric("raytpu_serve_controller_restarts_total")
                 >= restarts0 + 2, nudge=lambda: _groups("ft")), \
        "controller restarts counter missed a recovery"
    assert _metric_max("raytpu_serve_controller_checkpoint_seq") >= 1
    assert _metric("raytpu_serve_orphans_adopted_total") >= adopted0 + 1, \
        "recovery adopted no checkpointed replicas"
    assert _metric("raytpu_flightrec_triggers_total",
                   'reason="controller_recovery"') >= trig0 + 2, \
        "recoveries did not fire the flight-recorder trigger"

    # Post-recovery deep doctor: zero violations, and the
    # checkpoint-vs-census check actually ran.
    rep = state.doctor_report(deep=True)
    assert rep["violations"] == 0, rep
    checks = {row["check"] for r in rep["reports"]
              for row in r.get("checks", ())}
    assert "controller.checkpoint_census" in checks


# -- router ghost purge ------------------------------------------------------


class _FakeRef:
    """Stands in for an ObjectRef in _outstanding: hashable, carries an
    id the object store has never seen (so the reaper skips it)."""

    def __init__(self, tag: str):
        self.id = f"ghost-ref-{tag}".encode()


def test_router_ghost_entries_purged_on_authoritative_table(mini_app):
    """A replica that died during a controller outage still owns
    outstanding entries when the recovered controller's authoritative
    table arrives.  The table purge must release them (and fix the
    inflight gauge) immediately — not wait for the reaper to poll one
    of the ghost's refs."""
    assert mini_app.remote(7).result(timeout_s=60) == 7
    router = _router("mini", "Echo")
    # Freeze the table: stop the long-poll client so the controller's
    # real broadcasts can't race the injected ones.
    router._client.stop()
    time.sleep(0.1)
    with router._lock:
        assert router._replicas, "router table empty after first call"
        live_id = next(iter(router._replicas))
        handle = router._replicas[live_id].handle
    live_row = (live_id, handle, 8, False, None, "unified", None,
                0.0, False)
    ghost_row = ("mini#Echo#ghost", handle, 8, False, None, "unified",
                 None, 0.0, False)
    router._update_replicas([live_row, ghost_row])
    ghost_ref, live_ref = _FakeRef("dead"), _FakeRef("live")
    with router._lock:
        router._outstanding[ghost_ref] = "mini#Echo#ghost"
        router._outstanding[live_ref] = live_id
    # The new-epoch authoritative table no longer lists the ghost.
    router._update_replicas([live_row])
    with router._lock:
        assert ghost_ref not in router._outstanding, \
            "ghost replica kept its outstanding entry after the purge"
        assert router._outstanding.get(live_ref) == live_id, \
            "purge released a live replica's outstanding entry"
        assert set(router._replicas) == {live_id}
    assert _metric_max("raytpu_serve_router_inflight",
                       'deployment="Echo"') == 1.0
    with router._lock:
        del router._outstanding[live_ref]


# -- doctor fail-point -------------------------------------------------------


def test_doctor_detects_injected_stale_checkpoint(mini_app, monkeypatch):
    """RAYTPU_FAILPOINTS="doctor.stale_checkpoint:N" drops a replica
    row from the checkpoint the doctor flushes and reads back — the
    deep controller.checkpoint_census check must report the drift."""
    from ray_tpu.util import state

    assert mini_app.remote(1).result(timeout_s=60) == 1
    rep = state.doctor_report(deep=True)
    assert rep["violations"] == 0, rep

    monkeypatch.setenv("RAYTPU_FAILPOINTS", "doctor.stale_checkpoint:2")
    rep = state.doctor_report(deep=True)
    drift = [v for r in rep["reports"] for row in r.get("checks", ())
             if row["check"] == "controller.checkpoint_census"
             for v in row["violations"]]
    assert drift, "stale-checkpoint injection went undetected"
    assert rep["violations"] >= 1

    # Disarmed, the next doctor pass (which re-saves a full checkpoint)
    # is clean again.
    monkeypatch.setenv("RAYTPU_FAILPOINTS", "")
    rep = state.doctor_report(deep=True)
    assert rep["violations"] == 0, rep


# -- checkpoint round trip ---------------------------------------------------


class _FakeReplica:
    """Pingable stand-in for a ReplicaActor.  The class NAME matters:
    it is not ReplicaActor, so the recovery orphan sweep ignores it."""

    def check_health(self):
        return "HEALTHY"


def _echo_fn(x):
    return x


def _bare_controller(store):
    """A ServeController with __init__'s state but no threads and no
    actor shell — _recover()/_checkpoint_tables() run deterministically
    and the generation fence never trips (no shell to die)."""
    from ray_tpu.core.gcs_persistence import GcsPersistence

    c = ServeController.__new__(ServeController)
    c._lock = threading.RLock()
    c._host = LongPollHost()
    c._deployments = {}
    c._routes = {}
    c._app_ingress = {}
    c._tm = _telemetry()
    c._reconcile_errors_seen = set()
    c._shutdown = threading.Event()
    c._epoch = 1
    c._last_recovery = 0.0
    c._last_ckpt_wall = 0.0
    c._self_actor_id = None
    c._ckpt = GcsPersistence("", 10.0, store=store)
    return c


def test_checkpoint_roundtrip_mid_chaos_state(bare_runtime, tmp_path):
    """A checkpoint taken mid-chaos — scale intent armed, a DRAINING
    replica, disagg roles, adapter/prefix summaries — reloads into an
    equivalent _DeploymentState: live replicas adopted with state and
    role intact, the unreachable one dropped onto the replacement
    path, the intent timer re-armed from recovery time, and the
    restored autoscaler making NO decision from an empty metrics
    window."""
    from ray_tpu.core.gcs_persistence import FileStore

    store = FileStore(str(tmp_path / "ckpt.bin"))
    c1 = _bare_controller(store)

    fake_cls = api.remote(_FakeReplica)
    h_run, h_drain, h_dead = (fake_cls.remote(), fake_cls.remote(),
                              fake_cls.remote())
    h_pre, h_dec = fake_cls.remote(), fake_cls.remote()

    auto = AutoscalingConfig(min_replicas=1, max_replicas=4,
                             target_ongoing_requests=2.0,
                             upscale_delay_s=0.5)
    info_a = DeploymentInfo(
        name="Dep", func_or_class=_echo_fn,
        config=DeploymentConfig(autoscaling_config=auto,
                                graceful_shutdown_timeout_s=2.0),
        init_args=(), init_kwargs={}, is_ingress=True)
    st = _DeploymentState("aft", info_a)
    st.target_replicas = 2
    st.next_replica_idx = 3
    r0 = _Replica("aft#Dep#0", h_run, None)
    r0.state = "RUNNING"
    r0.prefix_summary = {"page": 16, "hashes": [11, 22]}
    r0.adapter_summary = {"adapters": ["lora-a"]}
    r1 = _Replica("aft#Dep#1", h_drain, None)
    r1.state = "DRAINING"
    r1.drain_deadline = time.monotonic() + 5.0
    r2 = _Replica("aft#Dep#2", h_dead, None)
    r2.state = "RUNNING"
    st.replicas = {r.replica_id: r for r in (r0, r1, r2)}
    st._scale_intent = (3, time.monotonic() - 10.0)  # armed mid-count
    st.last_decision = {"direction": "up", "from": 1, "to": 2,
                        "reason": "queue_age", "ts": time.time()}
    c1._deployments[("aft", "Dep")] = st

    info_b = DeploymentInfo(
        name="Disagg", func_or_class=_echo_fn,
        config=DeploymentConfig(
            num_replicas=2, disagg=DisaggConfig(prefill_replicas=1)),
        init_args=(), init_kwargs={}, is_ingress=False)
    st2 = _DeploymentState("aft", info_b)
    p0 = _Replica("aft#Disagg#0", h_pre, None)
    p0.state = "RUNNING"
    p0.role = "prefill"
    p1 = _Replica("aft#Disagg#1", h_dec, None)
    p1.state = "RUNNING"
    p1.role = "decode"
    st2.replicas = {p.replica_id: p for p in (p0, p1)}
    c1._deployments[("aft", "Disagg")] = st2

    c1._routes = {"/aft": ("aft", "Dep")}
    c1._app_ingress = {"aft": "Dep"}

    with c1._ckpt._save_lock:
        c1._ckpt.save(c1._checkpoint_tables())
    # One replica dies AFTER the checkpoint: recovery's census ping
    # must drop it onto the replacement path, not adopt a corpse.
    api.kill(h_dead, no_restart=True)

    t0 = time.monotonic()
    c2 = _bare_controller(store)
    c2._recover()

    assert c2._epoch == 2
    assert c2._last_recovery > 0.0
    assert c2._routes == {"/aft": ("aft", "Dep")}
    assert c2._app_ingress == {"aft": "Dep"}

    st_r = c2._deployments[("aft", "Dep")]
    assert st_r.target_replicas == 2
    assert st_r.next_replica_idx == 3
    assert st_r.last_decision["reason"] == "queue_age"
    # Intent desired survives; the countdown re-arms from recovery time
    # so a pre-crash timer can't fire a spurious scale event.
    assert st_r._scale_intent[0] == 3
    assert st_r._scale_intent[1] >= t0
    # The dead replica was NOT adopted.
    assert set(st_r.replicas) == {"aft#Dep#0", "aft#Dep#1"}
    rr0 = st_r.replicas["aft#Dep#0"]
    assert rr0.state == "RUNNING"
    assert rr0.prefix_summary == {"page": 16, "hashes": [11, 22]}
    assert rr0.adapter_summary == {"adapters": ["lora-a"]}
    rr1 = st_r.replicas["aft#Dep#1"]
    assert rr1.state == "DRAINING"
    assert rr1.drain_deadline is not None and rr1.drain_deadline > t0
    # Replica metrics are deliberately NOT persisted: the restored
    # autoscaler sizes from live pushes only — an empty look-back
    # window makes NO decision and leaves the intent armed.
    assert st_r.metrics == {}
    assert st_r.autoscale(time.monotonic()) is None
    assert st_r._scale_intent[0] == 3

    st2_r = c2._deployments[("aft", "Disagg")]
    assert st2_r.replicas["aft#Disagg#0"].role == "prefill"
    assert st2_r.replicas["aft#Disagg#1"].role == "decode"

    # The routing surface was rebuilt and rebroadcast BEFORE any
    # reconcile pass: routers resyncing against epoch 2 see full
    # tables, never an empty intermediate.
    assert c2._host._snapshots[ROUTES_KEY][1] == {"/aft": ("aft", "Dep")}
    table = c2._host._snapshots[replica_set_key("aft", "Dep")][1]
    assert [(row[0], row[8]) for row in table] == [
        ("aft#Dep#0", False), ("aft#Dep#1", True)]
    # Checkpoint seq resumed, not reset: mirrors keep preferring the
    # new generation's snapshots.
    assert c2._ckpt._seq == 1


def test_orphan_sweep_kills_unrecorded_replicas(bare_runtime, tmp_path):
    """A live actor with the ReplicaActor class name but no checkpoint
    record is invisible to reconciliation — recovery hard-kills it.
    Adopted ids are spared."""
    from ray_tpu.core.gcs_persistence import FileStore

    class ReplicaActor:  # the sweep matches on the class NAME
        def ping(self):
            return "ok"

    cls = api.remote(ReplicaActor)
    orphan = cls.remote()
    assert api.get(orphan.ping.remote()) == "ok"
    adopted = cls.remote()
    assert api.get(adopted.ping.remote()) == "ok"

    c = _bare_controller(FileStore(str(tmp_path / "c.bin")))
    assert c._kill_stale_orphans({adopted._actor_id}) == 1
    with pytest.raises(Exception):
        api.get(orphan.ping.remote(), timeout=5.0)
    assert api.get(adopted.ping.remote()) == "ok"


# -- store durability --------------------------------------------------------


def test_mirrored_store_survives_primary_loss(tmp_path):
    from ray_tpu.core.gcs_persistence import (
        FileStore,
        GcsPersistence,
        MirroredStore,
    )

    p = tmp_path / "primary.bin"
    m = tmp_path / "mirror.bin"

    def persistence(primary_path=p):
        return GcsPersistence("", 10.0, store=MirroredStore(
            FileStore(str(primary_path)), [FileStore(str(m))]))

    gp = persistence()
    gp.save({"epoch": 1, "x": "a"})
    gp.save({"epoch": 1, "x": "b"})
    assert p.exists() and m.exists()

    # Primary lost entirely: load falls back to the mirror and resumes
    # the save counter from it.
    p.unlink()
    gp2 = persistence()
    assert gp2.load() == {"epoch": 1, "x": "b"}
    assert gp2._seq == 2

    # Primary corrupt: the newest READABLE copy (the mirror) wins.
    p.write_bytes(b"\x00garbage, not a pickle")
    gp3 = persistence()
    assert gp3.load() == {"epoch": 1, "x": "b"}

    # Primary unwritable: the save proceeds through the mirror (warns,
    # does not raise), and the mirror alone serves the next load.
    gp4 = persistence(tmp_path / "no-such-dir-parent.bin" / "p.bin")
    gp4.load()
    gp4.save({"epoch": 2, "x": "c"})
    gp5 = GcsPersistence("", 10.0, store=FileStore(str(m)))
    assert gp5.load() == {"epoch": 2, "x": "c"}


def test_corrupt_checkpoint_rejected_loudly(bare_runtime, caplog):
    """A present-but-unreadable checkpoint blob must be rejected with a
    warning (silence would hide corruption) and the controller starts
    fresh rather than crashing or half-recovering."""
    from ray_tpu.core.gcs_persistence import GcsPersistence, KvStoreClient

    rt = api.runtime()
    rt.kv.put(CKPT_KEY, b"\x80garbage-not-a-pickle",
              namespace=CKPT_NAMESPACE)
    store = KvStoreClient(rt.kv, namespace=CKPT_NAMESPACE, key=CKPT_KEY)

    with caplog.at_level(logging.WARNING, logger="ray_tpu.gcs"):
        c = _bare_controller(store)
        c._recover()
    assert c._epoch == 1 and not c._deployments  # fresh start
    assert any("unreadable snapshot" in r.message for r in caplog.records)

    # A readable blob whose INNER layout version is unknown (e.g. a
    # downgrade) is also a loud fresh start.
    gp = GcsPersistence("", 10.0, store=store)
    gp.save({"ckpt_version": 999, "epoch": 7, "deployments": [],
             "routes": {}, "app_ingress": {}})
    caplog.clear()
    with caplog.at_level(logging.WARNING,
                         logger="ray_tpu.serve.controller"):
        c2 = _bare_controller(store)
        c2._recover()
    assert c2._epoch == 1 and not c2._deployments
    assert any("unknown layout version" in r.message
               for r in caplog.records)

    # The clean-shutdown tombstone keeps epoch continuity but must not
    # resurrect the deliberately torn-down app.
    gp.save({"ckpt_version": CKPT_VERSION, "epoch": 5,
             "clean_shutdown": True, "deployments": [], "routes": {},
             "app_ingress": {}})
    c3 = _bare_controller(store)
    c3._recover()
    assert c3._epoch == 6
    assert not c3._deployments
    assert c3._last_recovery == 0.0  # a tombstone is not a recovery
