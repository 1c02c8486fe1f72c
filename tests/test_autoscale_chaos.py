"""SLO-driven shard-group autoscaling under chaos (ISSUE 14 tentpole).

Three scenarios, all through the public handle / controller path
against real replica actors:

- Chaos ramp: sustained bursty waves of streaming completions drive
  the reconciler's scale-up (ongoing-count + admission-queue-age
  pressure); once the fleet holds >= 2 groups a replica is hard-killed
  out from under the live waves.  Group count must track load (up
  mid-ramp, drained back down after), goodput must hold, and every
  surviving stream must finish byte-identical to the greedy recompute
  oracle — chaos may cost latency, never tokens.

- Policy scale-down: when load stops, the excess group retires through
  the PR-5 DRAINING path: in-flight streams finish where they run
  (zero RETRYING), the draining replica leaves the route table only
  after it settles (capacity never dips below the new target), and
  `raytpu list replicas` surfaces the applied decision.

- Overload shedding: once the admission queue is older than the SLO
  budget (EngineConfig.shed_queue_age_s), new requests fail FAST with
  a retriable ShedError — a clean backpressure signal, never a silent
  client timeout.  The SHED terminal lands in the router's request
  ring, the shed counter moves, and the admitted streams still finish
  byte-exact: shedding protects goodput, it doesn't dent it.

- Predictive scale-up (ISSUE 18): with upscale_slope_threshold set and
  the reactive targets parked out of reach, a ramped arrival pattern
  must drive a scale-up whose decision reason is "arrival_slope" —
  the EWMA arrival-rate slope (serve/signals.ArrivalSignal) firing
  BEFORE any queue forms — while zero queue-age/goodput pressure
  decisions land, goodput holds, and the streams stay byte-exact.
  With the knob unset (every other scenario here) the reactive path
  must never emit an arrival_slope decision.

Deterministic where it matters: greedy (temperature=0) decoding,
seeded victim choice, bounded waits everywhere.
"""

import dataclasses
import re
import threading
import time

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.core import api
from ray_tpu.core.exceptions import ShedError
from ray_tpu.models import llama
from ray_tpu.serve import request_events
from ray_tpu.serve.llm_engine import (
    EngineConfig,
    LLMServer,
    llama_paged_adapter,
)
from ray_tpu.utils.test_utils import REPLICA_READY_S, ReplicaKiller
from tests import oracle

pytestmark = pytest.mark.long_file(138)

CFG = llama.LlamaConfig(
    vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, max_seq_len=128, remat=False, dtype=jnp.float32,
    param_dtype=jnp.float32,
)

DEP = "LLMServer"

# 12 new tokens keeps every resumed continuation's re-prefill (prompt
# + delivered prefix <= 15 tokens) inside the 16-token prefill bucket.
# The model is fp32, where the recompute oracle's argmax is every
# program's argmax (tests/oracle.py).
N_STREAMS = 8
N_NEW = 12
PROMPTS = [[i + 1, i + 2, i + 3] for i in range(N_STREAMS)]

# Paged + ragged engine (prefix_cache needs both) so scale-up warm
# starts have a trie to pull and the chaos path exercises the full
# serving engine, not the toy slot path.
ENG = EngineConfig(max_slots=8, max_seq_len=128, min_prefill_bucket=16,
                   page_size=16, ragged_batching=True, token_budget=64,
                   prefix_cache=True)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.key(0), CFG)


@pytest.fixture(scope="module")
def references(params):
    """Oracle token sequences: greedy decoding by full-prefix recompute."""
    return [oracle.greedy_tokens(params, CFG, p, N_NEW) for p in PROMPTS]


def _slow_paged_adapter_factory(cfg):
    """Paged adapter with a throttled ragged step so a 12-token stream
    spans an observable window (~0.4 s) and kills / drains reliably
    land mid-decode.  The sleep rides jax.debug.callback: ragged_step
    is traced under jit, so a bare time.sleep would only fire at trace
    time."""
    base = llama_paged_adapter(cfg)

    def slow_step(*args, **kwargs):
        jax.debug.callback(lambda: time.sleep(0.03), ordered=True)
        return base.ragged_step(*args, **kwargs)

    return dataclasses.replace(base, ragged_step=slow_step)


def _slow_adapter_factory(cfg):
    """Two-program-path variant for the shed app (max_slots=1
    queueing): the throttle rides its decode step."""
    base = llama_paged_adapter(cfg)

    def slow_decode(*args, **kwargs):
        jax.debug.callback(lambda: time.sleep(0.03), ordered=True)
        return base.decode_slots(*args, **kwargs)

    return dataclasses.replace(base, decode_slots=slow_decode)


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
    """Max over samples — for gauges that several worker processes
    export under distinct ``proc`` labels."""
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
    """Poll `pred` until true.  Replica/controller metrics live in
    worker processes and ship to the driver scrape at most once per
    second riding task replies — `nudge` issues a cheap RPC each poll
    so a fresh snapshot has a reply to ride."""
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
    """(target_groups, actual_groups) off `raytpu list replicas` rows —
    also nudges a controller reply, shipping its metric snapshot."""
    from ray_tpu.util import state

    rows = [r for r in state.list_replicas() if r["app"] == app_name]
    if not rows:
        return (0, 0)
    return (rows[0]["target_groups"], rows[0]["actual_groups"])


def _router(app):
    from ray_tpu.serve.handle import _routers

    return _routers[(app, DEP)]


def _serve_autoscaled(params, app_name, **auto_kw):
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
                      adapter_factory=_slow_paged_adapter_factory)
    return serve.run(app, name=app_name, route_prefix=None,
                     timeout_s=REPLICA_READY_S)


def _launch_stream(shandle, prompt_idx, recs, n_new=N_NEW,
                   prompt=None):
    gen = shandle.remote({
        "tokens": list(prompt if prompt is not None
                       else PROMPTS[prompt_idx]),
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
def chaos_app(params):
    handle = _serve_autoscaled(params, "chaos", max_replicas=3)
    yield handle
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.fixture
def pred_app(params):
    """Predictive arm isolated: the reactive targets are parked far out
    of reach (queue age 30 s, goodput 0.05, ongoing 100) so the ONLY
    signal that can force a scale-up during the ramp is the arrival
    slope."""
    handle = _serve_autoscaled(
        params, "pred", max_replicas=2,
        target_ongoing_requests=100.0,
        target_queue_age_s=30.0, target_goodput=0.05,
        upscale_slope_threshold=0.5,
        arrival_half_life_s=0.5, arrival_slope_window_s=3.0)
    yield handle
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.fixture
def scdn_app(params):
    handle = _serve_autoscaled(params, "scdn", max_replicas=2)
    yield handle
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.fixture
def shed_app(params):
    ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
    serve.start()
    app = serve.deployment(num_replicas=1, max_ongoing_requests=8)(
        LLMServer
    ).bind(
        CFG,
        # One slot + throttled decode: admissions queue behind the
        # running stream, so queue age climbs past the 0.25 s budget
        # while early submissions are still decoding.
        EngineConfig(max_slots=1, max_seq_len=128, min_prefill_bucket=16,
                     decode_chunk=1, shed_queue_age_s=0.25),
        lambda: params,
        adapter_factory=_slow_adapter_factory,
    )
    handle = serve.run(app, name="shed", route_prefix=None,
                       timeout_s=REPLICA_READY_S)
    yield handle
    serve.shutdown()
    ray_tpu.shutdown()


def test_chaos_scale_up_kill_drain_down_byte_exact(chaos_app,
                                                   references):
    """Ramped bursty waves against an autoscaled deployment with the
    replica killer active: the group count rises with load, a replica
    dies mid-traffic, every stream still finishes byte-identical to
    the oracle, and after the ramp the policy drains the fleet back to
    one group."""
    ups0 = _metric("raytpu_serve_autoscale_decisions_total",
                   'direction="up"')
    downs0 = _metric("raytpu_serve_autoscale_decisions_total",
                     'direction="down"')
    drains0 = _metric("raytpu_serve_replica_drains_total")
    slope0 = _metric("raytpu_serve_autoscale_decisions_total",
                     'reason="arrival_slope"')

    # Warm the compiled paths off the clock.
    chaos_app.remote({"tokens": [1, 2, 3], "max_new_tokens": 1,
                      "temperature": 0.0}).result(timeout_s=300)

    shandle = chaos_app.options(stream=True, max_retries=8)
    killer = ReplicaKiller(api.runtime(), seed=0)
    recs = []
    kills = 0
    max_groups = 0
    # Ramp: each wave lands before the last drains, so ongoing count
    # and admission-queue age climb and the reconciler scales up.  The
    # waves go on until the second group is live and one is killed.
    wave = 0
    deadline = time.monotonic() + REPLICA_READY_S
    while time.monotonic() < deadline:
        for i in range(N_STREAMS):
            _launch_stream(shandle, i, recs)
        time.sleep(0.4)
        max_groups = max(max_groups, _groups("chaos")[1])
        # Chaos arm: once capacity actually scaled beyond one group,
        # kill a replica out from under the live waves.
        if (kills == 0 and max_groups >= 2
                and len(killer.victims()) >= 2):
            if killer.kill_one() is not None:
                kills += 1
        if kills and wave >= 2:
            break
        wave += 1
    assert kills == 1, \
        f"fleet never reached 2 live groups to kill one (max {max_groups})"
    assert max_groups >= 2, f"never scaled up: max {max_groups} group(s)"
    assert _wait(lambda: _metric("raytpu_serve_autoscale_decisions_total",
                                 'direction="up"') >= ups0 + 1,
                 nudge=lambda: _groups("chaos")), \
        "scale-up applied but no up decision was counted"

    for rec in recs:
        rec["thread"].join(timeout=300)
    hung = [rec["i"] for rec in recs if rec["thread"].is_alive()]
    assert not hung, f"streams hung after kill: {hung}"
    errs = [rec["err"] for rec in recs if rec["err"] is not None]
    assert not errs, f"streams failed under chaos: {errs}"
    # Byte-exact goodput: chaos cost latency, never tokens.
    for rec in recs:
        assert rec["out"] == references[rec["i"]], rec["i"]
    # Everything completed => goodput ratio 1.0 >= the 0.5 target; the
    # engine gauge agrees (sheds are off in this app, nothing failed).
    def _touch():
        chaos_app.remote({"tokens": [1, 2, 3], "max_new_tokens": 1,
                          "temperature": 0.0}).result(timeout_s=60)

    assert _wait(lambda: _metric_max("raytpu_serve_goodput_ratio") >= 0.5,
                 nudge=_touch), "goodput gauge below target after chaos"

    # Ramp over: the policy must drain the extra groups back down —
    # through DRAINING (drain counter moves), never a hard stop.
    downs = lambda: _metric(  # noqa: E731
        "raytpu_serve_autoscale_decisions_total", 'direction="down"')
    # (1, 0) is a state on the way: the group left standing may be the
    # killed replica's replacement, still starting.
    assert _wait(lambda: downs() > downs0 and _groups("chaos") == (1, 1),
                 timeout_s=REPLICA_READY_S), \
        "fleet never drained back down to one group after the ramp"
    assert downs() >= downs0 + 1, "no scale-down decision after ramp"
    assert _wait(lambda: _metric("raytpu_serve_replica_drains_total")
                 >= drains0 + 1, nudge=lambda: _groups("chaos")), \
        "scale-down retired a group without draining it"
    # Signals off (no upscale_slope_threshold): the reactive path must
    # never have emitted a predictive decision.
    assert _metric("raytpu_serve_autoscale_decisions_total",
                   'reason="arrival_slope"') == slope0, \
        "arrival_slope decision counted with the predictive knob unset"


def test_predictive_scale_up_before_queue_pressure(pred_app,
                                                   references):
    """Ramped arrival against the predictive app: wave sizes grow, so
    the EWMA arrival rate's slope crosses the threshold and the
    controller scales up with reason "arrival_slope" — while the
    parked reactive targets record ZERO queue-age/goodput pressure
    decisions.  The point of the predictive arm: the replica is
    already warming before any queue exists for the reactive signals
    to see.  Goodput holds and every stream stays byte-exact."""
    def ups(reason):
        return _metric("raytpu_serve_autoscale_decisions_total",
                       f'direction="up"[^}}]*reason="{reason}"')

    slope0 = ups("arrival_slope")
    qage0 = ups("queue_age")
    good0 = ups("goodput")

    # Warm the compiled paths off the clock.
    pred_app.remote({"tokens": [1, 2, 3], "max_new_tokens": 1,
                     "temperature": 0.0}).result(timeout_s=300)

    shandle = pred_app.options(stream=True, max_retries=8)
    recs = []
    # Ramp: each wave is bigger than the last, so the arrival rate —
    # and with it the EWMA slope the controller watches — climbs
    # monotonically through the window.
    n = 0
    for wave in range(6):
        for _ in range(2 * (wave + 1)):
            _launch_stream(shandle, n % N_STREAMS, recs)
            n += 1
        time.sleep(0.4)
        if _metric("raytpu_serve_autoscale_decisions_total",
                   'reason="arrival_slope"') > slope0:
            break
    assert _wait(lambda: ups("arrival_slope") >= slope0 + 1,
                 nudge=lambda: _groups("pred")), \
        "ramped arrival never drove an arrival_slope scale-up"
    # Predictive means BEFORE pressure: the parked reactive targets
    # must not have tripped.
    assert ups("queue_age") == qage0, \
        "queue-age pressure fired — the scale-up was not predictive"
    assert ups("goodput") == good0, \
        "goodput pressure fired — the scale-up was not predictive"

    for rec in recs:
        rec["thread"].join(timeout=300)
    hung = [rec["i"] for rec in recs if rec["thread"].is_alive()]
    assert not hung, f"streams hung during predictive ramp: {hung}"
    errs = [rec["err"] for rec in recs if rec["err"] is not None]
    assert not errs, f"streams failed during predictive ramp: {errs}"
    # Byte-exact goodput, same bar as the chaos ramp.
    for rec in recs:
        assert rec["out"] == references[rec["i"]], rec["i"]

    def _touch():
        pred_app.remote({"tokens": [1, 2, 3], "max_new_tokens": 1,
                         "temperature": 0.0}).result(timeout_s=60)

    assert _wait(lambda: _metric_max("raytpu_serve_goodput_ratio") >= 0.5,
                 nudge=_touch), "goodput gauge below target after ramp"


def test_policy_scale_down_drains_without_capacity_dip(scdn_app,
                                                       params,
                                                       references):
    """Policy-driven scale-down retires the excess group through the
    DRAINING path: in-flight streams finish where they run (zero
    RETRYING), the route table never dips below the new target, and
    `raytpu list replicas` reports the applied decision."""
    retries0 = _metric("raytpu_serve_request_retries_total")
    drains0 = _metric("raytpu_serve_replica_drains_total")
    downs0 = _metric("raytpu_serve_autoscale_decisions_total",
                     'direction="down"')

    scdn_app.remote({"tokens": [1, 2, 3], "max_new_tokens": 1,
                     "temperature": 0.0}).result(timeout_s=300)
    router = _router("scdn")
    shandle = scdn_app.options(stream=True, max_retries=8)

    # Sustain load until the second group is actually routable.
    recs = []
    scaled = False
    deadline = time.monotonic() + REPLICA_READY_S
    while not scaled and time.monotonic() < deadline:
        for i in range(N_STREAMS):
            _launch_stream(shandle, i, recs)
        time.sleep(0.3)
        with router._lock:
            scaled = len(router._replicas) >= 2
    assert scaled, "never scaled up to 2 routable groups"

    # Two trailing long streams ride the drain window: 24 throttled
    # steps outlive the 0.3 s downscale delay, so the down decision
    # lands while they are mid-decode on the shrinking fleet.
    long_prompts = [[101, 102, 103], [111, 112, 113]]
    long_refs = [oracle.greedy_tokens(params, CFG, p, 24)
                 for p in long_prompts]
    tails = []
    for k, p in enumerate(long_prompts):
        _launch_stream(shandle, k, tails, n_new=24, prompt=p)

    # Watch the route table while the scale-down plays out: the
    # draining group must stay routable until it settles, and the
    # table must never dip below the new target of one.  The table is
    # driver-local (sampled tightly); the decision counter ships on
    # controller replies, so it is re-read on a coarser cadence.
    min_size = 2
    downs_now = downs0
    last_poll = 0.0
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        with router._lock:
            n = len(router._replicas)
        min_size = min(min_size, n)
        now = time.monotonic()
        if now - last_poll >= 0.25:
            last_poll = now
            _groups("scdn")
            downs_now = _metric("raytpu_serve_autoscale_decisions_total",
                                'direction="down"')
        if n == 1 and downs_now > downs0:
            break
        time.sleep(0.005)
    assert min_size >= 1, "route table dipped to zero during scale-down"
    with router._lock:
        assert len(router._replicas) == 1, \
            "excess group never left the route table"
    assert downs_now >= downs0 + 1, "no scale-down decision was counted"

    for rec in recs + tails:
        rec["thread"].join(timeout=300)
    assert not any(rec["thread"].is_alive() for rec in recs + tails)
    assert all(rec["err"] is None for rec in recs + tails), \
        [rec["err"] for rec in recs + tails if rec["err"] is not None]
    for rec in recs:
        assert rec["out"] == references[rec["i"]], rec["i"]
    for k, rec in enumerate(tails):
        assert rec["out"] == long_refs[k], k

    # Drain-safe: nothing was bounced off the retiring group.
    assert _metric("raytpu_serve_request_retries_total") == retries0
    assert _wait(lambda: _metric("raytpu_serve_replica_drains_total")
                 >= drains0 + 1, nudge=lambda: _groups("scdn")), \
        "scale-down retired a group without draining it"
    ring = "router:scdn/LLMServer"
    rows = {r["request_id"]: r for r in request_events.snapshot_rows()
            if r["engine"] == ring}
    for rec in tails:
        row = rows[rec["gen"].request_id]
        assert row["state"] == "FINISHED"
        assert row["attempt"] == 0

    # The decision is surfaced on `raytpu list replicas` rows.
    from ray_tpu.util import state

    rws = [r for r in state.list_replicas() if r["app"] == "scdn"]
    assert rws, "no replica rows for the autoscaled app"
    for r in rws:
        assert r["target_groups"] == 1
        assert r["actual_groups"] == 1
        assert r["autoscale"].startswith("down 2->1")


def test_overload_shed_fails_fast_with_ring_state(shed_app, params,
                                                  references):
    """Once the admission queue is over the SLO budget, new requests
    shed: a fast retriable ShedError (never a silent timeout), the SHED
    terminal in the router ring, the shed counter moving — while every
    admitted stream still finishes byte-exact."""
    shed0 = _metric("raytpu_serve_shed_total")
    shandle = shed_app.options(stream=True)

    # Warm the compiled paths off the clock (also primes the router):
    # two tokens, so that the decode program compiles here too.
    shed_app.remote({"tokens": [1, 2, 3], "max_new_tokens": 2,
                     "temperature": 0.0}).result(timeout_s=300)

    # Fill the single slot and stack the queue behind it: each stream
    # runs ~0.4 s serially, so the oldest-waiting age climbs past the
    # 0.25 s budget and stays there while the backlog drains.
    keep = []
    for i in range(5):
        _launch_stream(shandle, i, keep)
    time.sleep(0.5)

    shed = []
    t0 = time.monotonic()
    for i in range(5, 8):
        _launch_stream(shandle, i, shed)
    for rec in shed:
        rec["thread"].join(timeout=60)
    assert not any(rec["thread"].is_alive() for rec in shed)
    shed_errs = [rec for rec in shed if rec["err"] is not None]
    assert shed_errs, "queue over budget but nothing was shed"
    for rec in shed_errs:
        assert isinstance(rec["err"], ShedError), rec["err"]
        assert rec["err"].queue_age_s > 0.25
        # Fast-fail backpressure: the refusal arrives promptly, not as
        # a stream that silently times out.
        assert rec["done_at"] - t0 < 30.0

    def _touch():
        # Any reply from the replica worker ships its metric snapshot;
        # a nudge that itself sheds still replies (and still counts).
        shed_app.remote({"tokens": [1, 2, 3], "max_new_tokens": 1,
                         "temperature": 0.0}).result(timeout_s=60)

    n_shed = len(shed_errs)
    assert _wait(lambda: _metric("raytpu_serve_shed_total")
                 >= shed0 + n_shed, nudge=_touch), \
        "shed counter never reflected the refused requests"

    # The SHED terminal is the request's whole story in the router
    # ring (surfaced by `raytpu list requests`): no attempt ever ran.
    rows = {r["request_id"]: r for r in request_events.snapshot_rows()
            if r["engine"] == "router:shed/LLMServer"}
    for rec in shed_errs:
        row = rows[rec["gen"].request_id]
        assert row["state"] == "SHED"
        assert row["attempt"] == 0

    # Admitted work is untouched: byte-exact, and the goodput gauge
    # stays clean — sheds produced zero tokens, so they cost goodput
    # nothing.
    for rec in keep:
        rec["thread"].join(timeout=300)
    assert all(rec["err"] is None for rec in keep), \
        [rec["err"] for rec in keep if rec["err"] is not None]
    for rec in keep:
        assert rec["out"] == references[rec["i"]], rec["i"]
    assert _wait(lambda: _metric_max("raytpu_serve_goodput_ratio")
                 >= 0.99, nudge=_touch), \
        "sheds dented the goodput gauge (nothing ran, nothing failed)"
