"""Multi-host tensor-parallel serving replicas (ISSUE 9 tentpole).

A serve replica spans a SHARD GROUP of processes: rank 0 hosts the
engine over a hybrid dcn_tp x tp serving mesh (weights sharded from
the train plane's partition rules, KV pools sharded along heads),
ranks >= 1 are ShardMemberActors holding the group's placement-group
bundles.  On the CPU backend the mesh lives over rank 0's virtual
devices (contiguous groups emulate the host boundary) while the
members are real actors whose death fails the whole group.

Scenarios, all through the real router/controller path:

- bf16-fallback collectives: greedy decode through a 2-member x tp=2
  shard group is byte-identical to a single-process engine.
- int8 DCN allreduce: outputs match within tolerance and the recorded
  DCN bytes-on-wire drop >= 3x vs fp32.
- SIGKILL of one shard member: whole-group failover — every live
  stream resumes byte-identical on the surviving group via the PR-5
  continuation replay, with RETRYING recorded.
- `raytpu list replicas` rows are deterministic and carry mesh-shape
  and shard-group-membership columns.
"""

import dataclasses
import functools
import random
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
from ray_tpu.serve import request_events
from ray_tpu.serve.llm_engine import (
    EngineConfig,
    LLMEngine,
    LLMServer,
    llama_paged_adapter,
)
from ray_tpu.utils.test_utils import ReplicaKiller
from tests import midstream_kill
from tests.midstream_kill import hold  # noqa: F401 (fixture)

CFG = dataclasses.replace(
    llama.LlamaConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=8, n_kv_heads=4,
        mlp_dim=128, max_seq_len=256, remat=False,
    ),
    dtype=jnp.float32, param_dtype=jnp.float32,
)
ENG = EngineConfig(max_slots=8, max_seq_len=128, min_prefill_bucket=16,
                   max_new_tokens_default=12, page_size=16,
                   decode_chunk=1)

APP = "mh"
DEP = "LLMServer"
ROUTER_RING = f"router:{APP}/{DEP}"

N_STREAMS = 4
N_NEW = 12  # prompt (3) + prefix <= 15 stays in the 16-token bucket
PROMPTS = [[i + 1, i + 2, i + 3] for i in range(N_STREAMS)]

SHARD_GROUP = {"size": 2, "tensor_parallel": 2, "dcn_collective": "bf16"}


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.key(0), CFG)


@pytest.fixture(scope="module")
def references(params):
    """Oracle: the single-process paged engine, greedy."""
    eng = LLMEngine(params, llama_paged_adapter(CFG), ENG)
    outs = [eng.submit(p, max_new_tokens=N_NEW, temperature=0.0)
            for p in PROMPTS]
    refs = [s.result(timeout_s=180) for s in outs]
    eng.shutdown()
    return refs


def _slow_paged_adapter_factory(cfg, hold):
    """Paged adapter with a throttled decode step, which stands still
    while the file ``hold`` exists, so that a kill lands mid-stream
    (same trick as test_serve_failover)."""
    base = llama_paged_adapter(cfg)
    throttle = midstream_kill.throttle(hold, 0.03)

    def slow_decode(*args, **kwargs):
        # ordered=True is not allowed on a >1-device mesh; the
        # unordered callback still runs and throttles the step.
        jax.debug.callback(throttle)
        return base.decode_slots(*args, **kwargs)

    return dataclasses.replace(base, decode_slots=slow_decode)


def _serve_app(params, *, num_replicas, adapter_factory):
    ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
    serve.start()
    app = serve.deployment(
        num_replicas=num_replicas, max_ongoing_requests=8,
        health_check_period_s=0.1, shard_group=SHARD_GROUP,
    )(LLMServer).bind(CFG, ENG, lambda: params,
                      adapter_factory=adapter_factory)
    return serve.run(app, name=APP, route_prefix=None)


@pytest.fixture
def mh_app(params):
    handle = _serve_app(params, num_replicas=1,
                        adapter_factory=llama_paged_adapter)
    yield handle
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.fixture
def mh_app_two_groups(params, hold):
    handle = _serve_app(params, num_replicas=2,
                        adapter_factory=functools.partial(
                            _slow_paged_adapter_factory, hold=str(hold)))
    yield handle
    serve.shutdown()
    ray_tpu.shutdown()


def _metric_value(family: str, deployment: str) -> float:
    from ray_tpu.util import metrics

    total = 0.0
    pat = re.compile(
        rf'^{family}{{[^}}]*deployment="{deployment}"[^}}]*}} (\S+)$')
    for line in metrics.export_prometheus().splitlines():
        m = pat.match(line)
        if m:
            total += float(m.group(1))
    return total


def test_shard_group_bf16_byte_identical(mh_app, references):
    """2-process x tp=2 shard group through the real serve path: greedy
    decode byte-identical to the single-process engine.  Rides the same
    app for the `raytpu list replicas` contract (one shard-group spin-up
    is ~a minute of single-core CPU; the assertions are independent)."""
    outs = [mh_app.remote({"tokens": p, "max_new_tokens": N_NEW,
                           "temperature": 0.0}).result()
            for p in PROMPTS]
    assert [o["tokens"] for o in outs] == references

    # The group's decode put bytes on both link classes and the
    # membership gauge tracks the live group.
    from ray_tpu.util import metrics

    text = metrics.export_prometheus()
    assert re.search(
        r'raytpu_serve_collective_bytes_total{link="dcn"[^}]*} [1-9]',
        text), "no DCN collective bytes recorded"
    assert re.search(
        r'raytpu_serve_collective_bytes_total{link="ici"[^}]*} [1-9]',
        text), "no ICI collective bytes recorded"
    assert re.search(
        r'raytpu_serve_shard_group_members{[^}]*} 2\.0', text)

    # -- `raytpu list replicas`: columns + determinism ----------------
    from ray_tpu.util import state

    rows1 = state.list_replicas()
    rows2 = state.list_replicas()
    assert rows1 == rows2, "list_replicas is not deterministic"
    assert rows1, "no replica rows"
    r = rows1[0]
    assert set(r) == {"app", "deployment", "replica_id", "state", "role",
                      "shard_group", "mesh_shape", "members",
                      "target_groups", "actual_groups", "autoscale",
                      "ctl_epoch", "last_recovery"}
    assert r["ctl_epoch"] == 1          # never crashed in this test
    assert r["last_recovery"] == ""     # '' until a recovery happens
    assert r["app"] == APP
    assert r["state"] == "RUNNING"
    # Fixed-size deployment: target==actual and no autoscale decision.
    assert r["target_groups"] == r["actual_groups"] == 1
    assert r["autoscale"] == ""
    assert r["role"] == "unified"  # no DisaggConfig on this deployment
    assert r["shard_group"] == 2
    assert r["mesh_shape"] == "dcn_tp=2 x tp=2"
    # rank 0 + one member, each rank:actor — ids distinct.
    ranks = [p.split(":")[0] for p in r["members"].split(",")]
    ids = [p.split(":")[1] for p in r["members"].split(",")]
    assert ranks == ["0", "1"]
    assert len(set(ids)) == 2
    # filters ride the same path as every other list_* API
    assert state.list_replicas(filters=[("state", "=", "RUNNING")])
    assert not state.list_replicas(filters=[("state", "=", "STOPPING")])


def test_int8_dcn_allreduce_tolerance_and_wire_bytes(params, references):
    """int8 DCN collectives: decode matches the exact run within
    tolerance, and the analytic DCN bytes-on-wire drop >= 3x vs fp32
    (asserted on the exact accounting the bench/telemetry records
    use).  Direct engine drive — the serve path is covered above."""
    from ray_tpu.parallel.collectives import allreduce_wire_bytes
    from ray_tpu.parallel.mesh import create_serving_mesh

    cfg = dataclasses.replace(CFG, tensor_parallel=True,
                              dcn_quantized_allreduce=True,
                              dcn_allreduce_chunk=32)
    eng = LLMEngine(params, llama_paged_adapter(cfg), ENG,
                    mesh=create_serving_mesh(2, 2))
    outs = [eng.submit(p, max_new_tokens=N_NEW, temperature=0.0)
            for p in PROMPTS]
    got = [s.result(timeout_s=180) for s in outs]
    coll = eng._coll_bytes_fn(1)
    eng.shutdown()

    # Greedy argmax under per-chunk int8 quantization: nearly every
    # token survives; a rare near-tie flip is tolerated.
    total = sum(len(r) for r in references)
    matches = sum(a == b for g, r in zip(got, references)
                  for a, b in zip(g, r))
    assert matches / total >= 0.9, f"{matches}/{total} tokens match"

    # >= 3x DCN reduction per decode step, same accounting the
    # MULTICHIP dryrun and bench.py serving_multihost leg record.
    fp32 = 2 * CFG.n_layers * allreduce_wire_bytes(
        CFG.dim, axis_size=2, quantized=False)
    assert coll["dcn"] > 0
    assert fp32 / coll["dcn"] >= 3.0, fp32 / coll["dcn"]


def _start_streams(handle):
    shandle = handle.options(stream=True)
    gens = [
        shandle.remote({"tokens": PROMPTS[i], "max_new_tokens": N_NEW,
                        "temperature": 0.0})
        for i in range(N_STREAMS)
    ]
    outs = [[] for _ in range(N_STREAMS)]
    errs = [None] * N_STREAMS

    def consume(i):
        try:
            for tok in gens[i]:
                outs[i].append(tok)
        except BaseException as e:  # recorded, asserted on below
            errs[i] = e

    threads = [threading.Thread(target=consume, args=(i,), daemon=True)
               for i in range(N_STREAMS)]
    for t in threads:
        t.start()
    return gens, outs, errs, threads


def _wait_all_decoding(outs, min_tokens=2, timeout_s=180.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if all(len(o) >= min_tokens for o in outs):
            return
        time.sleep(0.005)
    raise TimeoutError(
        f"streams never reached {min_tokens} tokens: "
        f"{[len(o) for o in outs]}")


def test_shard_member_kill_fails_over_whole_group(
        mh_app_two_groups, references, hold):
    """SIGKILL one ShardMemberActor (rank >= 1) mid-decode: the
    controller detects the member loss, fails the WHOLE group (rank 0
    is hard-killed — a lost member means lost collectives), and every
    stream resumes byte-identical on the surviving group through the
    PR-5 continuation replay, with RETRYING recorded."""
    retries_before = _metric_value(
        "raytpu_serve_request_retries_total", DEP)
    gens, outs, errs, threads = _start_streams(mh_app_two_groups)
    _wait_all_decoding(outs)

    # Every stream stands where it is.  The group that compiled first
    # may have ended its streams by now: the victim is a member of a
    # seeded choice among the groups that still hold one mid-decode.
    from ray_tpu.util import state

    with midstream_kill.streams_held(hold):
        live = midstream_kill.replicas_holding_a_request(APP, DEP)
        assert live, f"every stream ended before the kill: " \
            f"{[len(o) for o in outs]}"
        group = random.Random(0).choice(list(live))
        (row,) = [r for r in state.list_replicas()
                  if r["replica_id"] == group]
        # "0:<rank 0's id>,1:<member's id>": the actors' hex[8:16]
        member_ids = {part.split(":")[1]
                      for part in row["members"].split(",")[1:]}
        killer = ReplicaKiller(api.runtime(),
                               class_name="ShardMemberActor")
        (victim,) = [a for a in killer.victims()
                     if a.hex()[8:16] in member_ids]
        assert killer.kill_one(actor_id=victim) is not None

    for t in threads:
        t.join(timeout=180)
    assert not any(t.is_alive() for t in threads), \
        f"streams hung after member kill: {[len(o) for o in outs]}"
    assert errs == [None] * N_STREAMS, f"streams failed: {errs}"
    assert outs == references  # exact continuation: no loss/dup/change

    rows = [r for r in request_events.snapshot_rows()
            if r["engine"] == ROUTER_RING]
    by_id = {r["request_id"]: r for r in rows}
    assert {g.request_id for g in gens} <= set(by_id)
    ours = [by_id[g.request_id] for g in gens]
    assert all(r["state"] == "FINISHED" for r in ours)
    retried = [r for r in ours if r["attempt"] >= 1]
    assert retried, "member kill landed mid-decode but nothing retried"
    for r in retried:
        assert "RETRYING" in r["state_ts"]
    assert _metric_value(
        "raytpu_serve_request_retries_total", DEP) > retries_before
