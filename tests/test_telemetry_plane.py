"""The unified telemetry plane, end to end: one serve request, one LLM
engine request, one data pipeline, and a short train run must all land
in the SAME tracer buffer and the SAME Prometheus registry, with the
merged ``ray_tpu.timeline()`` showing every plane — and tracing
disabled must add zero spans anywhere.
"""

import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu import data as rd
from ray_tpu import serve
from ray_tpu.models import llama
from ray_tpu.serve.llm_engine import (
    EngineConfig,
    LLMEngine,
    llama_paged_adapter,
)
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
from ray_tpu.parallel import MeshSpec
from ray_tpu.util import metrics, tracing, xprof

CFG = llama.LlamaConfig(
    vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, max_seq_len=128, remat=False,
)


def _load_check_metrics():
    path = (pathlib.Path(__file__).resolve().parent.parent
            / "scripts" / "check_metrics.py")
    spec = importlib.util.spec_from_file_location("check_metrics", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def rt():
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    tracing.clear()
    xprof.clear()
    yield
    tracing.disable_tracing()
    serve.shutdown()
    ray_tpu.shutdown()


def _run_serve_request():
    @serve.deployment
    class Echo:
        def __call__(self, x):
            return x + 1

    handle = serve.run(Echo.bind(), name="echo", route_prefix=None)
    assert handle.remote(41).result() == 42


def _run_engine_request():
    params = llama.init_params(jax.random.key(0), CFG)
    eng = LLMEngine(
        params, llama_paged_adapter(CFG),
        EngineConfig(max_slots=2, max_seq_len=128, min_prefill_bucket=16),
    )
    try:
        out = eng.generate([1, 5, 9], max_new_tokens=4, temperature=0.0)
        assert len(out) == 4
    finally:
        eng.shutdown()


def _run_data_pipeline():
    ds = rd.range(64).map_batches(lambda b: {"id": b["id"] * 2})
    total = 0
    for batch in ds.iter_batches(batch_size=16):
        total += len(batch["id"])
    assert total == 64


def _run_train_steps(num_steps=2):
    def init_params(r):
        return {"w": jax.random.normal(r, (8, 4))}

    def loss_fn(p, b):
        pred = b["x"] @ p["w"]
        return jnp.mean((pred - b["y"]) ** 2), {}

    def batches():
        rng = np.random.default_rng(0)
        while True:
            yield {
                "x": rng.normal(size=(16, 8)).astype(np.float32),
                "y": rng.normal(size=(16, 4)).astype(np.float32),
            }

    trainer = JaxTrainer(
        init_params=init_params,
        loss_fn=loss_fn,
        params_axes={"w": (None, None)},
        batch_axes={"x": ("batch", None), "y": ("batch", None)},
        scaling_config=ScalingConfig(mesh_spec=MeshSpec()),
        run_config=RunConfig(report_every=1),
    )
    result = trainer.fit(batches(), num_steps=num_steps)
    assert result.error is None


def _sample_value(text, sample_name):
    for line in text.splitlines():
        if line.startswith(sample_name) and not line.startswith("#"):
            return float(line.rsplit(" ", 1)[1])
    return None


def test_cross_plane_trace_and_metrics(rt, tmp_path, cpu_devices):
    tracing.enable_tracing()
    # The registry is the process's: a test file this worker ran before
    # has left its rows and steps in the counters, so what THIS workload
    # adds is what is compared.  The counters outlive a
    # ``registry().clear()`` (test_metrics.py, test_timeseries_plane.py)
    # in their modules, which put them back, counts and all, at the next
    # step or batch: they are put back here, before the first reading,
    # or it reads 0 where the second reads the earlier file's steps too.
    from ray_tpu.data import iterator as data_iterator
    from ray_tpu.train import trainer as train_trainer

    data_iterator._telemetry()
    train_trainer._telemetry()
    before = metrics.export_prometheus()
    rows_before = _sample_value(before, "raytpu_data_output_rows_total") or 0
    steps_before = _sample_value(before, "raytpu_train_steps_total") or 0

    with tracing.span("workload"):
        _run_serve_request()
        _run_engine_request()
    _run_data_pipeline()
    _run_train_steps()

    spans = {s["name"]: s for s in tracing.finished_spans()}

    # Serve plane: router root span with the queue wait under it, and
    # the replica's user-code span in the same trace.
    assert {"serve.request", "serve.queue_wait", "serve.replica"} \
        <= set(spans)
    assert (spans["serve.queue_wait"]["parent_id"]
            == spans["serve.request"]["span_id"])
    assert (spans["serve.replica"]["trace_id"]
            == spans["serve.request"]["trace_id"])
    # The serve request parents under the driver's workload span.
    assert (spans["serve.request"]["trace_id"]
            == spans["workload"]["trace_id"])

    # LLM engine: per-request phase spans hang off llm.request, which
    # joined the driver's trace via the submit-time context capture.
    assert {"llm.request", "llm.queue_wait", "llm.prefill", "llm.decode"} \
        <= set(spans)
    assert (spans["llm.request"]["trace_id"]
            == spans["workload"]["trace_id"])
    for child in ("llm.queue_wait", "llm.prefill", "llm.decode"):
        assert spans[child]["parent_id"] == spans["llm.request"]["span_id"]

    # Data plane: one span per operator stage (the read fuses with the
    # map, so the stage name carries both).
    data_spans = [n for n in spans if n.startswith("data.")]
    assert data_spans, sorted(spans)
    assert any("Range" in n for n in data_spans)

    # Train plane: per-step span with data-wait and compute children,
    # plus the first call's start-up span (the compile watch's counters
    # are tests/test_startup_record.py's).
    assert {"train.step", "train.data_wait", "train.compute",
            "train.first_step"} <= set(spans)
    assert (spans["train.data_wait"]["parent_id"]
            == spans["train.step"]["span_id"])
    assert (spans["train.compute"]["parent_id"]
            == spans["train.step"]["span_id"])

    # Device plane: every named jitted program registered its XLA cost
    # numbers, and the roofline join against the span walls above
    # produced utilization rows.
    progs = xprof.programs()
    assert {"train.step", "serve.prefill", "serve.decode"} <= set(progs)
    from ray_tpu.utils.accelerator import chip_spec

    rl = xprof.roofline(chip_spec("TPU v5 lite"))
    assert "train.step" in rl and "serve.decode" in rl
    assert rl["train.step"]["wall_s_per_step"] > 0
    assert 0 < rl["train.step"]["flops_utilization"]

    # One merged timeline: task events and library spans from every
    # plane in a single chrome-trace dump — now including one row per
    # device with the joined program events.
    out = tmp_path / "timeline.json"
    ray_tpu.timeline(str(out))
    events = json.loads(out.read_text())
    pids = {e["pid"] for e in events if e.get("ph") == "X"}
    assert {"serve", "llm", "data", "train"} <= pids, pids
    device_events = [e for e in events
                     if str(e.get("pid", "")).startswith("device:")
                     and e.get("ph") == "X"]
    assert device_events, sorted(pids)
    assert {e["cat"] for e in device_events} == {"xla"}
    assert {"train.step", "serve.decode"} \
        <= {e["name"] for e in device_events}

    # One registry: every plane's families in a single scrape, with the
    # request/step observations actually recorded.  Tick the history
    # plane's sampler explicitly first so its self-metric families are
    # live regardless of where the 1 s background cadence landed.
    from ray_tpu.util import timeseries
    timeseries.sample_now()
    text = metrics.export_prometheus()
    assert 'raytpu_xla_program_flops{program="train.step"}' in text
    assert 'raytpu_xla_program_flops{program="serve.decode"}' in text
    assert 'raytpu_xla_program_bytes_accessed{program="serve.prefill"}' \
        in text
    assert _sample_value(
        text, 'raytpu_xla_compile_seconds_total{program="train.step",') > 0
    assert 'raytpu_xla_roofline_flops_utilization{program="train.step"}' \
        in text
    assert 'raytpu_xla_roofline_hbm_utilization{program="serve.decode"}' \
        in text
    # CPU devices report no memory_stats: the HBM gauges stay ABSENT
    # (declared families, zero samples) rather than exporting zeros.
    assert not [l for l in text.splitlines()
                if l.startswith("raytpu_device_hbm_bytes_in_use{")]
    assert _sample_value(text, "raytpu_serve_ttft_seconds_count") >= 1
    assert _sample_value(text, "raytpu_serve_tpot_seconds_count") >= 1
    # Request-lifecycle plane: the engine request above reached FINISHED,
    # so the SLO/terminal/ITL families must all be live in the scrape.
    assert _sample_value(
        text, "raytpu_serve_request_itl_seconds_count") >= 1
    assert _sample_value(
        text, 'raytpu_serve_request_terminal_total{state="FINISHED"}') >= 1
    assert _sample_value(
        text, 'raytpu_serve_request_slo_total{outcome="met"}') >= 1
    assert "raytpu_serve_router_requests_total{" in text
    assert "raytpu_serve_request_latency_seconds_bucket{" in text
    assert "raytpu_data_op_tasks_total{" in text
    assert _sample_value(
        text, "raytpu_data_output_rows_total") == rows_before + 64
    assert _sample_value(
        text, "raytpu_train_steps_total") == steps_before + 2
    assert "raytpu_train_compile_seconds_total" not in text
    # Memory plane: opt-state footprint is derived from the arrays'
    # shardings so it exports real bytes even on CPU; the HBM-headroom
    # gauge follows the absent-not-zero rule (declared family, zero
    # samples on backends without memory_stats).
    assert _sample_value(
        text, 'raytpu_train_opt_state_bytes{scope="global"}') > 0
    assert _sample_value(
        text, 'raytpu_train_opt_state_bytes{scope="per_device"}') > 0
    assert not [l for l in text.splitlines()
                if l.startswith("raytpu_train_hbm_headroom_bytes{")]

    # The smoke check passes over the full live exposition, and the
    # fault-tolerance families are pinned: a serve session must always
    # export the retry/drain counters (even at zero) so dashboards and
    # alerts never silently lose them.
    cm = _load_check_metrics()
    assert cm.check_exposition(
        text,
        require=["raytpu_serve_request_retries_total",
                 "raytpu_serve_replica_drains_total",
                 "raytpu_serve_step_tokens_total",
                 # Multi-host serving plane: per-link collective
                 # traffic + the shard-group membership gauge.
                 "raytpu_serve_collective_bytes_total",
                 "raytpu_serve_collective_seconds",
                 "raytpu_serve_shard_group_members",
                 # ZeRO memory plane: opt-state footprint + per-device
                 # HBM headroom (the latter absent-not-zero on CPU).
                 "raytpu_train_opt_state_bytes",
                 "raytpu_train_hbm_headroom_bytes",
                 # Disaggregated serving plane: KV page-migration
                 # traffic + handoff outcomes, declared at engine
                 # construction even when no migration ever runs.
                 "raytpu_serve_kv_migration_pages_total",
                 "raytpu_serve_kv_migration_bytes_total",
                 "raytpu_serve_kv_migration_seconds",
                 "raytpu_serve_disagg_handoffs_total",
                 "raytpu_serve_disagg_requests_total",
                 # LoRA multiplexing plane: adapter-pool occupancy and
                 # hit/miss/eviction counters, declared with the engine
                 # telemetry even when no adapter is ever loaded.
                 "raytpu_serve_adapter_pool_pages",
                 "raytpu_serve_adapter_resident",
                 "raytpu_serve_adapter_hits_total",
                 "raytpu_serve_adapter_misses_total",
                 "raytpu_serve_adapter_evictions_total",
                 # Autoscaling plane: decision counter, target/actual
                 # group gauges (controller), and the admission-control
                 # shed counter (engine), all declared even when the
                 # policy never fires and nothing is ever shed.
                 "raytpu_serve_autoscale_decisions_total",
                 "raytpu_serve_autoscale_target_groups",
                 "raytpu_serve_autoscale_actual_groups",
                 "raytpu_serve_shed_total",
                 # Control-plane fault-tolerance plane: controller
                 # restart/checkpoint/orphan families, registered with
                 # the controller even when it never crashes.
                 "raytpu_serve_controller_restarts_total",
                 "raytpu_serve_controller_checkpoint_seq",
                 "raytpu_serve_controller_checkpoint_age_seconds",
                 "raytpu_serve_orphans_adopted_total",
                 "raytpu_serve_orphans_killed_total",
                 # Latency-attribution plane: the per-request waterfall
                 # histogram + the control-plane-share gauge (the
                 # ROADMAP item-6 baseline), plus the flight recorder's
                 # families — all declared with the engine telemetry
                 # even before anything ever triggers.
                 "raytpu_serve_request_overhead_seconds",
                 "raytpu_serve_control_plane_share",
                 "raytpu_flightrec_events",
                 "raytpu_flightrec_triggers_total",
                 "raytpu_flightrec_dumps_total",
                 # Telemetry history plane (util/timeseries): the
                 # store's self-metrics, live once the sampler ticks,
                 # plus the offered-load counter the predictive
                 # autoscaling signal is derived from.
                 "raytpu_timeseries_points",
                 "raytpu_timeseries_memory_bytes",
                 "raytpu_timeseries_samples_total",
                 "raytpu_timeseries_dropped_series_total",
                 "raytpu_serve_requests_arrived_total",
                 # Speculative decoding: declared with the engine
                 # telemetry even when the engine never speculates.
                 "raytpu_serve_spec_rounds_total",
                 "raytpu_serve_spec_drafted_tokens_total",
                 "raytpu_serve_spec_accepted_tokens_total",
                 "raytpu_serve_spec_accept_ratio",
                 # Invariant audit plane (util/doctor): violation and
                 # audit counters + last-audit gauges, declared with
                 # the engine telemetry so a scrape always shows the
                 # doctor families even before any audit runs.
                 "raytpu_doctor_violations_total",
                 "raytpu_doctor_audits_total",
                 "raytpu_doctor_last_audit_violations",
                 "raytpu_doctor_last_audit_checks",
                 "raytpu_doctor_last_audit_seconds"]) == []
    assert cm.check_registry() == []


def test_disabled_tracing_records_zero_spans(rt):
    assert not tracing.is_enabled()
    _run_engine_request()
    _run_data_pipeline()
    assert tracing.finished_spans() == []


def test_check_metrics_flags_bad_names():
    cm = _load_check_metrics()
    bad = (
        "# HELP other_counter_total x\n"
        "# TYPE other_counter_total counter\n"
        "other_counter_total 1\n"
        "# HELP raytpu_bad.name x\n"
        "# TYPE raytpu_bad.name gauge\n"
        "# HELP raytpu_dup_total x\n"
        "# TYPE raytpu_dup_total counter\n"
        "# TYPE raytpu_dup_total counter\n"
        "raytpu_dup_total 1\n"
    )
    problems = cm.check_exposition(bad)
    assert any("other_counter_total" in p and "repo grammar" in p
               for p in problems)
    assert any("raytpu_bad.name" in p for p in problems)
    assert any("duplicate family" in p for p in problems)


def test_check_metrics_label_consistency_and_require():
    cm = _load_check_metrics()
    # One family, two label-key shapes -> flagged; `le` (histogram
    # buckets) and `proc` (federation) never count against a family.
    mixed = (
        "# HELP raytpu_serve_requests x\n"
        "# TYPE raytpu_serve_requests gauge\n"
        'raytpu_serve_requests{State="FINISHED"} 1\n'
        "raytpu_serve_requests 2\n"
    )
    problems = cm.check_exposition(mixed)
    assert any("inconsistent label sets" in p
               and "raytpu_serve_requests" in p for p in problems)
    clean = (
        "# HELP raytpu_serve_ttft_seconds x\n"
        "# TYPE raytpu_serve_ttft_seconds histogram\n"
        'raytpu_serve_ttft_seconds_bucket{le="1"} 1\n'
        'raytpu_serve_ttft_seconds_bucket{le="+Inf"} 1\n'
        "raytpu_serve_ttft_seconds_sum 0.5\n"
        "raytpu_serve_ttft_seconds_count 1\n"
        'raytpu_serve_ttft_seconds_count{proc="worker-1"} 1\n'
    )
    assert cm.check_exposition(clean) == []
    # --require fails when an expected family is missing, passes when
    # present.
    assert any("required family" in p and "raytpu_absent_total" in p
               for p in cm.check_exposition(
                   clean, require=["raytpu_absent_total"]))
    assert not any("required family" in p for p in cm.check_exposition(
        clean, require=["raytpu_serve_ttft_seconds"]))
