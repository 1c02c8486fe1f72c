"""Device plane (util/xprof): per-program cost attribution, roofline
joins against tracer walls, the shared HBM sampler, on-demand profiler
capture, and — the acceptance contract — graceful degradation on CPU:
missing cost keys, memory_stats() -> None and an unavailable profiler
must yield ABSENT metrics, never zeros, never raises.
"""

import json
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu.util import metrics, tracing, xprof
from ray_tpu.utils.accelerator import chip_spec


@pytest.fixture(autouse=True)
def clean_plane():
    xprof.clear()
    tracing.clear()
    yield
    tracing.disable_tracing()
    xprof.clear()
    tracing.clear()


def _family_samples(name):
    """Non-comment sample lines of one family in the live exposition."""
    return [l for l in metrics.export_prometheus().splitlines()
            if l.startswith(name) and not l.startswith("#")]


def test_record_compiled_and_roofline():
    lowered = jax.jit(lambda x: (x @ x).sum()).lower(jnp.ones((64, 64)))
    rec = xprof.record_compiled("t.matmul", lowered, span_name="t.span")
    assert rec.flops and rec.flops > 0
    assert rec.bytes_accessed and rec.bytes_accessed > 0
    assert _family_samples("raytpu_xla_program_flops{")
    # the compile counter is the compile watch's, not this call's:
    # tests/test_startup_record.py

    # Join a measured wall → achieved vs. the chip peak.
    tracing.enable_tracing()
    t0 = time.time()
    tracing.record_span("t.span", t0, t0 + 0.01)
    # No published peaks for a CPU: the default spec is an error here,
    # never a nominal fallback; tests state the chip they pretend to be.
    with pytest.raises(LookupError):
        xprof.roofline()
    spec = chip_spec("TPU v5 lite")
    rl = xprof.roofline(spec)
    row = rl["t.matmul"]
    assert row["achieved_flops_per_s"] == pytest.approx(
        rec.flops / row["wall_s_per_step"])
    assert row["flops_utilization"] == pytest.approx(
        rec.flops / row["wall_s_per_step"] / spec["peak_flops"])
    assert 0 < row["hbm_utilization"] < 1
    assert _family_samples("raytpu_xla_roofline_flops_utilization{")


def test_roofline_divides_wall_by_steps_attr():
    lowered = jax.jit(lambda x: x * 2).lower(jnp.ones((8,)))
    xprof.record_compiled("t.stepped", lowered, span_name="t.loop",
                          steps_attr="tokens")
    tracing.enable_tracing()
    t0 = time.time()
    tracing.record_span("t.loop", t0, t0 + 1.0,
                        attributes={"tokens": 10})
    row = xprof.roofline(chip_spec("TPU v5 lite"))["t.stepped"]
    assert row["wall_s_per_step"] == pytest.approx(0.1, rel=1e-3)


def test_cost_analysis_missing_keys_yield_absent_metrics():
    class NoCost:
        def cost_analysis(self):
            return {}

    class ListCost:  # Compiled returns a list; sentinel -1 = unknown
        def cost_analysis(self):
            return [{"flops": -1.0}]

    class Raising:
        def cost_analysis(self):
            raise RuntimeError("unsupported backend")

    for i, prog in enumerate((NoCost(), ListCost(), Raising())):
        rec = xprof.record_compiled(f"t.none{i}", prog)
        assert rec.flops is None and rec.bytes_accessed is None
    text = metrics.export_prometheus()
    # Absent means absent: no zero-valued samples for these programs.
    assert "t.none" not in text
    # And with no measured wall there is no roofline row either.
    assert xprof.roofline(chip_spec("TPU v5 lite")) == {}


def test_memory_stats_none_yields_absent_gauges(cpu_devices):
    assert cpu_devices[0].memory_stats() is None  # CPU contract
    xprof.sample_device_memory()  # must not raise
    assert _family_samples("raytpu_device_hbm_bytes_in_use{") == []
    assert _family_samples("raytpu_device_hbm_bytes_peak{") == []


def test_profiler_unavailable_returns_none(monkeypatch):
    import jax.profiler as profiler

    def boom(*a, **k):
        raise RuntimeError("profiler backend unavailable")

    monkeypatch.setattr(profiler, "start_trace", boom)
    assert xprof.capture(0.01) is None


def test_capture_collects_trace_files(tmp_path):
    paths = xprof.capture(0.05, str(tmp_path / "trace"))
    assert paths, "CPU jax.profiler should produce trace files"
    assert all(p.startswith(str(tmp_path)) for p in paths)


def test_profile_endpoint_roundtrip():
    """Acceptance: POST /api/v0/profile against a live in-process
    runtime returns at least one trace path."""
    from ray_tpu.dashboard import start_dashboard

    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    dash = start_dashboard()
    try:
        req = urllib.request.Request(
            dash.address + "/api/v0/profile",
            data=json.dumps({"duration_s": 0.2}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=90) as r:
            payload = json.loads(r.read())
        assert payload["duration_s"] == pytest.approx(0.2)
        assert len(payload["traces"]) >= 1
        # Bad body → 400, not a hung capture.
        req = urllib.request.Request(
            dash.address + "/api/v0/profile",
            data=json.dumps({"duration_s": "soon"}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 400
    finally:
        dash.stop()
        ray_tpu.shutdown()


def test_profile_fans_out_to_pool_workers():
    """Process workers each capture into their own per-proc directory
    and the union of trace paths comes back through the head."""
    from ray_tpu.core import api as _api

    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    try:
        rt = _api.runtime()
        if rt.worker_pool is None:
            pytest.skip("thread-mode runtime has no worker pool")

        @ray_tpu.remote
        def warm():
            return 1

        assert ray_tpu.get(warm.remote()) == 1  # spawn ≥1 worker
        assert rt.worker_pool.all_workers()
        traces = xprof.distributed_capture(0.2)
        assert any("/driver/" in t for t in traces)
        assert any("/proc-" in t for t in traces), traces
    finally:
        ray_tpu.shutdown()


def test_cli_profile_command():
    from ray_tpu.dashboard import start_dashboard
    from ray_tpu.scripts.cli import main as cli_main
    import io

    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    dash = start_dashboard()
    try:
        out = io.StringIO()
        rc = cli_main(["--address", dash.address, "profile",
                       "--duration", "0.2"], out=out)
        assert rc == 0
        assert "captured" in out.getvalue()
    finally:
        dash.stop()
        ray_tpu.shutdown()


def test_chip_spec_versions():
    from ray_tpu.utils import accelerator as acc

    for v in (acc.GOOGLE_TPU_V4, acc.GOOGLE_TPU_V5E, acc.GOOGLE_TPU_V5P,
              acc.GOOGLE_TPU_V6E):
        spec = chip_spec(v)
        assert spec["chip"] == v
        assert spec["peak_flops"] > 1e14
        assert spec["peak_hbm_bytes_per_s"] > 1e11
    with pytest.raises(LookupError):   # unknown device: error, no default
        chip_spec("TPU-v999")