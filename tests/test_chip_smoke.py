"""chip_smoke.py off the chip: its phase functions at toy widths on the
CPU (platform stated explicitly), its refusal to report success without
a TPU, and the one-owner-per-chip rule the serve phase stands on.  The
five benchmark runners' dry runs are tests/test_chip_smoke_glm5.py,
_xing.py and _state.py."""

import dataclasses
import os
import pathlib
import subprocess
import sys

import jax.numpy as jnp
import pytest

import chip_smoke
from ray_tpu.models import llama

pytestmark = pytest.mark.long_file(207)

REPO = pathlib.Path(__file__).resolve().parent.parent

TINY = llama.LlamaConfig(
    vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=128, max_seq_len=128, remat=False)
DIMS = chip_smoke.KernelDims(
    heads=4, kv_heads=2, head_dim=16, page=16, slots=4, maxp=4, layers=2,
    flash_batch=1, flash_seq=256, flash_heads=4, flash_kv_heads=2,
    fused_cfg=dataclasses.replace(
        TINY, dim=128, n_heads=2, n_kv_heads=1, mlp_dim=256,
        max_seq_len=64, kv_int8=True))


def _clean_env():
    """The environment of a process a case starts.  The compilation cache
    stays: tests/conftest.py points it at the session's own directory,
    whatever the shell had set, and no case here reads the smoke's count
    of hits and misses."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("RAYTPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    return env


@pytest.mark.parametrize("part", ["flash", "paged", "ragged", "fused",
                                  "adam8"])
def test_kernels_phase_parts(part):
    fn = getattr(chip_smoke, f"_kernels_{part}")
    fn(DIMS, "cpu") if part == "flash" else fn(DIMS)


def test_phase_refuses_the_wrong_platform():
    with pytest.raises(RuntimeError, match="told platform='tpu'"):
        chip_smoke.phase_kernels("tpu", dims=DIMS)


def test_train_then_multichip_phase():
    """One device, then the same batch at fsdp=4 (step-0 loss must match
    the one-device value) and the tensor-parallel engine with a prompt
    in a longer prefill bucket."""
    one = chip_smoke.phase_train("cpu", cfg=TINY, batch=4, seq=32, steps=5)
    assert one["loss_last"] < one["loss0"]
    chip_smoke.phase_multichip(
        "cpu", expect_loss0=one["loss0"], cfg=TINY, batch=4, seq=32,
        serve_cfg=dataclasses.replace(TINY, n_kv_heads=4), prompt_len=20,
        long_prompt=40)
    with pytest.raises(AssertionError, match="differs from the one-device"):
        chip_smoke.phase_train("cpu", cfg=TINY, batch=4, seq=32, steps=3,
                               expect_loss0=one["loss0"] + 1.0)


def test_engine_legacy_phase():
    out = chip_smoke.phase_engine_legacy(
        "cpu", cfg=TINY, slots=4, n_requests=3, prompt_len=20, new_tokens=4)
    assert out["prefill_rel_err"] < 5e-2


def test_serve_phase_replica_is_a_cpu_worker_and_caller_stays_off_jax():
    """The whole serve phase in a fresh interpreter (so "this process
    never initialised a backend" means something): a replica that asked
    for no TPU is a worker with JAX_PLATFORMS=cpu."""
    code = (
        "import dataclasses, chip_smoke\n"
        "from ray_tpu.models import llama\n"
        "cfg = llama.LlamaConfig(vocab_size=256, dim=64, n_layers=2,"
        " n_heads=4, n_kv_heads=2, mlp_dim=128, max_seq_len=128,"
        " remat=False, kv_int8=True)\n"
        "out = chip_smoke.phase_serve('cpu', cfg=cfg, slots=4,"
        " n_requests=2, prompt_len=20, new_tokens=3, ref_layers=1,"
        " ready_timeout_s=120)\n"
        "assert out['device']['platform'] == 'cpu', out\n"
        "print('SERVE_OK', out['prefill_rel_err'])\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env=_clean_env(), capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SERVE_OK" in proc.stdout
    assert "JAX_PLATFORMS=cpu platform=cpu" in proc.stdout
    assert "caller never initialised a JAX backend" in proc.stdout


def test_chip_smoke_without_a_chip_fails_and_names_the_platform():
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=_clean_env(), capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "platform='cpu'" in proc.stdout
    assert '"ok"' not in proc.stdout


def test_init_initialises_no_jax_backend():
    code = (
        "import sys, ray_tpu\n"
        "ray_tpu.init(num_cpus=1)\n"
        "assert 'jax' not in sys.modules, 'init imported jax'\n"
        "import jax\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "ray_tpu.shutdown()\n"
        "print('INIT_OK')\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env=_clean_env(), capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "INIT_OK" in proc.stdout


def test_worker_env_follows_the_lease(monkeypatch):
    """No TPU request: JAX_PLATFORMS=cpu.  A TPU request: a fresh
    process pinned to the TPU backend, which on this machine cannot
    start one, so the task fails instead of running on the CPU."""
    import ray_tpu
    from ray_tpu.core.exceptions import RayTpuError

    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1")    # "two chips" here
    monkeypatch.setenv("RAYTPU_WORKERS", "process")
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2)
    try:
        assert ray_tpu.cluster_resources()["TPU"] == 2.0

        def env():
            return (os.getpid(), os.environ.get("JAX_PLATFORMS"),
                    os.environ.get("TPU_VISIBLE_CHIPS"))

        pid, platforms, _ = ray_tpu.get(ray_tpu.remote(env).remote())
        assert pid != os.getpid() and platforms == "cpu"
        with pytest.raises(RayTpuError, match="backend 'tpu'"):
            ray_tpu.get(ray_tpu.remote(num_tpus=1)(env).remote(),
                        timeout=120)
    finally:
        ray_tpu.shutdown()


def test_kernel_phase_lightning_and_block_sparse_cases_at_toy_widths():
    dims = chip_smoke.KernelDims(heads=8, head_dim=16, page=8, slots=4,
                                 maxp=8, layers=2)
    chip_smoke._kernels_lightning(dims)
    chip_smoke._kernels_block_sparse(dims)
