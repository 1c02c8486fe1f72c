"""chip_smoke.py off the chip: its phase functions at toy widths on the
CPU (platform stated explicitly), its refusal to report success without
a TPU, and the one-owner-per-chip rule the serve phase stands on."""

import dataclasses
import os
import pathlib
import subprocess
import sys

import jax.numpy as jnp
import pytest

import chip_smoke
from ray_tpu.models import llama

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
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("RAYTPU_", "JAX_COMPILATION"))}
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


def test_serve_phase_jamba_case_runs_the_runner_end_to_end():
    """The serving phase's Jamba case at toy widths: the benchmark's
    replica class for it (benchmarks/runners/serve_jamba.py) checks the
    ragged step against the plain reference, serves chunked prompts
    through serve.run, holds the served tokens to the reference and
    refuses an SSM state kept in bfloat16.  This is the CPU dry run of
    that runner, which ``--rehearse`` has no preset for."""
    code = (
        "import json, chip_smoke\n"
        "config = json.load(open('benchmarks/configs/jamba2_3b.json'))\n"
        "config.update(hidden_size=64, intermediate_size=96,"
        " num_attention_heads=4, head_dim=16, vocab_size=211,"
        " mamba_dt_rank=8, torch_dtype='float32')\n"
        "config['engine']['page_size'] = 16\n"
        "out = chip_smoke.phase_serve_jamba('cpu', config=config,"
        " n_requests=3, prompt_len=150, new_tokens=3,"
        " ready_timeout_s=240)\n"
        "check = out['reference_check']\n"
        "assert check['ok'] and check['layers'] == 3, check\n"
        "worst = max(check[k][e] for k in ('chunked', 'beside',"
        " 'reused_slot') for e in ('rel_err_prefill', 'rel_err_decode'))\n"
        "assert worst < 1e-5, check\n"
        "assert out['state_cache']['resets'] == 3, out\n"
        "served = out['served_check']\n"
        "assert served['ok'] and served['layers'] == 3, served\n"
        "assert served['requests'] == 3 and served['tokens'] == 9, served\n"
        "assert served['rel_short_swapped_median'] > 0.1, served\n"
        "control = out['state_control']\n"
        "assert not control['ok'], control\n"
        "assert min(control['rel_err'].values()) > 1000 * max("
        "check['ssm_state']['rel_err'].values()), (control, check)\n"
        "print('JAMBA_OK', worst)\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env=_clean_env(), capture_output=True, text=True,
                          timeout=420)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "JAMBA_OK" in proc.stdout


def test_serve_phase_brumby_case_runs_the_runner_end_to_end():
    """The serving phase's Brumby case at toy widths: the benchmark's
    replica class for it (benchmarks/runners/serve_brumby.py) checks the
    ragged step against the quadratic reference (logits and the first
    layer's state), serves chunked prompts through serve.run with no
    page allocated, holds the served tokens to the reference and refuses
    a retention state kept in bfloat16.  The CPU dry run of that runner,
    which ``--rehearse`` has no preset for."""
    code = (
        "import json, chip_smoke\n"
        "config = json.load(open('benchmarks/configs/brumby14b_pp4.json'))\n"
        "config.update(hidden_size=64, intermediate_size=96,"
        " num_attention_heads=4, num_key_value_heads=2, head_dim=16,"
        " vocab_size=211, torch_dtype='float32',"
        " model_options={'head_dim': 16, 'dtype': 'float32',"
        " 'param_dtype': 'float32'})\n"
        "config['engine']['prefill_chunk'] = 32\n"
        "out = chip_smoke.phase_serve_brumby('cpu', config=config,"
        " n_requests=3, prompt_len=70, new_tokens=3,"
        " ready_timeout_s=300)\n"
        "check = out['reference_check']\n"
        "assert check['ok'] and check['layers'] == 3, check\n"
        "state = out['state_cache']\n"
        "assert state['resets'] == 3 and state['bytes'] == 3 * 9 * 2"
        " * (160 * 16 + 160) * 4, state\n"
        "served = out['served_check']\n"
        "assert served['ok'] and served['layers'] == 3, served\n"
        "assert served['requests'] == 3 and served['tokens'] == 9, served\n"
        "control = out['state_control']\n"
        "assert not control['ok'], control\n"
        "worst = max(v for e in check['ret_state']['rel_err'].values()"
        " for v in e.values())\n"
        "print('BRUMBY_OK', worst, control['rel_err'])\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env=_clean_env(), capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "BRUMBY_OK" in proc.stdout


def test_serve_phase_xing_case_runs_the_runner_end_to_end():
    """The serving phase's Xing case at toy widths: the benchmark's
    replica class for it (benchmarks/runners/serve_xing.py) checks the
    ragged step against the plain reference (logits, routing, the first
    layer's latent pages), serves chunked prompts through serve.run,
    reads the experts' counters, holds every served token to the
    reference run with the choices the engine's steps logged in their
    pages, and refuses every control: a router computed in bfloat16 (all
    64 experts, top 4: the margins are the cell's), a wrong expert on
    every 50th token, a pool kept in float8_e4m3fn, another request's
    answer, one replaced token.  The CPU dry run of that runner, which
    ``--rehearse`` has no preset for."""
    code = (
        "import json, chip_smoke\n"
        "config = json.load(open('benchmarks/configs/xing4_29b_pp8.json'))\n"
        "config.update(hidden_size=64, intermediate_size=96,"
        " num_attention_heads=4, num_key_value_heads=4, q_lora_rank=16,"
        " kv_lora_rank=8, qk_nope_head_dim=16, qk_rope_head_dim=8,"
        " v_head_dim=16, moe_intermediate_size=16, vocab_size=211,"
        " torch_dtype='float32')\n"
        "config['engine'].update(prefill_chunk=16, page_size=16)\n"
        "out = chip_smoke.phase_serve_xing('cpu', config=config,"
        " n_requests=3, prompt_len=40, new_tokens=3,"
        " ready_timeout_s=300)\n"
        "check = out['reference_check']\n"
        "assert check['ok'] and check['layers'] == 3, check\n"
        "worst = max(check[k][e] for k in ('chunked', 'beside',"
        " 'reused_slot') for e in ('rel_err_prefill', 'rel_err_decode'))\n"
        "assert worst < 1e-5, check\n"
        "assert check['route']['router_mismatch_share'] == 0, check\n"
        "assert check['route']['step_mismatch_share'] == 0, check\n"
        "assert check['latent_pages']['rel_err'] < 1e-5, check\n"
        "served = out['served_check']\n"
        "assert served['ok'] and served['layers'] == 3, served\n"
        "assert served['requests'] == 3 and served['tokens'] == 9, served\n"
        "assert served['held'] == 3 and served['step_gap_max'] == 0, served\n"
        "assert served['rel_short_max'] < 1e-5, served\n"
        "assert not out['route_control']['ok'], out['route_control']\n"
        "wrong = out['wrong_expert_control']\n"
        "assert not wrong['ok'] and wrong['step_gap_max'] > "
        "2 * wrong['eps'], wrong\n"
        "assert wrong['router_score_rms'] <= wrong['tol'], wrong\n"
        "assert not out['cache_control']['ok'], out['cache_control']\n"
        "for name in ('other_answer', 'one_token'):\n"
        "    assert not out[name]['ok'], (name, out[name])\n"
        "    assert out[name]['rel_short_max'] > out[name]['margin'], "
        "out[name]\n"
        "assert out['cache_control']['rel_err'] > 1000 *"
        " check['latent_pages']['rel_err'], out\n"
        "print('XING_OK', worst, out['route_control'],"
        " out['cache_control'])\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env=_clean_env(), capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "XING_OK" in proc.stdout


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


def test_serve_phase_glm5_case_runs_the_runner_end_to_end():
    """The serving phase's GLM-5 case at toy widths: the benchmark's
    replica class for it (benchmarks/runners/serve_glm5.py) checks the
    ragged step against the plain reference given the same share (logits,
    routing, the selection, the first layer's attention output, both
    pools' pages), serves prompts past ``index_topk`` through serve.run,
    reads the held experts' counters, holds every served token to the
    reference, and refuses every control: attending to everything or to
    the newest positions, both pools kept in float8_e4m3fn, a router in
    bfloat16, a wrong expert on every 50th token, the neighbouring rank's
    experts, another request's answer, one replaced token.  (The index
    scores in bfloat16 are the chip's control: against float32
    activations the reading says nothing of the cell's limit.)"""
    code = (
        "import json, chip_smoke\n"
        "config = json.load(open('benchmarks/configs/glm5_ep16.json'))\n"
        "config.update(hidden_size=64, intermediate_size=96,"
        " num_attention_heads=4, num_key_value_heads=4, q_lora_rank=16,"
        " kv_lora_rank=8, qk_nope_head_dim=16, qk_rope_head_dim=8,"
        " v_head_dim=16, moe_intermediate_size=16, vocab_size=211,"
        " index_n_heads=4, index_head_dim=16, index_topk=24,"
        " n_routed_experts=4, router_experts=8, torch_dtype='float32')\n"
        "config['engine'].update(prefill_chunk=16, page_size=16)\n"
        "names = ['dense_control', 'recent_control', 'cache_control',"
        " 'index_cache_control', 'route_control', 'wrong_expert_control',"
        " 'neighbour_rank_control']\n"
        "out = chip_smoke.phase_serve_glm5('cpu', config=config,"
        " n_requests=3, prompt_len=40, new_tokens=3, controls=names,"
        " ready_timeout_s=400)\n"
        "check = out['reference_check']\n"
        "assert check['ok'] and check['layers'] == 3, check\n"
        "worst = max(check[k][e] for k in ('chunked', 'beside',"
        " 'reused_slot') for e in ('rel_err_prefill', 'rel_err_decode'))\n"
        "assert worst < 1e-5, check\n"
        "assert check['route']['step_mismatch_share'] == 0, check\n"
        "sel = check['selection']\n"
        "assert sel['sel_gap_max'] < 1e-5 and sel['sel_mismatch_share']"
        " < 1e-3, sel\n"
        "assert sel['attn_out_rel_err'] < 1e-5 and sel['index_score_rms']"
        " < 1e-5, sel\n"
        "pools = check['pool_pages']\n"
        "assert max(pools['latent_rel_err'], pools['index_key_rel_err'])"
        " < 1e-5, pools\n"
        "served = out['served_check']\n"
        "assert served['ok'] and served['layers'] == 3, served\n"
        "assert served['requests'] == 2 and served['tokens'] == 6, served\n"
        "assert served['held'] == 3 and served['rel_short_max'] < 1e-5,"
        " served\n"
        "for name in ('dense_control', 'recent_control'):\n"
        "    got = out[name]\n"
        "    assert not got['ok'] and got['sel_gap_max'] > got['eps'], got\n"
        "    assert got['attn_out_rel_err'] > got['attn_tol'], got\n"
        "assert out['cache_control']['latent_rel_err'] > "
        "out['cache_control']['tol'], out['cache_control']\n"
        "assert out['index_cache_control']['index_key_rel_err'] > "
        "out['index_cache_control']['index_key_tol'], out\n"
        "assert not out['route_control']['ok'], out['route_control']\n"
        "wrong = out['wrong_expert_control']\n"
        "assert not wrong['ok'] and wrong['step_gap_max'] > wrong['eps'],"
        " wrong\n"
        "near = out['neighbour_rank_control']\n"
        "assert not near['ok'] and near['rel_err_prefill'] > near['tol'],"
        " near\n"
        "for name in ('other_answer', 'one_token'):\n"
        "    assert not out[name]['ok'], (name, out[name])\n"
        "print('GLM5_OK', worst, sel)\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env=_clean_env(), capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "GLM5_OK" in proc.stdout


def test_serve_phase_sala_case_runs_the_runner_end_to_end():
    """The serving phase's MiniCPM-SALA case at toy widths: the
    benchmark's replica class for it (benchmarks/runners/serve_sala.py)
    checks the ragged step against the plain reference (logits, the
    lightning state, the selected pages), refuses the three controls
    (every position attended, the forced blocks alone, one decay for all
    heads), serves prompts past ``dense_len`` through serve.run, the
    state cache and the device's count of pages read add up, and what the
    engine served is the reference's continuation at the layers held."""
    code = (
        "import json, chip_smoke\n"
        "L, S = 'lightning-attn', 'minicpm4'\n"
        "config = json.load(open("
        "'benchmarks/configs/minicpm_sala_pp2.json'))\n"
        "config.update(vocab_size=97, hidden_size=64, intermediate_size=128,"
        " num_attention_heads=4, num_key_value_heads=2, head_dim=16,"
        " lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,"
        " dim_model_base=32, torch_dtype='float32',"
        " sparse_config={'kernel_size': 4, 'kernel_stride': 2,"
        " 'block_size': 8, 'topk': 4, 'window_size': 16, 'init_blocks': 1,"
        " 'dense_len': 32},"
        " check_hf={'num_hidden_layers': 3, 'first_layer': 21,"
        " 'mixer_types': [L, S, L]},"
        " check_plan={'chunk': 8, 'slots': 4, 'rows': {'beside': (2, 37, 6),"
        " 'long': (0, 100, 8), 'reused_slot': (2, 11, 3)}})\n"
        "config['engine'].update(prefill_chunk=8, page_size=8, max_slots=4,"
        " token_budget=9)\n"
        "config['served_plan'] = {'past': 60, 'length': 64, 'answer': 4}\n"
        "out = chip_smoke.phase_serve_sala('cpu', config=config,"
        " n_requests=3, prompt_len=60, new_tokens=4, ready_timeout_s=600)\n"
        "check = out['reference_check']\n"
        "assert check['ok'] and check['layers'] == 3, check\n"
        "worst = max(check[k][e] for k in ('long', 'beside', 'reused_slot')"
        " for e in ('rel_err_prefill', 'rel_err_decode'))\n"
        "assert worst < 1e-5, check\n"
        "assert max(check['lin_state']['rel_err'].values()) < 1e-5, check\n"
        "assert all(check['selection'][k]['kept'] == 0 for k in"
        " ('long', 'beside', 'reused_slot')), check\n"
        "for name in ('dense_control', 'recent_control', 'decay_control'):\n"
        "    assert check[name]['refused'], (name, check[name])\n"
        "assert out['state_cache']['resets'] == 3, out\n"
        "assert min(out['model_counters']['sel_pages']) > 0, out\n"
        "served = out['served_check']\n"
        "assert served['ok'] and served['requests'] == 2, served\n"
        "assert served['layers'] == 3 and served['tokens'] == 8, served\n"
        "assert served['rel_short_max'] < 1e-4, served\n"
        "pages = served['walk_pages']\n"
        "assert pages['device'][0] == pages['host'] > 0, served\n"
        "print('SALA_OK', worst)\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env=_clean_env(), capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SALA_OK" in proc.stdout


def test_kernel_phase_lightning_and_block_sparse_cases_at_toy_widths():
    dims = chip_smoke.KernelDims(heads=8, head_dim=16, page=8, slots=4,
                                 maxp=8, layers=2)
    chip_smoke._kernels_lightning(dims)
    chip_smoke._kernels_block_sparse(dims)
