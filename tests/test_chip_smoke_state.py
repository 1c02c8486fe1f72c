"""chip_smoke.py's serving cases for the models that keep a state by
slot (Jamba, Brumby, MiniCPM-SALA) off the chip: each benchmark runner's
replica class end to end at toy widths on the CPU, in a process of its
own (the rest: tests/test_chip_smoke.py, _glm5.py, _xing.py)."""

import subprocess
import sys

import pytest

from tests.test_chip_smoke import REPO, _clean_env

pytestmark = pytest.mark.long_file(166)


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
