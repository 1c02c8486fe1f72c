"""chip_smoke.py's serving case for GLM-5 off the chip: the benchmark
runner's replica class end to end at toy widths on the CPU, in a process
of its own.  The longest case of tier-1, in a file of its own so that it
starts first (the rest: tests/test_chip_smoke.py, _xing.py, _state.py)."""

import subprocess
import sys

import pytest

from tests.test_chip_smoke import REPO, _clean_env

pytestmark = pytest.mark.long_file(227)


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
