"""chip_smoke.py's serving case for Xing off the chip: the benchmark
runner's replica class end to end at toy widths on the CPU, in a process
of its own (the rest: tests/test_chip_smoke.py, _glm5.py, _state.py)."""

import subprocess
import sys

import pytest

from tests.test_chip_smoke import REPO, _clean_env

pytestmark = pytest.mark.long_file(161)


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
