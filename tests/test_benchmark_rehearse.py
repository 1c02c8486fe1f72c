"""The benchmark's own command, end to end on the CPU at toy sizes
(``--rehearse --trace 1``): the line of a training cell and of a serving
cell holds all seven ``setup_*`` metrics, their books balance, and the
serving line's start-up record names the client and the replica's
process."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SETUP = ("setup_import_s", "setup_runtime_s", "setup_weights_s",
         "setup_trace_lower_s", "setup_compile_s",
         "setup_cache_miss_programs", "setup_unnamed_s")


@pytest.mark.case_limit(300)
@pytest.mark.parametrize("cell,processes,spans", [
    ("internlm2_1b8-pretrain_4k", 1,
     {"import{ray_tpu.train}", "train.build", "train.init_state",
      "train.first_step{train.step}"}),
    ("mistral7b_w8-chat", 2,
     {"runtime.init", "serve.run", "worker.boot", "serve.replica_init",
      "llm.load_weights", "llm.init_cache",
      "import{ray_tpu.serve.llm_engine}"}),
])
def test_rehearsed_line_holds_the_setup_metrics(cell, processes, spans):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", cell,
         "--seed", "2100000011", "--seconds", "3", "--trace", "1",
         "--rehearse"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=280)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.splitlines()[-1])
    assert line["device"]["platform"] == "cpu"     # never a measurement
    metrics = line["metrics"]
    assert set(SETUP) <= set(metrics)
    books = line["notes"]["setup"]
    assert metrics["setup_unnamed_s"]["value"] >= 0.0
    assert books["books"]["ok"] and "dark" in books["unnamed_by_span"]
    named = sum(metrics[m]["value"] for m in SETUP
                if m not in ("setup_cache_miss_programs", "setup_unnamed_s"))
    assert named + metrics["setup_unnamed_s"]["value"] == pytest.approx(
        books["setup_s"] - books["ramp_s"])
    assert len(books["processes"]) >= processes
    assert spans <= set(books["by_span"])
    first = [p for p in books["by_program"]
             if p.startswith(("serve.ragged", "train.step"))]
    assert first and all(
        books["by_program"][p]["compile"]["n"] == 1 for p in first)
