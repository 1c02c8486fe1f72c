"""BENCHMARK.json and the files it names, checked without a chip: every
cell's configuration, traffic mix and metric readers load, the names
keep to the contract's alphabet, every per-layer metric moves an
end-to-end metric that each of its cells reports, and at most one cell
in four asks for four chips."""

import importlib
import json
import os
import re

import pytest

from benchmarks import run as bench_run

ROOT = bench_run.ROOT
BENCH = bench_run.benchmark_file()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def _cells_of(metric):
    return metric.get("workloads", CELLS)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_load(cell):
    cfg = bench_run.find(BENCH["configs"], cell["config"], "config")
    assert cfg["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    config = bench_run.load_json(os.path.join(ROOT, cfg["file"]))
    traffic = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", cell["traffic"] + ".json"))
    assert config["source"] == cfg["source"]
    assert sorted(config["reduced"]) == sorted(cfg["reduced"])
    runner = importlib.import_module(
        "benchmarks.runners." + config["runner"])
    assert callable(runner.run)
    assert traffic["loop"] in ("open", "closed", "train")
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_has_a_reader_and_legal_names(metric):
    assert NAME.match(metric["name"]), metric["name"]
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    assert callable(bench_run.reader(metric["name"]))
    # ".lat"/".thr" entries of one quantity read through one file
    base = metric["name"].split(".", 1)[0]
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "metric_readers", base + ".py"))
    for cell in _cells_of(metric):
        assert cell in CELLS, cell


@pytest.mark.parametrize("entry", BENCH["workloads"] + BENCH["configs"],
                         ids=lambda e: e["name"])
def test_entry_names(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_moves_is_reported_where_the_metric_is(metric):
    target = next(m for m in BENCH["end_to_end"]
                  if m["name"] == metric["moves"])
    for cell in _cells_of(metric):
        assert cell in _cells_of(target), (
            f"{metric['name']} is reported in {cell}, where "
            f"{target['name']} is not")
    assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200


def test_every_cell_reports_enough():
    for cell in CELLS:
        e2e = [m["name"] for m in bench_run.cell_metrics(
            BENCH, cell, "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert bench_run.cell_metrics(BENCH, cell, "per_layer"), cell


def test_bounds_and_chips():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1, m["name"]
        assert m["source"] in ("host_clock", "device_trace")
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024
    widths = ("hidden", "intermediate", "head_dim", "_dim", "_rank",
              "experts_per_tok")
    for c in BENCH["configs"]:
        assert not [k for k in c["reduced"] if any(w in k for w in widths)]
