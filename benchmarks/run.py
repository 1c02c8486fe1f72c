"""The benchmark's command.

    python3 -m benchmarks.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell: load, warm up, measure ``--seconds``, print one JSON
object as the last line of standard output, exit.  It fails, and prints no
result, where it finds no TPU or fewer chips than the cell asks for; it
never falls back to a CPU.  ``--rehearse`` is the builder's dry run on a
CPU at a toy size (benchmarks/harness/rehearse_presets.json): it walks the
same control flow, and its line names the platform it ran on, so nothing
can take it for a measurement.

Everything that belongs to one cell is data the harness finds by name:

    BENCHMARK.json                          cells, configurations, metrics
    benchmarks/configs/<config>.json        published sizes and deployment
    benchmarks/traffic/<traffic>.json       the mix's parameters
    benchmarks/runners/<runner>.py          how a kind of configuration is driven
    benchmarks/metric_readers/<metric>.py   read(run) -> value or None;
                                            <metric> is the name up to its first "."
"""

from __future__ import annotations

import time

_T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, "benchmarks_out")


@dataclasses.dataclass
class Context:
    cell: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    chips: int
    seed: int
    seconds: float
    trace: bool
    trace_dir: str
    rehearse: bool
    t_process_start: float
    sweep: Optional[list] = None


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark_file() -> Dict[str, Any]:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def find(entries, name: str, what: str) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json")


def cell_metrics(bench: Dict[str, Any], cell: str, group: str):
    """The metrics of ``group`` that this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def reader(name: str):
    """``read(run)`` of the metric's file.  A metric's name may end in
    ``.<cells>`` (``device_idle_share.lat``): BENCHMARK.json wants one
    entry for each end-to-end metric a quantity moves, and every such
    entry reads through the one file of the quantity's base name."""
    base = name.split(".", 1)[0]
    path = os.path.join(HERE, "metric_readers", base + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.metric_readers." + base, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def deep_merge(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for k, v in over.items():
        out[k] = (deep_merge(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


def build_context(args, bench: Dict[str, Any]) -> Context:
    cell = find(bench["workloads"], args.workload, "workload")
    cfg_entry = find(bench["configs"], cell["config"], "config")
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(
        HERE, "traffic", cell["traffic"] + ".json"))
    if args.rehearse:
        presets = load_json(os.path.join(
            HERE, "harness", "rehearse_presets.json"))
        config = deep_merge(config, presets["config"][config["runner"]])
        traffic = deep_merge(traffic, presets["traffic"].get(
            traffic.get("loop", "train"), {}))
    trace_dir = os.path.join(OUT_DIR, "trace", args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    return Context(cell=args.workload, config=config, traffic=traffic,
                   chips=int(cell["chips"]), seed=args.seed,
                   seconds=float(args.seconds), trace=bool(args.trace),
                   trace_dir=trace_dir, rehearse=args.rehearse,
                   t_process_start=_T_PROCESS_START,
                   sweep=([float(r) for r in args.sweep.split(",")]
                          if args.sweep else None))


def result_line(bench: Dict[str, Any], run, trace: bool) -> Dict[str, Any]:
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(bench, run.cell, group):
        try:
            value = reader(m["name"])(run)
        except KeyError:
            if run.device.get("platform") == "tpu":
                raise
            value = None    # a rehearsal's device has no published peaks
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = dict(run.device)
    line = {"correct": bool(run.correct), "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device,
            "notes": run.notes}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        line["breakdown"] = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m benchmarks.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="dry run on a CPU at a toy size; not a measurement")
    ap.add_argument("--sweep", default=None,
                    help="builder's knee sweep: comma-separated open-loop "
                         "rates run one after another in one process; "
                         "prints a line per rate and no result")
    ap.add_argument("--records", default=None,
                    help="also write the run's raw records to this file")
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    bench = benchmark_file()
    ctx = build_context(args, bench)
    runner = importlib.import_module(
        "benchmarks.runners." + ctx.config["runner"])
    run = runner.run(ctx)
    if run is None:     # a sweep: its lines are printed, there is no result
        return 0
    if not args.rehearse and run.device.get("platform") != "tpu":
        raise SystemExit("benchmark: the run was not on a TPU; no result")
    if args.records:
        os.makedirs(os.path.dirname(os.path.abspath(args.records)),
                    exist_ok=True)
        with open(args.records, "w") as f:
            json.dump({"cell": run.cell, "seed": args.seed,
                       "seconds": run.seconds, "setup_s": run.setup_s,
                       "requests": run.requests, "steps": run.steps,
                       "ring": run.ring, "counters0": run.counters0,
                       "counters1": run.counters1, "notes": run.notes,
                       "trace": run.trace}, f)
    line = result_line(bench, run, bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
