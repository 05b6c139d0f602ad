"""The benchmark's command.

    python3 benchmarks/run.py --workload <config>.<traffic> --seed N \
        --seconds S --trace 0|1

Finds the cell in ``BENCHMARK.json``, its configuration under
``benchmarks/configs/`` and its traffic under ``benchmarks/traffic/`` by
their names, hands them to the runner of the configuration's ``kind``, and
prints the contract's JSON object as the last line of standard output:
the cell's end-to-end metrics with ``--trace 0``, its per-layer metrics
(each read by ``benchmarks/layer_metrics/<name>``) with ``--trace 1``.
Exits non-zero, printing no result line, without a TPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def find_cell(manifest, workload):
    for cell in manifest["workloads"]:
        if cell["name"] == workload:
            return cell
    raise SystemExit(f"benchmark: no workload {workload!r} in BENCHMARK.json")


def metrics_of(manifest, group, workload):
    """The metrics of ``group`` that this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def read_layer_metric(name, run):
    """Value of one per-layer metric: its own ``<name>.py`` if there is
    one, else the generic reader its ``<name>.json`` names."""
    from benchmarks.harness import readers

    own = os.path.join(HERE, "layer_metrics", name + ".py")
    if os.path.exists(own):
        spec = importlib.util.spec_from_file_location(
            "layer_metric_" + name.replace(".", "_").replace("-", "_"), own)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read(run)
    spec = load_json("benchmarks", "layer_metrics", name + ".json")
    return readers.READERS[spec["reader"]](run, **spec.get("args", {}))


def result_line(manifest, workload, run, trace):
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_of(manifest, group, workload):
        if trace:
            v = read_layer_metric(m["name"], run)
        else:
            v = run["setup_s"] if m["name"] == "setup_s" \
                else run["values"].get(m["name"])
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    line = {"correct": bool(run["correct"]), "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics,
            "device": dict(run["device"])}
    if trace and run.get("trace"):
        line["device"]["busy_s"] = run["trace"]["busy_s"]
        line["device"]["window_s"] = run["trace"]["window_s"]
        line["breakdown"] = run["trace"]["breakdown"]
    return line


def detail(run):
    """The readings that do not belong on the last line; of each series,
    its median."""
    from benchmarks.harness import stats

    out = {k: run.get(k) for k in (
        "values", "counters", "check", "slice_rates", "setup_s",
        "compiles_in_window", "work", "attempted", "failed")}
    out["series_p50"] = {k: stats.percentile(v, 50)
                         for k, v in (run.get("series") or {}).items()}
    n = run.get("traced_steps")
    out["traced_steps"] = len(n) if isinstance(n, list) else n
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    manifest = load_json("BENCHMARK.json")
    cell = find_cell(manifest, args.workload)
    config = load_json("benchmarks", "configs", cell["config"] + ".json")
    traffic = load_json("benchmarks", "traffic", cell["traffic"] + ".json")

    from benchmarks.runners import common

    common.require_tpu(cell["chips"])
    cache = common.place_cache()
    print(f"[cache] {cache}", flush=True)
    runner = importlib.import_module("benchmarks.runners." + config["kind"])
    out_dir = os.path.join(ROOT, "benchmarks_out", args.workload)
    run = runner.run(config, traffic, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace),
                     out_dir=os.path.join(out_dir, "trace"),
                     t_start=T_START, chips=cell["chips"])
    print(f"[run] {json.dumps(detail(run))}", flush=True)
    if run["compiles_in_window"]:
        print(f"benchmark: {run['compiles_in_window']} compile(s) inside "
              "the measured window", file=sys.stderr)
        sys.exit(1)
    print(json.dumps(result_line(manifest, args.workload, run,
                                 bool(args.trace))), flush=True)


if __name__ == "__main__":
    main()
