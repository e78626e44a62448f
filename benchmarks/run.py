"""The benchmark's one command.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names the cell's
configuration and traffic mix; ``benchmarks/configs/<file>`` holds the
configuration, ``benchmarks/traffic/<traffic>.json`` names the job kind,
``benchmarks/jobs/<job>.py`` runs it, and each per-layer metric is read by
``benchmarks/layer_metrics/<metric>.py``.  A new cell, configuration, job kind
or metric is new files and new entries; nothing here names one.

The last line of standard output is the result.  Without an accelerator, or
with fewer chips than the cell asks for, it exits non-zero and prints none.
``--rehearse ROWS`` is for the sandbox: any platform, ROWS rows, and a result
that says ``"rehearsal": true`` on a device that is not the cell's.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse
import importlib
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _layer_reader(name: str):
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("layer_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, default=0, metavar="ROWS")
    ap.add_argument("--control", default="", help="comma list: bfloat16,half,frozen")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    bench = _load_json("BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        sys.exit(f"run.py: no workload {args.workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = _load_json(conf["file"])
    traffic = _load_json("benchmarks", "traffic", cell["traffic"] + ".json")

    import jax
    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and (dev.platform == "cpu" or len(devices) < cell["chips"]):
        sys.exit(f"run.py: {args.workload} needs {cell['chips']} accelerator chip(s), "
                 f"jax found {len(devices)} x {dev.platform} ({dev.device_kind}); "
                 "there is no fallback")

    def log(obj):
        print(json.dumps(obj, default=float), flush=True)

    job = importlib.import_module("benchmarks.jobs." + traffic["job"])
    run = job.run({
        "config": config, "traffic": traffic, "seed": args.seed,
        "seconds": args.seconds, "trace": bool(args.trace),
        "rows": args.rehearse or int(config["rows"]),
        "clock": lambda: time.perf_counter() - _T0, "log": log,
        "control": [c for c in args.control.split(",") if c]})
    run["device_kind"] = dev.device_kind

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": run["memory_peak_bytes"]}
    if args.trace:
        from benchmarks import trace_reduce
        values = {m["name"]: _layer_reader(m["name"])(run)
                  for m in bench["per_layer"] if _applies(m, cell["name"])}
    else:
        values = {m["name"]: run["end_to_end"].get(m["name"])
                  for m in bench["end_to_end"] if _applies(m, cell["name"])}
    units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}
    metrics = {name: {"value": v, "unit": units[name]}
               for name, v in values.items() if v is not None}
    result = {"correct": run["correct"], "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics, "device": device}
    if args.trace and run["trace"] is not None:
        device["busy_s"] = trace_reduce.busy_seconds(run["trace"])
        device["window_s"] = trace_reduce.window_seconds(run["trace"])
        result["breakdown"] = trace_reduce.breakdown(run["trace"])
    if args.rehearse:
        result["rehearsal"] = True
    result["compared"] = run["compared"]
    print("compared " + json.dumps(run["compared"]), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
