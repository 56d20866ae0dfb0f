"""The benchmark's one command:

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one JSON object as the last line of stdout. The
cell, its configuration and the per-layer metrics are data files found by
name (``workloads/<cell>.json``, ``configs/<config>.json``,
``layer_metrics/*.py`` by regime); nothing here or under ``harness/``
names one. Runs on a TPU or not at all.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up counts from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str):
    """(manifest, cell, config) for the manifest's workload ``name``."""
    manifest = load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        sys.exit(f"no workload {name!r} in BENCHMARK.json")
    cell = load_json(HERE, "workloads", name + ".json")
    conf = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    config = load_json(ROOT, conf["file"])
    if cell["config"] != entry["config"] or cell["chips"] != entry["chips"]:
        sys.exit(f"workloads/{name}.json disagrees with BENCHMARK.json")
    return manifest, cell, config


def load_layer_metrics(regime: str, names) -> list:
    """The readers under ``layer_metrics/`` that serve ``regime`` and that
    the manifest lists, as (META, read) in name order."""
    out = []
    folder = os.path.join(HERE, "layer_metrics")
    for fname in sorted(os.listdir(folder)):
        if not fname.endswith(".py"):
            continue
        spec = importlib.util.spec_from_file_location(
            "layer_metric_" + fname[:-3].replace(".", "_"),
            os.path.join(folder, fname))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        if regime in mod.META["regimes"] and mod.META["name"] in names:
            out.append((mod.META, mod.read))
    return out


def read_layer_metrics(readers, ctx: dict) -> dict:
    """A reader that finds nothing to read returns None and its metric is
    left out of the line."""
    metrics = {}
    for meta, read in readers:
        value = read(ctx)
        if value is not None:
            metrics[meta["name"]] = {"value": float(value),
                                     "unit": meta["unit"]}
    return metrics


def result_line(res: dict, metrics: dict, device: dict, breakdown=None) -> dict:
    """The one JSON object of the contract, and no key beyond it."""
    line = {"correct": bool(res["correct"]), "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    return line


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args()
    manifest, cell, config = load_cell(args.workload)

    # ask for the TPU before jax is imported: a machine without one fails
    # here instead of measuring a CPU under a device metric's name
    os.environ["JAX_PLATFORMS"] = "tpu"
    import jax

    from harness import common, flops, peaks as peaks_mod, trace_reduce

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        sys.exit(f"cell needs {cell['chips']} TPU chip(s); jax gave "
                 f"{len(devices)} x {devices[0].platform}")
    peaks = peaks_mod.for_device_kind(devices[0].device_kind)

    from paddle_tpu.framework import compile_cache

    cache_dir = compile_cache.enable_persistent_cache()
    common.log(f"{args.workload} seed {args.seed} seconds {args.seconds} "
               f"trace {args.trace}; {len(devices)} x "
               f"{devices[0].device_kind}; compile cache {cache_dir}")

    harness = common.resolve(cell["harness"])
    res = harness(cell, config, args.seed, args.seconds, bool(args.trace))
    setup_s = res["t_window_open"] - T_START
    common.log(f"setup_s {setup_s:.3f}; compiles over the whole run "
               f"{compile_cache.backend_compile_stats()}")

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    breakdown = None
    if args.trace:
        tr = res["ctx"]["trace"]
        if tr is None or not tr["busy_s"] > 0:
            sys.exit("the traced slice shows no operation on the device")
        ctx = dict(res["ctx"], cell=cell, config=config, peaks=peaks,
                   flops=flops, trace_reduce=trace_reduce, log=common.log,
                   resolve=common.resolve)
        names = {m["name"] for m in manifest["per_layer"]}
        metrics = read_layer_metrics(
            load_layer_metrics(cell["regime"], names), ctx)
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        breakdown = trace_reduce.breakdown(tr)
    else:
        # the harness measures what its regime can; the manifest says which
        # of those are this cell's end-to-end metrics
        listed = {m["name"] for m in manifest["end_to_end"]
                  if args.workload in m.get("workloads", [args.workload])}
        metrics = {k: {"value": float(v), "unit": u}
                   for k, (v, u) in res["end_to_end"].items() if k in listed}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    line = result_line(res, metrics, device, breakdown)
    common.log("end-to-end " + json.dumps(
        {k: v for k, (v, _) in res["end_to_end"].items()}))
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
