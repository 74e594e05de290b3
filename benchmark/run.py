"""``python3 -m benchmark.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell, in one process.

Refuses (exit 2, no result line) unless the default JAX backend is a TPU
whose ``device_kind`` is in the benchmark's peak table and holds the chips
the cell asks for; loads, warms up, measures, compares with the plain
reference, and prints the one result line last."""
from __future__ import annotations

import time

_T_START = time.perf_counter()      # as near the process's start as we get

import argparse
import importlib
import json
import os
import sys
import traceback


def parse(argv):
    ap = argparse.ArgumentParser(prog="benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(args, manifest_path=None, require_chip=True, fault=None,
             t_start=None):
    """Drive one run; returns (result dict, checks, notes).  ``require_chip``
    and ``fault`` are for the tests, which rehearse on the CPU and break the
    timed path; the command line reaches neither."""
    from . import harness
    from .compile_clock import CompileClock

    loaded = harness.load_cell(args.workload, manifest_path)
    # the system under test is THIS checkout's, never an installed copy
    if not os.path.isdir(os.path.join(harness.ROOT, "mxnet_tpu")):
        raise harness.Refused("no mxnet_tpu/ beside benchmark/ in %s: the "
                              "system under test is not here" % harness.ROOT)
    if harness.ROOT not in sys.path:
        sys.path.insert(0, harness.ROOT)
    import mxnet_tpu  # noqa: F401
    harness.use_compile_cache()
    devices = harness.find_chip(int(loaded["cell"]["chips"]), require_chip)
    clock = CompileClock()
    spans = harness.Spans(args.trace)
    tracer = harness.Tracer(args.trace)
    driver = importlib.import_module(
        "benchmark.drivers." + loaded["traffic"]["driver"])
    out = driver.run(loaded, args, devices, spans, tracer, clock,
                     _T_START if t_start is None else t_start, fault=fault)

    with open(os.path.join(loaded["base"], loaded["manifest"]["paths"][0],
                           "limits", args.workload + ".json")) as f:
        limits = json.load(f)
    checks = {k: [float(out["numbers"][k]), float(limits[k])]
              for k in limits}
    correct = harness.checks_ok(checks) and out["failed"] == 0
    if args.trace:
        metrics = harness.read_per_layer(loaded, out["obs"])
    else:
        wanted = {m["name"]: m for m in
                  harness.metrics_for(loaded, "end_to_end")}
        metrics = {k: {"value": float(v), "unit": wanted[k]["unit"]}
                   for k, v in out["end_to_end"].items() if k in wanted}
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": harness.device_block(
                  devices, out["memory_peak"],
                  out["reduced"] if args.trace else None)}
    if args.trace and out["reduced"] is not None:
        result["breakdown"] = {"device_ops": out["reduced"]["device_ops"],
                               "idle_gaps": out["reduced"]["idle_gaps"]}
    return result, checks, list(out.get("tails", [])) + list(out["notes"])


def main(argv=None):
    from . import harness
    args = parse(sys.argv[1:] if argv is None else argv)
    try:
        result, checks, notes = run_cell(args)
    except harness.Refused as e:
        print("benchmark.run: refused: %s" % e, file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    harness.emit(result, checks, notes)
    return 0


if __name__ == "__main__":
    sys.exit(main())
