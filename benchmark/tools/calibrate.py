"""Read the two ends a limit is set between, on the chip, at a cell's own
size: ``python3 -m benchmark.tools.calibrate --workload <cell> --seeds a,b,c
--seconds <s> [--controls N]``.

For every seed: the PROGRAM's numbers (a short run of the cell through the
same driver the benchmark uses: the lower reading is their largest).  For
the first N seeds also the CONTROL (the plain reference recomputed in the
precision below the configuration's, put in the program's place) and, for a
training cell, the planted fault that leaves half the batch out and takes
the mean over the rest.  One JSON line per seed; nothing is compared with a
limit here."""
from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--set", action="append", default=[],
                    help="diagnosis only: config.a.b=json or traffic.a=json")
    ap.add_argument("--program-only", action="store_true")
    ap.add_argument("--only", default="",
                    help="training: read only this one of control_fp8, "
                         "reference_bf16, fault_half_batch")
    a = ap.parse_args(argv)

    import importlib
    from .. import compare, harness
    from ..compile_clock import CompileClock
    from ..references import lowprec
    loaded = harness.load_cell(a.workload)
    harness.use_compile_cache()
    devices = harness.find_chip(int(loaded["cell"]["chips"]))
    clock = CompileClock()
    for item in a.set:
        path, value = item.split("=", 1)
        node = loaded
        keys = path.split(".")
        for key in keys[:-1]:
            node = node[key]
        node[keys[-1]] = json.loads(value)
    cfg, mix = loaded["config"], loaded["traffic"]
    driver = importlib.import_module("benchmark.drivers." + mix["driver"])
    for i, seed in enumerate(int(s) for s in a.seeds.split(",")):
        args = argparse.Namespace(workload=a.workload, seed=seed,
                                  seconds=a.seconds, trace=0)
        out = driver.run(loaded, args, devices, harness.Spans(False),
                         harness.Tracer(False), clock, time.perf_counter(),
                         check_it=not a.program_only)
        row = {"seed": seed, "program": out["numbers"],
               "end_to_end": out["end_to_end"], "notes": out["notes"],
               "memory_peak_gb": out["memory_peak"] / 1e9}
        if a.program_only:
            print(json.dumps(row), flush=True)
            continue
        if i < a.controls and mix["driver"] == "fit":
            w0, ring_x, ring_y = out["inputs"]
            r = out["refs"]
            for name, kw in (
                    ("control_fp8", {"hooks": (lowprec.q_operand,
                                               lowprec.q_cotangent)}),
                    ("reference_bf16", {"hooks": (lowprec.bf16,
                                                  lowprec.bf16)}),
                    ("fault_half_batch", {"rows": int(mix["batch"]) // 2})):
                if a.only and name != a.only:
                    continue
                losses, g, d = driver.reference_norms(
                    cfg, mix, devices, w0, ring_x, ring_y, r["names"], **kw)
                row[name], _ = compare.training_numbers(
                    losses, r["losses"], g, r["grad_norms"], d,
                    r["update_norms"])
        if i < a.controls and mix["driver"] == "decode":
            served, control = driver.served_gaps(
                cfg, mix, out["weights"], out["sample"], lowprec.q_operand)
            row["control_fp8"] = {"logit_gap_max": float(control.max()),
                                  "tokens": int(len(control))}
            _, control16 = driver.served_gaps(
                cfg, mix, out["weights"], out["sample"], lowprec.bf16)
            row["control_bf16"] = {"logit_gap_max": float(control16.max())}
        del out
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
