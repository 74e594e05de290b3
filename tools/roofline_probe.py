"""Kernel-class roofline decomposition of the ResNet-50 bf16 train step.

Answers the round-4 verdict's MFU question with profiler evidence
instead of a hand-waved "bandwidth-bound": capture a device trace of
the fused training loop, aggregate kernel time per HLO class, and
report

  mxu_share        fraction of device step time inside convolution/dot
                   kernels (the only kernels doing MXU FLOPs)
  mem_share        fraction in everything else (fusions, reduces,
                   copies/layout, select-and-scatter, ...) — memory-
                   system-bound kernel classes by construction
  conv_tflops      the FLOP rate achieved INSIDE the conv kernels
  mfu_ceiling      step MFU if the memory-class time were zero
                   (= measured_mfu / mxu_share)

If mfu_ceiling is far above the measured MFU while conv_tflops sits
near the chip's practical conv peak, the step's MFU is capped by the
memory-class kernel time — the roofline claim, kernel-by-kernel.

Usage: python tools/roofline_probe.py [--iters 30]
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def capture_trace(iters):
    """The EXACT training loop bench.py times (one shared
    construction, bench.build_resnet_train_loop), run under the
    profiler."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    import bench

    rng = np.random.RandomState(0)
    ctx = mx.context.accelerator()
    loop, params0, mom0, aux0, flops, _ = bench.build_resnet_train_loop(
        mx, jax, ctx, rng, compute_dtype=jnp.bfloat16)

    float(loop(2, params0, mom0, aux0))  # warm/compile
    hlo = jax.jit(loop).lower(2, params0, mom0, aux0).compile().as_text()
    logdir = tempfile.mkdtemp(prefix="roofline_")
    jax.profiler.start_trace(logdir)
    float(loop(iters, params0, mom0, aux0))
    jax.profiler.stop_trace()
    return logdir, flops, hlo


def parse_device_events(logdir):
    """Leaf kernel events: the device process's "XLA Ops" lane only
    (the Steps/Modules lanes and host lanes are containers/controls
    that would double-count)."""
    paths = glob.glob(os.path.join(logdir, "**", "*.trace.json.gz"),
                      recursive=True)
    assert paths, "no trace.json.gz under %s" % logdir
    with gzip.open(paths[0], "rt") as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    dev_pids = {e["pid"] for e in events
                if e.get("ph") == "M" and e.get("name") == "process_name"
                and "/device:" in str(e.get("args", {}).get("name", ""))}
    op_lanes = {(e["pid"], e["tid"]) for e in events
                if e.get("ph") == "M" and e.get("name") == "thread_name"
                and e["pid"] in dev_pids
                and e.get("args", {}).get("name") == "XLA Ops"}
    out = []
    for e in events:
        if e.get("ph") == "X" and (e.get("pid"), e.get("tid")) in op_lanes:
            name = e.get("name", "")
            if name.startswith(("while", "jit_", "body")) \
                    or name.isdigit():
                continue  # control/region containers inside the op lane
            out.append((name, float(e.get("dur", 0.0))))
    return out


def mxu_kernels_from_hlo(hlo):
    """Kernel (instruction) names whose fused computation contains a
    convolution or dot — the MXU-work carriers.  Parsed from the
    optimized HLO text: fusion instructions reference their computation
    via calls=..., and the computation bodies are in the same dump."""
    import re
    # computation name -> body text
    comps = {}
    cur, buf = None, []
    for line in hlo.splitlines():
        m = re.match(r"\s*(%?[\w\.\-]+)\s+\([^)]*\)\s*->.*{", line)
        if line.strip().endswith("{") and ("fused_computation" in line
                                           or "computation" in line
                                           or line.lstrip().startswith("%")):
            if cur is not None:
                comps[cur] = "\n".join(buf)
            name = line.strip().split()[0].lstrip("%")
            cur, buf = name, []
            continue
        if line.strip() == "}" and cur is not None:
            comps[cur] = "\n".join(buf)
            cur, buf = None, []
            continue
        if cur is not None:
            buf.append(line)

    def has_mxu(text):
        return " convolution(" in text or " dot(" in text \
            or "= convolution" in text or "= dot" in text

    mxu = set()
    # direct (unfused) conv/dot instructions keep their own kernel name
    for m in re.finditer(r"%?([\w\.\-]+)\s*=\s*[\w\[\],{}\s]*"
                         r"(convolution|dot)\(", hlo):
        mxu.add(m.group(1))
    # fusions calling an MXU-bearing computation
    for m in re.finditer(r"%?([\w\.\-]+)\s*=\s*\S+\s+fusion\([^\n]*?"
                         r"calls=%?([\w\.\-]+)", hlo):
        kern, comp = m.group(1), m.group(2)
        if has_mxu(comps.get(comp, "")):
            mxu.add(kern)
    return mxu


def classify(name, mxu_set):
    low = name.lower()
    base = name.split("/")[-1]
    if base in mxu_set or low.startswith(("convolution", "dot")) \
            or "conv" in low.split(".")[0]:
        return "mxu"
    if "copy" in low or "transpose" in low or "bitcast" in low:
        return "copy"
    if "reduce" in low or "scatter" in low:
        return "reduce"
    if "fusion" in low or "loop" in low:
        return "fusion"
    return "other"


def kernel_family(name):
    """Kernel-family key for cross-round attribution: kernel (HLO
    instruction) numbering is compilation-specific, so rounds are
    compared on the name with its trailing instance number stripped
    (select-and-scatter.11 -> select-and-scatter; convert_reduce_fusion.191
    -> convert_reduce_fusion).  Truncate to the report's 60-char key
    width FIRST so a full current name and its stored (already
    truncated, possibly mid-suffix) previous key canonicalize the same
    way."""
    import re
    return re.sub(r"\.\d*$", "", name.split("/")[-1][:60])


def previous_report(baseline):
    """The round-of-record to diff against: an explicit --baseline path,
    or the newest ROOFLINE_r*.json in the repo root."""
    if baseline == "none":
        return None, None
    if baseline != "auto":
        with open(baseline) as f:
            return json.load(f), baseline
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = sorted(glob.glob(os.path.join(root, "ROOFLINE_r*.json")))
    if not paths:
        return None, None
    with open(paths[-1]) as f:
        return json.load(f), paths[-1]


def attribute_deltas(report, by_name, iters, prev, threshold_us=20.0):
    """Per-kernel-class attribution vs the previous round (the ISSUE-7
    satellite: every future perf PR gets automatic attribution).  Diffs
    ``class_shares`` per class and us/step per kernel FAMILY (families
    present in either round; the previous round contributes its recorded
    top list), and splits families into wins (freed us/step) and
    regressions."""
    share_delta = {}
    classes = set(report["class_shares"]) | set(prev.get("class_shares",
                                                         {}))
    for c in sorted(classes):
        share_delta[c] = round(report["class_shares"].get(c, 0.0)
                               - prev.get("class_shares", {}).get(c, 0.0),
                               3)
    cur_fam, prev_fam = {}, {}
    for name, dur in by_name.items():
        f = kernel_family(name)
        cur_fam[f] = cur_fam.get(f, 0.0) + dur / iters
    for name, us in prev.get("top_kernels_us_per_step", {}).items():
        f = kernel_family(name)
        prev_fam[f] = prev_fam.get(f, 0.0) + float(us)
    fam_delta = {}
    for f in set(cur_fam) | set(prev_fam):
        fam_delta[f] = round(cur_fam.get(f, 0.0) - prev_fam.get(f, 0.0), 1)
    wins = {f: d for f, d in fam_delta.items() if d <= -threshold_us}
    regress = {f: d for f, d in fam_delta.items() if d >= threshold_us}
    return {
        "device_step_ms_delta": round(
            report["device_step_ms"] - prev.get("device_step_ms", 0.0), 3),
        "device_mfu_delta": round(
            report["device_mfu"] - prev.get("device_mfu", 0.0), 3),
        "class_share_delta": share_delta,
        "kernel_family_us_delta": dict(
            sorted(fam_delta.items(), key=lambda kv: kv[1])),
        "wins_us_per_step": dict(sorted(wins.items(),
                                        key=lambda kv: kv[1])),
        "regressions_us_per_step": dict(sorted(regress.items(),
                                               key=lambda kv: -kv[1])),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--baseline", default="auto",
                    help="previous ROOFLINE_*.json to attribute deltas "
                         "against: a path, 'auto' (newest in the repo "
                         "root, default), or 'none'")
    args = ap.parse_args()
    import jax

    logdir, flops, hlo = capture_trace(args.iters)
    mxu_set = mxu_kernels_from_hlo(hlo)
    events = parse_device_events(logdir)
    by_class, by_name = {}, {}
    for name, dur in events:
        c = classify(name, mxu_set)
        by_class[c] = by_class.get(c, 0.0) + dur
        by_name[name] = by_name.get(name, 0.0) + dur
    total = sum(by_class.values())
    assert total > 0, "no device events captured"
    mxu_t = by_class.get("mxu", 0.0)
    peak = 197e12
    step_us = total / args.iters
    conv_tflops = flops / (mxu_t / args.iters * 1e-6) / 1e12 \
        if mxu_t else 0.0
    measured_mfu = flops / (step_us * 1e-6) / peak
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:12]
    report = {
        "metric": "train_step_roofline",
        "device_step_ms": round(step_us / 1e3, 3),
        "mxu_share": round(mxu_t / total, 3),
        "class_shares": {k: round(v / total, 3)
                         for k, v in sorted(by_class.items())},
        "conv_kernel_tflops": round(conv_tflops, 1),
        "conv_kernel_mfu": round(conv_tflops * 1e12 / peak, 3),
        "device_mfu": round(measured_mfu, 3),
        "mfu_ceiling_if_mem_free": round(
            measured_mfu / max(mxu_t / total, 1e-9), 3),
        "top_kernels_us_per_step": {
            n[:60]: round(d / args.iters, 1) for n, d in top},
    }
    prev, prev_path = previous_report(args.baseline)
    if prev is not None:
        report["vs_previous"] = dict(
            {"baseline": os.path.basename(prev_path)},
            **attribute_deltas(report, by_name, args.iters, prev))
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
