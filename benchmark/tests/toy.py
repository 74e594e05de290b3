"""A toy benchmark made the way a later PR adds a cell: new data files and
new manifest entries beside the real ones, no edit to a file that is there.
The real configuration and traffic files are read, shrunk and written under
new names into a scratch directory with its own ``BENCHMARK.json``."""
from __future__ import annotations

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# between what the toy program reads on the CPU (medians under 0.003 and
# 0.0055) and what its fp8 control reads (over 0.013 and 0.011); the planted
# faults read 0.17 and more on all three
TRAIN_LIMITS = {"grad_norm_gap_median": 0.007,
                "update_norm_gap_median": 0.0085,
                "total_update_norm_gap": 0.1}
DECODE_LIMITS = {"logit_gap_max": 1e-6, "length_mismatch": 0}


def _load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def _dump(obj, path):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make(tmp):
    """Write the toy benchmark under ``tmp``; returns its manifest path."""
    tmp = str(tmp)
    real = _load("BENCHMARK.json")
    bench = os.path.join(tmp, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark", "metrics"),
                    os.path.join(bench, "metrics"))

    resnet = _load("benchmark/configs/resnet50-v2-bf16.json")
    resnet["name"] = "toy-resnet"
    resnet["model"].update(num_layers=8, num_classes=10,
                           image_shape=[3, 16, 16])
    _dump(resnet, os.path.join(bench, "configs", "toy-resnet.json"))
    gpt = _load("benchmark/configs/gpt2-small-f32.json")
    gpt["name"] = "toy-gpt"
    gpt["model"].update(vocab_size=64, n_positions=64, n_ctx=64, n_embd=32,
                        n_head=2, n_layer=2, n_inner=128)
    gpt["serving"].update(slots=3, pool_pages=24, page_tokens=8, max_len=64)
    _dump(gpt, os.path.join(bench, "configs", "toy-gpt.json"))

    fit = _load("benchmark/traffic/fit-b256.json")
    fit.update(name="toy-fit", batch=8, ring_batches=4, warmup_steps=5,
               trace_seconds=1)
    _dump(fit, os.path.join(bench, "traffic", "toy-fit.json"))
    dp4 = _load("benchmark/traffic/fit-dp4-b1024.json")
    dp4.update(name="toy-fit-dp4", batch=16, ring_batches=4, warmup_steps=5,
               trace_seconds=1)
    _dump(dp4, os.path.join(bench, "traffic", "toy-fit-dp4.json"))
    dec = _load("benchmark/traffic/decode-closed64.json")
    dec.update(name="toy-decode", requests=64, trace_seconds=1,
               check_requests=24)
    dec["arrivals"].update(clients=3, stagger_iterations=12)
    dec["prompt_tokens"].update(median=6, min=2, max=12)
    dec["output_tokens"].update(min=6, max=14)
    _dump(dec, os.path.join(bench, "traffic", "toy-decode.json"))

    # cells whose data files exist but which the manifest does not hold yet
    # (PERF.md, Open questions) are rehearsed all the same
    cells = {"toy-train": ("toy-resnet", "toy-fit", 1, "resnet50-train-b256"),
             "toy-train-dp4": ("toy-resnet", "toy-fit-dp4", 4,
                               "resnet50-train-dp4"),
             "toy-decode": ("toy-gpt", "toy-decode", 1,
                            "gpt2s-decode-closed64")}
    manifest = dict(real)
    manifest["configs"] = [
        {"name": n, "source": "toy", "file": "benchmark/configs/%s.json" % n,
         "reduced": [], "why": "toy"} for n in ("toy-resnet", "toy-gpt")]
    manifest["workloads"] = [
        {"name": k, "config": c, "traffic": t, "chips": chips, "why": "toy"}
        for k, (c, t, chips, _) in cells.items()]

    def toy_cells(name):
        """The toy cells that report a metric, by the metric's name."""
        if name.startswith("decode_"):
            return ["toy-decode"]
        if "pallas" in name:
            return ["toy-train"]
        if "collective" in name:
            return ["toy-train-dp4"]
        if name.startswith(("fit_", "train_")):
            return ["toy-train", "toy-train-dp4"]
        return None

    # every end-to-end metric the drivers report and every per-layer metric
    # that has a file, whichever cells the real manifest holds today
    manifest["end_to_end"] = []
    for name, unit, better in (
            ("train_samples_per_s", "samples/s", "higher"),
            ("decode_output_tokens_per_s", "tokens/s", "higher"),
            ("decode_ttft_p95_ms", "ms", "lower"),
            ("decode_itl_p95_ms", "ms", "lower"), ("setup_s", "s", "lower")):
        e = {"name": name, "unit": unit, "better": better, "bound": 0.1,
             "source": "host_clock"}
        if toy_cells(name):
            e["workloads"] = toy_cells(name)
        manifest["end_to_end"].append(e)
    manifest["per_layer"] = []
    metrics_dir = os.path.join(ROOT, "benchmark", "metrics")
    for fname in sorted(os.listdir(metrics_dir)):
        spec = _load("benchmark/metrics/" + fname)
        e = {k: spec[k] for k in ("name", "unit", "better", "source", "layer",
                                  "moves")}
        if toy_cells(e["name"]):
            e["workloads"] = toy_cells(e["name"])
        manifest["per_layer"].append(e)
    # a per-layer metric of the toy's own: one new data file, one new reader
    manifest["per_layer"].append(
        {"name": "toy_steps", "unit": "count", "better": "higher",
         "source": "host_clock", "layer": "entry point: Module.fit loop",
         "moves": "train_samples_per_s", "workloads": ["toy-train"]})
    _dump({"name": "toy_steps", "reader": "toy_steps_reader"},
          os.path.join(bench, "metrics", "toy_steps.json"))
    with open(os.path.join(tmp, "toy_steps_reader.py"), "w") as f:
        f.write("def read(obs):\n    return obs.get('steps')\n")
    _dump(manifest, os.path.join(tmp, "BENCHMARK.json"))
    for k in cells:
        _dump(DECODE_LIMITS if k == "toy-decode" else TRAIN_LIMITS,
              os.path.join(bench, "limits", k + ".json"))
    return os.path.join(tmp, "BENCHMARK.json")
