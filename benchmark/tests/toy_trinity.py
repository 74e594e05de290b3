"""The windowed language-model cell at toy size, made as ``toy_lm.py`` makes
the other: the real configuration and traffic files read, shrunk and written
under new names into a scratch directory with a ``BENCHMARK.json`` of its
own."""
from __future__ import annotations

import os
import shutil

from .toy import ROOT, _dump, _load

# between what the toy program reads on the CPU over four seeds (medians
# 0.0029-0.0039 / 0.0016-0.0018, the whole change under 0.0012) and what the
# fp8 control (0.0100-0.0132 / 0.0048-0.0050) and the six planted faults
# (the gradient's median 0.0101-0.059) read.  Each half of a block ends in a
# norm, which takes a wrong scale out again: the faults show in the median
# leaf's gradient, hardly in the whole change
LIMITS = {"grad_norm_gap_median": 0.007, "update_norm_gap_median": 0.003,
          "total_update_norm_gap": 0.01}
CELL, REAL_CELL = "toy-train-swa", "trinity-mini-train-s8k-b1"
# one dense layer, then window, window, full; 96 tokens are three windows
TOY_MODEL = dict(
    hidden_size=64, vocab_size=96, num_hidden_layers=4, num_dense_layers=1,
    layers_kept=[1, 4, 6, 7], sliding_window=32, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_experts=4, router_num_experts=16, first_expert=4,
    num_experts_per_tok=3, moe_intermediate_size=32)


def make(tmp):
    """Write the toy benchmark under ``tmp``; returns its manifest path."""
    tmp = str(tmp)
    real = _load("BENCHMARK.json")
    bench = os.path.join(tmp, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark", "metrics"),
                    os.path.join(bench, "metrics"))
    cfg = _load("benchmark/configs/trinity-mini-26b-a3b-ep8-bf16.json")
    cfg["name"] = "toy-trinity"
    cfg.update(TOY_MODEL)
    # weights wide enough that a toy's 64-wide products are not all noise
    cfg["init"]["rules"] = [[s, "normal:0.1" if k == "normal:0.02" else k]
                            for s, k in cfg["init"]["rules"]]
    _dump(cfg, os.path.join(bench, "configs", "toy-trinity.json"))
    mix = _load("benchmark/traffic/fit-lm-swa-s8k-b1.json")
    mix.update(name="toy-fit-lm-swa", batch=2, seq_len=96, warmup_steps=5,
               trace_seconds=1)
    _dump(mix, os.path.join(bench, "traffic", "toy-fit-lm-swa.json"))
    manifest = dict(real)
    manifest["configs"] = [{"name": "toy-trinity", "source": "toy",
                            "file": "benchmark/configs/toy-trinity.json",
                            "reduced": cfg["reduced"], "why": "toy"}]
    manifest["workloads"] = [{"name": CELL, "config": "toy-trinity",
                              "traffic": "toy-fit-lm-swa", "chips": 1,
                              "why": "toy"}]
    for group in ("end_to_end", "per_layer"):
        manifest[group] = [
            dict(m, workloads=[CELL]) if "workloads" in m else dict(m)
            for m in real[group]
            if "workloads" not in m or REAL_CELL in m["workloads"]]
    _dump(manifest, os.path.join(tmp, "BENCHMARK.json"))
    _dump(LIMITS, os.path.join(bench, "limits", CELL + ".json"))
    return os.path.join(tmp, "BENCHMARK.json")
