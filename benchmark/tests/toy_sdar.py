"""The block-diffusion language-model cell at toy size, made as
``toy_trinity.py`` makes the windowed one: the real configuration and traffic
files read, shrunk and written under new names into a scratch directory with
a ``BENCHMARK.json`` of its own."""
from __future__ import annotations

import os
import shutil

from .toy import ROOT, _dump, _load

# between what the toy program reads on the CPU over four seeds (medians
# 0.0011-0.0038 / 0.0003-0.0012, the whole change under 0.0008; the bfloat16
# witness 0.0005-0.0020 / 0.0002-0.0007) and what the fp8 control
# (0.019-0.031 / 0.0044-0.0078) and the four planted faults (the gradient's
# median 0.0245-0.65) read; the ring must be the reference's own noising
LIMITS = {"grad_norm_gap_median": 0.007, "update_norm_gap_median": 0.003,
          "total_update_norm_gap": 0.01, "noise_gap": 0.0}
CELL, REAL_CELL = "toy-train-bd", "sdar-train-bd4-s8k-b1"
# two expert layers; 48 clean tokens in blocks of 4 are 96 positions
TOY_MODEL = dict(
    hidden_size=64, vocab_size=97, num_hidden_layers=2,
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
    cfg = _load("benchmark/configs/sdar-30b-a3b-ep8-bf16.json")
    cfg["name"] = "toy-sdar"
    cfg.update(TOY_MODEL)
    # weights wide enough that a toy's 64-wide products are not all noise
    cfg["init"]["rules"] = [[s, "normal:0.1" if k == "normal:0.02" else k]
                            for s, k in cfg["init"]["rules"]]
    _dump(cfg, os.path.join(bench, "configs", "toy-sdar.json"))
    mix = _load("benchmark/traffic/fit-lm-bd4-s8k-b1.json")
    mix.update(name="toy-fit-lm-bd", batch=2, seq_len=48, warmup_steps=5,
               trace_seconds=1)
    _dump(mix, os.path.join(bench, "traffic", "toy-fit-lm-bd.json"))
    manifest = dict(real)
    manifest["configs"] = [{"name": "toy-sdar", "source": "toy",
                            "file": "benchmark/configs/toy-sdar.json",
                            "reduced": cfg["reduced"], "why": "toy"}]
    manifest["workloads"] = [{"name": CELL, "config": "toy-sdar",
                              "traffic": "toy-fit-lm-bd", "chips": 1,
                              "why": "toy"}]
    for group in ("end_to_end", "per_layer"):
        manifest[group] = [
            dict(m, workloads=[CELL]) if "workloads" in m else dict(m)
            for m in real[group]
            if "workloads" not in m or REAL_CELL in m["workloads"]]
    _dump(manifest, os.path.join(tmp, "BENCHMARK.json"))
    _dump(LIMITS, os.path.join(bench, "limits", CELL + ".json"))
    return os.path.join(tmp, "BENCHMARK.json")
