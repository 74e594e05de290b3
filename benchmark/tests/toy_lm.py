"""The language-model cell at toy size, made as ``toy.py`` makes the others:
the real configuration and traffic files read, shrunk and written under new
names into a scratch directory with a ``BENCHMARK.json`` of its own."""
from __future__ import annotations

import os
import shutil

from .toy import ROOT, _dump, _load

# between what the toy program reads on the CPU over its seeds (medians under
# 0.011 / 0.006, the whole change under 0.002) and what the fp8 control and
# the four planted faults read
LIMITS = {"grad_norm_gap_median": 0.03, "update_norm_gap_median": 0.02,
          "total_update_norm_gap": 0.01}
TOY_MODEL = dict(
    hidden_size=64, vocab_size=96, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, num_experts=4, router_num_experts=16,
    first_expert=4, num_experts_per_tok=3, moe_intermediate_size=32,
    shared_expert_intermediate_size=32)


def make(tmp):
    """Write the toy benchmark under ``tmp``; returns its manifest path."""
    tmp = str(tmp)
    real = _load("BENCHMARK.json")
    bench = os.path.join(tmp, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark", "metrics"),
                    os.path.join(bench, "metrics"))
    cfg = _load("benchmark/configs/qwen3-next-80b-a3b-ep16-bf16.json")
    cfg["name"] = "toy-qwen3next"
    cfg.update(TOY_MODEL)
    # weights wide enough that a toy's 64-wide products are not all noise
    cfg["init"]["rules"] = [[s, "normal:0.1" if k == "normal:0.02" else k]
                            for s, k in cfg["init"]["rules"]]
    _dump(cfg, os.path.join(bench, "configs", "toy-qwen3next.json"))
    mix = _load("benchmark/traffic/fit-lm-s8k-b2.json")
    mix.update(name="toy-fit-lm", seq_len=96, warmup_steps=5, trace_seconds=1)
    _dump(mix, os.path.join(bench, "traffic", "toy-fit-lm.json"))
    cell = "toy-train-lm"
    manifest = dict(real)
    manifest["configs"] = [{"name": "toy-qwen3next", "source": "toy",
                            "file": "benchmark/configs/toy-qwen3next.json",
                            "reduced": cfg["reduced"], "why": "toy"}]
    manifest["workloads"] = [{"name": cell, "config": "toy-qwen3next",
                              "traffic": "toy-fit-lm", "chips": 1,
                              "why": "toy"}]
    real_cell = "qwen3next-train-s8k-b2"
    for group in ("end_to_end", "per_layer"):
        manifest[group] = [
            dict(m, workloads=[cell]) if "workloads" in m else dict(m)
            for m in real[group]
            if "workloads" not in m or real_cell in m["workloads"]]
    _dump(manifest, os.path.join(tmp, "BENCHMARK.json"))
    _dump(LIMITS, os.path.join(bench, "limits", cell + ".json"))
    return os.path.join(tmp, "BENCHMARK.json")
