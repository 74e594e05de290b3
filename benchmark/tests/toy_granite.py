"""The Mamba-2 / attention hybrid cell at toy size, made as ``toy_lm.py``
makes the other: the real configuration and traffic files read, shrunk and
written under new names into a scratch directory with a ``BENCHMARK.json`` of
its own."""
from __future__ import annotations

import os
import shutil

from .toy import ROOT, _dump, _load

# between what the toy program reads on the CPU over six seeds (the median
# leaf's gradient 0.00044-0.0009, the worst leaf's 0.0026-0.0049, the median
# leaf's change 0.00026-0.00044) and what the fp8 control (0.0024-0.0034 /
# 0.018-0.046 / 0.0022-0.0028) and the five planted faults read: the skip,
# the gate's place and the residual multiplier move the median leaf's
# gradient (0.015 and more), the conv bias and the attention's scale only
# their own leaves (the worst leaf's gradient 0.53 and more)
LIMITS = {"grad_norm_gap_median": 0.0018, "grad_norm_gap": 0.012,
          "update_norm_gap_median": 0.0015}
CELL, REAL_CELL = "toy-train-ssm", "granite-h-train-s8k-b1"
# two periods of mamba, mamba, attention, shrunk in width; 96 tokens are a
# chunk and a half of the recurrence
TOY_MODEL = dict(
    hidden_size=64, vocab_size=96, num_hidden_layers=6,
    layers_kept=[0, 1, 2, 3, 4, 5],
    layer_types=["mamba", "mamba", "attention"] * 2,
    num_attention_heads=4, num_key_value_heads=2, attention_multiplier=0.0625,
    mamba_n_heads=4, mamba_d_head=8, mamba_d_state=8, mamba_n_groups=1,
    shared_intermediate_size=128, intermediate_size=128)


def make(tmp):
    """Write the toy benchmark under ``tmp``; returns its manifest path."""
    tmp = str(tmp)
    real = _load("BENCHMARK.json")
    bench = os.path.join(tmp, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark", "metrics"),
                    os.path.join(bench, "metrics"))
    cfg = _load("benchmark/configs/granite-4.0-h-micro-vp8-bf16.json")
    cfg["name"] = "toy-granite"
    cfg.update(TOY_MODEL)
    # weights wide enough that a toy's 64-wide products are not all noise
    cfg["init"]["rules"] = [[s, "normal:0.1" if k == "normal:0.02" else k]
                            for s, k in cfg["init"]["rules"]]
    _dump(cfg, os.path.join(bench, "configs", "toy-granite.json"))
    mix = _load("benchmark/traffic/fit-lm-ssm-s8k-b1.json")
    mix.update(name="toy-fit-lm-ssm", batch=2, seq_len=96, warmup_steps=5,
               trace_seconds=1)
    _dump(mix, os.path.join(bench, "traffic", "toy-fit-lm-ssm.json"))
    manifest = dict(real)
    manifest["configs"] = [{"name": "toy-granite", "source": "toy",
                            "file": "benchmark/configs/toy-granite.json",
                            "reduced": cfg["reduced"], "why": "toy"}]
    manifest["workloads"] = [{"name": CELL, "config": "toy-granite",
                              "traffic": "toy-fit-lm-ssm", "chips": 1,
                              "why": "toy"}]
    for group in ("end_to_end", "per_layer"):
        manifest[group] = [
            dict(m, workloads=[CELL]) if "workloads" in m else dict(m)
            for m in real[group]
            if "workloads" not in m or REAL_CELL in m["workloads"]]
    _dump(manifest, os.path.join(tmp, "BENCHMARK.json"))
    _dump(LIMITS, os.path.join(bench, "limits", CELL + ".json"))
    return os.path.join(tmp, "BENCHMARK.json")
