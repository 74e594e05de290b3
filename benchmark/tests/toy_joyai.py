"""The latent-attention language-model cell at toy size, made as
``toy_trinity.py`` makes the windowed one: the real configuration and traffic
files read, shrunk and written under new names into a scratch directory with
a ``BENCHMARK.json`` of its own."""
from __future__ import annotations

import os
import shutil

from .toy import ROOT, _dump, _load

# between what the toy program reads on the CPU over four seeds (medians
# 0.0005-0.0025 / 0.0006-0.0014, the whole change under 0.0003; the bfloat16
# witness 0.0012 / 0.0007) and what the fp8 control (0.0109-0.0114 /
# 0.0038-0.0042) and four of the five planted faults (the gradient's median
# 0.0100-0.046) read; the fifth moves one leaf of sixty and no median
LIMITS = {"grad_norm_gap_median": 0.007, "update_norm_gap_median": 0.003,
          "total_update_norm_gap": 0.01}
CELL, REAL_CELL = "toy-train-mla", "joyai-flash-train-s8k-b1"
# one dense block, two expert blocks and the prediction module's; keys of 24
# (16 + 8 shared) on values of 12
TOY_MODEL = dict(
    hidden_size=64, vocab_size=96, num_hidden_layers=3, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24,
    v_head_dim=12, head_dim=8, n_routed_experts=4, router_num_experts=16,
    first_expert=4, num_experts_per_tok=3, moe_intermediate_size=32)


def make(tmp):
    """Write the toy benchmark under ``tmp``; returns its manifest path."""
    tmp = str(tmp)
    real = _load("BENCHMARK.json")
    bench = os.path.join(tmp, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark", "metrics"),
                    os.path.join(bench, "metrics"))
    cfg = _load("benchmark/configs/joyai-llm-flash-48b-ep16-bf16.json")
    cfg["name"] = "toy-joyai"
    cfg.update(TOY_MODEL)
    # weights wide enough that a toy's 64-wide products are not all noise
    cfg["init"]["rules"] = [[s, "normal:0.1" if k == "normal:0.02" else k]
                            for s, k in cfg["init"]["rules"]]
    _dump(cfg, os.path.join(bench, "configs", "toy-joyai.json"))
    mix = _load("benchmark/traffic/fit-lm-mla-s8k-b1.json")
    mix.update(name="toy-fit-lm-mla", batch=2, seq_len=96, warmup_steps=5,
               trace_seconds=1)
    _dump(mix, os.path.join(bench, "traffic", "toy-fit-lm-mla.json"))
    manifest = dict(real)
    manifest["configs"] = [{"name": "toy-joyai", "source": "toy",
                            "file": "benchmark/configs/toy-joyai.json",
                            "reduced": cfg["reduced"], "why": "toy"}]
    manifest["workloads"] = [{"name": CELL, "config": "toy-joyai",
                              "traffic": "toy-fit-lm-mla", "chips": 1,
                              "why": "toy"}]
    for group in ("end_to_end", "per_layer"):
        manifest[group] = [
            dict(m, workloads=[CELL]) if "workloads" in m else dict(m)
            for m in real[group]
            if "workloads" not in m or REAL_CELL in m["workloads"]]
    _dump(manifest, os.path.join(tmp, "BENCHMARK.json"))
    _dump(LIMITS, os.path.join(bench, "limits", CELL + ".json"))
    return os.path.join(tmp, "BENCHMARK.json")
