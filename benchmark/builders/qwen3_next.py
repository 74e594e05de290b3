"""qwen3-next-80b-a3b-ep16-bf16 and its kin -> the program's objects."""
from __future__ import annotations


def symbol(cfg):
    from mxnet_tpu import models
    return models.qwen3_next.get_symbol(cfg, dtype=cfg["precision"]["compute"])
