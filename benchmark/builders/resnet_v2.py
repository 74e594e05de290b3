"""resnet50-v2-bf16 and its kin -> the program's objects."""
from __future__ import annotations


def symbol(cfg):
    from mxnet_tpu import models
    m = cfg["model"]
    return models.resnet.get_symbol(
        num_classes=m["num_classes"], num_layers=m["num_layers"],
        image_shape=",".join(str(d) for d in m["image_shape"]),
        dtype=cfg["precision"]["compute"])
