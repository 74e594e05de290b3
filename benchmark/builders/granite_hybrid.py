"""granite-4.0-h-micro-vp8-bf16 and its kin -> the program's objects."""
from __future__ import annotations


def symbol(cfg):
    from mxnet_tpu import models
    from .. import harness
    if not hasattr(models, "granite_hybrid"):
        # a checkout from before the model (the parent of the change that
        # added the cell): say so at once instead of failing somewhere inside
        raise harness.Refused("this checkout's mxnet_tpu has no "
                              "models.granite_hybrid: it cannot run %s"
                              % cfg["name"])
    return models.granite_hybrid.get_symbol(
        cfg, dtype=cfg["precision"]["compute"])
