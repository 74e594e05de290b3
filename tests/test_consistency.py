"""cpu <-> accelerator consistency (ref: tests/python/gpu/
test_operator_gpu.py — re-running op tests on the second backend and
comparing with check_consistency, SURVEY.md §4.2).  On a chip host
(MXTPU_CHIP_TESTS=1) the second backend is the TPU; when only CPU exists,
the tests compare cpu vs cpu(1) (still exercising the machinery)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import check_consistency


def _second_ctx():
    # chip comparisons only in the opt-in serial tier (MXTPU_CHIP_TESTS=1
    # -n 0): a chip belongs to one process, so parallel workers cannot
    # share it
    import os
    if os.environ.get("MXTPU_CHIP_TESTS") == "1":
        import jax
        if any(d.platform != "cpu" for d in jax.local_devices()):
            return mx.tpu(0)
    return mx.cpu(1)


def test_conv_block_consistency():
    sym = mx.sym.Convolution(mx.sym.var("data"), kernel=(3, 3),
                             num_filter=4, pad=(1, 1), name="conv")
    sym = mx.sym.Activation(sym, act_type="relu")
    sym = mx.sym.Pooling(sym, kernel=(2, 2), stride=(2, 2),
                         pool_type="max")
    ctx_list = [
        {"ctx": mx.cpu(0), "data": (2, 3, 8, 8), "type_dict": {}},
        {"ctx": _second_ctx(), "data": (2, 3, 8, 8), "type_dict": {}},
    ]
    check_consistency(sym, ctx_list, tol=2e-2)


def test_fc_softmax_consistency():
    sym = mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=5)
    sym = mx.sym.SoftmaxOutput(sym, name="softmax")
    ctx_list = [
        {"ctx": mx.cpu(0), "data": (4, 7), "type_dict": {}},
        {"ctx": _second_ctx(), "data": (4, 7), "type_dict": {}},
    ]
    check_consistency(sym, ctx_list, tol=2e-2)


def test_batchnorm_consistency():
    sym = mx.sym.BatchNorm(mx.sym.var("data"), name="bn")
    ctx_list = [
        {"ctx": mx.cpu(0), "data": (4, 3, 6, 6), "type_dict": {}},
        {"ctx": _second_ctx(), "data": (4, 3, 6, 6), "type_dict": {}},
    ]
    check_consistency(sym, ctx_list, tol=2e-2)
