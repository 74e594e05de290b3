"""``shapes_ssm``: ``shapes_window``'s count plus ``4 * N * P`` a token and
value head forward for the ``ssd`` node, and the state-space kernels' work
pinned by hand for one small shape and for the cell's."""
import pytest

from mxnet_tpu import models

from benchmark import shapes, shapes_ssm, shapes_window
from benchmark.tests import toy_granite
from benchmark.tests.toy import _load

SHAPE = {"data": (2, 96), "softmax_label": (2, 96)}


def _toy():
    cfg = _load("benchmark/configs/granite-4.0-h-micro-vp8-bf16.json")
    cfg.update(toy_granite.TOY_MODEL)
    return models.granite_hybrid.get_symbol(cfg), cfg


def test_the_recurrence_is_counted_beside_shapes_window():
    sym, cfg = _toy()
    # four ssd nodes of 4 heads of P = 8, N = 8, over 2 x 96 tokens
    ssd = 4 * (4.0 * 2 * 96 * 4 * 8 * 8)
    assert shapes_ssm.train_flops(sym, cfg, **SHAPE) == pytest.approx(
        shapes_window.train_flops(sym, cfg, **SHAPE) + 3 * ssd)


def test_the_kernels_work_by_hand():
    sym, cfg = _toy()
    fwd = shapes_ssm.ssd_scan_forward_work(sym, 2, **SHAPE)
    bwd = shapes_ssm.ssd_scan_backward_work(sym, 2, **SHAPE)
    per_call = 4.0 * 2 * 96 * 4 * 8 * 8
    # x and y 2 x 96 x 32, B and C 2 x 96 x 8, bfloat16; the decay 2 x 96 x 4
    # float32; states: 2 chunks of 64 (the tail padded) x 8 x 32
    operands = 2 * (2 * 2 * 96 * 32) + 2 * (2 * 2 * 96 * 8) + 4 * 2 * 96 * 4
    states = 2 * 2 * 2 * 8 * 32
    # every node is mirrored: two forward calls a step, the states once
    assert fwd == {"flops": 4 * 2 * per_call,
                   "bytes": 4 * (2 * operands + states)}
    assert bwd == {"flops": 4 * 2 * per_call,
                   "bytes": 4 * (2 * operands + states)}


def test_the_cells_own_counts():
    """The real configuration at 8,192 tokens: 2.10 MFLOP a token a layer
    in the recurrence, nine layers; 134 MB of states a layer."""
    cfg = _load("benchmark/configs/granite-4.0-h-micro-vp8-bf16.json")
    sym = models.granite_hybrid.get_symbol(cfg, dtype="bfloat16")
    shape = {"data": (1, 8192), "softmax_label": (1, 8192)}
    at = shapes.symbol_shapes(sym, **shape)
    nodes = shapes_ssm._ssd_nodes(sym, at)
    assert len(nodes) == 9 and all(n[3] for n in nodes)
    assert nodes[0][:3] == ((1, 8192, 64, 64), (1, 8192, 1, 128), 64)
    assert shapes_ssm.ssd_forward_flops(1, 8192, 64, 64, 128) \
        == 4 * 128 * 64 * 64 * 8192
    assert shapes_ssm._state_bytes(nodes[0][0], nodes[0][1], 64, 2) \
        == 134217728
    total = shapes_ssm.train_flops(sym, cfg, **shape)
    # 3 x 13.07 TFLOP: the dense products, the attention and the recurrence
    assert 39.0e12 < total < 39.4e12
