"""``shapes_mla``: equal to ``shapes_window`` where the widths are equal, a
pair at ``2 * d_qk + 2 * d_v`` where they are not (640 forward and 1,664
backward at 192 / 128), and the shared key part moved once."""
import pytest

from mxnet_tpu import models

from benchmark import shapes, shapes_mla, shapes_window
from benchmark.tests import toy_joyai, toy_trinity
from benchmark.tests.toy import _load

SHAPE = {"data": (2, 96), "softmax_label": (2, 96)}


def _joyai(**changed):
    cfg = _load("benchmark/configs/joyai-llm-flash-48b-ep16-bf16.json")
    cfg.update(toy_joyai.TOY_MODEL)
    cfg.update(changed)
    return models.joyai_flash.get_symbol(cfg), cfg


def _trinity():
    cfg = _load("benchmark/configs/trinity-mini-26b-a3b-ep8-bf16.json")
    cfg.update(toy_trinity.TOY_MODEL)
    return models.trinity.get_symbol(cfg), cfg


def test_equal_widths_read_what_shapes_window_reads():
    sym, cfg = _trinity()
    assert shapes_mla.train_flops(sym, cfg, **SHAPE) \
        == shapes_window.train_flops(sym, cfg, **SHAPE)
    mine = shapes_mla.flash_forward_work(sym, 2, **SHAPE)
    theirs = shapes_window.flash_forward_work(sym, 2, **SHAPE)
    assert mine == pytest.approx(theirs)


def test_a_pair_costs_its_two_widths():
    sym, cfg = _joyai(qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128)
    at = shapes.symbol_shapes(sym, **SHAPE)
    nodes = shapes_mla.attention_nodes(sym, at)
    # three blocks and the module's, all mirrored, all with the shared part
    assert len(nodes) == 4 and all(n["mirrored"] and n["shared"] == 64
                                   for n in nodes)
    assert nodes[0]["q"] == (2, 96, 4, 192) and nodes[0]["k"] == (2, 96, 4, 128)
    pairs = 2 * 4 * (96 * 97 // 2)
    assert shapes_mla.attention_forward_flops(nodes[0]) == 640 * pairs
    assert shapes_mla.attention_backward_flops(nodes[0]) == 1664 * pairs
    fwd = shapes_mla.flash_forward_work(sym, 2, **SHAPE)
    bwd = shapes_mla.flash_backward_work(sym, 2, **SHAPE)
    assert fwd["flops"] == 4 * 2 * 640 * pairs      # forward twice a stage
    assert bwd["flops"] == 4 * 1664 * pairs
    # q 192, k 128, v 128 and the output 128 a head, the shared 64 once
    once = 2 * 2 * 96 * (4 * (192 + 128 + 128 + 128) + 64)
    assert fwd["bytes"] == 4 * 2 * once and bwd["bytes"] == 4 * 2 * once
    # the whole count: shapes_window's with 4 * 192 a pair put right
    over = 3 * 4 * (4 * 192 - 640) * pairs
    assert shapes_mla.train_flops(sym, cfg, **SHAPE) == pytest.approx(
        shapes_window.train_flops(sym, cfg, **SHAPE) - over)


def test_the_cells_own_counts():
    """The real configuration at the cell's shape: 0.687 TFLOP a layer
    forward in attention, six calls."""
    cfg = _load("benchmark/configs/joyai-llm-flash-48b-ep16-bf16.json")
    sym = models.joyai_flash.get_symbol(cfg, dtype="bfloat16")
    shape = {"data": (1, 8192), "softmax_label": (1, 8192)}
    at = shapes.symbol_shapes(sym, **shape)
    nodes = shapes_mla.attention_nodes(sym, at)
    assert len(nodes) == 6
    assert shapes_mla.attention_forward_flops(nodes[0]) \
        == 640 * 32 * (8192 * 8193 // 2)
    total = sum(int(__import__("numpy").prod(s)) for n, s in at.items()
                if n in sym.list_arguments() and n not in shape)
    assert total == 680441088
