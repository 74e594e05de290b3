"""``shapes_bd``: the block-diffusion mask's visible pairs by its closed form
and by a count over the mask written out, and ``shapes_mla``'s count
wherever no node has the mask."""
import numpy as np
import pytest

from mxnet_tpu import models

from benchmark import shapes, shapes_bd, shapes_mla
from benchmark.tests import toy_sdar, toy_trinity
from benchmark.tests.toy import _load

SHAPE = {"data": (2, 96), "softmax_label": (2, 96)}


def _explicit(seq, block):
    """The mask written out over ``seq`` positions, counted."""
    half = seq // 2
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    qb, kb = (i % half) // block, (j % half) // block
    seen = ((i < half) & (j < half) & (qb == kb)) \
        | ((i < half) & (j >= half) & (kb < qb)) \
        | ((i >= half) & (j >= half) & (kb <= qb))
    return float(seen.sum())


@pytest.mark.parametrize("length,block", [(48, 4), (20, 4), (30, 4),
                                          (21, 3)])
def test_visible_pairs(length, block):
    got = shapes_bd.bd_visible_pairs(2 * length, block)
    assert got == _explicit(2 * length, block)
    if length % block == 0:
        n = length // block
        assert got == block * block * n * n + length * block


def _sdar(**changed):
    cfg = _load("benchmark/configs/sdar-30b-a3b-ep8-bf16.json")
    cfg.update(toy_sdar.TOY_MODEL)
    cfg.update(changed)
    return models.sdar.get_symbol(cfg), cfg


def test_a_node_counts_its_masks_pairs():
    sym, cfg = _sdar(head_dim=128)
    at = shapes.symbol_shapes(sym, **SHAPE)
    nodes = shapes_bd.attention_nodes(sym, at)
    assert len(nodes) == 2 and all(n["mirrored"] and n["block"] == 4
                                   for n in nodes)
    pairs = 2 * 4 * 2496.0                  # batch x heads x the mask's
    assert shapes_bd.attention_forward_flops(nodes[0]) == 512 * pairs
    assert shapes_bd.attention_backward_flops(nodes[0]) == 1280 * pairs
    fwd = shapes_bd.flash_forward_work(sym, 2, **SHAPE)
    bwd = shapes_bd.flash_backward_work(sym, 2, **SHAPE)
    assert fwd["flops"] == 2 * 2 * 512 * pairs      # forward twice a stage
    assert bwd["flops"] == 2 * 1280 * pairs
    # q, k, v and the output: 4 + 2 + 2 + 4 heads of 128, 96 positions
    once = 2 * 2 * 96 * 128 * (4 + 2 + 2 + 4)
    assert fwd["bytes"] == 2 * 2 * once and bwd["bytes"] == 2 * 2 * once
    # the whole count: shapes_mla's, all 96 x 96 pairs put right
    over = 3 * 2 * 512 * (2 * 4 * 96 * 96 - pairs)
    assert shapes_bd.train_flops(sym, cfg, **SHAPE) == pytest.approx(
        shapes_mla.train_flops(sym, cfg, **SHAPE) - over)


def test_without_the_mask_it_reads_what_shapes_mla_reads():
    cfg = _load("benchmark/configs/trinity-mini-26b-a3b-ep8-bf16.json")
    cfg.update(toy_trinity.TOY_MODEL)
    sym = models.trinity.get_symbol(cfg)
    assert shapes_bd.train_flops(sym, cfg, **SHAPE) \
        == shapes_mla.train_flops(sym, cfg, **SHAPE)
    assert shapes_bd.flash_forward_work(sym, 2, **SHAPE) \
        == shapes_mla.flash_forward_work(sym, 2, **SHAPE)


def test_the_cells_own_counts():
    """The real configuration at the cell's shape: 1.10 TFLOP a layer
    forward in attention, six layers, 645,623,296 parameters."""
    cfg = _load("benchmark/configs/sdar-30b-a3b-ep8-bf16.json")
    sym = models.sdar.get_symbol(cfg, dtype="bfloat16")
    shape = {"data": (1, 16384), "softmax_label": (1, 16384)}
    at = shapes.symbol_shapes(sym, **shape)
    nodes = shapes_bd.attention_nodes(sym, at)
    assert len(nodes) == 6
    assert shapes_bd.attention_forward_flops(nodes[0]) \
        == 512 * 32 * (8192 * 8192 + 8192 * 4)
    total = sum(int(np.prod(s)) for n, s in at.items()
                if n in sym.list_arguments() and n not in shape)
    assert total == 645623296
