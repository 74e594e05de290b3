"""Operations that a language model under the BLOCK-DIFFUSION mask requires,
and the work of its flash kernels, from the symbol's shapes.

An attention node with ``block_diffusion`` ``B`` reads ``2 L`` positions, a
noisy copy of a sequence and its clean copy, and its mask lets a noisy query
see its own block's noisy keys and the clean keys of the blocks before it, a
clean query the clean keys of its block and those before: ``B^2 n^2 + L B``
pairs a head where the ``n = L / B`` blocks are whole (counted here from the
mask's definition, block by block, not from the program).  ``shapes_mla``
counts such a node, which has no causal flag, over all ``(2 L)^2`` pairs;
here it counts the visible ones, at ``shapes_mla``'s cost a pair (``2 d_qk +
2 d_v`` forward, the five products ``2 (3 d_qk + 2 d_v)`` backward), and
everything else as ``shapes_mla`` does, so that a symbol without the mask
reads the same number there and here.

``flash_forward_work`` and ``flash_backward_work`` are what the kernels'
calls REQUIRE, as ``shapes_mla``'s: the forward twice where the node lies in
a ``__mirror_stage__``; q, k, v and the output moved once a forward call,
with ``dO``, ``dq``, ``dk`` and ``dv`` once a backward."""
from __future__ import annotations

import numpy as np

from . import shapes, shapes_mla


def bd_visible_pairs(seq, block):
    """(query, key) pairs the block-diffusion mask lets through over ``seq
    = 2 L`` positions, one head: each block's ``size`` noisy rows see its
    own ``size`` noisy keys and the ``start`` clean keys before it, its clean
    rows the ``start + size`` clean keys up to its end."""
    half = int(seq) // 2
    starts = np.arange(0, half, int(block), dtype=np.int64)
    sizes = np.minimum(starts + int(block), half) - starts
    return float(2 * np.sum(sizes * (starts + sizes)))


def attention_nodes(symbol, at):
    """``shapes_mla.attention_nodes`` with each node's ``block`` (its
    ``block_diffusion``, 0 where none)."""
    nodes = shapes_mla.attention_nodes(symbol, at)
    blocks = [int(attrs.get("block_diffusion") or 0)
              for op, _, attrs, _ in shapes.symbol_nodes(symbol)
              if op == "scaled_dot_product_attention"]
    for node, block in zip(nodes, blocks):
        node["block"] = block
    return nodes


def pairs(node):
    """Visible (query, key) pairs of a node, batch and heads counted."""
    if not node["block"]:
        return shapes_mla._pairs(node)
    b, sq, heads, _ = node["q"]
    return b * heads * bd_visible_pairs(sq, node["block"])


def attention_forward_flops(node):
    return (2.0 * node["q"][3] + 2.0 * node["v"][3]) * pairs(node)


def attention_backward_flops(node):
    d_qk, d_v = node["q"][3], node["v"][3]
    return 2.0 * (3 * d_qk + 2 * d_v) * pairs(node)


def train_flops(symbol, model, **input_shapes):
    """Forward+backward operations of one batch through the symbol (three
    forwards; recomputation is not required work): ``shapes_mla``'s count
    with each block-diffusion node's visible pairs in place of all."""
    at = shapes.symbol_shapes(symbol, **input_shapes)
    fwd = sum(attention_forward_flops(n)
              - shapes_mla.attention_forward_flops(n)
              for n in attention_nodes(symbol, at))
    return shapes_mla.train_flops(symbol, model, **input_shapes) + 3.0 * fwd


def flash_forward_work(symbol, itemsize, **input_shapes):
    """{"flops", "bytes"} of every ``flash_attn_fwd`` call of one training
    step."""
    at = shapes.symbol_shapes(symbol, **input_shapes)
    flops = moved = 0.0
    for node in attention_nodes(symbol, at):
        calls = 2.0 if node["mirrored"] else 1.0
        flops += calls * attention_forward_flops(node)
        moved += calls * shapes_mla._moved(node, itemsize, 1)
    return {"flops": float(flops), "bytes": float(moved)}


def flash_backward_work(symbol, itemsize, **input_shapes):
    """{"flops", "bytes"} of the ``flash_attn_bwd_*`` calls of one training
    step: one backward a node, in however many kernels."""
    at = shapes.symbol_shapes(symbol, **input_shapes)
    nodes = attention_nodes(symbol, at)
    return {"flops": float(sum(attention_backward_flops(n) for n in nodes)),
            "bytes": float(sum(shapes_mla._moved(n, itemsize, 2)
                               for n in nodes))}
