"""Operations that a language model whose attention has TWO widths requires
(latent attention: keys and queries ``d_qk`` wide, values ``d_v``), and the
work of its flash kernels, from the symbol's shapes.

``shapes_window.train_flops`` counts an attention node ``4 * heads *
head_dim`` a visible pair with q's width for both products; here a pair costs
``2 * d_qk`` (the score) ``+ 2 * d_v`` (the weighted sum), and everything else
is ``shapes_window``'s own count, so that a symbol whose widths are equal reads
the same number there and here.

``flash_forward_work`` and ``flash_backward_work`` are what the calls REQUIRE,
whatever implements them, for ``readers/kernel_compute_roofline.py``:

- forward, ``(2 * d_qk + 2 * d_v)`` a pair a head (640 at 192 / 128), twice
  where the node lies in a ``__mirror_stage__`` (the backward pass recomputes
  the stage and the reader divides by the time of every matching call);
- backward, the five products any backward must do, the score again, ``dp = dO
  v^T``, ``dv = p^T dO``, ``dq = ds k`` and ``dk = ds^T q``: ``2 * (d_qk + d_v
  + d_v + d_qk + d_qk)`` a pair a head (1,664 at 192 / 128).  A backward in
  two kernels computes the score and ``dp`` in each, seven products: it cannot
  read over 5 / 7 of the peak by this count;
- bytes: q, k, v and the output once a forward call; q, k, v, the output,
  ``dO``, ``dq``, ``dk`` and ``dv`` once a backward.  A key part that all
  heads share counts once, not once a head: the design may read it so."""
from __future__ import annotations

import numpy as np

from . import shapes, shapes_window


def _flag(attrs, key):
    return str(attrs.get(key)) in ("True", "1")


def attention_nodes(symbol, at):
    """[{q, k, v shapes, the shared key part's width, causal, window,
    mirrored}] of the symbol's ``scaled_dot_product_attention`` nodes; ``k``
    is the heads' own part of the key."""
    out = []
    for op, _, attrs, inputs in shapes.symbol_nodes(symbol):
        if op == "scaled_dot_product_attention":
            shared = at[inputs[-1]][-1] if _flag(attrs, "use_shared_key") else 0
            out.append(dict(q=at[inputs[0]], k=at[inputs[1]], v=at[inputs[2]],
                            shared=int(shared), causal=_flag(attrs, "causal"),
                            window=int(attrs.get("window") or 0),
                            mirrored="__mirror_stage__" in attrs))
    return out


def _pairs(node):
    """Visible (query, key) pairs of a node, batch and heads counted."""
    b, sq, heads, _ = node["q"]
    return b * heads * shapes_window.visible_pairs(
        sq, node["k"][1], node["causal"], node["window"])


def attention_forward_flops(node):
    return (2.0 * node["q"][3] + 2.0 * node["v"][3]) * _pairs(node)


def attention_backward_flops(node):
    d_qk, d_v = node["q"][3], node["v"][3]
    return 2.0 * (d_qk + d_v + d_v + d_qk + d_qk) * _pairs(node)


def train_flops(symbol, model, **input_shapes):
    """Forward+backward operations of one batch through the symbol (three
    forwards; recomputation is not required work): ``shapes_window``'s count
    with each attention node's two products at their own widths."""
    at = shapes.symbol_shapes(symbol, **input_shapes)
    fwd = 0.0
    for node in attention_nodes(symbol, at):
        (b, sq, heads, d), sk = node["q"], node["k"][1]
        fwd += attention_forward_flops(node) \
            - shapes_window.attention_forward_flops(
                b, sq, sk, heads, d, node["causal"], node["window"])
    return shapes_window.train_flops(symbol, model, **input_shapes) + 3.0 * fwd


def _moved(node, itemsize, times):
    """Bytes of q, k (the shared part once), v and the output, ``times``
    each."""
    b, sq, heads, _ = node["q"]
    sk = node["k"][1]
    elements = np.prod(node["q"]) + np.prod(node["k"]) + np.prod(node["v"]) \
        + b * sk * node["shared"] + b * sq * heads * node["v"][3]
    return float(times * itemsize * elements)


def flash_forward_work(symbol, itemsize, **input_shapes):
    """{"flops", "bytes"} of every ``flash_attn_fwd`` call of one training
    step."""
    at = shapes.symbol_shapes(symbol, **input_shapes)
    flops = moved = 0.0
    for node in attention_nodes(symbol, at):
        calls = 2.0 if node["mirrored"] else 1.0
        flops += calls * attention_forward_flops(node)
        moved += calls * _moved(node, itemsize, 1)
    return {"flops": float(flops), "bytes": float(moved)}


def flash_backward_work(symbol, itemsize, **input_shapes):
    """{"flops", "bytes"} of the ``flash_attn_bwd_*`` calls of one training
    step: one backward a node, in however many kernels."""
    at = shapes.symbol_shapes(symbol, **input_shapes)
    nodes = attention_nodes(symbol, at)
    return {"flops": float(sum(attention_backward_flops(n) for n in nodes)),
            "bytes": float(sum(_moved(n, itemsize, 2) for n in nodes))}
