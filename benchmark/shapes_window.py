"""Operations that a language model with WINDOWED attention requires, and the
work of its flash forward kernel, from the symbol's shapes.

``shapes_lm.train_flops`` counts every causal attention node over all earlier
keys; a node with a ``window`` asks for less.  Here an attention node counts
the (query, key) pairs its mask lets through, ``4 * heads * head_dim`` a pair
(the score and the weighted sum); ``FullyConnected``, ``moe_experts`` and
``gated_delta_rule`` count as ``shapes_lm`` counts them, by its own functions,
so that a symbol without a window reads the same number there and here.

``flash_forward_work`` is what the ``flash_attn_fwd`` kernel
(``mxnet_tpu/ops/pallas_kernels.py``) must do in one training step, for its
share of the roofline: the same operations a call, and q, k, v and the output
moved once a call; a node inside a ``__mirror_stage__`` runs its forward twice
a step (the backward pass recomputes the stage), and the reader divides by
the device time of every matching call, so both count."""
from __future__ import annotations

import numpy as np

from . import shapes, shapes_lm


def visible_pairs(seq_q, seq_k, causal=True, window=0):
    """(query, key) pairs a mask lets through: key ``j`` for query ``i`` iff
    ``j <= i`` under ``causal`` and ``i - j < window`` where ``window`` is
    not 0."""
    if not causal:
        return float(seq_q) * float(seq_k)
    i = np.arange(int(seq_q), dtype=np.int64)
    seen = np.minimum(i, int(seq_k) - 1) + 1
    if window:
        seen = seen - np.maximum(i - int(window) + 1, 0)
    return float(np.maximum(seen, 0).sum())


def attention_forward_flops(batch, seq_q, seq_k, heads, head_dim, causal=True,
                            window=0):
    return 4.0 * batch * heads * head_dim * visible_pairs(seq_q, seq_k,
                                                          causal, window)


def _attention_nodes(symbol, at):
    """[(q shape, k shape, causal, window, mirrored)] of the symbol's
    ``scaled_dot_product_attention`` nodes."""
    out = []
    for op, _, attrs, inputs in shapes.symbol_nodes(symbol):
        if op == "scaled_dot_product_attention":
            out.append((at[inputs[0]], at[inputs[1]],
                        str(attrs.get("causal")) in ("True", "1"),
                        int(attrs.get("window") or 0),
                        "__mirror_stage__" in attrs))
    return out


def train_flops(symbol, model, **input_shapes):
    """Forward+backward operations of one batch through the symbol (three
    forwards; recomputation is not required work)."""
    at = shapes.symbol_shapes(symbol, **input_shapes)
    fwd = 0.0
    for op, name, attrs, inputs in shapes.symbol_nodes(symbol):
        out = at.get(name + "_output", at.get(name + "_output0"))
        if op == "FullyConnected":
            weight = at[inputs[1]]                  # [out, in]
            fwd += shapes.dense_forward_flops(
                float(np.prod(out[:-1])), weight[1], weight[0])
        elif op == "gated_delta_rule":
            b, s, hv, dv = at[inputs[2]]
            fwd += shapes_lm.delta_rule_forward_flops(b, s, hv,
                                                      at[inputs[0]][3], dv)
        elif op == "moe_experts":
            tokens, hidden = at[inputs[0]]
            fwd += shapes_lm.moe_forward_flops(
                tokens, hidden, int(attrs["num_hidden"]),
                int(attrs["num_experts"]),
                int(attrs.get("experts_held") or attrs["num_experts"]),
                int(attrs["top_k"]))
    for (b, sq, h, d), k, causal, window, _ in _attention_nodes(symbol, at):
        fwd += attention_forward_flops(b, sq, k[1], h, d, causal, window)
    return 3.0 * fwd


def flash_forward_work(symbol, itemsize, **input_shapes):
    """{"flops", "bytes"} of every ``flash_attn_fwd`` call of one training
    step."""
    at = shapes.symbol_shapes(symbol, **input_shapes)
    flops = moved = 0.0
    for q, k, causal, window, mirrored in _attention_nodes(symbol, at):
        calls = 2.0 if mirrored else 1.0
        b, sq, h, d = q
        flops += calls * attention_forward_flops(b, sq, k[1], h, d, causal,
                                                 window)
        moved += calls * itemsize * (2.0 * np.prod(q) + 2.0 * np.prod(k))
    return {"flops": float(flops), "bytes": float(moved)}
