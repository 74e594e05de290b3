"""Operations that a hybrid linear-attention expert model REQUIRES, worked
out from the symbol's shapes, as ``shapes.py`` does for the convolutional
ones: two operations per multiply-add of a matrix product, training three
forwards, recomputation NOT counted (it is work the program chose to do).

``shapes.symbol_train_flops`` counts ``Convolution`` and a flattening
``FullyConnected``; here every ``FullyConnected`` counts all its rows
(``flatten=False`` on ``[batch, seq, hidden]``), and the ops that
``mxnet_tpu/ops/lm_ops.py`` adds count by what the mathematics asks for:

- causal attention: the scores and the weighted sum over the positions at or
  before each query, ``4 * heads * head_dim * (seq + 1) / 2`` a token;
- the gated delta rule: per token and value head the read ``S^T k``, the
  rank-one update and the read ``S^T q``, ``6 * dk * dv`` — the recurrence as
  it is defined; what the chunked form adds to turn it into matrix products
  is not required work;
- the expert layer: the router over all experts, and the three products of
  ``F_e`` for every (token, held expert) choice; the EXPECTED number of
  those under even routing, ``tokens * top_k * held / experts``, so that the
  count depends on shapes alone (``fit_moe_held_selection_share`` says how
  near a run came).

No Pallas kernel of this family is on the path (PERF.md, PR 27), so no byte
count is kept here yet."""
from __future__ import annotations

import numpy as np

from . import shapes


def attention_forward_flops(batch, seq, heads, head_dim, causal=True):
    keys = (seq + 1) / 2.0 if causal else float(seq)
    return 4.0 * batch * seq * heads * head_dim * keys


def delta_rule_forward_flops(batch, seq, value_heads, dk, dv):
    return 6.0 * batch * seq * value_heads * dk * dv


def moe_forward_flops(tokens, hidden, mid, experts, held, top_k):
    router = 2.0 * tokens * hidden * experts
    choices = tokens * top_k * held / float(experts)
    return router + choices * 3 * 2.0 * hidden * mid


def train_flops(symbol, model, **input_shapes):
    """Forward+backward operations of one batch through the symbol."""
    at = shapes.symbol_shapes(symbol, **input_shapes)
    fwd = 0.0
    for op, name, attrs, inputs in shapes.symbol_nodes(symbol):
        out = at.get(name + "_output", at.get(name + "_output0"))
        if op == "FullyConnected":
            weight = at[inputs[1]]                  # [out, in]
            fwd += shapes.dense_forward_flops(
                float(np.prod(out[:-1])), weight[1], weight[0])
        elif op == "scaled_dot_product_attention":
            b, s, h, d = at[inputs[0]]
            fwd += attention_forward_flops(
                b, s, h, d, str(attrs.get("causal")) in ("True", "1"))
        elif op == "gated_delta_rule":
            b, s, hv, dv = at[inputs[2]]
            fwd += delta_rule_forward_flops(b, s, hv, at[inputs[0]][3], dv)
        elif op == "moe_experts":
            tokens, hidden = at[inputs[0]]
            fwd += moe_forward_flops(
                tokens, hidden, int(attrs["num_hidden"]),
                int(attrs["num_experts"]),
                int(attrs.get("experts_held") or attrs["num_experts"]),
                int(attrs["top_k"]))
    return 3.0 * fwd
