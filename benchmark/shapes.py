"""Operations and bytes that the models REQUIRE, worked out from shapes.

Nothing here reads what XLA compiled (``cost_analysis`` counts what was
built, recomputation and padding included).  Matrix products count two
operations per multiply-add; a backward pass costs twice the forward's
(one product for the input's gradient, one for the weight's), so training
is three forwards.  Elementwise work, normalisation and softmax are left
out: against a matmul peak they are not what the peak measures."""
from __future__ import annotations

import json

import numpy as np


def symbol_shapes(symbol, **input_shapes):
    """{name: shape} of every argument and every internal output of an
    ``mxnet_tpu`` symbol at the given input shapes (the program's own
    shape inference; only shapes are taken from it)."""
    internals = symbol.get_internals()
    arg_shapes, out_shapes, _ = internals.infer_shape(**input_shapes)
    shapes = dict(zip(internals.list_arguments(), arg_shapes))
    shapes.update(zip(internals.list_outputs(), out_shapes))
    return shapes


def symbol_nodes(symbol):
    """[(op, name, attrs, [input names])] off the symbol's JSON."""
    graph = json.loads(symbol.tojson())
    nodes = graph["nodes"]
    out = []
    for node in nodes:
        if node["op"] == "null":
            continue
        attrs = node.get("attrs") or node.get("attr") or node.get("param") or {}
        inputs = []
        for ref in node["inputs"]:
            src = nodes[ref[0]]
            inputs.append(src["name"] if src["op"] == "null"
                          else src["name"] + "_output")
        out.append((node["op"], node["name"], attrs, inputs))
    return out


def conv_forward_flops(out_shape, in_channels, kernel, groups=1):
    """2 x (output elements) x (multiply-adds per output element)."""
    return 2.0 * float(np.prod(out_shape)) * (in_channels // groups) \
        * float(np.prod(kernel))


def dense_forward_flops(rows, in_features, out_features):
    return 2.0 * rows * in_features * out_features


def symbol_train_flops(symbol, **input_shapes):
    """Forward+backward matrix-product operations of one batch through a
    symbol: 3 x the forward's convolutions and fully-connected layers."""
    shapes = symbol_shapes(symbol, **input_shapes)
    fwd = 0.0
    for op, name, attrs, inputs in symbol_nodes(symbol):
        if op == "Convolution":
            weight = shapes[name + "_weight"]       # [out, in/groups, kh, kw]
            groups = int(attrs.get("num_group", 1))
            fwd += conv_forward_flops(shapes[name + "_output"],
                                      weight[1] * groups, weight[2:], groups)
        elif op == "FullyConnected":
            weight = shapes[name + "_weight"]       # [out, in]
            rows = shapes[name + "_output"][0]
            fwd += dense_forward_flops(rows, weight[1], weight[0])
    return 3.0 * fwd


def bn_pool_kernel_bytes(symbol, itemsize, eligible=None, **input_shapes):
    """Bytes the BatchNorm channel-sum kernels and the pooling backward
    kernels of one training step must move.

    BatchNorm: the forward pair (sum x, sum x^2) reads the activation once,
    the backward pair (sum dy, sum dy*x) reads dy and x: three passes over
    the input, plus four float32 rows of C.  Max pooling backward reads x
    and dy and writes dx; average pooling backward reads dy and writes dx.
    ``eligible(shape)`` says which BatchNorm inputs run as a kernel."""
    shapes = symbol_shapes(symbol, **input_shapes)
    total = 0.0
    for op, name, attrs, inputs in symbol_nodes(symbol):
        if op == "BatchNorm":
            shape = shapes[inputs[0]]
            if eligible is not None and not eligible(tuple(shape)):
                continue
            total += 3.0 * np.prod(shape) * itemsize + 4 * shape[1] * 4
        elif op == "Pooling":
            n_in = float(np.prod(shapes[inputs[0]]))
            n_out = float(np.prod(shapes[name + "_output"]))
            if attrs.get("pool_type", "max") == "max":
                total += (2 * n_in + n_out) * itemsize
            else:
                total += (n_in + n_out) * itemsize
    return float(total)


# -- decoder-only transformer (GPT-2 layout: learned positions, pre-LN,
# 4 attention projections, 2 feed-forward products, untied head) ----------

def lm_token_flops(cfg, context, with_head):
    """Operations one token requires at ``context`` live positions (its
    own included): the projections and feed-forward of every layer, the
    scores and the weighted sum over the context, and the vocabulary head
    where the token's logits are sampled from."""
    e, f, layers = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    per_layer = 2.0 * (4 * e * e + 2 * e * f) + 4.0 * context * e
    head = 2.0 * e * cfg["vocab_size"] if with_head else 0.0
    return layers * per_layer + head


def lm_weight_bytes(cfg, itemsize=4):
    """Bytes of the weights one iteration must read once: every layer's
    matrices, biases and norms, the final norm and the head.  The embedding
    and position tables are gathered by row and counted per token."""
    e, f, layers, v = (cfg["n_embd"], cfg["n_inner"], cfg["n_layer"],
                       cfg["vocab_size"])
    per_layer = 4 * e * e + 4 * e + 2 * e * f + f + e + 4 * e
    return float(itemsize * (layers * per_layer + 2 * e + v * e + v))


def lm_iteration_bytes(cfg, contexts, slots, itemsize=4):
    """Bytes one decode iteration must move: the weights once, the live
    keys and values of the active streams once, this step's K/V rows
    written, two table rows per token, and a logits row per slot."""
    e, layers, v = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    live = float(sum(contexts))
    kv = 2.0 * layers * e * itemsize * (live + len(contexts))
    rows = 2.0 * e * itemsize * len(contexts)
    return lm_weight_bytes(cfg, itemsize) + kv + rows + slots * v * itemsize
