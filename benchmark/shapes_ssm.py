"""Operations that a Mamba-2 / attention hybrid REQUIRES, and the work of its
state-space kernels, from the symbol's shapes.

``train_flops`` counts as ``shapes_window`` counts (every ``FullyConnected``
over all its rows, an attention node by the pairs its mask lets through)
and adds the ``ssd`` node (``mxnet_tpu/ops/lm_ops.py``): per token and value
head the state's update ``delta x B^T`` and the read ``S C``, ``4 * N * P``
— the recurrence as it is defined.  What the chunked form adds to turn it
into matrix products (the chunks' ``[c, c]`` matrices) is the program's
choice and not required work, so a kernel with another chunk reads the same
yardstick.

``ssd_scan_forward_work`` / ``ssd_scan_backward_work`` are what the
``ssd_scan_fwd`` and ``ssd_scan_bwd`` kernels must do in one training step,
for their shares of the roofline: the forward's operations a call and its
operands x (``delta x`` as the kernel reads it), B, C, the log decay (float32)
and its output y moved once a call, with the chunk states the backward needs
written once a step (by the forward the backward differentiates; an ``ssd``
node inside a ``__mirror_stage__`` runs its forward twice a step, once
without them); the backward's twice the forward's operations, those operands,
y's cotangent and the states read once, and the cotangents of x, B, C and
the decay written once."""
from __future__ import annotations

from . import shapes, shapes_window


def ssd_forward_flops(batch, seq, heads, width, state):
    return 4.0 * batch * seq * heads * width * state


def _ssd_nodes(symbol, at):
    """[(x shape [b, s, heads, P], B shape [b, s, groups, N], chunk,
    mirrored)] of the symbol's ``ssd`` nodes."""
    out = []
    for op, _, attrs, inputs in shapes.symbol_nodes(symbol):
        if op == "ssd":
            out.append((at[inputs[0]], at[inputs[1]],
                        int(attrs.get("chunk") or 64),
                        "__mirror_stage__" in attrs))
    return out


def train_flops(symbol, model, **input_shapes):
    """Forward+backward operations of one batch through the symbol (three
    forwards; recomputation is not required work)."""
    at = shapes.symbol_shapes(symbol, **input_shapes)
    fwd = sum(ssd_forward_flops(b, s, h, p, bc[3])
              for (b, s, h, p), bc, _, _ in _ssd_nodes(symbol, at))
    return shapes_window.train_flops(symbol, model, **input_shapes) \
        + 3.0 * fwd


def _operand_bytes(x, bc, itemsize):
    """x, B, C and y in the compute dtype, the log decay float32."""
    b, s, h, p = x
    return 2.0 * itemsize * b * s * h * p + 2.0 * itemsize * b * s * bc[2] \
        * bc[3] + 4.0 * b * s * h


def _state_bytes(x, bc, chunk, itemsize):
    b, s, h, p = x
    return float(itemsize) * b * (-(-s // chunk)) * bc[3] * h * p


def ssd_scan_forward_work(symbol, itemsize, **input_shapes):
    """{"flops", "bytes"} of every ``ssd_scan_fwd`` call of one training
    step."""
    at = shapes.symbol_shapes(symbol, **input_shapes)
    flops = moved = 0.0
    for x, bc, chunk, mirrored in _ssd_nodes(symbol, at):
        calls = 2.0 if mirrored else 1.0
        flops += calls * ssd_forward_flops(*x, bc[3])
        moved += calls * _operand_bytes(x, bc, itemsize) \
            + _state_bytes(x, bc, chunk, itemsize)
    return {"flops": float(flops), "bytes": float(moved)}


def ssd_scan_backward_work(symbol, itemsize, **input_shapes):
    """{"flops", "bytes"} of every ``ssd_scan_bwd`` call of one training
    step."""
    at = shapes.symbol_shapes(symbol, **input_shapes)
    flops = moved = 0.0
    for x, bc, chunk, _ in _ssd_nodes(symbol, at):
        flops += 2.0 * ssd_forward_flops(*x, bc[3])
        # in: x, B, C, the decay, y's cotangent; out: the cotangents of
        # the first four; and the states read
        moved += 2.0 * _operand_bytes(x, bc, itemsize) \
            + _state_bytes(x, bc, chunk, itemsize)
    return {"flops": float(flops), "bytes": float(moved)}
