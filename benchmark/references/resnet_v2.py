"""Plain reference: ResNet v2 (He et al. 2016, "Identity Mappings in Deep
Residual Networks", as MXNet 1.0's example/image-classification/symbols/
resnet.py lays it out) with its softmax cross-entropy, gradients and the
SGD-with-momentum step, in straightforward ``jax.numpy`` float32.

It imports nothing of the program and takes nothing the program made: the
weights and the batches come from the benchmark's own generators.  No
kernels, no bf16, no masters: float32 at ``highest`` matmul precision.
Each residual unit is rematerialised in the backward pass
(``jax.checkpoint``) so that batch 256 at 224x224 fits one chip after the
program's state is freed; that changes memory, not the mathematics.

``operand``/``cotangent`` hooks let the CONTROL recompute the same network
in a lower precision (references/lowprec.py)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 2e-5
UNITS = {18: [2, 2, 2, 2], 34: [3, 4, 6, 3], 50: [3, 4, 6, 3],
         101: [3, 4, 23, 3], 152: [3, 8, 36, 3]}


def layout(cfg):
    """(units, filters, bottleneck) of the published depth table."""
    depth = cfg["num_layers"]
    if cfg["image_shape"][1] <= 28:
        n = (depth - 2) // 6
        return [n] * 3, [16, 16, 32, 64], False
    if depth >= 50:
        return UNITS[depth], [64, 256, 512, 1024, 2048], True
    return UNITS[depth], [64, 64, 128, 256, 512], False


def param_shapes(cfg):
    """{name: shape} of every trainable parameter, MXNet's published names."""
    units, filters, bottleneck = layout(cfg)
    c_in = cfg["image_shape"][0]
    small = cfg["image_shape"][1] <= 32
    shapes = {"bn_data_gamma": (c_in,), "bn_data_beta": (c_in,)}
    k0 = 3 if small else 7
    shapes["conv0_weight"] = (filters[0], c_in, k0, k0)
    if not small:
        shapes["bn0_gamma"] = shapes["bn0_beta"] = (filters[0],)
    width = filters[0]
    for i, n in enumerate(units):
        nf = filters[i + 1]
        for j in range(n):
            name = "stage%d_unit%d" % (i + 1, j + 1)
            plan = [(nf // 4, 1), (nf // 4, 3), (nf, 1)] if bottleneck \
                else [(nf, 3), (nf, 3)]
            c = width
            for k, (f, ks) in enumerate(plan, 1):
                shapes["%s_bn%d_gamma" % (name, k)] = (c,)
                shapes["%s_bn%d_beta" % (name, k)] = (c,)
                shapes["%s_conv%d_weight" % (name, k)] = (f, c, ks, ks)
                c = f
            if j == 0:
                shapes[name + "_sc_weight"] = (nf, width, 1, 1)
            width = nf
    shapes["bn1_gamma"] = shapes["bn1_beta"] = (width,)
    shapes["fc1_weight"] = (cfg["num_classes"], width)
    shapes["fc1_bias"] = (cfg["num_classes"],)
    return shapes


def _conv(x, w, stride, pad, hooks):
    operand, cotangent = hooks
    y = lax.conv_general_dilated(
        operand(x), operand(w), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=lax.Precision.HIGHEST)
    return cotangent(y)


def _bn(x, gamma, beta, rows=None):
    """Training-mode batch normalisation: biased batch statistics.  ``rows``
    restricts the statistics to the first rows (a planted fault)."""
    src = x if rows is None else x[:rows]
    mean = jnp.mean(src, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(src - mean), axis=(0, 2, 3), keepdims=True)
    xn = (x - mean) * lax.rsqrt(var + BN_EPS)
    return xn * gamma.reshape(1, -1, 1, 1) + beta.reshape(1, -1, 1, 1)


def _unit(x, p, name, plan, stride, dim_match, hooks, rows):
    body, entry = x, None
    for k, (ks, st) in enumerate(plan, 1):
        body = jax.nn.relu(_bn(body, p["%s_bn%d_gamma" % (name, k)],
                               p["%s_bn%d_beta" % (name, k)], rows))
        entry = body if entry is None else entry
        body = _conv(body, p["%s_conv%d_weight" % (name, k)], st, ks // 2,
                     hooks)
    if dim_match:
        return body + x
    return body + _conv(entry, p[name + "_sc_weight"], stride, 0, hooks)


def logits(params, data, cfg, hooks=None, rows=None):
    hooks = hooks or (lambda a: a, lambda a: a)
    units, filters, bottleneck = layout(cfg)
    small = cfg["image_shape"][1] <= 32
    x = data.astype(jnp.float32)
    # bn_data: fix_gamma, so the scale is one whatever the leaf holds
    x = _bn(x, jnp.ones_like(params["bn_data_gamma"]),
            params["bn_data_beta"], rows)
    if small:
        x = _conv(x, params["conv0_weight"], 1, 1, hooks)
    else:
        x = _conv(x, params["conv0_weight"], 2, 3, hooks)
        x = jax.nn.relu(_bn(x, params["bn0_gamma"], params["bn0_beta"],
                            rows))
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2),
                              [(0, 0), (0, 0), (1, 1), (1, 1)])
    for i, n in enumerate(units):
        for j in range(n):
            name = "stage%d_unit%d" % (i + 1, j + 1)
            stride = 1 if (i == 0 or j > 0) else 2
            plan = [(1, 1), (3, stride), (1, 1)] if bottleneck \
                else [(3, stride), (3, 1)]
            sub = {k: v for k, v in params.items() if k.startswith(name + "_")}
            unit = jax.checkpoint(functools.partial(
                _unit, name=name, plan=plan, stride=stride,
                dim_match=j > 0, hooks=hooks, rows=rows))
            x = unit(x, sub)
    x = jax.nn.relu(_bn(x, params["bn1_gamma"], params["bn1_beta"], rows))
    x = jnp.mean(x, axis=(2, 3))
    operand, cotangent = hooks
    return cotangent(jnp.dot(operand(x), operand(params["fc1_weight"]).T,
                             precision=lax.Precision.HIGHEST)) \
        + params["fc1_bias"]


def loss_fn(params, data, label, cfg, hooks=None, rows=None):
    """Mean softmax cross-entropy of the batch (of its first ``rows`` when a
    fault leaves the rest out)."""
    z = logits(params, data, cfg, hooks, rows)
    logp = jax.nn.log_softmax(z, axis=-1)
    nll = -jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None],
                               axis=1)[:, 0]
    return jnp.mean(nll if rows is None else nll[:rows])


def weight_decay_of(name, wd):
    """MXNet's rule: decay weights and BatchNorm scales, not shifts/biases."""
    return wd if name.endswith(("_weight", "_gamma")) else 0.0


def sgd_step(params, mom, data, label, cfg, opt, hooks=None, rows=None):
    """One step of MXNet's SGD with momentum on the mean loss:
    ``mom = m*mom - lr*(g + wd*w); w += mom``.  Returns the loss, the
    gradient the optimizer got, the new parameters and the new momentum."""
    loss, grads = jax.value_and_grad(loss_fn)(params, data, label, cfg,
                                              hooks, rows)
    new_p, new_m = {}, {}
    for name, w in params.items():
        wd = weight_decay_of(name, opt["wd"])
        new_m[name] = opt["momentum"] * mom[name] \
            - opt["learning_rate"] * (grads[name] + wd * w)
        new_p[name] = w + new_m[name]
    return loss, grads, new_p, new_m
