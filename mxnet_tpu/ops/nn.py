"""Neural-network layer operators.

TPU-native rebuild of src/operator/nn/ + the root legacy layer ops
(Convolution convolution-inl.h, FullyConnected fully_connected-inl.h,
BatchNorm batch_norm-inl.h, Pooling pool.h, SoftmaxOutput
softmax_output-inl.h, Activation, Dropout, LRN, Embedding ...).  Conv/FC
lower to lax.conv_general_dilated / jnp.matmul so XLA tiles them onto the
MXU; loss heads (SoftmaxOutput, *RegressionOutput, make_loss) reproduce the
reference's custom backward semantics via jax.custom_vjp so that whole-graph
vjp matches MXNet's Executor.backward exactly.
"""
from __future__ import annotations

import functools as _functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..base import np_dtype, MXNetError
from .registry import register, pShape, pInt, pFloat, pBool, pStr, pDtype, pAny

# ---------------------------------------------------------------------------
# Activation / LeakyReLU / softmax family
# ---------------------------------------------------------------------------

_ACTS = {
    "relu": lambda x: jnp.maximum(x, 0),
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "softrelu": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
}


def _activation(x, act_type="relu"):
    return _ACTS[act_type](x)


register("Activation", _activation, num_inputs=1,
         params={"act_type": (pStr, "relu")})


def _leaky_relu(x, act_type="leaky", slope=0.25, lower_bound=0.125,
                upper_bound=0.334):
    if act_type in ("leaky", "rrelu"):  # rrelu uses mean slope at inference
        s = slope if act_type == "leaky" else (lower_bound + upper_bound) / 2.0
        return jnp.where(x > 0, x, s * x)
    if act_type == "elu":
        return jnp.where(x > 0, x, slope * jnp.expm1(x))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(x > 0, x, alpha * jnp.expm1(x))
    if act_type == "gelu":  # exact erf form (transformer FFN activation)
        inv_sqrt2 = jnp.asarray(0.7071067811865476, x.dtype)
        return 0.5 * x * (1.0 + jax.lax.erf(x * inv_sqrt2))
    raise MXNetError("unknown LeakyReLU act_type %s" % act_type)


register("LeakyReLU", _leaky_relu, num_inputs=1,
         params={"act_type": (pStr, "leaky"), "slope": (pFloat, 0.25),
                 "lower_bound": (pFloat, 0.125), "upper_bound": (pFloat, 0.334)})


def _prelu(x, gamma):
    g = gamma.reshape((1, -1) + (1,) * (x.ndim - 2)) if x.ndim > 1 else gamma
    return jnp.where(x > 0, x, g * x)


register("_PReLU", _prelu, num_inputs=2)


def _softmax(x, axis=-1, temperature=None):
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    return jax.nn.softmax(x, axis=int(axis))


register("softmax", _softmax, num_inputs=1,
         params={"axis": (pAny, -1), "temperature": (pAny, None)})
register("log_softmax", lambda x, axis=-1, temperature=None:
         jax.nn.log_softmax(x if not temperature else x / temperature, axis=int(axis)),
         num_inputs=1, params={"axis": (pAny, -1), "temperature": (pAny, None)})


def _softmax_activation(x, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(x, axis=1)
    return jax.nn.softmax(x.reshape(x.shape[0], -1), axis=-1).reshape(x.shape)


register("SoftmaxActivation", _softmax_activation, num_inputs=1,
         params={"mode": (pStr, "instance")})

# ---------------------------------------------------------------------------
# FullyConnected (ref: fully_connected-inl.h:114 linalg_gemm)
# ---------------------------------------------------------------------------

def _fully_connected(data, weight, *rest, num_hidden=1, no_bias=False, flatten=True):
    x = data.reshape(data.shape[0], -1) if flatten or data.ndim == 2 else data
    # bf16 operands hit the MXU directly; the MXU accumulates partial
    # products in f32 regardless of operand dtype, so no explicit
    # preferred_element_type is needed (and an f32 preferred type breaks
    # the conv/dot transpose rules under vjp by mixing cotangent dtypes)
    out = jnp.matmul(x, weight.T)
    if not no_bias:
        out = out + rest[0]
    return out


def _fc_infer_shape(in_shapes, attrs, out_shapes=None):
    num_hidden = int(attrs["num_hidden"])
    no_bias = attrs.get("no_bias", False)
    flatten = attrs.get("flatten", True)
    dshape = in_shapes[0]
    if dshape is None:
        return in_shapes, [None]
    filled = list(in_shapes)
    # backward inference: heal unknown (0) leading data dims from a known
    # output shape — RNN begin_state zeros (0, H) feeding h2h resolve their
    # batch dim this way (the reference's pass is bidirectional)
    out = out_shapes[0] if out_shapes else None
    if out is not None and any(int(d) == 0 for d in dshape):
        if flatten or len(dshape) == 2:
            if int(dshape[0]) == 0 and int(out[0]) != 0:
                dshape = (int(out[0]),) + tuple(dshape[1:])
        elif len(out) == len(dshape):
            dshape = tuple(int(o) if int(d) == 0 and int(o) != 0 else int(d)
                           for d, o in zip(dshape[:-1], out[:-1])) \
                + (dshape[-1],)
        filled[0] = dshape
    if flatten or len(dshape) == 2:
        in_dim = int(np.prod(dshape[1:]))
        unknown = any(int(d) == 0 for d in dshape[1:])
    else:
        in_dim = int(dshape[-1])
        unknown = in_dim == 0  # middle dims don't affect the weight shape
    if not unknown:
        filled[1] = (num_hidden, in_dim)
    if not no_bias:
        filled[2] = (num_hidden,)
    oshape = (dshape[0], num_hidden) if (flatten or len(dshape) == 2) \
        else tuple(dshape[:-1]) + (num_hidden,)
    return filled, [oshape]


register("FullyConnected", _fully_connected,
         input_names=("data", "weight", "bias"),
         infer_shape=_fc_infer_shape, bidirectional_infer=True,
         params={"num_hidden": (pInt, 1), "no_bias": (pBool, False),
                 "flatten": (pBool, True)})

# ---------------------------------------------------------------------------
# Convolution / Deconvolution (ref: convolution-inl.h; NCHW + OIHW layout —
# XLA re-lays-out for the MXU internally)
# ---------------------------------------------------------------------------

def _conv_dims(kernel):
    return len(kernel)


def _conv_dn(nd):
    if nd == 1:
        return ("NCH", "OIH", "NCH")
    if nd == 2:
        return ("NCHW", "OIHW", "NCHW")
    return ("NCDHW", "OIDHW", "NCDHW")


def _s2d_axis_map(k, s, p):
    """Tap map for one spatial axis of the space-to-depth stem rewrite:
    original kernel index kk lands on s2d plane (kk-p) mod s at tap
    (kk-p-q)//s.  Returns (planes, taps, tap_count, dmin)."""
    qs, ds = [], []
    for kk in range(k):
        q = (kk - p) % s
        qs.append(q)
        ds.append((kk - p - q) // s)
    dmin = min(ds)
    return qs, [d - dmin for d in ds], max(ds) - dmin + 1, dmin


def _conv_s2d_stem(data, weight, kernel, stride, pad):
    """Space-to-depth rewrite of a strided small-channel conv (the RGB
    stem).  A C<8 contraction never reaches the MXU: XLA lowers the
    7x7/s2 stem fwd+bwd as ~8 TFLOP/s loop fusions costing 2.6 ms of a
    13 ms ResNet-50/b32 train step on v5e (20% of the step for 2% of the
    FLOPs).  Regrouping s x s input phases into channels makes it a
    stride-1 conv over s*s*C >= 8 channels — measured 2.2 -> 1.1 ms/iter
    for the stem fwd+bwd micro.  Exact: weights are repacked tap-by-tap
    inside the jit (logical/checkpoint weight stays (O, C, kh, kw)), and
    the naive-pad alternative is a no-op (the algebraic simplifier undoes
    conv(pad(x), pad(w)) — traced, round 3)."""
    N, C, H, W = data.shape
    kh_, kw_ = kernel
    sh_, sw_ = stride
    ph_, pw_ = pad
    O = weight.shape[0]
    qh, th, Th, dmin_h = _s2d_axis_map(kh_, sh_, ph_)
    qw, tw, Tw, dmin_w = _s2d_axis_map(kw_, sw_, pw_)
    # x: (N, C, H, W) -> (N, sh*sw*C, H/sh, W/sw), channel = (qh, qw, c)
    x2 = data.reshape(N, C, H // sh_, sh_, W // sw_, sw_)
    x2 = x2.transpose(0, 3, 5, 1, 2, 4).reshape(
        N, sh_ * sw_ * C, H // sh_, W // sw_)
    w2 = jnp.zeros((O, sh_ * sw_ * C, Th, Tw), weight.dtype)
    for i in range(kh_):
        for j in range(kw_):
            plane = (qh[i] * sw_ + qw[j]) * C
            w2 = w2.at[:, plane:plane + C, th[i], tw[j]].set(
                weight[:, :, i, j])
    out_h = (H + 2 * ph_ - kh_) // sh_ + 1
    out_w = (W + 2 * pw_ - kw_) // sw_ + 1
    pad_h = (-dmin_h, out_h - 1 + (Th - 1 + dmin_h) - (H // sh_ - 1))
    pad_w = (-dmin_w, out_w - 1 + (Tw - 1 + dmin_w) - (W // sw_ - 1))
    return lax.conv_general_dilated(
        x2, w2, (1, 1), [pad_h, pad_w],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _convolution(data, weight, *rest, kernel=(1, 1), stride=None, dilate=None,
                 pad=None, num_filter=1, num_group=1, no_bias=False,
                 workspace=1024, cudnn_tune=None, cudnn_off=False, layout=None,
                 _train=False):
    nd = _conv_dims(kernel)
    stride = stride or (1,) * nd
    dilate = dilate or (1,) * nd
    pad = pad or (0,) * nd
    # train-only: the s2d win is in the backward (the 57 GB/s stem
    # input-grad fusion); forward-only bf16 inference measured FASTER on
    # XLA's own stem lowering (bench: 50.2% plain vs 45.0% with s2d), so
    # eval mode keeps the plain conv
    if (_train and nd == 2 and num_group == 1 and tuple(dilate) == (1, 1)
            and data.shape[1] < 8 and max(stride) > 1
            and data.shape[1] * stride[0] * stride[1] >= 8
            and kernel[0] >= stride[0] and kernel[1] >= stride[1]
            and data.shape[2] % stride[0] == 0
            and data.shape[3] % stride[1] == 0):
        out = _conv_s2d_stem(data, weight, kernel, tuple(stride), tuple(pad))
    else:
        out = lax.conv_general_dilated(
            data, weight,
            window_strides=stride,
            padding=[(p, p) for p in pad],
            rhs_dilation=dilate,
            dimension_numbers=_conv_dn(nd),
            feature_group_count=int(num_group),
        )
    if not no_bias:
        b = rest[0].reshape((1, -1) + (1,) * nd)
        out = out + b
    return out


def _conv_out_dim(d, k, s, p, dil):
    return (d + 2 * p - (dil * (k - 1) + 1)) // s + 1


def _conv_infer_shape(in_shapes, attrs):
    kernel = attrs["kernel"]
    nd = len(kernel)
    stride = attrs.get("stride") or (1,) * nd
    dilate = attrs.get("dilate") or (1,) * nd
    pad = attrs.get("pad") or (0,) * nd
    num_filter = int(attrs["num_filter"])
    num_group = int(attrs.get("num_group", 1))
    no_bias = attrs.get("no_bias", False)
    dshape = in_shapes[0]
    if dshape is None:
        return in_shapes, [None]
    filled = list(in_shapes)
    filled[1] = (num_filter, dshape[1] // num_group) + tuple(kernel)
    if not no_bias:
        filled[2] = (num_filter,)
    spatial = tuple(_conv_out_dim(dshape[2 + i], kernel[i], stride[i], pad[i], dilate[i])
                    for i in range(nd))
    return filled, [(dshape[0], num_filter) + spatial]


_CONV_PARAMS = {
    "kernel": (pShape, (1, 1)), "stride": (pShape, None), "dilate": (pShape, None),
    "pad": (pShape, None), "num_filter": (pInt, 1), "num_group": (pInt, 1),
    "no_bias": (pBool, False), "workspace": (pInt, 1024),
    "cudnn_tune": (pStr, None), "cudnn_off": (pBool, False), "layout": (pStr, None),
}

register("Convolution", _convolution, input_names=("data", "weight", "bias"),
         infer_shape=_conv_infer_shape, params=_CONV_PARAMS,
         takes_train_flag=True, aliases=("Convolution_v1",))


def _deconv_pad_adj(in_spatial, ke, stride, pad, adj, target_shape):
    """Effective (pad, adj) per spatial dim.  target_shape overrides both
    with a CENTERED crop (ref: deconvolution-inl.h InferPad:116-137 —
    total = s(i-1)+ke-t, pad=(total+1)/2, adj=total%2)."""
    nd = len(ke)
    if not target_shape:
        return tuple(pad), (tuple(adj) if adj else (0,) * nd)
    pads, adjs = [], []
    for t, i, s, k in zip(target_shape, in_spatial, stride, ke):
        total = s * (int(i) - 1) + k - int(t)
        if total < 0:
            raise MXNetError(
                "Deconvolution: target_shape %s exceeds the full output "
                "size" % (tuple(target_shape),))
        adjs.append(total % 2)
        pads.append((total + 1) // 2)
    return tuple(pads), tuple(adjs)


def _deconvolution(data, weight, *rest, kernel=(1, 1), stride=None, dilate=None,
                   pad=None, adj=None, target_shape=None, num_filter=1,
                   num_group=1, no_bias=True, workspace=1024, cudnn_tune=None,
                   cudnn_off=False, layout=None):
    nd = _conv_dims(kernel)
    stride = stride or (1,) * nd
    dilate = dilate or (1,) * nd
    pad = pad or (0,) * nd
    # Deconv == gradient of conv w.r.t. input.  The MXNet weight layout is
    # (C_in, num_filter/g, kh, kw) — with transpose_kernel=True and OIHW
    # dimension numbers, conv_transpose wants exactly the forward conv's
    # kernel layout (O_fwd=C_in, I_fwd=num_filter/g), so the weight passes
    # through unchanged (deconvolution-inl.h semantics).
    #
    # conv_transpose's explicit padding applies to the stride-dilated input,
    # so MXNet's crop semantics (out = (i-1)*s + ke - 2p + adj, where
    # ke = (k-1)*dilate + 1) translate to (ke-1-p, ke-1-p+adj) per side.
    ke = [(k - 1) * d + 1 for k, d in zip(kernel, dilate)]
    pad, adjv = _deconv_pad_adj(data.shape[2:], ke, stride, pad, adj,
                                target_shape)
    padding = [(k - 1 - p, k - 1 - p + a)
               for k, p, a in zip(ke, pad, adjv)]

    def one_group(d, w):
        return lax.conv_transpose(
            d, w,
            strides=stride,
            padding=padding,
            rhs_dilation=dilate,
            dimension_numbers=_conv_dn(nd),
            transpose_kernel=True,
        )

    g = int(num_group)
    if g == 1:
        out = one_group(data, weight)
    else:
        # conv_transpose has no group support: split C_in into g groups,
        # transpose-convolve each, concatenate the per-group outputs
        d_groups = jnp.split(data, g, axis=1)
        w_groups = jnp.split(weight, g, axis=0)
        out = jnp.concatenate(
            [one_group(d, w) for d, w in zip(d_groups, w_groups)], axis=1)
    if not no_bias:
        out = out + rest[0].reshape((1, -1) + (1,) * nd)
    return out


def _deconv_infer_shape(in_shapes, attrs):
    kernel = attrs["kernel"]
    nd = len(kernel)
    stride = attrs.get("stride") or (1,) * nd
    dilate = attrs.get("dilate") or (1,) * nd
    pad = attrs.get("pad") or (0,) * nd
    num_filter = int(attrs["num_filter"])
    num_group = int(attrs.get("num_group", 1))
    no_bias = attrs.get("no_bias", True)
    dshape = in_shapes[0]
    if dshape is None:
        return in_shapes, [None]
    filled = list(in_shapes)
    filled[1] = (dshape[1], num_filter // num_group) + tuple(kernel)
    if not no_bias:
        filled[2] = (num_filter,)
    ke = [(kernel[i] - 1) * dilate[i] + 1 for i in range(nd)]
    pad_eff, adj_eff = _deconv_pad_adj(
        dshape[2:], ke, stride, pad, attrs.get("adj"),
        attrs.get("target_shape"))
    spatial = tuple(stride[i] * (dshape[2 + i] - 1) + ke[i]
                    - 2 * pad_eff[i] + adj_eff[i] for i in range(nd))
    return filled, [(dshape[0], num_filter) + spatial]


register("Deconvolution", _deconvolution, input_names=("data", "weight", "bias"),
         infer_shape=_deconv_infer_shape,
         params=dict(_CONV_PARAMS, adj=(pShape, None), target_shape=(pShape, None),
                     no_bias=(pBool, True)))

# ---------------------------------------------------------------------------
# Pooling (ref: pooling-inl.h, pool.h) — lax.reduce_window forward.  The
# input gradient of avg/sum pooling is always XLA's transpose of the
# reduce_window-add: a fan-out of dy that fuses into dx's consumer (a
# broadcast for a window that covers the input).  For max pooling it is
# either XLA's autodiff (select-and-scatter) or the hand-scheduled Pallas
# kernel (ops/pallas_kernels.py, flag MXNET_TPU_PALLAS_POOL) selected at
# trace time through a custom_vjp — so the fused fwd_bwd program
# (module/fused_step.py, executor_cache.py) picks the kernel up with no
# module-layer change.
# ---------------------------------------------------------------------------

def _pool_spatial_pads(spatial, kernel, stride, pad, convention):
    """Per-axis (lo, hi) spatial padding honoring the 'full' ceil mode
    (widen the right pad so ceil division is covered)."""
    nd = len(kernel)
    if convention != "full":
        return tuple((p, p) for p in pad)
    pads = []
    for i in range(nd):
        d = spatial[i]
        out_full = int(np.ceil((d + 2 * pad[i] - kernel[i])
                               / stride[i])) + 1
        needed = (out_full - 1) * stride[i] + kernel[i] - d - pad[i]
        pads.append((pad[i], max(needed, pad[i])))
    return tuple(pads)


def _pool_out_shape(spatial, kernel, stride, pad, convention):
    out = []
    for i in range(len(kernel)):
        span = spatial[i] + 2 * pad[i] - kernel[i]
        o = (int(np.ceil(span / stride[i])) if convention == "full"
             else span // stride[i]) + 1
        out.append(int(o))
    return tuple(out)


def _pool_window_counts(spatial, kernel, stride, pad, convention):
    """(OH, ...) float32 map of VALID (non-padded) elements per window —
    the count_include_pad=False divisor (ref: pooling-inl.h, where padded
    zeros are excluded from the average's denominator)."""
    pads = _pool_spatial_pads(spatial, kernel, stride, pad, convention)
    ones = jnp.ones(tuple(spatial), jnp.float32)
    cnt = lax.reduce_window(ones, 0.0, lax.add, tuple(kernel),
                            tuple(stride), pads)
    return jnp.maximum(cnt, 1.0)


def _pool_xla_forward(data, pool_type, kernel, stride, pad, convention,
                      count_include_pad):
    window = (1, 1) + tuple(kernel)
    strides = (1, 1) + tuple(stride)
    pads = ((0, 0), (0, 0)) + _pool_spatial_pads(
        data.shape[2:], kernel, stride, pad, convention)
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) \
            else jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, window, strides, pads)
    out = lax.reduce_window(data, 0.0, lax.add, window, strides, pads)
    if pool_type == "avg":
        if count_include_pad:
            out = out / float(np.prod(kernel))
        else:
            # data-independent valid-count divisor: XLA constant-folds it
            cnt = _pool_window_counts(data.shape[2:], kernel, stride, pad,
                                      convention)
            out = out / cnt.reshape((1, 1) + cnt.shape)
    return out.astype(data.dtype)


@_functools.lru_cache(maxsize=None)
def _pool_core(pool_type, kernel, stride, pad, convention,
               count_include_pad, mode):
    """Per-static-config pooling core.  avg/sum pooling, and max pooling
    under mode 'off', return the plain XLA forward (autodiff = XLA's own
    backward, bit-identical to a build without the kernel); max pooling
    under 'pallas'/'interpret' wraps it in a custom_vjp whose backward is
    the recompute-argmax Pallas kernel.  The forward saves the phase-major
    (s2d) input view as the residual so the transpose fuses into the
    producer's epilogue."""
    fwd_fn = lambda x: _pool_xla_forward(  # noqa: E731
        x, pool_type, kernel, stride, pad, convention, count_include_pad)
    if mode == "off" or pool_type != "max":
        return fwd_fn
    from . import pallas_kernels as _pk
    interpret = True if mode == "interpret" else None

    @jax.custom_vjp
    def core(x):
        return fwd_fn(x)

    def fwd(x):
        oshape = _pool_out_shape(x.shape[2:], kernel, stride, pad,
                                 convention)
        xs = _pk.pool_s2d(x, kernel, stride, pad, oshape, -jnp.inf)
        # x rides along for its shape/dtype only; XLA DCEs the unused
        # residual (the make_loss precedent above)
        return fwd_fn(x), (x, xs)

    def bwd(res, dy):
        x, xs = res
        oshape = _pool_out_shape(x.shape[2:], kernel, stride, pad,
                                 convention)
        dx = _pk.max_pool_backward(xs, dy, x.shape, x.dtype, kernel,
                                   stride, pad, oshape,
                                   interpret=interpret)
        return (dx.astype(x.dtype),)

    core.defvjp(fwd, bwd)
    return core


def _pooling(data, pool_type="max", kernel=(1, 1), stride=None, pad=None,
             global_pool=False, pooling_convention="valid", cudnn_off=False,
             count_include_pad=True):
    nd = len(kernel)
    if global_pool:
        kernel = data.shape[2:]
        stride = (1,) * len(kernel)
        pad = (0,) * len(kernel)
        nd = len(kernel)
    stride = tuple(stride or (1,) * nd)
    pad = tuple(pad or (0,) * nd)
    kernel = tuple(int(k) for k in kernel)
    if pool_type not in ("max", "avg", "sum"):
        raise MXNetError("unknown pool_type %s" % pool_type)
    from . import pallas_kernels as _pk
    mode = _pk.kernel_mode("pool")
    if mode != "off" and not (
            data.ndim == 4 and nd == 2
            and jnp.issubdtype(data.dtype, jnp.floating)
            and int(np.prod(kernel)) <= 64):  # tap loop is unrolled
        mode = "off"
    core = _pool_core(pool_type, kernel, stride, pad,
                      str(pooling_convention), bool(count_include_pad),
                      mode)
    return core(data)


def _pool_infer_shape(in_shapes, attrs):
    dshape = in_shapes[0]
    if dshape is None:
        return in_shapes, [None]
    kernel = attrs["kernel"]
    nd = len(kernel)
    if attrs.get("global_pool", False):
        return in_shapes, [tuple(dshape[:2]) + (1,) * (len(dshape) - 2)]
    stride = attrs.get("stride") or (1,) * nd
    pad = attrs.get("pad") or (0,) * nd
    conv = attrs.get("pooling_convention", "valid")
    sp = []
    for i in range(nd):
        if conv == "full":
            o = int(np.ceil((dshape[2 + i] + 2 * pad[i] - kernel[i]) / stride[i])) + 1
        else:
            o = (dshape[2 + i] + 2 * pad[i] - kernel[i]) // stride[i] + 1
        sp.append(o)
    return in_shapes, [tuple(dshape[:2]) + tuple(sp)]


register("Pooling", _pooling, num_inputs=1, infer_shape=_pool_infer_shape,
         aliases=("Pooling_v1",),
         params={"pool_type": (pStr, "max"), "kernel": (pShape, (1, 1)),
                 "stride": (pShape, None), "pad": (pShape, None),
                 "global_pool": (pBool, False),
                 "pooling_convention": (pStr, "valid"),
                 "cudnn_off": (pBool, False),
                 "count_include_pad": (pBool, True)})

# ---------------------------------------------------------------------------
# BatchNorm (ref: batch_norm-inl.h). inputs: data, gamma, beta; aux:
# moving_mean, moving_var. Outputs: (out, mean, var, new_mm, new_mv) — the
# last two are state outputs the executor folds back into the aux arrays.
# ---------------------------------------------------------------------------

@_functools.lru_cache(maxsize=None)
def _bn_train_core(ndim, ax, eps, kernel_mode="off"):
    """Training-mode BN with a hand-written VJP (ref: batch_norm-inl.h
    backward).  Autodiff of the naive formulation makes XLA carry f32
    normalized activations as residuals and re-reduce twice — on TPU the
    train step is HBM-bound, so BN is rebuilt around minimal traffic:
    one-pass f32 stats (sum / sum-of-squares fused into a single read),
    scale/shift forward (y = x*A + B with per-channel A, B), and residuals
    of just the compute-dtype input plus per-channel mean/invstd.  The
    backward is exact, including the cotangent paths through the returned
    batch mean/var (which feed the moving-average update and
    output_mean_var consumers).

    kernel_mode != 'off' (MXNET_TPU_PALLAS_BN, NCHW only) routes BOTH
    reduction pairs — forward (sum x, sum x^2) and backward (sum dy,
    sum dy*x) — through the single-pass Pallas channel-sums kernel
    (ops/pallas_kernels.py): the bf16 activation is read once per pair
    with f32 VMEM accumulation, replacing XLA's convert_reduce_fusion.*
    kernels and their materialized f32 converts."""
    red = tuple(i for i in range(ndim) if i != ax)
    bshape = tuple(-1 if i == ax else 1 for i in range(ndim))
    interpret = True if kernel_mode == "interpret" else None
    if kernel_mode != "off":
        from . import pallas_kernels as _pk

    def stats(x):
        if kernel_mode != "off":
            m_count = 1.0
            for i in red:
                m_count *= x.shape[i]
            s1, s2 = _pk.bn_channel_sums(x, interpret=interpret)
            m = s1 / m_count
            var = jnp.maximum(s2 / m_count - jnp.square(m), 0.0)
            return m, var
        x32 = x.astype(jnp.float32)
        m = jnp.mean(x32, axis=red)
        sq = jnp.mean(jnp.square(x32), axis=red)
        var = jnp.maximum(sq - jnp.square(m), 0.0)
        return m, var

    @jax.custom_vjp
    def core(x, g, b):
        mean, var = stats(x)
        inv = lax.rsqrt(var + eps)
        A = (g.astype(jnp.float32) * inv).reshape(bshape)
        B = (b.astype(jnp.float32)
             - mean * g.astype(jnp.float32) * inv).reshape(bshape)
        y = (x.astype(jnp.float32) * A + B).astype(x.dtype)
        return y, mean, var

    def fwd(x, g, b):
        mean, var = stats(x)
        inv = lax.rsqrt(var + eps)
        A = (g.astype(jnp.float32) * inv).reshape(bshape)
        B = (b.astype(jnp.float32)
             - mean * g.astype(jnp.float32) * inv).reshape(bshape)
        y = (x.astype(jnp.float32) * A + B).astype(x.dtype)
        return (y, mean, var), (x, g, mean, inv)

    def bwd(res, cts):
        x, g, mean, inv = res
        dy, dmean, dvar = cts
        M = 1
        for i in red:
            M *= x.shape[i]
        x32 = x.astype(jnp.float32)
        dy32 = dy.astype(jnp.float32)
        xc = x32 - mean.reshape(bshape)          # x - mean (recomputed)
        if kernel_mode != "off":
            # one fused pass instead of two reductions: sum dy*(x-mean)
            # expands to sum dy*x - mean*sum dy
            sum_dy, sum_dy_x = _pk.bn_channel_sums(dy, x,
                                                   interpret=interpret)
            sum_dy_xc = sum_dy_x - mean * sum_dy
        else:
            sum_dy = jnp.sum(dy32, axis=red)
            sum_dy_xc = jnp.sum(dy32 * xc, axis=red)
        g32 = g.astype(jnp.float32)
        # y-path (batch stats depend on x), + mean/var output cotangents
        dx = (g32 * inv).reshape(bshape) * (
            dy32 - (sum_dy / M).reshape(bshape)
            - xc * (inv * inv * sum_dy_xc / M).reshape(bshape))
        dx = dx + (dmean / M).reshape(bshape) \
            + xc * (2.0 * dvar / M).reshape(bshape)
        dg = sum_dy_xc * inv
        db = sum_dy
        return dx.astype(x.dtype), dg.astype(g.dtype), db.astype(g.dtype)

    core.defvjp(fwd, bwd)
    return core


def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
                momentum=0.9, fix_gamma=True, use_global_stats=False,
                output_mean_var=False, axis=1, cudnn_off=False, _train=False):
    ax = int(axis) % data.ndim
    bshape = tuple(data.shape[ax] if i == ax else 1 for i in range(data.ndim))
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    if _train and not use_global_stats:
        from . import pallas_kernels as _pk
        kmode = _pk.kernel_mode("bn")
        if kmode != "off" and not (ax == 1
                                   and _pk.bn_sums_eligible(data.shape)
                                   and jnp.issubdtype(data.dtype,
                                                      jnp.floating)):
            kmode = "off"  # the channel-sums kernel is NCHW-shaped
        core = _bn_train_core(data.ndim, ax, float(eps), kmode)
        out, mean, var = core(data, g, beta)
        new_mm = moving_mean * momentum + mean.astype(moving_mean.dtype) * (1 - momentum)
        new_mv = moving_var * momentum + var.astype(moving_var.dtype) * (1 - momentum)
    else:
        mean, var = moving_mean.astype(jnp.float32), moving_var.astype(jnp.float32)
        new_mm, new_mv = moving_mean, moving_var
        inv = lax.rsqrt(var + eps)
        out = (data.astype(jnp.float32) - mean.reshape(bshape)) * inv.reshape(bshape)
        # g/beta are f32 in half-width nets (_bn_infer_type) — keep the
        # output in the data dtype so train and eval modes agree
        out = (out.astype(data.dtype) * g.reshape(bshape)
               + beta.reshape(bshape)).astype(data.dtype)
    if output_mean_var:
        return (out, mean.astype(data.dtype), var.astype(data.dtype),
                new_mm, new_mv)
    return out, new_mm, new_mv


def _bn_infer_type(in_dtypes, attrs):
    """gamma/beta/moving stats stay float32 when data is half-width
    (ref: batch_norm-inl.h InferType — fp16 nets keep f32 BN params; on
    TPU the same rule applies to bfloat16)."""
    from ..base import dtype_name
    d = in_dtypes[0]
    if d is None:
        return in_dtypes, None
    pt = np.float32 if dtype_name(d) in ("float16", "bfloat16") else d
    filled = [d, pt, pt, pt, pt][:len(in_dtypes)]
    n_out = 3 if attrs.get("output_mean_var") else 1
    return filled, [d] * n_out


def _bn_infer_shape(in_shapes, attrs):
    dshape = in_shapes[0]
    if dshape is None:
        return in_shapes, [None]
    ax = int(attrs.get("axis", 1)) % len(dshape)
    c = (dshape[ax],)
    filled = [dshape] + [c, c, c, c]
    if attrs.get("output_mean_var"):
        return filled, [dshape, c, c, c, c]
    return filled, [dshape, c, c]


register("BatchNorm", _batch_norm,
         input_names=("data", "gamma", "beta"),
         aux_names=("moving_mean", "moving_var"),
         num_outputs=lambda attrs: 3 if attrs.get("output_mean_var") else 1,
         mutate_map=(3, 4),
         takes_train_flag=True,
         infer_shape=_bn_infer_shape,
         infer_type=_bn_infer_type,
         aliases=("BatchNorm_v1",),
         params={"eps": (pFloat, 1e-3), "momentum": (pFloat, 0.9),
                 "fix_gamma": (pBool, True), "use_global_stats": (pBool, False),
                 "output_mean_var": (pBool, False), "axis": (pInt, 1),
                 "cudnn_off": (pBool, False)})


def _instance_norm(data, gamma, beta, eps=1e-3):
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    out = (data - mean) * lax.rsqrt(var + eps)
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


def _in_infer_shape(in_shapes, attrs):
    dshape = in_shapes[0]
    if dshape is None:
        return in_shapes, [None]
    return [dshape, (dshape[1],), (dshape[1],)], [dshape]


register("InstanceNorm", _instance_norm, input_names=("data", "gamma", "beta"),
         infer_shape=_in_infer_shape, params={"eps": (pFloat, 1e-3)})


def _layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.mean(jnp.square(data - mean), axis=axis, keepdims=True)
    out = (data - mean) * jax.lax.rsqrt(var + eps)
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    out = out * gamma.reshape(bshape) + beta.reshape(bshape)
    if output_mean_var:
        return out, jnp.squeeze(mean, axis), jnp.squeeze(var, axis)
    return out


def _ln_infer_shape(in_shapes, attrs):
    dshape = in_shapes[0]
    if dshape is None:
        return in_shapes, None
    axis = int(attrs.get("axis", -1))
    c = dshape[axis]
    filled = [dshape, (c,), (c,)]
    n_out = 1
    if attrs.get("output_mean_var"):
        red = tuple(s for i, s in enumerate(dshape)
                    if i != (axis % len(dshape)))
        return filled, [dshape, red, red]
    return filled, [dshape]


register("LayerNorm", _layer_norm, input_names=("data", "gamma", "beta"),
         infer_shape=_ln_infer_shape,
         num_outputs=lambda attrs: 3 if attrs.get("output_mean_var") else 1,
         params={"axis": (pInt, -1), "eps": (pFloat, 1e-5),
                 "output_mean_var": (pBool, False)})


def _l2_normalization(data, eps=1e-10, mode="instance"):
    if mode == "instance":
        n = jnp.sqrt(jnp.sum(jnp.square(data.reshape(data.shape[0], -1)), axis=1) + eps)
        return data / n.reshape((-1,) + (1,) * (data.ndim - 1))
    if mode == "channel":
        n = jnp.sqrt(jnp.sum(jnp.square(data), axis=1, keepdims=True) + eps)
        return data / n
    n = jnp.sqrt(jnp.sum(jnp.square(data), axis=(1,), keepdims=True) + eps)  # spatial
    return data / n


register("L2Normalization", _l2_normalization, num_inputs=1,
         params={"eps": (pFloat, 1e-10), "mode": (pStr, "instance")})


def _lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    sq = jnp.square(data)
    half = int(nsize) // 2
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    window = jnp.zeros_like(sq)
    for i in range(int(nsize)):
        window = window + lax.dynamic_slice_in_dim(padded, i, sq.shape[1], axis=1)
    norm = jnp.power(knorm + alpha * window, beta)
    return data / norm


register("LRN", _lrn, num_inputs=1,
         params={"alpha": (pFloat, 1e-4), "beta": (pFloat, 0.75),
                 "knorm": (pFloat, 2.0), "nsize": (pInt, 5)})

# ---------------------------------------------------------------------------
# Dropout (ref: dropout-inl.h) — functional RNG key threaded by dispatch
# ---------------------------------------------------------------------------

def _dropout(key, data, p=0.5, mode="training", axes=None, _train=False):
    if not _train and mode != "always":
        return data
    if p <= 0.0:
        return data
    shape = data.shape
    if axes:
        shape = tuple(1 if i in axes else s for i, s in enumerate(data.shape))
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, shape).astype(data.dtype) / keep
    return data * mask


register("Dropout", _dropout, num_inputs=1, needs_rng=True, takes_train_flag=True,
         params={"p": (pFloat, 0.5), "mode": (pStr, "training"),
                 "axes": (pShape, None)})

# ---------------------------------------------------------------------------
# Embedding (ref: indexing_op.h) — gather; grad is scatter-add (XLA native)
# ---------------------------------------------------------------------------

def _embedding(data, weight, input_dim=1, output_dim=1, dtype="float32",
               sparse_grad=False):
    idx = data.astype(jnp.int32)
    return jnp.take(weight, idx, axis=0)


def _embedding_infer_shape(in_shapes, attrs):
    dshape = in_shapes[0]
    filled = list(in_shapes)
    filled[1] = (int(attrs["input_dim"]), int(attrs["output_dim"]))
    if dshape is None:
        return filled, [None]
    return filled, [tuple(dshape) + (int(attrs["output_dim"]),)]


register("Embedding", _embedding, input_names=("data", "weight"),
         infer_shape=_embedding_infer_shape,
         params={"input_dim": (pInt, 1), "output_dim": (pInt, 1),
                 "dtype": (pDtype, "float32"), "sparse_grad": (pBool, False)})

# ---------------------------------------------------------------------------
# UpSampling (nearest / bilinear-ish via resize)
# ---------------------------------------------------------------------------

def _upsampling(*args, scale=1, sample_type="nearest", num_args=1,
                num_filter=0, multi_input_mode="concat", workspace=512):
    data = args[0]
    n, c, h, w = data.shape
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
    else:
        out = jax.image.resize(data, (n, c, h * scale, w * scale), "bilinear")
    return out


register("UpSampling", _upsampling, num_inputs=None, key_var_num_args="num_args",
         params={"scale": (pInt, 1), "sample_type": (pStr, "nearest"),
                 "num_args": (pInt, 1), "num_filter": (pInt, 0),
                 "multi_input_mode": (pStr, "concat"), "workspace": (pInt, 512)})

# ---------------------------------------------------------------------------
# Loss heads with reference-exact custom backward
# (ref: softmax_output-inl.h:158-257, regression_output-inl.h:106-119)
# ---------------------------------------------------------------------------

def _softmax_fwd(data, label, multi_output, preserve_shape):
    if multi_output:
        return jax.nn.softmax(data, axis=1)
    if preserve_shape:
        return jax.nn.softmax(data, axis=-1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


def _softmax_cross_entropy(data, label):
    """Summed cross-entropy of softmax(data) picked at integer labels
    (ref: loss_binary_op.cc:30 softmax_cross_entropy — 2-D data, 1-D
    label, scalar [1] output; backward is softmax minus one-hot via
    autodiff of this forward)."""
    logp = jax.nn.log_softmax(data.astype(jnp.float32), axis=-1)
    idx = lax.stop_gradient(label).astype(jnp.int32)
    picked = jnp.take_along_axis(logp, idx[:, None], axis=-1)
    return (-jnp.sum(picked)).reshape(1).astype(data.dtype)


def _sce_infer_shape(in_shapes, attrs):
    d, l = in_shapes
    filled = list(in_shapes)
    if d is not None and l is None:
        filled[1] = (d[0],)
    return filled, [(1,)]


register("softmax_cross_entropy", _softmax_cross_entropy,
         input_names=("data", "label"), infer_shape=_sce_infer_shape)


def _softmax_output_grad(out, label, grad_scale, ignore_label, use_ignore,
                         normalization, multi_output):
    if multi_output:
        # data: (n, k, x...); label: (n, x...)
        k = out.shape[1]
        lab = label.astype(jnp.int32)
        onehot = jax.nn.one_hot(lab, k, dtype=out.dtype, axis=1)
        grad = out - onehot
        valid = jnp.ones(lab.shape, out.dtype)
        if use_ignore:
            valid = (label != ignore_label).astype(out.dtype)
            grad = grad * valid[:, None]
        if normalization == "batch":
            grad = grad / out.shape[0]
        elif normalization == "valid":
            grad = grad / jnp.maximum(valid.sum(), 1.0)
        return grad * grad_scale
    k = out.shape[-1]
    lab = label.astype(jnp.int32)
    onehot = jax.nn.one_hot(lab, k, dtype=out.dtype)
    grad = out - onehot.reshape(out.shape)
    valid = jnp.ones(lab.shape, out.dtype)
    if use_ignore:
        valid = (label != ignore_label).astype(out.dtype)
        grad = grad * valid.reshape(valid.shape + (1,) * (grad.ndim - valid.ndim))
    if normalization == "batch":
        grad = grad / out.shape[0]
    elif normalization == "valid":
        grad = grad / jnp.maximum(valid.sum(), 1.0)
    return grad * grad_scale


@_functools.lru_cache(maxsize=None)
def _softmax_output_core(grad_scale, ignore_label, use_ignore, normalization,
                         multi_output, preserve_shape):
    """custom_vjp core per static-attr combination; MXNet semantics: the head
    gradient is ignored — SoftmaxOutput *is* the loss."""

    @jax.custom_vjp
    def core(data, label):
        return _softmax_fwd(data, label, multi_output, preserve_shape)

    def fwd(data, label):
        out = _softmax_fwd(data, label, multi_output, preserve_shape)
        return out, (out, label)

    def bwd(res, g):
        out, label = res
        grad = _softmax_output_grad(out, label, grad_scale, ignore_label,
                                    use_ignore, normalization, multi_output)
        return (grad.astype(out.dtype), jnp.zeros_like(label))

    core.defvjp(fwd, bwd)
    return core


def _softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                    multi_output=False, use_ignore=False, preserve_shape=False,
                    normalization="null", out_grad=False, smooth_alpha=0.0):
    core = _softmax_output_core(grad_scale, ignore_label, use_ignore,
                                normalization, multi_output, preserve_shape)
    return core(data, label)


def _softmax_output_infer_shape(in_shapes, attrs):
    dshape = in_shapes[0]
    if dshape is None:
        return in_shapes, [None]
    filled = list(in_shapes)
    if attrs.get("multi_output", False):
        filled[1] = (dshape[0],) + tuple(dshape[2:])
    else:
        filled[1] = (dshape[0],)
    return filled, [dshape]


register("SoftmaxOutput", _softmax_output, input_names=("data", "label"),
         infer_shape=_softmax_output_infer_shape,
         aliases=("Softmax",),
         params={"grad_scale": (pFloat, 1.0), "ignore_label": (pFloat, -1.0),
                 "multi_output": (pBool, False), "use_ignore": (pBool, False),
                 "preserve_shape": (pBool, False),
                 "normalization": (pStr, "null"), "out_grad": (pBool, False),
                 "smooth_alpha": (pFloat, 0.0)})


def _regression_core(link, grad_fn, name):
    @_functools.lru_cache(maxsize=None)
    def factory(grad_scale):
        @jax.custom_vjp
        def core(data, label):
            return link(data)

        def fwd(data, label):
            out = link(data)
            return out, (out, label)

        def bwd(res, g):
            out, label = res
            # ref: regression_output-inl.h:119 — scale grad_scale/num_output
            num_output = int(np.prod(out.shape[1:])) if out.ndim > 1 else 1
            grad = grad_fn(out, label.reshape(out.shape)) * (grad_scale / num_output)
            return (grad.astype(out.dtype), jnp.zeros_like(label))

        core.defvjp(fwd, bwd)
        return core

    factory.__name__ = name
    return factory


_linear_reg = _regression_core(lambda x: x, lambda o, l: o - l, "linear_reg")
_mae_reg = _regression_core(lambda x: x, lambda o, l: jnp.sign(o - l), "mae_reg")
_logistic_reg = _regression_core(jax.nn.sigmoid, lambda o, l: o - l, "logistic_reg")


def _reg_infer_shape(in_shapes, attrs):
    dshape = in_shapes[0]
    if dshape is None:
        return in_shapes, [None]
    filled = list(in_shapes)
    if filled[1] is None:
        filled[1] = dshape if len(dshape) != 2 or dshape[1] != 1 else (dshape[0],)
        filled[1] = dshape
    return filled, [dshape]


for _name, _core in (("LinearRegressionOutput", _linear_reg),
                     ("MAERegressionOutput", _mae_reg),
                     ("LogisticRegressionOutput", _logistic_reg)):
    register(_name,
             (lambda factory: lambda data, label, grad_scale=1.0:
              factory(grad_scale)(data, label))(_core),
             input_names=("data", "label"), infer_shape=_reg_infer_shape,
             params={"grad_scale": (pFloat, 1.0)})


@_functools.lru_cache(maxsize=None)
def _make_loss_core(grad_scale):
    @jax.custom_vjp
    def core(data):
        return data

    def fwd(data):
        return data, data  # residual only carries shape/dtype; XLA DCEs it

    def bwd(res, g):
        return (jnp.full_like(res, grad_scale),)

    core.defvjp(fwd, bwd)
    return core


def _make_loss_op(data, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    return _make_loss_core(grad_scale)(data)


register("MakeLoss", _make_loss_op, num_inputs=1,
         params={"grad_scale": (pFloat, 1.0), "valid_thresh": (pFloat, 0.0),
                 "normalization": (pStr, "null")})


def _svm_output(data, label, margin=1.0, regularization_coefficient=1.0,
                use_linear=False):
    return data


register("SVMOutput", _svm_output, input_names=("data", "label"),
         infer_shape=_softmax_output_infer_shape,
         params={"margin": (pFloat, 1.0),
                 "regularization_coefficient": (pFloat, 1.0),
                 "use_linear": (pBool, False)})

# ---------------------------------------------------------------------------
# Sequence ops (ref: sequence_last/mask/reverse-inl.h); data layout TNC
# ---------------------------------------------------------------------------

def _seq_last(data, *rest, use_sequence_length=False, axis=0):
    if not use_sequence_length:
        return jnp.take(data, data.shape[int(axis)] - 1, axis=int(axis))
    seqlen = rest[0].astype(jnp.int32)
    idx = seqlen - 1
    if int(axis) == 0:
        return data[idx, jnp.arange(data.shape[1])]
    return data[jnp.arange(data.shape[0]), idx]


register("SequenceLast", _seq_last, input_names=("data", "sequence_length"),
         params={"use_sequence_length": (pBool, False), "axis": (pInt, 0)})


def _seq_mask(data, *rest, use_sequence_length=False, value=0.0, axis=0):
    if not use_sequence_length:
        return data
    seqlen = rest[0].astype(jnp.int32)
    T = data.shape[int(axis)]
    t = jnp.arange(T)
    if int(axis) == 0:
        mask = t[:, None] < seqlen[None, :]
        mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    else:
        mask = t[None, :] < seqlen[:, None]
        mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return jnp.where(mask, data, jnp.asarray(value, data.dtype))


register("SequenceMask", _seq_mask, input_names=("data", "sequence_length"),
         params={"use_sequence_length": (pBool, False), "value": (pFloat, 0.0),
                 "axis": (pInt, 0)})


def _seq_reverse(data, *rest, use_sequence_length=False, axis=0):
    if not use_sequence_length:
        return jnp.flip(data, 0)
    seqlen = rest[0].astype(jnp.int32)
    T = data.shape[0]
    t = jnp.arange(T)[:, None]
    rev_idx = jnp.where(t < seqlen[None, :], seqlen[None, :] - 1 - t, t)
    return jnp.take_along_axis(
        data, rev_idx.reshape(rev_idx.shape + (1,) * (data.ndim - 2)), axis=0)


register("SequenceReverse", _seq_reverse, input_names=("data", "sequence_length"),
         params={"use_sequence_length": (pBool, False), "axis": (pInt, 0)})
