"""Registry-driven operator sweep.

The reference's operator coverage lives in a 4,886-LoC test_operator.py plus
a GPU re-import pass (SURVEY.md §4.1-4.2).  Here the same bar is enforced
structurally: every canonical op in the registry must either have a sweep
case below (forward via the imperative jit-cache path, forward via the
symbol/whole-graph-jit path — compared against each other — and a
finite-difference gradient check where differentiable) or appear in the
ledger with the test file that covers it / the reason it cannot run under
the generic harness.  `test_every_op_is_accounted_for` fails when a newly
registered op is missing from all three.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ndarray import _invoke
from mxnet_tpu.ops import registry as _registry
from mxnet_tpu.test_utils import assert_almost_equal, check_numeric_gradient

RNG = np.random.RandomState(7)


def pos(*s):
    """(0.1, 0.9): in-domain for log/sqrt/arcsin/... and away from kinks."""
    return (RNG.rand(*s) * 0.8 + 0.1).astype(np.float32)


def signed(*s):
    """(-0.9, -0.1) U (0.1, 0.9): away from 0 (abs/sign/relu kinks)."""
    base = RNG.rand(*s) * 0.8 + 0.1
    flip = RNG.rand(*s) < 0.5
    return (np.where(flip, -base, base)).astype(np.float32)


def gt1(*s):
    return (RNG.rand(*s) * 0.8 + 1.2).astype(np.float32)


def fidx(hi, *s):
    """Float-typed integer indices (the reference's index convention)."""
    return RNG.randint(0, hi, s).astype(np.float32)


def spd(n):
    a = RNG.rand(n, n).astype(np.float32)
    return a @ a.T + n * np.eye(n, dtype=np.float32)


def tril(n):
    return np.tril(RNG.rand(n, n).astype(np.float32) + 0.5)


class Case:
    def __init__(self, inputs, attrs=None, grad=True, grad_nodes=None,
                 rtol=5e-2, atol=1e-3, fwd_rtol=1e-4, mode="pair",
                 train=False, check=None):
        self.inputs = inputs          # list of np arrays
        self.attrs = dict(attrs or {})
        self.grad = grad              # run check_numeric_gradient
        self.grad_nodes = grad_nodes  # subset of in<i> names (None = floats)
        self.rtol = rtol
        self.atol = atol
        self.fwd_rtol = fwd_rtol      # imperative vs symbolic tolerance
        self.mode = mode              # pair | imperative
        self.train = train
        self.check = check            # extra fn(list[np outputs])


CASES = {}


def case(name, *args, **kw):
    CASES.setdefault(name, []).append(Case(*args, **kw))


# which test file covers ops the generic harness cannot (stateful layers,
# multi-phase protocols, iterator-coupled ops, ...)
TESTED_ELSEWHERE = {
    "round": "tests/test_operator.py (test_round_half_away_from_zero)",
    "reshape_like": "tests/test_operator.py (test_reshape_like)",
    "softmax_cross_entropy":
        "tests/test_operator.py (test_softmax_cross_entropy)",
    "linalg_gelqf": "tests/test_operator.py (test_linalg_gelqf_syevd)",
    "linalg_syevd": "tests/test_operator.py (test_linalg_gelqf_syevd)",
    "khatri_rao": "tests/test_operator.py (test_khatri_rao)",
    "_contrib_bipartite_matching":
        "tests/test_operator.py (test_bipartite_matching)",
    "RNN": "tests/test_rnn.py",
    # the decoder-block ops of ops/lm_ops.py: each against the plain
    # reference (benchmark/references/qwen3_next.py), values and gradients
    "RMSNorm": "tests/test_qwen3_next.py (test_rms_norm)",
    "rotary_embedding":
        "tests/test_qwen3_next.py (test_rotary_touches_the_first_dims_only)",
    "SwiGLU": "tests/test_qwen3_next.py (the expert and DeltaNet layers)",
    "causal_conv1d":
        "tests/test_qwen3_next.py (test_causal_conv1d_sees_no_later_token)",
    "gated_delta_rule":
        "tests/test_qwen3_next.py (the chunked scan against the recurrence)",
    "moe_experts": "tests/test_qwen3_next.py (the routed part, the shares)",
    "ssd": "tests/test_granite_hybrid.py (the op against the token-by-token "
           "recurrence, on the scan and through the kernels)",
    "sequence_cross_entropy":
        "tests/test_qwen3_next.py "
        "(test_sequence_cross_entropy_and_its_gradient)",
    "Custom": "tests/test_contrib_custom.py",
    "BatchNorm": "tests/test_module.py (train/eval aux semantics)",
    "Dropout": "tests/test_operator.py",
    "_contrib_CTCLoss": "tests/test_contrib_custom.py",
    "_contrib_fft": "tests/test_contrib_custom.py",
    "_contrib_ifft": "tests/test_contrib_custom.py",
    "_contrib_quantize": "tests/test_contrib_custom.py",
    "_contrib_dequantize": "tests/test_contrib_custom.py",
    "_contrib_quantized_conv":
        "tests/test_pallas_kernels.py (int8 predict + served replay)",
    "_contrib_quantized_fc":
        "tests/test_pallas_kernels.py (int8 predict + served replay)",
    "_contrib_count_sketch": "tests/test_detection.py",
    "_contrib_Proposal": "tests/test_detection.py",
    "_contrib_MultiProposal": "tests/test_detection.py",
    "_contrib_PSROIPooling": "tests/test_detection.py",
    "_contrib_DeformableConvolution": "tests/test_detection.py",
    "_contrib_DeformablePSROIPooling": "tests/test_detection.py",
    "_contrib_MultiBoxPrior": "tests/test_detection.py",
    "_contrib_MultiBoxTarget": "tests/test_detection.py",
    "_contrib_MultiBoxDetection": "tests/test_detection.py",
    "_contrib_box_iou": "tests/test_detection.py",
    "_contrib_box_nms": "tests/test_detection.py",
    "cast_storage": "tests/test_operator.py (storage ops)",
    "sparse_retain": "tests/test_operator.py (storage ops)",
    "_square_sum": "tests/test_operator.py (storage ops)",
    "sgd_update": "tests/test_optimizer.py (vs numpy reference)",
    "sgd_mom_update": "tests/test_optimizer.py",
    "mp_sgd_update": "tests/test_optimizer.py (multi-precision)",
    "mp_sgd_mom_update": "tests/test_optimizer.py",
    "adam_update": "tests/test_optimizer.py",
    "adamax_update": "tests/test_optimizer.py",
    "nadam_update": "tests/test_optimizer.py",
    "ftml_update": "tests/test_optimizer.py",
    "ftrl_update": "tests/test_optimizer.py",
    "rmsprop_update": "tests/test_optimizer.py",
    "rmspropalex_update": "tests/test_optimizer.py",
    "signsgd_update": "tests/test_optimizer.py",
    "signum_update": "tests/test_optimizer.py",
    "nag_mom_update": "tests/test_optimizer.py",
    "sgld_update": "tests/test_optimizer.py",
    "scaled_dot_product_attention":
        "tests/test_attention.py (vs exact-softmax reference, fwd+grad)",
    "multi_head_attention":
        "tests/test_attention.py (vs manual-projection oracle + flag contract)",
}

# ---------------------------------------------------------------------------
# elementwise unary: (data_fn, grad?) — grad=False only where the true
# derivative is 0 a.e. or undefined (comparisons, rounding, sign)
# ---------------------------------------------------------------------------
UNARY = {
    "abs": (signed, True), "arccos": (pos, True), "arccosh": (gt1, True),
    "arcsin": (pos, True), "arcsinh": (signed, True), "arctan": (signed, True),
    "arctanh": (pos, True), "cbrt": (pos, True), "ceil": (pos, False),
    "cos": (signed, True), "cosh": (signed, True), "degrees": (signed, True),
    "erf": (signed, True), "exp": (signed, True), "expm1": (signed, True),
    "fix": (pos, False), "floor": (pos, False), "gamma": (gt1, True),
    "gammaln": (gt1, True), "log": (pos, True), "log10": (pos, True),
    "log1p": (pos, True), "log2": (pos, True), "logical_not": (pos, False),
    "negative": (signed, True), "radians": (signed, True),
    "rcbrt": (pos, True), "reciprocal": (pos, True), "relu": (signed, True),
    "rint": (pos, False), "rsqrt": (pos, True), "sigmoid": (signed, True),
    "sign": (signed, False), "sin": (signed, True), "sinh": (signed, True),
    "softsign": (signed, True), "sqrt": (pos, True), "square": (signed, True),
    "tan": (pos, True), "tanh": (signed, True), "trunc": (pos, False),
    "zeros_like": (signed, False), "ones_like": (signed, False),
    "shape_array": (signed, False), "size_array": (signed, False),
    "_copy": (signed, True), "BlockGrad": (signed, False),
    "make_loss": (signed, False), "Flatten": (signed, True),
    "argmax_channel": (pos, False),
}
for name, (fn, grad) in UNARY.items():
    case(name, [fn(3, 4)], grad=grad)

# scalar-attr elementwise
for name, data_fn, attrs, grad in [
    ("_plus_scalar", signed, {"scalar": 1.5}, True),
    ("_minus_scalar", signed, {"scalar": 1.5}, True),
    ("_rminus_scalar", signed, {"scalar": 1.5}, True),
    ("_mul_scalar", signed, {"scalar": -2.0}, True),
    ("_div_scalar", signed, {"scalar": 2.0}, True),
    ("_rdiv_scalar", pos, {"scalar": 2.0}, True),
    ("_mod_scalar", pos, {"scalar": 0.4}, False),
    ("_rmod_scalar", pos, {"scalar": 0.7}, False),
    ("_power_scalar", pos, {"scalar": 2.5}, True),
    ("_rpower_scalar", pos, {"scalar": 2.0}, True),
    ("_maximum_scalar", signed, {"scalar": 0.05}, True),
    ("_minimum_scalar", signed, {"scalar": 0.05}, True),
    ("_hypot_scalar", signed, {"scalar": 1.0}, True),
    ("_equal_scalar", pos, {"scalar": 0.5}, False),
    ("_not_equal_scalar", pos, {"scalar": 0.5}, False),
    ("_greater_scalar", pos, {"scalar": 0.5}, False),
    ("_greater_equal_scalar", pos, {"scalar": 0.5}, False),
    ("_lesser_scalar", pos, {"scalar": 0.5}, False),
    ("_lesser_equal_scalar", pos, {"scalar": 0.5}, False),
    ("smooth_l1", signed, {"scalar": 1.0}, True),
    ("clip", signed, {"a_min": -0.5, "a_max": 0.5}, True),
    ("Cast", signed, {"dtype": "float64"}, False),
]:
    case(name, [data_fn(3, 4)], attrs=attrs, grad=grad)

# binary elementwise (same shape)
for name, grad in [
    ("elemwise_add", True), ("elemwise_sub", True), ("elemwise_mul", True),
    ("elemwise_div", True), ("elemwise_power", True),
    ("elemwise_maximum", True), ("elemwise_minimum", True),
    ("elemwise_hypot", True), ("elemwise_mod", False), ("_grad_add", True),
    ("_equal", False), ("_not_equal", False), ("_greater", False),
    ("_greater_equal", False), ("_lesser", False), ("_lesser_equal", False),
]:
    case(name, [pos(3, 4), pos(3, 4) + 0.05], grad=grad)

# broadcasting binary
for name, grad in [
    ("broadcast_add", True), ("broadcast_sub", True), ("broadcast_mul", True),
    ("broadcast_div", True), ("broadcast_power", True),
    ("broadcast_maximum", True), ("broadcast_minimum", True),
    ("broadcast_hypot", True), ("broadcast_mod", False),
    ("broadcast_equal", False), ("broadcast_not_equal", False),
    ("broadcast_greater", False), ("broadcast_greater_equal", False),
    ("broadcast_lesser", False), ("broadcast_lesser_equal", False),
]:
    case(name, [pos(2, 3, 1), pos(1, 3, 4) + 0.05], grad=grad)

# reductions (max/min: distinct values keep the argmax stable under eps)
for name in ["sum", "mean", "prod", "nansum", "nanprod", "max", "min"]:
    case(name, [pos(3, 4)], attrs={"axis": 1}, grad=True)
    case(name, [pos(3, 4)], attrs={"axis": 0, "keepdims": True}, grad=False)
case("norm", [signed(3, 4)], grad=True)

# shape / layout ops
case("Reshape", [signed(3, 4)], attrs={"shape": (4, 3)})
case("expand_dims", [signed(3, 4)], attrs={"axis": 1})
case("squeeze", [signed(3, 1, 4)], attrs={"axis": 1})
case("transpose", [signed(2, 3, 4)], attrs={"axes": (2, 0, 1)})
case("SwapAxis", [signed(2, 3, 4)], attrs={"dim1": 0, "dim2": 2})
case("slice", [signed(4, 5)], attrs={"begin": (1, 0), "end": (3, 4)})
case("slice_axis", [signed(4, 5)], attrs={"axis": 1, "begin": 1, "end": 4})
case("slice_like", [signed(4, 5), signed(2, 3)], attrs={"axes": (0, 1)},
     grad_nodes=["in0"])
case("tile", [signed(2, 3)], attrs={"reps": (2, 2)})
case("repeat", [signed(2, 3)], attrs={"repeats": 2, "axis": 1})
case("reverse", [signed(3, 4)], attrs={"axis": 1})
case("broadcast_to", [signed(1, 4)], attrs={"shape": (3, 4)})
case("broadcast_axis", [signed(1, 4)], attrs={"axis": 0, "size": 3})
case("depth_to_space", [signed(1, 8, 2, 2)], attrs={"block_size": 2})
case("space_to_depth", [signed(1, 2, 4, 4)], attrs={"block_size": 2})
case("Pad", [signed(1, 2, 3, 3)],
     attrs={"mode": "constant", "pad_width": (0, 0, 0, 0, 1, 1, 1, 1)})
case("where", [fidx(2, 3, 4), signed(3, 4), signed(3, 4)],
     grad=True, grad_nodes=["in1", "in2"])
case("Concat", [signed(2, 3), signed(2, 5)], attrs={"dim": 1})
case("stack", [signed(2, 3), signed(2, 3)], attrs={"axis": 1})
case("add_n", [signed(2, 3), signed(2, 3), signed(2, 3)])
case("khatri_rao", [signed(2, 3), signed(4, 3)])
case("SliceChannel", [signed(2, 6)],
     attrs={"num_outputs": 3, "axis": 1}, grad=False)
case("Crop", [signed(1, 2, 6, 6)], attrs={"h_w": (3, 3), "num_args": 1},
     grad=False)
case("UpSampling", [signed(1, 2, 3, 3)],
     attrs={"scale": 2, "sample_type": "nearest", "num_args": 1})

# indexing
case("one_hot", [fidx(5, 4)], attrs={"depth": 5}, grad=False)
case("take", [signed(5, 3), fidx(5, 4)], grad=True, grad_nodes=["in0"])
case("batch_take", [signed(4, 3), fidx(3, 4)], grad=False)
case("pick", [signed(4, 5), fidx(5, 4)], attrs={"axis": 1},
     grad=True, grad_nodes=["in0"])
case("gather_nd", [signed(4, 5), fidx(4, 2, 3).reshape(2, 3)],
     grad=False)
case("scatter_nd", [signed(3), fidx(4, 1, 3).reshape(1, 3)],
     attrs={"shape": (4,)}, grad=False)
case("Embedding", [fidx(6, 2, 3), signed(6, 4)],
     attrs={"input_dim": 6, "output_dim": 4},
     grad=True, grad_nodes=["in1"])

# ordering
case("sort", [pos(3, 4)], attrs={"axis": 1})
case("argsort", [pos(3, 4)], attrs={"axis": 1}, grad=False)
case("argmax", [pos(3, 4)], attrs={"axis": 1}, grad=False)
case("argmin", [pos(3, 4)], attrs={"axis": 1}, grad=False)
case("topk", [pos(3, 5)], attrs={"axis": 1, "k": 2}, grad=False)

# linear algebra
case("dot", [signed(3, 4), signed(4, 2)])
case("batch_dot", [signed(2, 3, 4), signed(2, 4, 2)])
case("linalg_gemm", [signed(3, 4), signed(4, 2), signed(3, 2)],
     attrs={"alpha": 1.5, "beta": 0.5})
case("linalg_gemm2", [signed(3, 4), signed(4, 2)], attrs={"alpha": 2.0})
case("linalg_syrk", [signed(3, 4)], attrs={"alpha": 1.0})
case("linalg_potrf", [spd(3)], grad=False)      # SPD-manifold numeric grad
case("linalg_potri", [spd(3)], grad=False)      # is not well-posed under
case("linalg_trmm", [tril(3), signed(3, 4)], grad=True)
case("linalg_trsm", [tril(3), signed(3, 4)], grad=False)
case("linalg_sumlogdiag", [spd(3)], grad=True)

# nn layers through the pair harness (explicit weight/bias inputs)
case("Activation", [signed(3, 4)], attrs={"act_type": "tanh"})
case("SoftmaxActivation", [signed(3, 4)])
case("softmax", [signed(3, 4)], attrs={"axis": 1})
case("log_softmax", [signed(3, 4)], attrs={"axis": 1})
case("LeakyReLU", [signed(3, 4)], attrs={"act_type": "leaky", "slope": 0.1})
case("_PReLU", [signed(3, 4), pos(1)], grad=True)
case("FullyConnected", [signed(2, 4), signed(3, 4), signed(3)],
     attrs={"num_hidden": 3})
case("Convolution", [signed(1, 2, 5, 5), signed(3, 2, 3, 3), signed(3)],
     attrs={"kernel": (3, 3), "num_filter": 3}, rtol=8e-2)
case("Deconvolution", [signed(1, 2, 4, 4), signed(2, 3, 2, 2), signed(3)],
     attrs={"kernel": (2, 2), "num_filter": 3}, rtol=8e-2)
case("Deconvolution", [signed(1, 2, 4, 4), signed(2, 3, 3, 3), signed(3)],
     attrs={"kernel": (3, 3), "num_filter": 3, "stride": (2, 2),
            "pad": (1, 1), "adj": (1, 1)}, rtol=8e-2)
case("Deconvolution", [signed(1, 4, 4, 4), signed(4, 2, 2, 2), signed(4)],
     attrs={"kernel": (2, 2), "num_filter": 4, "num_group": 2}, rtol=8e-2)
case("Deconvolution", [signed(1, 2, 3, 3), signed(2, 2, 3, 3), signed(2)],
     attrs={"kernel": (3, 3), "num_filter": 2, "stride": (2, 2),
            "target_shape": (6, 6)}, grad=False)
case("Deconvolution", [signed(1, 2, 5, 5), signed(2, 2, 2, 2), signed(2)],
     attrs={"kernel": (2, 2), "num_filter": 2, "dilate": (2, 2)},
     rtol=8e-2)
case("Pooling", [signed(1, 2, 4, 4)],
     attrs={"kernel": (2, 2), "stride": (2, 2), "pool_type": "max"})
case("Pooling", [pos(1, 2, 4, 4)],
     attrs={"kernel": (2, 2), "stride": (2, 2), "pool_type": "avg"})
case("LRN", [pos(1, 4, 3, 3)], attrs={"nsize": 3}, grad=False)
case("LayerNorm", [signed(3, 4), pos(4), signed(4)])
case("InstanceNorm", [signed(2, 3, 4, 4), pos(3), signed(3)], grad=False)
case("L2Normalization", [signed(3, 4)])
case("SoftmaxOutput", [signed(4, 5), fidx(5, 4)], grad=False, train=True)
case("LinearRegressionOutput", [signed(4, 3), signed(4, 3)], grad=False)
case("MAERegressionOutput", [signed(4, 3), signed(4, 3)], grad=False)
case("LogisticRegressionOutput", [signed(4, 3), pos(4, 3)], grad=False)
case("SVMOutput", [signed(4, 5), fidx(5, 4)], grad=False)
case("MakeLoss", [pos(3, 4)], grad=False)
case("IdentityAttachKLSparseReg", [pos(3, 4)], grad=False)
case("SequenceLast", [signed(5, 3, 4), np.array([2, 4, 5], np.float32)],
     attrs={"use_sequence_length": True}, grad=False)
case("SequenceMask", [signed(5, 3, 4), np.array([2, 4, 5], np.float32)],
     attrs={"use_sequence_length": True}, grad=False)
case("SequenceReverse", [signed(5, 3, 4), np.array([2, 4, 5], np.float32)],
     attrs={"use_sequence_length": True}, grad=False)
case("GridGenerator",
     [np.tile(np.array([[1, 0, 0, 0, 1, 0]], np.float32), (2, 1))],
     attrs={"transform_type": "affine", "target_shape": (4, 4)}, grad=False)
case("BilinearSampler",
     [signed(2, 3, 4, 4),
      np.stack([np.stack(np.meshgrid(np.linspace(-0.9, 0.9, 4),
                                     np.linspace(-0.9, 0.9, 4)))
                for _ in range(2)]).astype(np.float32)],
     grad=False)
case("SpatialTransformer",
     [signed(2, 3, 4, 4),
      np.tile(np.array([[1, 0, 0, 0, 1, 0]], np.float32), (2, 1))],
     attrs={"transform_type": "affine", "sampler_type": "bilinear",
            "target_shape": (4, 4)}, grad=False)
case("ROIPooling",
     [pos(1, 2, 6, 6), np.array([[0, 0, 0, 3, 3]], np.float32)],
     attrs={"pooled_size": (2, 2), "spatial_scale": 1.0}, grad=False)
case("Correlation", [pos(1, 2, 5, 5), pos(1, 2, 5, 5)],
     attrs={"kernel_size": 1, "max_displacement": 1, "stride1": 1,
            "stride2": 1, "pad_size": 1}, grad=False)

# image ops (HWC float)
for name in ["_image_flip_left_right", "_image_flip_top_bottom",
             "_image_to_tensor"]:
    case(name, [pos(4, 4, 3)], grad=False)
case("_image_normalize", [pos(3, 4, 4)],
     attrs={"mean": (0.5, 0.5, 0.5), "std": (0.2, 0.2, 0.2)}, grad=False)
case("_image_adjust_lighting", [pos(4, 4, 3)],
     attrs={"alpha": (0.1, 0.0, -0.1)}, grad=False)
for name in ["_image_random_brightness", "_image_random_contrast",
             "_image_random_saturation"]:
    case(name, [pos(4, 4, 3)], attrs={"min_factor": 0.8, "max_factor": 1.2},
         grad=False, mode="imperative")
case("_image_random_hue", [pos(4, 4, 3)],
     attrs={"min_factor": 0.9, "max_factor": 1.1},
     grad=False, mode="imperative")
case("_image_random_color_jitter", [pos(4, 4, 3)],
     attrs={"brightness": 0.1, "contrast": 0.1, "saturation": 0.1,
            "hue": 0.05}, grad=False, mode="imperative")
case("_image_random_lighting", [pos(4, 4, 3)], attrs={"alpha_std": 0.05},
     grad=False, mode="imperative")
for name in ["_image_random_flip_left_right", "_image_random_flip_top_bottom"]:
    case(name, [pos(4, 4, 3)], grad=False, mode="imperative")

# init ops (attrs only)
case("_zeros", [], attrs={"shape": (2, 3)}, grad=False,
     check=lambda outs: np.testing.assert_allclose(outs[0], np.zeros((2, 3))))
case("_ones", [], attrs={"shape": (2, 3)}, grad=False,
     check=lambda outs: np.testing.assert_allclose(outs[0], np.ones((2, 3))))
case("_full", [], attrs={"shape": (2, 3), "value": 2.5}, grad=False,
     check=lambda outs: np.testing.assert_allclose(outs[0], np.full((2, 3), 2.5)))
case("_eye", [], attrs={"N": 3}, grad=False,
     check=lambda outs: np.testing.assert_allclose(outs[0], np.eye(3)))
case("_arange", [], attrs={"start": 1.0, "stop": 5.0}, grad=False,
     check=lambda outs: np.testing.assert_allclose(outs[0], [1, 2, 3, 4]))

# random ops: imperative forward, moment checks
def _moment_check(lo, hi):
    def chk(outs):
        m = float(np.mean(outs[0]))
        assert lo < m < hi, "mean %.3f outside (%s, %s)" % (m, lo, hi)
    return chk


for name, attrs, chk in [
    ("_random_uniform", {"shape": (4000,), "low": 0.0, "high": 1.0},
     _moment_check(0.4, 0.6)),
    ("_random_normal", {"shape": (4000,), "loc": 1.0, "scale": 0.5},
     _moment_check(0.9, 1.1)),
    ("_random_gamma", {"shape": (4000,), "alpha": 2.0, "beta": 1.0},
     _moment_check(1.8, 2.2)),
    ("_random_exponential", {"shape": (4000,), "lam": 2.0},
     _moment_check(0.4, 0.6)),
    ("_random_poisson", {"shape": (4000,), "lam": 3.0},
     _moment_check(2.8, 3.2)),
    ("_random_negative_binomial", {"shape": (4000,), "k": 3, "p": 0.5},
     _moment_check(2.6, 3.4)),
    ("_random_generalized_negative_binomial",
     {"shape": (4000,), "mu": 2.0, "alpha": 0.4}, _moment_check(1.7, 2.3)),
    ("_random_randint", {"shape": (4000,), "low": 0, "high": 10},
     _moment_check(4.0, 5.0)),
]:
    case(name, [], attrs=attrs, grad=False, mode="imperative", check=chk)

case("_sample_uniform", [np.array([0.0, 5.0], np.float32),
                         np.array([1.0, 6.0], np.float32)],
     attrs={"shape": (3000,)}, grad=False, mode="imperative",
     check=lambda outs: np.testing.assert_allclose(
         outs[0].mean(axis=1), [0.5, 5.5], atol=0.1))
case("_sample_normal", [np.array([0.0, 4.0], np.float32),
                        np.array([1.0, 1.0], np.float32)],
     attrs={"shape": (3000,)}, grad=False, mode="imperative",
     check=lambda outs: np.testing.assert_allclose(
         outs[0].mean(axis=1), [0.0, 4.0], atol=0.15))
case("_sample_gamma", [np.array([1.0, 8.0], np.float32),
                       np.array([1.0, 2.0], np.float32)],
     attrs={"shape": (3000,)}, grad=False, mode="imperative",
     check=lambda outs: np.testing.assert_allclose(
         outs[0].mean(axis=1), [1.0, 16.0], rtol=0.15))
case("_sample_exponential", [np.array([1.0, 4.0], np.float32)],
     attrs={"shape": (3000,)}, grad=False, mode="imperative",
     check=lambda outs: np.testing.assert_allclose(
         outs[0].mean(axis=1), [1.0, 0.25], rtol=0.2))
case("_sample_poisson", [np.array([2.0, 10.0], np.float32)],
     attrs={"shape": (3000,)}, grad=False, mode="imperative",
     check=lambda outs: np.testing.assert_allclose(
         outs[0].mean(axis=1), [2.0, 10.0], rtol=0.15))
case("_sample_negative_binomial", [np.array([3.0], np.float32),
                                   np.array([0.4], np.float32)],
     attrs={"shape": (4000,)}, grad=False, mode="imperative",
     check=lambda outs: np.testing.assert_allclose(
         outs[0].mean(), 4.5, rtol=0.2))
case("_sample_generalized_negative_binomial",
     [np.array([5.0], np.float32), np.array([0.3], np.float32)],
     attrs={"shape": (4000,)}, grad=False, mode="imperative",
     check=lambda outs: np.testing.assert_allclose(
         outs[0].mean(), 5.0, rtol=0.2))
case("_sample_multinomial", [np.array([[0.1, 0.9], [0.9, 0.1]], np.float32)],
     attrs={"shape": (2000,)}, grad=False, mode="imperative",
     check=lambda outs: np.testing.assert_allclose(
         outs[0].mean(axis=1), [0.9, 0.1], atol=0.06))
case("_shuffle", [np.arange(24, dtype=np.float32).reshape(8, 3)],
     grad=False, mode="imperative",
     check=lambda outs: np.testing.assert_allclose(
         np.sort(outs[0], axis=0), np.arange(24).reshape(8, 3)))


# ---------------------------------------------------------------------------
# edge-case battery: tricky parameterizations checked against NUMPY
# expectations, not just imperative/symbolic agreement (the reference's
# test_operator.py exercises these attr corners one by one; here each gets
# an explicit oracle via `check=`)
# ---------------------------------------------------------------------------

def expect(fn):
    """check= adapter: fn(outs) -> (got, want) compared to 1e-5."""
    def chk(outs):
        got, want = fn(outs)
        np.testing.assert_allclose(got, np.asarray(want, np.float32),
                                   rtol=1e-5, atol=1e-6)
    return chk


_A = signed(2, 3, 4)

# reductions: axis tuple / negative axis / exclude / axis=None
case("sum", [_A], attrs={"axis": (0, 2)}, grad=True,
     check=expect(lambda o: (o[0], _A.sum((0, 2)))))
case("sum", [_A], attrs={"axis": -1}, grad=True,
     check=expect(lambda o: (o[0], _A.sum(-1))))
case("sum", [_A], attrs={"axis": 1, "exclude": True}, grad=True,
     check=expect(lambda o: (o[0], _A.sum((0, 2)))))
case("sum", [_A], grad=True,
     check=expect(lambda o: (o[0], _A.sum())))
case("mean", [_A], attrs={"axis": (1, 2), "keepdims": True}, grad=True,
     check=expect(lambda o: (o[0], _A.mean((1, 2), keepdims=True))))
case("max", [_A], attrs={"axis": (0, 1)}, grad=False,
     check=expect(lambda o: (o[0], _A.max((0, 1)))))
# norm in the reference's generation is a FULL L2 reduce — no axis attr
# (broadcast_reduce_op_value.cc); axis/ord arrived in later MXNet
case("norm", [_A], grad=True,
     check=expect(lambda o: (o[0], np.linalg.norm(_A.ravel()))))

# ordering: flattened (axis=None), mask mode, ascending, k edges
_O = np.array([[3.0, 1.0, 4.0, 1.5], [9.0, 2.0, 6.0, 5.0]], np.float32)
case("topk", [_O], attrs={"axis": None, "k": 3}, grad=False,
     mode="imperative",
     check=expect(lambda o: (o[0], [4.0, 6.0, 7.0])))  # flat indices of top3
case("topk", [_O], attrs={"axis": 1, "k": 2, "ret_typ": "mask"}, grad=False,
     check=expect(lambda o: (o[0], [[1, 0, 1, 0], [1, 0, 1, 0]])))
case("topk", [_O], attrs={"axis": 1, "k": 2, "ret_typ": "value",
                          "is_ascend": True}, grad=False,
     check=expect(lambda o: (o[0], [[1.0, 1.5], [2.0, 5.0]])))
case("topk", [_O], attrs={"axis": 0, "k": 1, "ret_typ": "both"}, grad=False,
     check=lambda outs: (
         np.testing.assert_allclose(outs[0], [[9.0, 2.0, 6.0, 5.0]]),
         np.testing.assert_allclose(outs[1], [[1, 1, 1, 1]])))
case("sort", [_O], attrs={"axis": None}, grad=False, mode="imperative",
     check=expect(lambda o: (o[0], np.sort(_O, axis=None))))
case("sort", [_O], attrs={"axis": 0, "is_ascend": False}, grad=False,
     check=expect(lambda o: (o[0], -np.sort(-_O, axis=0))))
case("argsort", [_O], attrs={"axis": None}, grad=False, mode="imperative",
     check=expect(lambda o: (o[0], np.argsort(_O, axis=None))))
case("argmax", [_O], grad=False,
     check=expect(lambda o: (o[0], _O.argmax())))  # axis=None flattens
case("argmax", [_O], attrs={"axis": 1, "keepdims": True}, grad=False,
     check=expect(lambda o: (o[0], _O.argmax(1, keepdims=True))))

# Reshape special codes (ref matrix_op-inl.h: 0 copy, -1 infer, -2 copy
# rest, -3 merge two, -4 split)
_R = signed(2, 3, 4)
case("Reshape", [_R], attrs={"shape": (0, -1)},
     check=expect(lambda o: (o[0], _R.reshape(2, 12))))
case("Reshape", [_R], attrs={"shape": (-1, 0)},
     check=expect(lambda o: (o[0], _R.reshape(8, 3))))
case("Reshape", [_R], attrs={"shape": (-2,)},
     check=expect(lambda o: (o[0], _R)))
case("Reshape", [_R], attrs={"shape": (-3, 0)},
     check=expect(lambda o: (o[0], _R.reshape(6, 4))))
case("Reshape", [_R], attrs={"shape": (-4, 1, 2, 0, 0)},
     check=expect(lambda o: (o[0], _R.reshape(1, 2, 3, 4))))
case("Reshape", [signed(6, 4)], attrs={"shape": (-4, 2, -1, 0)},
     check=lambda outs: np.testing.assert_equal(outs[0].shape, (2, 3, 4)))

# take modes: out-of-range indices clip vs wrap (ref indexing_op.h)
_T = np.arange(12, dtype=np.float32).reshape(4, 3)
_TI = np.array([-1.0, 0.0, 5.0], np.float32)
case("take", [_T, _TI], attrs={"mode": "clip"}, grad=False,
     check=expect(lambda o: (o[0], _T[[0, 0, 3]])))
case("take", [_T, _TI], attrs={"mode": "wrap"}, grad=False,
     check=expect(lambda o: (o[0], _T[[-1 % 4, 0, 5 % 4]])))
case("take", [_T, np.array([1.0, 0.0], np.float32)],
     attrs={"axis": 1}, grad=True, grad_nodes=["in0"],
     check=expect(lambda o: (o[0], _T[:, [1, 0]])))

# slice with step / negative bounds (ref matrix_op slice with step)
_S = np.arange(20, dtype=np.float32).reshape(4, 5)
case("slice", [_S], attrs={"begin": (0, 4), "end": (4, 0), "step": (1, -2)},
     grad=False,
     check=expect(lambda o: (o[0], _S[0:4, 4:0:-2])))
case("slice", [_S], attrs={"begin": (1, 2), "end": (-1, -1)},
     grad=False,  # negative ends (ref slice supports negative bounds)
     check=expect(lambda o: (o[0], _S[1:-1, 2:-1])))
case("slice_axis", [_S], attrs={"axis": -1, "begin": -3, "end": None},
     grad=False,
     check=expect(lambda o: (o[0], _S[:, -3:])))

# softmax numerics + attrs
_L = np.array([[1e4, 1e4 - 1, 0.0], [-1e4, 0.0, 1.0]], np.float32)
case("log_softmax", [_L], attrs={"axis": 1}, grad=False,
     check=lambda outs: np.testing.assert_allclose(
         outs[0][0, :2], [-0.31326, -1.31326], rtol=1e-4))
case("softmax", [signed(3, 4)], attrs={"axis": 0}, grad=True,
     check=lambda outs: np.testing.assert_allclose(
         outs[0].sum(0), np.ones((4,)), rtol=1e-5))
case("softmax", [_O], attrs={"temperature": 2.0}, grad=False,
     check=expect(lambda o: (
         o[0],
         np.exp(_O / 2.0) / np.exp(_O / 2.0).sum(1, keepdims=True))))

# one_hot attrs
case("one_hot", [np.array([0.0, 2.0], np.float32)],
     attrs={"depth": 3, "on_value": 5.0, "off_value": -1.0}, grad=False,
     check=expect(lambda o: (o[0], [[5, -1, -1], [-1, -1, 5]])))

# dot / batch_dot transpose flags
_DA, _DB = signed(3, 4), signed(3, 5)
case("dot", [_DA, _DB], attrs={"transpose_a": True},
     check=expect(lambda o: (o[0], _DA.T @ _DB)))
case("dot", [signed(4, 3), signed(5, 3)], attrs={"transpose_b": True},
     check=lambda outs: np.testing.assert_equal(outs[0].shape, (4, 5)))
_BA, _BB = signed(2, 3, 4), signed(2, 3, 5)
case("batch_dot", [_BA, _BB], attrs={"transpose_a": True},
     check=expect(lambda o: (o[0],
                             np.einsum("bij,bik->bjk", _BA, _BB))))

# FullyConnected flatten=False keeps leading axes
case("FullyConnected", [signed(2, 3, 4), signed(5, 4), signed(5)],
     attrs={"num_hidden": 5, "flatten": False},
     check=lambda outs: np.testing.assert_equal(outs[0].shape, (2, 3, 5)))

# negative-axis layout ops
case("Concat", [signed(2, 3), signed(2, 5)], attrs={"dim": -1},
     check=lambda outs: np.testing.assert_equal(outs[0].shape, (2, 8)))
case("stack", [signed(2, 3), signed(2, 3)], attrs={"axis": -1},
     check=lambda outs: np.testing.assert_equal(outs[0].shape, (2, 3, 2)))
case("expand_dims", [signed(2, 3)], attrs={"axis": -1},
     check=lambda outs: np.testing.assert_equal(outs[0].shape, (2, 3, 1)))
_R2 = signed(2, 3)
case("repeat", [_R2], attrs={"repeats": 2},  # axis=None: flatten, repeat
     check=expect(lambda o: (o[0], np.repeat(_R2, 2))))
case("tile", [signed(2, 3)], attrs={"reps": (2, 1, 3)},
     check=lambda outs: np.testing.assert_equal(outs[0].shape, (2, 2, 9)))
case("reverse", [_S], attrs={"axis": (0, 1)}, grad=False,
     check=expect(lambda o: (o[0], _S[::-1, ::-1])))
case("squeeze", [signed(1, 3, 1)], attrs={},
     check=lambda outs: np.testing.assert_equal(outs[0].shape, (3,)))
case("transpose", [signed(2, 3, 4)], attrs={},
     check=lambda outs: np.testing.assert_equal(outs[0].shape, (4, 3, 2)))

# clip half-open ranges are rejected upstream in the reference; both
# bounds always arrive — but the values may sit exactly ON data points
case("clip", [np.array([-1.0, -0.5, 0.0, 0.5, 1.0], np.float32)],
     attrs={"a_min": -0.5, "a_max": 0.5}, grad=False,
     check=expect(lambda o: (o[0], [-0.5, -0.5, 0.0, 0.5, 0.5])))

# SequenceMask value attr + axis
_SEQ = np.arange(24, dtype=np.float32).reshape(4, 2, 3)
case("SequenceMask", [_SEQ, np.array([2.0, 3.0], np.float32)],
     attrs={"use_sequence_length": True, "value": -7.0}, grad=False,
     check=lambda outs: (
         np.testing.assert_allclose(outs[0][2:, 0], -7.0),
         np.testing.assert_allclose(outs[0][3:, 1], -7.0),
         np.testing.assert_allclose(outs[0][:2], _SEQ[:2])))

# Pooling 'full' (ceil) convention output size (ref pooling-inl.h)
case("Pooling", [pos(1, 1, 5, 5)],
     attrs={"kernel": (2, 2), "stride": (2, 2),
            "pooling_convention": "full", "pool_type": "max"}, grad=False,
     check=lambda outs: np.testing.assert_equal(outs[0].shape, (1, 1, 3, 3)))

# Convolution 1D / 3D / depthwise / dilated.  atol 1e-2 throughout: finite
# differences on conv are noisy at tiny-|g| points (see the stem case note)
case("Convolution", [signed(2, 3, 8), signed(4, 3, 3), signed(4)],
     attrs={"kernel": (3,), "num_filter": 4}, rtol=8e-2, atol=1e-2)
case("Convolution", [signed(1, 2, 4, 4, 4), signed(3, 2, 2, 2, 2),
                     signed(3)],
     attrs={"kernel": (2, 2, 2), "num_filter": 3}, rtol=8e-2, atol=1e-2)
case("Convolution", [signed(1, 4, 5, 5), signed(4, 1, 3, 3), signed(4)],
     attrs={"kernel": (3, 3), "num_filter": 4, "num_group": 4}, rtol=8e-2,
     atol=1e-2)
case("Convolution", [signed(1, 2, 7, 7), signed(3, 2, 3, 3), signed(3)],
     attrs={"kernel": (3, 3), "num_filter": 3, "dilate": (2, 2)}, rtol=8e-2,
     atol=1e-2)
# stem shape (C_in=3): exercises the MXU channel-padding path.  atol 1e-2:
# finite differences on a strided conv are noisy at tiny-|g| points (the
# unpadded C_in=8 control shows the identical deviation; raw jax.grad
# matches central differences to 1e-3 at the flagged points)
case("Convolution", [signed(2, 3, 8, 8), signed(4, 3, 3, 3), signed(4)],
     attrs={"kernel": (3, 3), "num_filter": 4, "stride": (2, 2),
            "pad": (1, 1)}, rtol=8e-2, atol=1e-2)

# BatchNorm use_global_stats under train (ref batch_norm-inl.h): moving
# stats are used even when is_train=True
_BNX, _BNG, _BNB = signed(2, 3, 4, 4), pos(3), signed(3)
_BNM, _BNV = signed(3), pos(3)
case("BatchNorm", [_BNX, _BNG, _BNB, _BNM, _BNV],
     attrs={"use_global_stats": True, "fix_gamma": False, "eps": 1e-3},
     grad=False, train=True, mode="imperative",
     check=lambda outs: np.testing.assert_allclose(
         outs[0],
         (_BNX - _BNM.reshape(1, 3, 1, 1))
         / np.sqrt(_BNV.reshape(1, 3, 1, 1) + 1e-3)
         * _BNG.reshape(1, 3, 1, 1) + _BNB.reshape(1, 3, 1, 1),
         rtol=2e-5, atol=1e-5))

# where: condition enters as float mask; gradient only to branches
case("where", [np.array([1.0, 0.0, 1.0], np.float32),
               signed(3), signed(3)],
     grad=True, grad_nodes=["in1", "in2"])

# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------

def _canonical_ops():
    seen = {}
    for name, op in _registry.op_registry().items():
        seen.setdefault(op.name, op)
    return seen


def _as_list(x):
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _run_imperative(name, c):
    nds = [mx.nd.array(a) for a in c.inputs]
    outs = _as_list(_invoke(name, nds, dict(c.attrs)))
    res = [o.asnumpy() for o in outs]
    for r in res:
        if np.issubdtype(r.dtype, np.floating):
            assert np.isfinite(r).all(), "%s produced non-finite values" % name
    if c.check is not None:
        c.check(res)
    return res


def _run_symbolic(name, c, imp_outs):
    variables = [mx.sym.Variable("in%d" % i) for i in range(len(c.inputs))]
    sym = getattr(mx.sym, name)(*variables, **c.attrs)
    args = {"in%d" % i: mx.nd.array(a) for i, a in enumerate(c.inputs)}
    exe = sym.bind(mx.cpu(), args=args)
    outs = _as_list(exe.forward(is_train=c.train))
    assert len(outs) == len(imp_outs), \
        "%s: symbol path yields %d outputs, imperative %d" % (
            name, len(outs), len(imp_outs))
    for o, ref in zip(outs, imp_outs):
        assert_almost_equal(o.asnumpy(), ref, rtol=c.fwd_rtol, atol=1e-5,
                            names=("symbolic", "imperative"))
    return sym


def _run_grad(name, c, sym):
    if c.grad_nodes is not None:
        nodes = list(c.grad_nodes)
    else:
        nodes = ["in%d" % i for i, a in enumerate(c.inputs)
                 if np.issubdtype(np.asarray(a).dtype, np.floating)]
    check_numeric_gradient(sym, list(c.inputs), grad_nodes=nodes,
                           rtol=c.rtol, atol=c.atol)


@pytest.mark.parametrize(
    "name,idx",
    [(n, i) for n in sorted(CASES) for i in range(len(CASES[n]))],
    ids=lambda v: str(v))
def test_op_case(name, idx):
    c = CASES[name][idx]
    imp = _run_imperative(name, c)
    if c.mode == "pair" and c.inputs:
        sym = _run_symbolic(name, c, imp)
        if c.grad:
            _run_grad(name, c, sym)
    elif c.mode == "pair":
        # attrs-only op: symbol path has no bindable inputs; imperative
        # result was already validated by c.check
        pass


def test_every_op_is_accounted_for():
    """The sweep's reason to exist: no registered op goes untested
    silently."""
    missing = []
    for name in sorted(_canonical_ops()):
        if name in CASES or name in TESTED_ELSEWHERE:
            continue
        missing.append(name)
    assert not missing, (
        "ops registered but neither swept here nor recorded in "
        "TESTED_ELSEWHERE: %s" % missing)


def test_tested_elsewhere_ledger_is_current():
    """Every TESTED_ELSEWHERE entry must reference an existing test file
    and a registered op, so the ledger cannot rot."""
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    ops = _canonical_ops()
    for name, where in TESTED_ELSEWHERE.items():
        assert name in ops, "ledger entry %r is not a registered op" % name
        fname = where.split(" ")[0]
        assert os.path.exists(os.path.join(os.path.dirname(here), fname)), \
            "ledger entry %r points at missing file %r" % (name, fname)


def test_deconvolution_is_gradient_of_convolution():
    """Semantic anchor for every Deconvolution branch: deconv(y, w) must
    equal d/dx[sum(conv(x, w) * y)] — computed through the framework's own
    autograd over its Convolution, an independent code path."""
    from mxnet_tpu import autograd

    def grad_of_conv(y_np, w_np, x_shape, **conv_kw):
        x = mx.nd.zeros(x_shape)
        x.attach_grad()
        with autograd.record():
            out = mx.nd.Convolution(x, mx.nd.array(w_np), no_bias=True,
                                    **conv_kw)
            s = mx.nd.sum(out * mx.nd.array(y_np))
        s.backward()
        return x.grad.asnumpy()

    for conv_kw, x_shape, w_shape in [
        ({"kernel": (2, 2), "num_filter": 2}, (1, 3, 6, 6), (2, 3, 2, 2)),
        ({"kernel": (3, 3), "num_filter": 2, "stride": (2, 2),
          "pad": (1, 1)}, (1, 3, 7, 7), (2, 3, 3, 3)),
        ({"kernel": (2, 2), "num_filter": 2, "dilate": (2, 2)},
         (1, 3, 7, 7), (2, 3, 2, 2)),
        ({"kernel": (2, 2), "num_filter": 4, "num_group": 2},
         (1, 4, 5, 5), (4, 2, 2, 2)),
    ]:
        w_np = RNG.randn(*w_shape).astype(np.float32)
        x_probe = mx.nd.Convolution(
            mx.nd.array(RNG.randn(*x_shape).astype(np.float32)),
            mx.nd.array(w_np), no_bias=True, **conv_kw)
        y_np = RNG.randn(*x_probe.shape).astype(np.float32)
        expect = grad_of_conv(y_np, w_np, x_shape, **conv_kw)
        # deconv kernel/stride/... mirror the conv attrs; weight layout
        # (C_in_of_conv_output, num_filter_of_deconv, kh, kw) is shared
        deconv_kw = dict(conv_kw)
        deconv_kw["num_filter"] = x_shape[1]
        got = mx.nd.Deconvolution(mx.nd.array(y_np), mx.nd.array(w_np),
                                  no_bias=True, **deconv_kw)
        assert_almost_equal(got.asnumpy(), expect, rtol=1e-4, atol=1e-5,
                            names=("deconv", "grad_of_conv"))
