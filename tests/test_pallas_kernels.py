"""Roofline kernel-sprint tier: pooling backward + BN-stats epilogue +
int8 serving path (ISSUE 7; docs/kernels.md).

Every Pallas kernel runs here through the interpreter (the same kernel
code path the chip compiles) and is validated against its XLA fallback —
the select-and-scatter / two-pass-reduction programs the flag-off path
still traces bit-identically.  Under ``MXTPU_CHIP_TESTS=1`` on a TPU
(``make chip``) the same parity tests run the COMPILED kernels against
XLA's TPU programs.  The int8 tests reuse PR 4's dispatch-bucket replay
oracle: a served response must be bitwise equal to a plain Predictor run
at the recorded dispatch bucket.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import executor_cache, serving
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops import quantize as quant
from mxnet_tpu.ops.nn import (_bn_train_core, _pool_core, _pool_out_shape,
                              _pooling)
from mxnet_tpu.predict import Predictor


# the kernel side of every parity test: Mosaic-compiled on the chip tier,
# interpreted everywhere else
COMPILED = os.environ.get("MXTPU_CHIP_TESTS") == "1" and mx.on_tpu()
KERNEL_MODE = "pallas" if COMPILED else "interpret"
INTERPRET = None if COMPILED else True


def _rng(seed=0):
    return np.random.RandomState(seed)


# ---------------------------------------------------------------------------
# Pooling backward: max = the Pallas kernel vs XLA's select-and-scatter;
# avg/sum = XLA's own gradient (no kernel under any flag, PR 26) vs a
# closed-form NumPy fan-out
# ---------------------------------------------------------------------------

def _pool_grad(mode, x, cfg):
    core = _pool_core(*cfg, mode)
    return jax.grad(
        lambda v: jnp.sum(core(v).astype(jnp.float32) ** 2))(x)


def _np_fanout_grad(x, cfg):
    """d/dx of sum(pool(x)^2) for avg/sum pooling, window by window in
    float64: every valid tap of a window takes dy / divisor, the divisor
    being 1 (sum), prod(kernel) (avg) or the window's count of non-padded
    taps (avg, count_include_pad=False); 'full' windows clip past the
    data."""
    pool_type, kernel, stride, pad, convention, include_pad = cfg
    x = np.asarray(x, np.float64)
    h, w = x.shape[2:]
    oh, ow = _pool_out_shape((h, w), kernel, stride, pad, convention)
    dx = np.zeros_like(x)
    for i in range(oh):
        for j in range(ow):
            h0, w0 = i * stride[0] - pad[0], j * stride[1] - pad[1]
            hs = slice(max(h0, 0), min(h0 + kernel[0], h))
            ws = slice(max(w0, 0), min(w0 + kernel[1], w))
            valid = (hs.stop - hs.start) * (ws.stop - ws.start)
            div = 1.0 if pool_type == "sum" else float(
                np.prod(kernel) if include_pad else max(valid, 1))
            out = x[:, :, hs, ws].sum((2, 3)) / div
            dx[:, :, hs, ws] += (2.0 * out / div)[:, :, None, None]
    return dx


POOL_CASES = [
    # (pool_type, kernel, stride, pad, convention, count_include_pad)
    ("max", (3, 3), (2, 2), (1, 1), "valid", True),
    ("max", (3, 2), (2, 3), (1, 0), "valid", True),   # stride != kernel
    ("max", (3, 3), (2, 2), (1, 1), "full", True),    # ceil-mode widening
    ("max", (2, 2), (2, 2), (0, 0), "valid", True),
    ("avg", (3, 3), (2, 2), (1, 1), "valid", True),
    ("avg", (3, 3), (2, 2), (1, 1), "valid", False),  # exclude padding
    ("avg", (3, 2), (1, 2), (1, 1), "full", False),
    ("sum", (2, 3), (2, 1), (0, 1), "valid", True),
]


@pytest.mark.parametrize("case", POOL_CASES,
                         ids=["-".join(map(str, c)) for c in POOL_CASES])
def test_pool_backward_matches_xla_oracle(case):
    x = jnp.asarray(_rng(1).randn(2, 3, 11, 13).astype(np.float32))
    got = _pool_grad(KERNEL_MODE, x, case)
    if case[0] == "max":    # Pallas kernel vs XLA select-and-scatter
        want = _pool_grad("off", x, case)
    else:                   # XLA's reduce_window transpose vs closed form
        want = _np_fanout_grad(x, case)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mode", ["off", "interpret", "pallas"])
@pytest.mark.parametrize("pool_type", ["avg", "sum"])
def test_avg_sum_pool_core_is_plain_xla(pool_type, mode):
    """avg/sum pooling never wraps a custom_vjp, whatever the pool flag
    resolves to: its gradient is XLA's transpose of reduce_window-add."""
    core = _pool_core(pool_type, (3, 3), (2, 2), (1, 1), "valid", True, mode)
    assert not hasattr(core, "defvjp")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_global_avg_pool_grad_is_broadcast(dtype, monkeypatch):
    """The window covers the input: dx is dy / 49 on every tap, rounded
    once — with the pool flag on, as the fused step on the chip has it."""
    monkeypatch.setenv("MXNET_TPU_PALLAS_POOL", "1")
    x = jnp.asarray(_rng(13).randn(2, 16, 7, 7)).astype(dtype)
    dy = jnp.asarray(_rng(14).randn(2, 16, 1, 1)).astype(dtype)
    out, vjp = jax.vjp(lambda v: _pooling(
        v, pool_type="avg", kernel=(1, 1), global_pool=True), x)
    assert out.shape == dy.shape and out.dtype == x.dtype
    dx, = vjp(dy)
    want = (np.asarray(dy, np.float32) / np.float32(49)).astype(dx.dtype)
    assert dx.dtype == x.dtype
    dx = np.asarray(dx)
    assert np.array_equal(dx, np.broadcast_to(dx[:, :, :1, :1], x.shape))
    if COMPILED:    # the chip's float32 divide is not correctly rounded
        np.testing.assert_allclose(dx[:, :, :1, :1].astype(np.float32),
                                   want.astype(np.float32), rtol=2e-7)
    else:
        assert np.array_equal(dx[:, :, :1, :1], want)


def test_pool_backward_bf16():
    """bf16 activations: the kernel compares/accumulates in f32 and casts
    once on the way out, matching the fallback to bf16 resolution."""
    x = jnp.asarray(_rng(2).randn(2, 4, 12, 12)).astype(jnp.bfloat16)
    cfg = ("max", (3, 3), (2, 2), (1, 1), "valid", True)
    want = _pool_grad("off", x, cfg).astype(jnp.float32)
    got = _pool_grad(KERNEL_MODE, x, cfg).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


def test_pool_flag_off_is_untouched():
    """use_pallas=False twin: the flag-off core is the PLAIN forward (no
    custom_vjp wrapper at all), so its backward is exactly the parent
    program's select-and-scatter autodiff."""
    cfg = ("max", (3, 3), (2, 2), (1, 1), "valid", True)
    core = _pool_core(*cfg, "off")
    assert not hasattr(core, "defvjp"), \
        "flag-off pooling must not wrap a custom_vjp"
    x = jnp.asarray(_rng(3).randn(1, 2, 9, 9).astype(np.float32))
    direct = jax.grad(lambda v: jnp.sum(core(v) ** 2))(x)
    raw = jax.grad(lambda v: jnp.sum(_pooling(
        v, pool_type="max", kernel=(3, 3), stride=(2, 2),
        pad=(1, 1)) ** 2))(x)
    assert np.array_equal(np.asarray(direct), np.asarray(raw))


def test_count_include_pad_false_divisor():
    """MXNet pooling-inl.h semantics: padded zeros leave the divisor —
    shape-edge case where corner/edge/interior windows all see different
    valid counts (and 'full' windows clip past the data)."""
    x = np.arange(25, dtype=np.float32).reshape(1, 1, 5, 5)
    out = np.asarray(_pooling(jnp.asarray(x), pool_type="avg",
                              kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                              count_include_pad=False))
    # manual reference: mean over the VALID window slice only
    want = np.zeros((1, 1, 3, 3), np.float32)
    for oh in range(3):
        for ow in range(3):
            h0, w0 = oh * 2 - 1, ow * 2 - 1
            hs = slice(max(h0, 0), min(h0 + 3, 5))
            ws = slice(max(w0, 0), min(w0 + 3, 5))
            want[0, 0, oh, ow] = x[0, 0, hs, ws].mean()
    np.testing.assert_allclose(out, want, rtol=1e-6)
    # include_pad=True (the default) keeps dividing by prod(kernel)
    out_pad = np.asarray(_pooling(jnp.asarray(x), pool_type="avg",
                                  kernel=(3, 3), stride=(2, 2),
                                  pad=(1, 1)))
    assert abs(out_pad[0, 0, 0, 0] - x[0, 0, :2, :2].sum() / 9.0) < 1e-5
    # the divisor change must not touch shapes
    sym = mx.sym.Pooling(mx.sym.Variable("data"), pool_type="avg",
                         kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                         count_include_pad=False)
    _, out_shapes, _ = sym.infer_shape(data=(1, 1, 5, 5))
    assert out_shapes[0] == (1, 1, 3, 3)


# ---------------------------------------------------------------------------
# BN-stats epilogue vs the two-pass reference
# ---------------------------------------------------------------------------

def test_bn_channel_sums_vs_two_pass():
    x = jnp.asarray(_rng(4).randn(4, 6, 5, 7).astype(np.float32))
    s1, s2 = pk.bn_channel_sums(x, interpret=INTERPRET)
    np.testing.assert_allclose(np.asarray(s1),
                               np.asarray(jnp.sum(x, (0, 2, 3))),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2),
                               np.asarray(jnp.sum(x * x, (0, 2, 3))),
                               rtol=1e-5, atol=1e-4)
    dy = jnp.asarray(_rng(5).randn(4, 6, 5, 7).astype(np.float32))
    a1, a2 = pk.bn_channel_sums(dy, x, interpret=INTERPRET)
    np.testing.assert_allclose(np.asarray(a1),
                               np.asarray(jnp.sum(dy, (0, 2, 3))),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(a2),
                               np.asarray(jnp.sum(dy * x, (0, 2, 3))),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bn_train_core_kernel_matches_fallback(dtype):
    """Full BN training core (forward stats + custom-vjp backward) with
    the channel-sums kernel vs the two-pass XLA fallback."""
    tol = dict(rtol=1e-4, atol=1e-4) if dtype == "float32" \
        else dict(rtol=3e-2, atol=3e-2)
    x = jnp.asarray(_rng(6).randn(4, 6, 5, 7)).astype(dtype)
    g = jnp.asarray(_rng(7).rand(6).astype(np.float32))
    b = jnp.asarray(_rng(8).rand(6).astype(np.float32))
    on = _bn_train_core(4, 1, 1e-3, KERNEL_MODE)
    off = _bn_train_core(4, 1, 1e-3, "off")

    def loss(core):
        def f(x, g, b):
            out, m, v = core(x, g, b)
            return (jnp.sum(out.astype(jnp.float32) ** 2)
                    + jnp.sum(m) + jnp.sum(v))
        return f

    out_on = on(x, g, b)
    out_off = off(x, g, b)
    for a, w in zip(out_on, out_off):
        np.testing.assert_allclose(np.asarray(a, dtype=np.float32),
                                   np.asarray(w, dtype=np.float32), **tol)
    g_on = jax.grad(loss(on), argnums=(0, 1, 2))(x, g, b)
    g_off = jax.grad(loss(off), argnums=(0, 1, 2))(x, g, b)
    for a, w in zip(g_on, g_off):
        np.testing.assert_allclose(np.asarray(a, dtype=np.float32),
                                   np.asarray(w, dtype=np.float32), **tol)


# ---------------------------------------------------------------------------
# The shapes the chip actually runs: every BatchNorm / Pooling input of the
# ResNet-50 train step at batch 32 (read off the symbol by the lowering
# test), compiled kernel vs XLA's TPU program
# ---------------------------------------------------------------------------

chip_only = pytest.mark.skipif(
    not COMPILED, reason="full-size parity runs compiled, on the chip tier")


@chip_only
def test_bn_channel_sums_at_resnet50_shapes():
    from test_pallas_tpu_lowering import BN_SHAPES
    for i, shape in enumerate(BN_SHAPES):
        x = jnp.asarray(_rng(i).randn(*shape), jnp.bfloat16)
        dy = jnp.asarray(_rng(100 + i).randn(*shape), jnp.bfloat16)
        x32, dy32 = x.astype(jnp.float32), dy.astype(jnp.float32)
        # f32 sums over M = N*H*W terms: round-off grows with sqrt(M),
        # like the sums themselves
        tol = dict(rtol=1e-3,
                   atol=1e-3 * (shape[0] * shape[2] * shape[3]) ** 0.5)
        for got, want in zip(
                pk.bn_channel_sums(x) + pk.bn_channel_sums(dy, x),
                (jnp.sum(x32, (0, 2, 3)), jnp.sum(x32 * x32, (0, 2, 3)),
                 jnp.sum(dy32, (0, 2, 3)), jnp.sum(dy32 * x32, (0, 2, 3)))):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       err_msg=str(shape), **tol)


@chip_only
def test_pool_backward_at_resnet50_shapes():
    from test_pallas_tpu_lowering import pool_configs
    for i, (shape, cfg) in enumerate(pool_configs()):
        x = jnp.asarray(_rng(i).randn(*shape), jnp.bfloat16)
        got = _pool_grad("pallas", x, cfg).astype(jnp.float32)
        if cfg[0] == "max":
            want = _pool_grad("off", x, cfg).astype(jnp.float32)
        else:   # XLA on the chip vs the closed form
            want = _np_fanout_grad(x, cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2, err_msg=str(shape))


# ---------------------------------------------------------------------------
# Kernel flags: executor-cache retrace contract (docs/kernels.md)
# ---------------------------------------------------------------------------

def _convnet():
    net = mx.sym.Convolution(mx.sym.Variable("data"), kernel=(3, 3),
                             num_filter=4, pad=(1, 1), name="conv1")
    net = mx.sym.BatchNorm(net, fix_gamma=False, name="bn1")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                         pool_type="max", name="pool1")
    net = mx.sym.Flatten(net, name="flat1")
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc1")
    return mx.sym.SoftmaxOutput(net, name="softmax")


@pytest.fixture
def _kernel_flags():
    saved = {k: os.environ.pop(k, None)
             for k in ("MXNET_TPU_PALLAS_POOL", "MXNET_TPU_PALLAS_BN")}
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def test_kernel_flags_key_the_program_cache(_kernel_flags):
    """Enabling the kernel flags costs exactly one retrace of the fused
    fwd_bwd program; disabling retraces nothing and the off-path grads
    are bitwise what they were before the round trip."""
    sym = _convnet()

    def run():
        from mxnet_tpu.io import DataBatch, DataDesc
        r = np.random.RandomState(3)
        mod = mx.mod.Module(sym, context=mx.cpu())
        mod.bind([("data", (4, 3, 6, 6))], [("softmax_label", (4,))])
        mx.random.seed(0)
        mod.init_params(mx.initializer.Xavier())
        batch = DataBatch(
            data=[mx.nd.array(r.rand(4, 3, 6, 6).astype(np.float32))],
            label=[mx.nd.array(r.randint(0, 3, (4,)).astype(np.float32))],
            provide_data=[DataDesc("data", (4, 3, 6, 6))],
            provide_label=[DataDesc("softmax_label", (4,))])
        with executor_cache.watch_traces() as w:
            mod.forward_backward(batch)
        exe = mod._exec_group.execs[0]
        return w, {n: np.asarray(g._h.array)
                   for n, g in exe.grad_dict.items()}

    run()  # warm the off-path program
    w_off, g_off = run()
    assert w_off.total() == 0, w_off.delta()

    os.environ["MXNET_TPU_PALLAS_POOL"] = "1"
    os.environ["MXNET_TPU_PALLAS_BN"] = "1"
    w_on, g_on = run()
    assert w_on.total() == 1 \
        and w_on.delta().get("traces_fwd_bwd") == 1, w_on.delta()
    for k in g_off:
        np.testing.assert_allclose(g_on[k], g_off[k], rtol=1e-4,
                                   atol=1e-4)

    del os.environ["MXNET_TPU_PALLAS_POOL"]
    del os.environ["MXNET_TPU_PALLAS_BN"]
    w_back, g_back = run()
    assert w_back.total() == 0, w_back.delta()
    assert all(np.array_equal(g_off[k], g_back[k]) for k in g_off), \
        "off-path gradients changed after a kernel-flag round trip"


# ---------------------------------------------------------------------------
# int8 serving path (ops/quantize.py; docs/serving.md §int8)
# ---------------------------------------------------------------------------

def _mlp_with_params(seed=0):
    r = _rng(seed)
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    arg_shapes, _, _ = sym.infer_shape(data=(1, 8))
    args = {n: mx.nd.array(r.normal(0, 0.5, s).astype(np.float32))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}
    return sym, args


def test_quantize_weight_roundtrip():
    w = _rng(9).randn(6, 10).astype(np.float32)
    q, s = quant.quantize_weight(w)
    assert q.dtype == np.int8 and s.shape == (6,)
    np.testing.assert_allclose(q.astype(np.float32) * s[:, None], w,
                               atol=float(np.max(s)) * 0.51)


def test_int8_predict_allclose_vs_f32():
    sym, args = _mlp_with_params()
    blob = {"arg:%s" % k: v for k, v in args.items()}
    x = _rng(10).rand(8, 8).astype(np.float32)
    p32 = Predictor(sym.tojson(), dict(blob), {"data": (8, 8)})
    p8 = Predictor(sym.tojson(), dict(blob), {"data": (8, 8)},
                   quantize="int8")
    p32.forward(data=x)
    p8.forward(data=x)
    o32 = p32.get_output(0).asnumpy()
    o8 = p8.get_output(0).asnumpy()
    np.testing.assert_allclose(o8, o32, atol=0.05)
    # recorded accuracy-delta check: top-1 agreement on this batch
    agree = float((np.argmax(o8, 1) == np.argmax(o32, 1)).mean())
    assert agree >= 0.99, "int8 top-1 delta %.3f" % (1.0 - agree)


def test_int8_calibration_table():
    sym, args = _mlp_with_params(1)
    r = _rng(11)
    batches = [{"data": r.rand(4, 8).astype(np.float32)}
               for _ in range(3)]
    table = quant.calibrate(sym, args, {}, {"data": (4, 8)}, batches)
    assert set(table) == {"fc1", "fc2"}
    assert all(v > 0 for v in table.values())
    # serializable layout in the health-sentinel describe() style
    again = quant.CalibrationTable.loads(table.dumps())
    assert again == {k: pytest.approx(v) for k, v in table.items()}
    blob = {"arg:%s" % k: v for k, v in args.items()}
    x = batches[0]["data"]
    pc = Predictor(sym.tojson(), dict(blob), {"data": (4, 8)},
                   quantize="int8", calibration=table)
    p32 = Predictor(sym.tojson(), dict(blob), {"data": (4, 8)})
    pc.forward(data=x)
    p32.forward(data=x)
    np.testing.assert_allclose(pc.get_output(0).asnumpy(),
                               p32.get_output(0).asnumpy(), atol=0.05)


def test_int8_served_bucket_replay_bitwise():
    """ServedModel(quantize='int8') through the real dynamic batcher:
    warmup()'s zero-retrace verification passes, and every response is
    bitwise-reproducible by a plain int8 Predictor at the recorded
    dispatch bucket (PR 4's replay oracle, applied to the quantized
    graph — dynamic activation ranging included, since the padded rows
    are zeros in both runs)."""
    sym, args = _mlp_with_params(2)
    server = serving.Server(max_batch_size=4, batch_window_ms=2.0,
                            queue_depth=32)
    server.add_model("q8", sym, args, input_shapes={"data": (8,)},
                     quantize="int8")
    server.warmup()  # raises if the verify sweep retraces
    r = _rng(12)
    payloads = [r.rand(1 + i % 3, 8).astype(np.float32)
                for i in range(12)]
    with executor_cache.watch_traces() as w:
        futs = [server.submit_async("q8", {"data": p}) for p in payloads]
        results = [f.result(timeout=60) for f in futs]
    assert w.total() == 0, w.delta()
    blob = {"arg:%s" % k: v for k, v in args.items()}
    oracles = {}
    for p, fut, outs in zip(payloads, futs, results):
        b = fut.request.dispatch_bucket
        oracle = oracles.get(b)
        if oracle is None:
            oracle = oracles[b] = Predictor(
                sym.tojson(), dict(blob), {"data": (b, 8)},
                quantize="int8")
        solo = np.zeros((b, 8), np.float32)
        solo[:p.shape[0]] = p
        oracle.forward(data=solo)
        want = oracle.get_output(0).asnumpy()[:p.shape[0]]
        assert np.array_equal(outs[0], want), \
            "served int8 response differs from bucket replay"
    server.close(drain=True, timeout=30)


def test_quantize_env_default(_kernel_flags):
    """MXNET_TPU_QUANTIZE=int8 is the ServedModel default mode."""
    sym, args = _mlp_with_params(3)
    os.environ["MXNET_TPU_QUANTIZE"] = "int8"
    try:
        model = serving.ServedModel("m", sym, args, {},
                                    {"data": (8,)}, max_batch_size=2)
        assert model.quantize == "int8"
        assert any(n.endswith("_int8")
                   for n in model._base._exe.arg_dict)
    finally:
        del os.environ["MXNET_TPU_QUANTIZE"]
    model2 = serving.ServedModel("m2", sym, args, {}, {"data": (8,)},
                                 max_batch_size=2)
    assert model2.quantize is None
