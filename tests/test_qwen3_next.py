"""Qwen3-Next at toy size on the CPU against its plain reference
(``benchmark/references/qwen3_next.py``, which imports nothing of the
program and computes the delta rule token by token): every op, the whole
model's logits, loss and every gradient leaf, the chip's share of an expert
layer, the executor's recomputation, and ``Module.fit`` through the fused
step against the reference's Adam steps."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.executor import _Program
from mxnet_tpu.observability import telemetry
from mxnet_tpu.ops import gdn_kernels, lm_ops
from mxnet_tpu.ops import pallas_kernels as pk

from benchmark.references import qwen3_next as ref

CFG = dict(
    hidden_size=32, vocab_size=50, num_hidden_layers=4,
    full_attention_interval=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, partial_rotary_factor=0.25, rope_theta=1e7,
    rms_norm_eps=1e-6, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=8, linear_conv_kernel_dim=4,
    num_experts=4, router_num_experts=16, first_expert=4,
    num_experts_per_tok=3, norm_topk_prob=True, moe_intermediate_size=16,
    shared_expert_intermediate_size=16)
BATCH, SEQ = 2, 70          # 70: the scan's last chunk is padded
PLAIN = (lambda a: a, lambda a: a)


def _normal(seed, shape, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).normal(0, scale, shape),
                       jnp.float32)


def _params(cfg, seed=0, scale=0.3):
    return {n: _normal(seed + i, s, scale)
            for i, (n, s) in enumerate(sorted(ref.param_shapes(cfg).items()))}


def _tokens(seed=0, cfg=CFG, batch=BATCH, seq=SEQ):
    ids = np.random.RandomState(seed).randint(0, cfg["vocab_size"],
                                              (batch, seq + 1))
    return ids[:, :-1].astype(np.float32), ids[:, 1:].astype(np.float32)


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * max(1.0, np.abs(want).max()))


# -- each op ----------------------------------------------------------------------

@pytest.mark.parametrize("zero_centered", [True, False])
def test_rms_norm(zero_centered):
    x, w = _normal(1, (2, 5, 32)), _normal(2, (32,), 0.2)
    _close(lm_ops._rms_norm(x, w, 1e-6, zero_centered),
           ref.rms_norm(x, w, 1e-6, zero_centered))


def test_rotary_touches_the_first_dims_only():
    x = _normal(3, (2, 9, 4, 16))
    got = lm_ops._rotary_embedding(x, rotary_dim=4, base=1e7)
    _close(got, ref.rotary(x, 4, 1e7))
    assert np.array_equal(np.asarray(got[..., 4:]), np.asarray(x[..., 4:]))
    assert np.array_equal(np.asarray(got[:, 0]), np.asarray(x[:, 0]))


def test_causal_conv1d_sees_no_later_token():
    x, w = _normal(4, (2, 11, 6)), _normal(5, (6, 4))
    y = lm_ops._causal_conv1d(x, w, kernel=4, activation="none")
    want = sum(np.pad(np.asarray(x), ((0, 0), (3, 0), (0, 0)))[:, j:j + 11]
               * np.asarray(w)[:, j] for j in range(4))
    _close(y, want)
    later = x.at[:, 7:].set(0.0)
    assert np.array_equal(
        np.asarray(lm_ops._causal_conv1d(later, w, kernel=4)[:, :7]),
        np.asarray(lm_ops._causal_conv1d(x, w, kernel=4)[:, :7]))


def _delta_inputs(seq, seed=6, heads=(2, 4), dk=8, dv=8):
    hk, hv = heads
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(_normal(seed, (BATCH, seq, hk, dk)))
    k = unit(_normal(seed + 1, (BATCH, seq, hk, dk)))
    v = _normal(seed + 2, (BATCH, seq, hv, dv))
    g = -jnp.abs(_normal(seed + 3, (BATCH, seq, hv))) * 0.3
    beta = jax.nn.sigmoid(_normal(seed + 4, (BATCH, seq, hv)))
    return q, k, v, g, beta


def _ops_layout(q, k, v, g, beta):
    """The reference's operands as the op's scan takes them: heads first,
    the value heads grouped by their key head."""
    b, s, hk, _ = q.shape
    first = lambda x: jnp.swapaxes(x, 1, 2)
    group = lambda x: first(x).reshape((b, hk, v.shape[2] // hk, s)
                                       + x.shape[3:])
    return first(q), first(k), group(v), group(g), group(beta)


def _chunked(q, k, v, g, beta, chunk=64):
    """The program's chunked scan on the reference's layout."""
    b, s = q.shape[:2]
    out = lm_ops.chunked_gated_delta_rule(*_ops_layout(q, k, v, g, beta),
                                          chunk)
    return jnp.swapaxes(out.reshape(b, v.shape[2], s, -1), 1, 2)


def _token_by_token(q, k, v, g, beta):
    rep = v.shape[2] // q.shape[2]
    return ref.delta_rule(jnp.repeat(q, rep, 2), jnp.repeat(k, rep, 2), v,
                          jnp.exp(g), beta)


@pytest.mark.parametrize("seq", [64, 128, 37, 100, 1])
def test_chunked_scan_is_the_token_by_token_recurrence(seq):
    args = _delta_inputs(seq)
    _close(_chunked(*args), _token_by_token(*args), 1e-4)


@pytest.mark.parametrize("seq,chunk", [(128, 64), (100, 64), (50, 16)])
def test_chunked_scan_backward_is_the_recurrence_s(seq, chunk):
    args = _delta_inputs(seq, seed=11)
    w = _normal(20, (BATCH, seq, 4, 8))
    got = jax.grad(lambda *a: jnp.sum(_chunked(*a, chunk=chunk) * w),
                   argnums=range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(_token_by_token(*a) * w),
                    argnums=range(5))(*args)
    for g, r, name in zip(got, want, ("q", "k", "v", "g", "beta")):
        _close(g, r, 2e-4), name


# -- the scan as Pallas kernels (ops/gdn_kernels.py), in the interpreter ---------

def _wide_inputs(seq, r, dtype, seed=80):
    """Delta-rule operands at the kernels' widths (dk = dv = 128), on the
    reference's layout: 2 key heads, ``r`` value heads each."""
    q, k, v, g, beta = _delta_inputs(seq, seed, heads=(2, 2 * r), dk=128,
                                     dv=128)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def _through(kernel, monkeypatch, *args):
    """``_chunked`` with the recurrence over chunks as ``kernel`` says
    (what ``gdn_kernels.mode`` would, steered here) and the chunk-local part
    XLA's ``_chunk_local`` either way: the scan kernels alone
    (tests/test_gdn_local.py holds the local kernels)."""
    monkeypatch.setattr(gdn_kernels, "mode", lambda *a: kernel)
    monkeypatch.setattr(gdn_kernels, "local_planned", lambda *a: False)
    return _chunked(*args)


@pytest.mark.parametrize("seq", [128, 100, 37])
@pytest.mark.parametrize("r", [1, 2])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_scan_kernels_are_the_scan_and_the_recurrence(dtype, r, seq,
                                                      monkeypatch):
    """Outputs and all five gradients of the two kernels against the
    ``lax.scan`` they replace (same precisions: equal to the rounding of
    their sums) and against the token-by-token recurrence in float32."""
    args = _wide_inputs(seq, r, dtype)
    w = _normal(81, (BATCH, seq, 2 * r, 128))
    f32 = lambda x: x.astype(jnp.float32)

    def both(fn, *a):
        out, pull = jax.vjp(fn, *a)
        return out, pull(w.astype(out.dtype))

    out_k, g_k = both(lambda *a: _through("interpret", monkeypatch, *a),
                      *args)
    out_s, g_s = both(lambda *a: _through(None, monkeypatch, *a), *args)
    out_r, g_r = both(_token_by_token, *(f32(x) for x in args))
    assert out_k.dtype == jnp.dtype(dtype)
    near, far = (1e-5, 2e-4) if dtype == "float32" else (1e-2, 3e-2)
    _close(f32(out_k), f32(out_s), near)
    _close(f32(out_k), out_r, far)
    for got, scan, want, name in zip(g_k, g_s, g_r,
                                     ("q", "k", "v", "g", "beta")):
        assert got.dtype == scan.dtype, name
        _close(f32(got), f32(scan), near), name
        _close(f32(got), want, far), name


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_scan_kernel_saves_the_states_the_scan_saves(dtype):
    args = _ops_layout(*_wide_inputs(256, 2, dtype))
    _, kept = lm_ops._gdr_forward(*args, 64, kernel="interpret")
    _, scanned = lm_ops._gdr_forward(*args, 64)
    assert kept.dtype == scanned.dtype == jnp.dtype(dtype)
    assert kept.shape == (BATCH, 2, 4, 2, 128, 128)     # [b, hk, n, r, ..]
    _close(kept.astype(jnp.float32),
           jnp.moveaxis(scanned, 0, 2).astype(jnp.float32),
           1e-6 if dtype == "float32" else 1e-2)


CELL_GDN = dict(query=(2, 8192, 16, 128), key=(2, 8192, 16, 128),
                value=(2, 8192, 32, 128), a=(2, 8192, 32), b=(2, 8192, 32),
                A_log=(32,), dt_bias=(32,))


def _gdn_jaxpr(shapes, dtype, platform):
    """The jaxpr text of the op's forward and gradient, traced for
    ``platform`` at ``shapes`` (nothing runs)."""
    avals = [jax.ShapeDtypeStruct(s, jnp.dtype(dtype))
             for s in shapes.values()]

    def grad(*a):
        with pk.trace_scope(platform=platform):
            return jax.grad(lambda *b: jnp.sum(lm_ops._gated_delta_rule(
                *b, chunk=64).astype(jnp.float32)), argnums=range(7))(*a)

    return str(jax.make_jaxpr(grad)(*avals))


def test_a_tpu_program_at_the_cells_shape_holds_the_kernels_and_no_loop():
    text = _gdn_jaxpr(CELL_GDN, "bfloat16", "tpu")
    for kernel in ("gdn_scan_fwd", "gdn_scan_bwd", "gdn_local_fwd",
                   "gdn_local_bwd"):
        assert "name=%s" % kernel in text, kernel
    assert "scan[" not in text and "while[" not in text
    loop = _gdn_jaxpr(CELL_GDN, "bfloat16", "cpu")
    assert "pallas_call" not in loop and loop.count("scan[") == 2
    assert "gdn_local" not in loop
    # XLA partitions the program by itself: no Mosaic kernel can be in it
    with pk.trace_scope(partitioned=True):
        assert "pallas_call" not in _gdn_jaxpr(CELL_GDN, "bfloat16", "tpu")


def test_a_narrow_head_falls_back_to_the_same_scan():
    """dk = dv = 8 (the toy model's): a TPU program holds the program a CPU
    one holds, and computes the same bits."""
    narrow = {n: s[:3] + (8,) if len(s) == 4 else s
              for n, s in CELL_GDN.items()}
    narrow = {n: (2, 128) + s[2:] if len(s) > 1 else s
              for n, s in narrow.items()}
    assert _gdn_jaxpr(narrow, "float32", "tpu") \
        == _gdn_jaxpr(narrow, "float32", "cpu")
    args = _delta_inputs(100)
    with pk.trace_scope(platform="tpu"):
        as_tpu = _chunked(*args)
    np.testing.assert_array_equal(np.asarray(as_tpu),
                                  np.asarray(_chunked(*args)))


@pytest.mark.parametrize("bh,r,n,itemsize", [
    (32, 2, 128, 2),          # the cell: 2 x 16 key heads, 8,192 tokens
    (32, 2, 128, 4), (6, 1, 3, 2), (7, 4, 1024, 2), (1, 2, 16, 4),
    (32, 2, 1024, 2)])
def test_gdn_plan_divides_the_grid_and_fits_its_budget(bh, r, n, itemsize):
    heads = gdn_kernels._gdn_plan(bh, r, n, 64, 128, 128, itemsize)
    assert heads and bh % heads == 0 and heads <= gdn_kernels._GDN_MAX_HEADS
    assert gdn_kernels._gdn_vmem_bytes(heads, r, n, 64, 128, 128, itemsize) \
        <= gdn_kernels._GDN_VMEM_BUDGET
    more = [h for h in range(heads + 1, gdn_kernels._GDN_MAX_HEADS + 1)
            if bh % h == 0]
    assert all(gdn_kernels._gdn_vmem_bytes(h, r, n, 64, 128, 128, itemsize)
               > gdn_kernels._GDN_VMEM_BUDGET for h in more)


def test_gdn_kernels_take_whole_tiles_on_an_unpartitioned_tpu_only():
    q, v = (2, 16, 8192, 128), (2, 16, 2, 8192, 128)
    assert gdn_kernels.mode(q, v, 64, jnp.bfloat16) is None     # a CPU here
    with pk.trace_scope(platform="tpu"):
        assert gdn_kernels.mode(q, v, 64, jnp.bfloat16) == "pallas"
        assert gdn_kernels.mode(q, v, 64, jnp.float32) == "pallas"
        assert gdn_kernels.mode(q, v, 8, jnp.bfloat16) is None  # 16 sublanes
        assert gdn_kernels.mode(q, v, 8, jnp.float32) == "pallas"
        assert gdn_kernels.mode(q, v, 64, jnp.float16) is None
        assert gdn_kernels.mode(q[:3] + (64,), v, 64, jnp.bfloat16) is None
        assert gdn_kernels.mode(q, v[:4] + (192,), 64, jnp.bfloat16) is None
        # a head's decay vectors stay resident: too long a sequence does not
        long = 64 * 40000
        assert gdn_kernels.mode(q[:2] + (long, 128), v[:3] + (long, 128), 64,
                                jnp.bfloat16) is None
        with pk.trace_scope(partitioned=True):
            assert gdn_kernels.mode(q, v, 64, jnp.bfloat16) is None


def test_gated_delta_rule_op_is_the_reference_layer():
    p = {k[len("layer0_"):]: v for k, v in _params(CFG).items()
         if k.startswith("layer0_gdn_")}
    x = _normal(30, (BATCH, SEQ, 32))
    net = models.qwen3_next._Builder(CFG, "float32").delta_net(
        mx.sym.Variable("x"), "gdn_")
    got = _Program(net).evaluate(dict(p, x=x), {}, (), True)[0][0]
    _close(got, ref.gated_delta_net(x, p, CFG, PLAIN), 1e-4)


def test_gated_attention_is_the_reference_layer():
    cfg = dict(CFG, full_attention_interval=1)
    p = {k[len("layer0_"):]: v for k, v in _params(cfg).items()
         if k.startswith("layer0_attn_")}
    x = _normal(31, (BATCH, SEQ, 32))
    net = models.qwen3_next._Builder(cfg, "float32").attention(
        mx.sym.Variable("x"), "attn_")
    got = _Program(net).evaluate(dict(p, x=x), {}, (), True)[0][0]
    _close(got, ref.gated_attention(x, p, cfg, PLAIN), 1e-4)


def test_sequence_cross_entropy_and_its_gradient():
    z, y = _normal(32, (2, 7, 11), 2.0), jnp.asarray(
        np.random.RandomState(33).randint(0, 11, (2, 7)), jnp.float32)
    plain = lambda z: -jnp.mean(jnp.take_along_axis(
        jax.nn.log_softmax(z, -1), y.astype(jnp.int32)[..., None], -1)[..., 0],
        axis=1)
    _close(lm_ops._sequence_cross_entropy(z, y), plain(z))
    w = jnp.asarray([1.0, -2.0])
    _close(jax.grad(lambda z: jnp.sum(
        lm_ops._sequence_cross_entropy(z, y) * w))(z),
        jax.grad(lambda z: jnp.sum(plain(z) * w))(z))
    half = lm_ops._sequence_cross_entropy(z.astype(jnp.bfloat16), y)
    assert half.dtype == jnp.float32


# -- the expert layer and the chip's share -------------------------------------------

def _moe_params(cfg, seed=40):
    return {k[len("layer0_"):]: v for k, v in _params(cfg, seed).items()
            if k.startswith(("layer0_moe_", "layer0_shared_"))}


def _routed(x, p, cfg):
    return lm_ops._moe_experts(
        x, p["moe_router_weight"], p["moe_gate_weight"], p["moe_up_weight"],
        p["moe_down_weight"], num_experts=cfg["router_num_experts"],
        num_hidden=cfg["moe_intermediate_size"],
        experts_held=cfg["num_experts"], first_expert=cfg["first_expert"],
        top_k=cfg["num_experts_per_tok"], norm_topk_prob=True)


def test_expert_op_is_the_reference_s_routed_part():
    p, x = _moe_params(CFG), _normal(41, (24, 32))
    y, counts = _routed(x, p, CFG)
    want = ref.moe(x[None], dict(p, shared_down_proj_weight=jnp.zeros_like(
        p["shared_down_proj_weight"])), CFG, PLAIN)[0]
    _close(y, want, 1e-4)
    assert counts.shape == (16,) and float(counts.sum()) == 24 * 3
    weights, top_e = ref.routed_weights(x, p, CFG)
    assert np.array_equal(np.asarray(counts),
                          np.bincount(np.asarray(top_e).ravel(), minlength=16))
    # normalised over the three chosen wherever they live: a token's held
    # weights sum to 1 only if all three of its experts are held here
    assert float(weights.sum(1).max()) <= 1.0 + 1e-6
    assert float(weights.sum(1).min()) < 0.999


def _leaves_rows_unwritten(real):
    """``lax.ragged_dot`` as the TPU runs it: the rows past the groups, of
    the result and of the left operand's gradient, hold whatever was there
    (NaN here)."""
    def spoil(x, sizes):
        return jnp.where((jnp.arange(x.shape[0]) < jnp.sum(sizes))[:, None],
                         x, jnp.nan)

    @jax.custom_vjp
    def dirty(lhs, rhs, sizes):
        return spoil(real(lhs, rhs, sizes), sizes)

    def bwd(res, d):
        lhs, rhs, sizes = res
        d_lhs, d_rhs = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)[1](d)
        return (spoil(d_lhs, sizes), d_rhs,
                np.zeros(sizes.shape, jax.dtypes.float0))

    dirty.defvjp(lambda *a: (dirty(*a), a), bwd)
    return dirty


def test_rows_past_the_held_groups_may_hold_anything(monkeypatch):
    """What the grouped product leaves in the rows that are other chips'
    reaches neither the output nor any gradient (on the v5e it reached the
    tokens' gradient, 3e8 times the true one: PERF.md, PR 27)."""
    p, x = _moe_params(CFG), _normal(43, (24, 32))
    names = sorted(k for k in p if k.startswith("moe_"))

    def run():
        return jax.value_and_grad(
            lambda x, w: jnp.sum(_routed(x, dict(p, **w), CFG)[0] ** 2),
            argnums=(0, 1))(x, {k: p[k] for k in names})

    want = run()
    monkeypatch.setattr(lm_ops.lax, "ragged_dot",
                        _leaves_rows_unwritten(jax.lax.ragged_dot))
    got = run()
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        _close(a, b, 1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """4 shares of 4 experts each of a 16-expert layer: the routed parts
    summed, the shared expert counted once, are the uncut reference."""
    whole = dict(CFG, num_experts=16, first_expert=0)
    p, x = _moe_params(whole, seed=50), _normal(51, (2, 13, 32))
    want = ref.moe(x, p, whole, PLAIN)
    flat = x.reshape(-1, 32)
    routed = 0.0
    for share in range(4):
        lo = 4 * share
        mine = dict(p, **{k: p[k][lo:lo + 4] for k in (
            "moe_gate_weight", "moe_up_weight", "moe_down_weight")})
        part, counts = _routed(flat, mine, dict(CFG, first_expert=lo))
        routed = routed + part
    shared = ref._ffn(flat, p["shared_gate_proj_weight"].T,
                      p["shared_up_proj_weight"].T,
                      p["shared_down_proj_weight"].T, PLAIN) \
        * jax.nn.sigmoid(flat @ p["shared_gate_weight"].T)
    _close((routed + shared).reshape(x.shape), want, 1e-4)
    assert float(counts.sum()) == 26 * 3        # every share routes over all


# -- the whole model ----------------------------------------------------------------

def _evaluate(net, params, x, y):
    prog = _Program(net)

    def f(p):
        outs, _ = prog.evaluate(dict(p, data=jnp.asarray(x),
                                     softmax_label=jnp.asarray(y)),
                                {}, (), True)
        return jnp.mean(outs[0]), outs[1]
    # MakeLoss hands every sequence's loss a gradient of one whatever is
    # made of it afterwards: the sum's gradient, BATCH times the mean's
    out, grads = jax.value_and_grad(f, has_aux=True)(params)
    return prog, (out, {n: g / BATCH for n, g in grads.items()})


def test_logits_loss_and_every_gradient_leaf():
    params, (x, y) = _params(CFG), _tokens()
    net = models.qwen3_next.get_symbol(CFG)
    assert sorted(n for n in net.list_arguments()
                  if n not in ("data", "softmax_label")) == sorted(params)
    logits = net.get_internals()["lm_head_output"]
    got = _Program(logits).evaluate(dict(params, data=jnp.asarray(x)), {}, (),
                                    False)[0][0]
    _close(got, ref.logits(params, x, CFG), 1e-4)
    prog, ((loss, counts), grads) = _evaluate(net, params, x, y)
    want_loss, want = jax.value_and_grad(ref.loss_fn)(params, x, y, CFG)
    _close(loss, want_loss, 1e-5)
    assert counts.shape == (4, 16) and float(counts.sum()) == 4 * 140 * 3
    for name in sorted(params):
        _close(grads[name], want[name], 3e-4), name


def test_mirroring_recomputes_and_changes_no_gradient():
    params, (x, y) = _params(CFG, seed=60), _tokens(1)
    on, (out_on, g_on) = _evaluate(models.qwen3_next.get_symbol(CFG), params,
                                   x, y)
    off, (out_off, g_off) = _evaluate(
        models.qwen3_next.get_symbol(CFG, recompute=False), params, x, y)
    assert on.mirror_stages == 8 and off.mirror_stages == 0
    text = lambda prog: str(jax.make_jaxpr(lambda p: jax.grad(lambda q: jnp.sum(
        prog.evaluate(dict(q, data=jnp.asarray(x), softmax_label=jnp.asarray(
            y)), {}, (), True)[0][0]))(p))(params))
    assert "checkpoint" in text(on) or "remat" in text(on)
    assert "checkpoint" not in text(off) and "remat" not in text(off)
    _close(out_on[0], out_off[0], 1e-6)
    for name in sorted(params):
        _close(g_on[name], g_off[name], 1e-5), name


def test_a_stage_that_feeds_itself_is_refused():
    x = mx.sym.Variable("x")
    with mx.AttrScope(__mirror_stage__="s"):
        a = mx.sym.exp(x)
    b = mx.sym.sigmoid(a)
    with mx.AttrScope(__mirror_stage__="s"):
        c = mx.sym.exp(b)
    with pytest.raises(mx.MXNetError, match="feed each other"):
        _Program(c).evaluate({"x": jnp.ones((2,))}, {}, (), True)


def test_no_mirror_attribute_no_checkpoint():
    net = models.mlp.get_symbol(num_classes=4)
    prog = _Program(net)
    assert prog.mirror_stages == 0
    assert all(not isinstance(u, mx.executor._Stage) for u in prog._units())


# -- Module.fit -------------------------------------------------------------------------

def test_fit_trains_through_the_fused_step_like_three_adam_steps():
    params = _params(CFG, seed=70, scale=0.2)
    xs, ys = _tokens(2, batch=3 * BATCH)
    # an epsilon of the gradients' own size: the update then follows the
    # gradient smoothly, where 1e-8 would make it a sign
    opt = dict(learning_rate=1e-2, beta1=0.9, beta2=0.95, epsilon=1e-3, wd=0.0)
    telemetry.reset()
    mod = mx.mod.Module(models.qwen3_next.get_symbol(CFG),
                        context=mx.cpu())
    losses = []
    mod.fit(mx.io.NDArrayIter(xs, ys, batch_size=BATCH), num_epoch=1,
            eval_metric="loss", optimizer="adam", optimizer_params=opt,
            arg_params={n: mx.nd.NDArray(a) for n, a in params.items()},
            batch_end_callback=lambda p: losses.append(
                float(mod.get_outputs()[0].asnumpy().mean())))
    assert mod._fused_step is not None and mod._fused_step.ran
    assert len(mod.get_outputs()) == 2 and len(losses) == 3
    snap = telemetry.snapshot()
    assert snap["module.recompute.blocks"]["value"] == 3 * 8
    assert snap["module.moe.selections_total"]["value"] == 3 * 4 * 140 * 3
    held = snap["module.moe.selections_held"]["value"]
    assert 0.15 < held / (3 * 4 * 140 * 3) < 0.35       # 4 of 16 experts
    assert snap["module.moe.expert_load_max"]["value"] \
        >= snap["module.moe.expert_load_mean"]["value"]
    # three delta-rule layers, two chunks of 2 x 2 key heads, three passes
    # (forward, the recomputed forward, backward); a CPU program scans
    assert snap["module.gdn.chunk_steps"]["value"] == 3 * (3 * 2 * 4 * 3)
    assert snap["module.gdn.chunk_steps_in_kernel"]["value"] == 0
    assert snap["module.gdn.local_chunks_in_kernel"]["value"] == 0

    p, m = dict(params), {n: jnp.zeros_like(a) for n, a in params.items()}
    v = dict(m)
    want = []
    for t in range(3):
        lo = t * BATCH
        loss, _, p, m, v = ref.adam_step(p, m, v, t + 1.0, xs[lo:lo + BATCH],
                                         ys[lo:lo + BATCH], CFG, opt)
        want.append(float(loss))
    _close(losses[:1], want[:1], 1e-5)
    _close(losses, want, 1e-4)
    got = dict(zip(mod._fused_step.param_names, mod._fused_step._masters))
    for name in sorted(params):
        moved = np.asarray(p[name] - params[name], np.float64)
        gap = np.asarray(got[name] - params[name], np.float64) - moved
        assert np.linalg.norm(gap) <= 0.05 * np.linalg.norm(moved), name


WIDE = dict(CFG, linear_key_head_dim=128, linear_value_head_dim=128)


def _one_fused_step(recompute=True):
    """(the loss of one fused Adam step of the toy model at the kernels'
    head widths, its parameters after it, the counters)."""
    params = _params(WIDE, seed=90, scale=0.2)
    xs, ys = _tokens(3, cfg=WIDE)
    telemetry.reset()
    mod = mx.mod.Module(models.qwen3_next.get_symbol(
        WIDE, recompute=recompute), context=mx.cpu())
    mod.fit(mx.io.NDArrayIter(xs, ys, batch_size=BATCH), num_epoch=1,
            eval_metric="loss", optimizer="adam",
            optimizer_params=dict(learning_rate=1e-2, epsilon=1e-3),
            arg_params={n: mx.nd.NDArray(a) for n, a in params.items()})
    assert mod._fused_step is not None and mod._fused_step.ran
    after = dict(zip(mod._fused_step.param_names, mod._fused_step._masters))
    return (float(mod.get_outputs()[0].asnumpy().mean()), after,
            telemetry.snapshot())


def test_chunk_step_counters_say_whether_the_kernels_engage(monkeypatch):
    """``module.gdn.chunk_steps`` counts the scans' work; ``_in_kernel`` and
    ``local_chunks_in_kernel`` are 0 where the step program scans (any CPU
    program) and the same number where it holds the scan and the local
    kernels: here the interpreter's, steered in the test."""
    loss, after, snap = _one_fused_step()
    assert snap["module.gdn.chunk_steps"]["value"] == 3 * 2 * 4 * 3
    assert snap["module.gdn.chunk_steps_in_kernel"]["value"] == 0
    assert snap["module.gdn.local_chunks_in_kernel"]["value"] == 0
    _, _, snap = _one_fused_step(recompute=False)       # no second forward
    assert snap["module.gdn.chunk_steps"]["value"] == 2 * 2 * 4 * 3

    real = gdn_kernels.mode
    monkeypatch.setattr(pk, "traced_for_unpartitioned_tpu", lambda: True)
    monkeypatch.setattr(gdn_kernels, "mode",
                        lambda *a: real(*a) and "interpret")
    loss_k, after_k, snap = _one_fused_step()
    assert snap["module.gdn.chunk_steps"]["value"] == 3 * 2 * 4 * 3
    assert snap["module.gdn.chunk_steps_in_kernel"]["value"] == 3 * 2 * 4 * 3
    assert snap["module.gdn.local_chunks_in_kernel"]["value"] \
        == 3 * 2 * 4 * 3
    _close([loss_k], [loss], 1e-5)
    for name in sorted(after):
        _close(after_k[name], after[name], 1e-3), name
