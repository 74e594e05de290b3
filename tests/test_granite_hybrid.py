"""Granite 4.0-H (``models/granite_hybrid.py``; docs/granite_hybrid.md) on the
CPU: Mamba-2's state-space op (``lm_ops._ssd``) against a token-by-token
float32 recurrence on the ``lax.scan`` path and, in the Pallas interpreter,
through ``gdn_kernels``' ``ssd_scan_fwd`` / ``ssd_scan_bwd`` at 64-wide
value heads; the conv's bias; where the kernels engage and what the program
counts; and the whole symbol at a toy size against the plain reference
(``benchmark/references/granite_hybrid.py``), loss and every gradient."""
import hashlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.observability import telemetry
from mxnet_tpu.ops import gdn_kernels, lm_ops
from mxnet_tpu.ops import pallas_kernels as pk

from benchmark.references import granite_hybrid as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "granite-4.0-h-micro-vp8-bf16.json")) as _f:
    REAL = json.load(_f)
CFG = dict(REAL, hidden_size=64, vocab_size=96, num_hidden_layers=6,
           layers_kept=list(range(6)),
           layer_types=["mamba", "mamba", "attention"] * 2,
           num_attention_heads=4, num_key_value_heads=2,
           attention_multiplier=1.0 / 16, mamba_n_heads=4, mamba_d_head=8,
           mamba_d_state=8, mamba_n_groups=1, shared_intermediate_size=128,
           intermediate_size=128)
NAMES = ("x", "B", "C", "dt", "A_log", "dt_bias", "D")


def _gap(got, want):
    """The largest difference over the larger of 1 and the largest
    ``want``."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    return np.abs(got - want).max() / max(1.0, np.abs(want).max())


def _operands(seq, heads, width, groups, state, dtype=jnp.float32, seed=0,
              batch=2):
    """x [b, s, heads, P], B, C [b, s, groups, N] in ``dtype``; dt [b, s,
    heads] and the float32 A_log = log(1..heads), dt_bias, D."""
    r = np.random.RandomState(seed)
    arr = lambda *s: r.normal(size=s)
    return (jnp.asarray(arr(batch, seq, heads, width), dtype),
            jnp.asarray(arr(batch, seq, groups, state) * 0.3, dtype),
            jnp.asarray(arr(batch, seq, groups, state) * 0.3, dtype),
            jnp.asarray(arr(batch, seq, heads) - 1.0, jnp.float32),
            jnp.asarray(np.log(np.arange(1.0, heads + 1)), jnp.float32),
            jnp.asarray(1.0 + 0.1 * arr(heads), jnp.float32),
            jnp.asarray(1.0 + 0.1 * arr(heads), jnp.float32))


def _token_by_token(x, B, C, dt, A_log, dt_bias, D):
    """The reference's own recurrence and skip, in float32."""
    f32 = lambda t: t.astype(jnp.float32)
    x, B, C = f32(x), f32(B), f32(C)
    rep = x.shape[2] // B.shape[2]
    delta = jax.nn.softplus(dt + dt_bias)
    with jax.default_matmul_precision("highest"):
        y = ref.state_space(x, jnp.repeat(B, rep, 2), jnp.repeat(C, rep, 2),
                            delta, -jnp.exp(A_log))
    return y + D[:, None] * x


def _ssd(kernel, monkeypatch, chunk=64):
    """``lm_ops._ssd`` with the recurrence's path as ``kernel`` says (what
    ``gdn_kernels.mode`` would, steered here)."""
    monkeypatch.setattr(gdn_kernels, "mode", lambda *a: kernel)
    return lambda *a: lm_ops._ssd(*a, chunk=chunk)


def _vjp(fn, args, seed=9):
    out, pull = jax.vjp(fn, *args)
    w = jnp.asarray(np.random.RandomState(seed).normal(size=out.shape),
                    out.dtype)
    return out, pull(w)


# -- the op on the lax.scan path ----------------------------------------------

@pytest.mark.parametrize("seq,groups,chunk", [
    (64, 1, 64), (100, 1, 64), (37, 2, 16), (1, 1, 64), (130, 2, 64)])
def test_ssd_is_the_token_by_token_recurrence(seq, groups, chunk,
                                              monkeypatch):
    """Outputs and all seven gradients; float32 both ways, the chunked form
    summing in another order (measured under 1e-5 of the largest)."""
    args = _operands(seq, 4, 8, groups, 8)
    out, grads = _vjp(_ssd(None, monkeypatch, chunk), args)
    want, want_g = _vjp(_token_by_token, args)
    assert _gap(out, want) < 1e-4
    for g, w, name in zip(grads, want_g, NAMES):
        assert _gap(g, w) < 2e-4, name


def test_causal_conv1d_adds_its_bias_before_the_silu():
    r = np.random.RandomState(3)
    x = jnp.asarray(r.normal(size=(2, 11, 6)), jnp.float32)
    w = jnp.asarray(r.normal(size=(6, 4)), jnp.float32)
    b = jnp.asarray(r.normal(size=(6,)), jnp.float32)
    plain = sum(np.pad(np.asarray(x), ((0, 0), (3, 0), (0, 0)))[:, j:j + 11]
                * np.asarray(w)[:, j] for j in range(4)) + np.asarray(b)
    got = lm_ops._causal_conv1d(x, w, b, kernel=4, activation="none",
                                use_bias=True)
    np.testing.assert_allclose(np.asarray(got), plain, rtol=1e-5, atol=1e-5)
    silu = lm_ops._causal_conv1d(x, w, b, kernel=4, use_bias=True)
    np.testing.assert_allclose(np.asarray(silu),
                               np.asarray(jax.nn.silu(jnp.asarray(plain))),
                               rtol=1e-5, atol=1e-5)
    # through the symbol: the bias is a third input of its own shape
    net = mx.sym.causal_conv1d(mx.sym.var("data"), weight=mx.sym.var("w"),
                               bias=mx.sym.var("b"), use_bias=True, kernel=4)
    assert net.list_arguments() == ["data", "w", "b"]
    shapes, _, _ = net.infer_shape(data=(2, 11, 6))
    assert shapes == [(2, 11, 6), (6, 4), (6,)]


# -- the kernels (ops/gdn_kernels.py), in the interpreter ----------------------

# (dtype, value heads, groups, seq): 4 heads of 64 make one 256-lane block;
# 16 take two blocks of eight (512 lanes); two groups of four, a tail chunk
KERNEL_CASES = [(jnp.float32, 4, 1, 128), (jnp.float32, 16, 1, 100),
                (jnp.bfloat16, 8, 2, 128)]


@pytest.mark.parametrize("dtype,heads,groups,seq", KERNEL_CASES,
                         ids=["f32-h4", "f32-h16-tail", "bf16-h8-g2"])
def test_ssd_kernels_are_the_scan_and_the_recurrence(dtype, heads, groups,
                                                     seq, monkeypatch):
    """``ssd_scan_fwd`` / ``ssd_scan_bwd`` at P = 64, N = 128 against the
    ``lax.scan`` they replace (same precisions: to the rounding of their
    sums) and the float32 recurrence (bfloat16 to its rounding)."""
    args = _operands(seq, heads, 64, groups, 128, dtype, seed=4, batch=1)
    out_k, g_k = _vjp(_ssd("interpret", monkeypatch), args)
    out_s, g_s = _vjp(_ssd(None, monkeypatch), args)
    out_r, g_r = _vjp(_token_by_token, args)
    assert out_k.dtype == jnp.dtype(dtype)
    near, far = (1e-5, 1e-4) if dtype == jnp.float32 else (2e-2, 3e-2)
    assert _gap(out_k, out_s) < near
    assert _gap(out_k, out_r) < far
    for got, scan, want, name in zip(g_k, g_s, g_r, NAMES):
        assert got.dtype == scan.dtype, name
        assert _gap(got, scan) < near, name
        assert _gap(got, want) < far, name


def test_ssd_kernel_keeps_one_state_a_chunk():
    """The differentiated forward's states: the one each chunk starts from,
    ``[b hk, n, N, r P]`` with a key head's value heads side by side; the
    scan's, ``[n, b, hk, r, N, P]``, hold the same numbers."""
    x, B, C, dt, A_log, dt_bias, _ = _operands(256, 4, 64, 1, 128, seed=5,
                                               batch=1)
    q, k = (jnp.swapaxes(t, 1, 2) for t in (C, B))
    v = jnp.swapaxes(x, 1, 2)[:, None]
    g = jnp.swapaxes(-jnp.exp(A_log) * jax.nn.softplus(dt + dt_bias), 1,
                     2)[:, None]
    _, kept = lm_ops._gdr_forward(q, k, v, g, None, 64, kernel="interpret")
    _, scanned = lm_ops._gdr_forward(q, k, v, g, None, 64)
    assert kept.shape == (1, 4, 128, 4 * 64)
    as_kernel = jnp.transpose(scanned[:, 0, 0], (0, 2, 1, 3)).reshape(
        4, 128, 256)                                        # [n, N, r P]
    assert _gap(kept[0], as_kernel) < 1e-6


CELL_SSD = dict(x=(1, 8192, 64, 64), B=(1, 8192, 1, 128),
                C=(1, 8192, 1, 128), dt=(1, 8192, 64), A_log=(64,),
                dt_bias=(64,), D=(64,))


def _ssd_jaxpr(platform, dtype="bfloat16"):
    """The jaxpr text of the op's gradient traced for ``platform`` at the
    cell's shape (nothing runs)."""
    avals = [jax.ShapeDtypeStruct(s, jnp.dtype(dtype if n in "xBC"
                                               else "float32"))
             for n, s in CELL_SSD.items()]

    def grad(*a):
        with pk.trace_scope(platform=platform):
            return jax.grad(lambda *b: jnp.sum(lm_ops._ssd(*b).astype(
                jnp.float32)), argnums=range(7))(*a)

    return str(jax.make_jaxpr(grad)(*avals))


def test_a_tpu_program_at_the_cells_shape_holds_the_kernels_and_no_loop():
    text = _ssd_jaxpr("tpu")
    for kernel in ("ssd_scan_fwd", "ssd_scan_bwd"):
        assert "name=%s" % kernel in text, kernel
    assert "scan[" not in text and "while[" not in text
    loop = _ssd_jaxpr("cpu")
    assert "pallas_call" not in loop and loop.count("scan[") == 2
    with pk.trace_scope(partitioned=True):
        assert "pallas_call" not in _ssd_jaxpr("tpu")


def test_ssd_kernels_take_lane_dense_blocks_on_an_unpartitioned_tpu_only():
    q, v = (1, 1, 8192, 128), (1, 1, 64, 8192, 64)
    assert gdn_kernels.mode(q, v, 64, jnp.bfloat16, False) is None  # a CPU
    with pk.trace_scope(platform="tpu"):
        assert gdn_kernels.mode(q, v, 64, jnp.bfloat16, False) == "pallas"
        # the delta rule with its correction still asks 128-wide heads
        assert gdn_kernels.mode(q, v, 64, jnp.bfloat16) is None
        # one 64-wide head alone is half a tile
        assert gdn_kernels.mode(q, v[:2] + (1,) + v[3:], 64, jnp.bfloat16,
                                False) is None
        assert gdn_kernels.mode(q[:3] + (64,), v, 64, jnp.bfloat16,
                                False) is None
        assert gdn_kernels.mode(q, v, 8, jnp.bfloat16, False) is None
        with pk.trace_scope(partitioned=True):
            assert gdn_kernels.mode(q, v, 64, jnp.bfloat16, False) is None


@pytest.mark.parametrize("r,dv,want", [(64, 64, 8), (4, 64, 4), (2, 64, 2),
                                       (6, 64, 6), (8, 128, 4), (1, 64, None),
                                       (3, 64, None)])
def test_ssd_plan_takes_whole_tiles_within_its_budget(r, dv, want):
    hb = gdn_kernels._ssd_plan(r, 64, 128, dv, 2)
    assert hb == want
    if hb:
        assert r % hb == 0 and (hb * dv) % 128 == 0
        assert gdn_kernels._ssd_vmem_bytes(hb, 64, 128, dv, 2) \
            <= gdn_kernels._GDN_VMEM_BUDGET


def test_lowerings_are_counted_by_path(monkeypatch):
    def count(name):
        snap = telemetry.snapshot()
        return snap[name]["value"] if name in snap else 0

    args = _operands(64, 4, 64, 1, 128, batch=1)
    before = {n: count("ops.ssm.lowered_" + n) for n in ("xla", "kernel")}
    jax.make_jaxpr(lambda *a: lm_ops._ssd(*a))(*args)
    assert count("ops.ssm.lowered_xla") == before["xla"] + 1
    with pk.trace_scope(platform="tpu"):
        jax.make_jaxpr(lambda *a: lm_ops._ssd(*a))(*args)
    assert count("ops.ssm.lowered_kernel") == before["kernel"] + 1
    assert count("ops.ssm.lowered_xla") == before["xla"] + 1


# -- the model ----------------------------------------------------------------

def _model(cfg):
    return {k: v for k, v in cfg.items()
            if k not in ("name", "source", "builder", "reference", "reduced",
                         "published", "deployment", "precision", "optimizer",
                         "init", "assumed")}


def test_the_real_configuration_builds_the_stated_parameters():
    sym = models.granite_hybrid.get_symbol(_model(REAL), "bfloat16")
    shapes, outs, _ = sym.infer_shape(data=(1, 8192),
                                      softmax_label=(1, 8192))
    spec = dict(zip(sym.list_arguments(), shapes))
    del spec["data"], spec["softmax_label"]
    assert spec == ref.param_shapes(_model(REAL))
    assert sum(int(np.prod(s)) for s in spec.values()) == 772160448
    assert outs == [(1,)]
    assert models.granite_hybrid.layer_kinds(REAL) == ["mamba"] * 9 \
        + ["attention"]


def test_loss_and_every_gradient_are_the_references():
    """float32 on the CPU: the program's chunked scan and the reference's
    token-by-token one sum in other orders, as do the two attentions; the
    worst leaf measured 1.4e-5 of its largest entry, 1e-4 leaves room and
    is far below what any planted fault moves (10% and more)."""
    model = _model(CFG)
    sym = models.granite_hybrid.get_symbol(model, "float32")
    r = np.random.RandomState(0)
    ids = r.randint(0, 96, (2, 41))
    data, label = ids[:, :-1].astype(np.float32), ids[:, 1:].astype(np.float32)
    spec = ref.param_shapes(model)
    ones = ("_gamma", "_D", "_dt_bias")
    params = {n: jnp.asarray(r.normal(size=s) * (0.3 if len(s) > 1 else 0.1)
                             + (1.0 if n.endswith(ones) else 0.0),
                             jnp.float32) for n, s in spec.items()}
    exe = sym.simple_bind(mx.cpu(), data=(2, 40), softmax_label=(2, 40),
                          grad_req="write")
    for n, v in params.items():
        exe.arg_dict[n][:] = mx.nd.array(np.asarray(v))
    exe.arg_dict["data"][:] = mx.nd.array(data)
    exe.arg_dict["softmax_label"][:] = mx.nd.array(label)
    loss = float(np.mean(exe.forward(is_train=True)[0].asnumpy()))
    exe.backward()
    with jax.default_matmul_precision("highest"):
        want, grads = jax.value_and_grad(ref.loss_fn)(
            params, jnp.asarray(data), jnp.asarray(label), model)
    assert abs(loss - float(want)) < 1e-5 * abs(float(want))
    for n in spec:
        got = exe.grad_dict[n].asnumpy() / 2        # the batch's mean
        want_g = np.asarray(grads[n])
        assert np.abs(got - want_g).max() \
            <= 1e-4 * np.abs(want_g).max() + 1e-9, n


@pytest.mark.parametrize("module,config,count,digest", [
    ("qwen3_next", "qwen3-next-80b-a3b-ep16-bf16", 72, "e4688e7d751e97e5"),
    ("trinity", "trinity-mini-26b-a3b-ep8-bf16", 95, "8289e4423747d436"),
    ("joyai_flash", "joyai-llm-flash-48b-ep16-bf16", 106, "6de49b8bec5ee14d"),
    ("sdar", "sdar-30b-a3b-ep8-bf16", 77, "aef104714395cdc9")])
def test_the_other_models_list_the_same_arguments(module, config, count,
                                                  digest):
    """The shared tail (a tied head, a divisor, no counts) changed nothing
    of the four models before it: the same arguments, in the same order."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    args = getattr(models, module).get_symbol(cfg, "bfloat16") \
        .list_arguments()
    assert len(args) == count
    assert hashlib.sha1(",".join(args).encode()).hexdigest()[:16] == digest
