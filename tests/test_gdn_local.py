"""The delta rule's chunk-local algebra as two Pallas kernels
(``ops/gdn_kernels.py``: ``gdn_local_fwd``, ``gdn_local_bwd``), in the
Pallas interpreter, against the XLA ``lm_ops._chunk_local`` they replace
and its ``jax.vjp``; the whole chunked rule with both the local and the scan
kernels against the ``lax.scan`` oracle; and the step program's count of
the chunks whose local part runs in the kernels."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import models
from mxnet_tpu.executor import _Program
from mxnet_tpu.ops import gdn_kernels, lm_ops
from mxnet_tpu.ops import pallas_kernels as pk

from benchmark.references import qwen3_next as ref

CHUNK = 64
NAMES = ("q", "k", "v", "g", "beta")


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * max(1.0, np.abs(want).max()))


def _gap(got, want):
    """The largest difference over the larger of 1 and the largest
    ``want``."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / max(1.0, np.abs(want).max())


def _f32(x):
    return x.astype(jnp.float32)


def _operands(seq, r, dtype, seed=0, hk=2, batch=2):
    """q, k ``[b, hk, seq, 128]`` (unit rows, q scaled as the op scales
    it), v ``[b, hk, r, seq, 128]`` in ``dtype``; g, beta ``[b, hk, r,
    seq]`` float32: the op's layout at the kernels' widths."""
    rs = np.random.RandomState(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rs.normal(size=(batch, hk, seq, 128))) / np.sqrt(128)
    k = unit(rs.normal(size=(batch, hk, seq, 128)))
    v = rs.normal(size=(batch, hk, r, seq, 128))
    g = -np.abs(rs.normal(size=(batch, hk, r, seq))) * 0.3
    beta = 1.0 / (1.0 + np.exp(-rs.normal(size=(batch, hk, r, seq))))
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(v, dtype), jnp.asarray(g, jnp.float32),
            jnp.asarray(beta, jnp.float32))


def _chunked(seq, r, dtype, seed=0):
    """The operands as ``_chunk_local`` takes them, a tail chunk padded as
    ``chunked_gated_delta_rule`` pads it (beta 0, decay 1)."""
    q, k, v, g, beta = _operands(seq, r, dtype, seed)
    pad = (-seq) % CHUNK
    at = lambda x, axis: jnp.pad(
        x, [(0, pad if i == axis else 0) for i in range(x.ndim)])
    return lm_ops._chunks(at(q, 2), at(k, 2), at(v, 3), at(g, 3),
                          at(beta, 3), CHUNK)


CASES = [(dtype, r, seq) for dtype in ("float32", "bfloat16")
         for r in (1, 2) for seq in (128, 100)]


@pytest.mark.parametrize("dtype,r,seq", CASES)
def test_local_fwd_is_chunk_local(dtype, r, seq):
    """u, m, qk, grow, shrink and g_all of ``gdn_local_fwd`` are
    ``_chunk_local``'s, in their dtypes and layout; float32 to its rounding,
    bfloat16 to the rounding of the cast outputs."""
    args = _chunked(seq, r, dtype)
    want = lm_ops._chunk_local(*args)
    got, inv = gdn_kernels.local_fwd(*args, keep_inverse=True,
                                     interpret=True)
    assert gdn_kernels.local_fwd(*args, interpret=True)[1] is None
    b, hk, n, c, _ = args[0].shape
    assert inv.shape == (b * hk, n, c, r * c) and inv.dtype == jnp.float32
    tol = 1e-5 if dtype == "float32" else 1e-2
    for name, x, w in zip(("u", "m", "qk", "grow", "shrink", "g_all"),
                          got, want):
        assert x.shape == w.shape and x.dtype == w.dtype, name
        _close(_f32(x), _f32(w), tol), name


@pytest.mark.parametrize("dtype,r,seq", CASES)
def test_local_bwd_is_the_pull_back(dtype, r, seq):
    """``gdn_local_bwd``'s five cotangents against ``jax.vjp(_chunk_local)``
    for random cotangents of all six outputs: ``g_all``'s reaches the
    kernel inside ``grow``'s last column, as ``scan_bwd`` hands it.  In
    float32 to the rounding; in bfloat16 each within 1e-2 of the float32
    pull-back of the same operands and no further from it than XLA's own
    bfloat16 pull-back is."""
    args = _chunked(seq, r, dtype)
    local, pull = jax.vjp(lm_ops._chunk_local, *args)
    rs = np.random.RandomState(7)
    d = [jnp.asarray(rs.normal(size=x.shape), x.dtype) for x in local]
    want = pull(tuple(d))
    folded = list(d)
    folded[3] = d[3].at[..., -1].add(d[5])
    folded[5] = jnp.zeros_like(d[5])
    _, inv = gdn_kernels.local_fwd(*args, keep_inverse=True, interpret=True)
    got = gdn_kernels.local_bwd(*args, inv, tuple(folded), interpret=True)
    if dtype == "float32":
        for name, x, w in zip(NAMES, got, want):
            assert x.dtype == w.dtype, name
            _close(x, w, 1e-5), name
        return
    _, pull32 = jax.vjp(lm_ops._chunk_local, *(_f32(x) for x in args))
    truth = pull32(tuple(_f32(x) for x in d))
    for name, x, w, t in zip(NAMES, got, want, truth):
        assert x.dtype == w.dtype, name
        _close(_f32(x), t, 1e-2), name
        assert _gap(_f32(x), t) <= _gap(_f32(w), t) + 1e-3, name


def _rule(kernel, monkeypatch, q, k, v, g, beta):
    """``chunked_gated_delta_rule`` with the recurrence as ``kernel`` says
    (what ``gdn_kernels.mode`` would, steered here): "interpret" runs the
    scan kernels and, the shape having their plan, the local kernels."""
    monkeypatch.setattr(gdn_kernels, "mode", lambda *a: kernel)
    return lm_ops.chunked_gated_delta_rule(q, k, v, g, beta, CHUNK)


def _token_by_token(q, k, v, g, beta):
    """The reference's recurrence, token by token in float32, on the op's
    layout."""
    b, hk, r, t, dv = v.shape
    rows = lambda x: jnp.repeat(jnp.swapaxes(x, 1, 2), r, 2)
    grouped = lambda x: jnp.moveaxis(x.reshape((b, hk * r, t) + x.shape[4:]),
                                     1, 2)
    out = ref.delta_rule(rows(q), rows(k), grouped(v), jnp.exp(grouped(g)),
                         grouped(beta))
    return jnp.moveaxis(out, 2, 1).reshape(v.shape)


@pytest.mark.parametrize("dtype,r,seq", [
    ("float32", 2, 128), ("float32", 1, 100), ("bfloat16", 2, 100),
    ("bfloat16", 1, 128)])
def test_chunked_rule_with_both_kernels_is_the_scan(dtype, r, seq,
                                                    monkeypatch):
    """Outputs and the five gradients of the rule with the local and the
    scan kernels against the ``lax.scan`` after XLA's ``_chunk_local`` (the
    oracle) and the token-by-token recurrence: float32 to the rounding of
    their sums; bfloat16 outputs within 1e-2 of the oracle's, and every
    gradient within 3e-2 of the recurrence's and no further from it than
    the oracle's."""
    args = _operands(seq, r, dtype, seed=3)
    w = jnp.asarray(np.random.RandomState(4).normal(size=args[2].shape),
                    jnp.float32)

    def both(fn, *a):
        out, pull = jax.vjp(fn, *a)
        return out, pull(w.astype(out.dtype))

    out_k, g_k = both(lambda *a: _rule("interpret", monkeypatch, *a), *args)
    out_s, g_s = both(lambda *a: _rule(None, monkeypatch, *a), *args)
    out_r, g_r = both(_token_by_token, *(_f32(x) for x in args))
    assert out_k.dtype == jnp.dtype(dtype)
    if dtype == "float32":
        _close(out_k, out_s, 1e-5)
        for name, x, s in zip(NAMES, g_k, g_s):
            _close(x, s, 1e-5), name
        return
    _close(_f32(out_k), _f32(out_s), 1e-2)
    _close(_f32(out_k), out_r, 3e-2)
    for name, x, s, t in zip(NAMES, g_k, g_s, g_r):
        assert x.dtype == s.dtype, name
        _close(_f32(x), t, 3e-2), name
        assert _gap(_f32(x), t) <= _gap(_f32(s), t) + 1e-3, name


@pytest.mark.parametrize("bh,r,n,itemsize", [
    (32, 2, 128, 2),          # the cell: 2 x 16 key heads, 8,192 tokens
    (32, 2, 128, 4), (6, 1, 3, 2), (7, 4, 256, 2), (1, 2, 16, 4)])
def test_local_plan_divides_the_grid_and_fits_its_budget(bh, r, n,
                                                         itemsize):
    most = gdn_kernels._GDN_MAX_HEADS
    count = gdn_kernels._local_vmem_bytes
    heads = gdn_kernels._gdn_plan(bh, r, n, 64, 128, 128, itemsize, count)
    assert heads and bh % heads == 0 and heads <= most
    assert count(heads, r, n, 64, 128, 128, itemsize) \
        <= gdn_kernels._GDN_VMEM_BUDGET
    assert all(count(h, r, n, 64, 128, 128, itemsize)
               > gdn_kernels._GDN_VMEM_BUDGET
               for h in range(heads + 1, most + 1) if bh % h == 0)


def test_local_kernels_take_the_cell_and_leave_a_long_sequence_to_xla():
    """The shape alone decides: the cell's has a plan; a sequence whose
    decay vectors cannot stay resident has none (the scan kernels may still
    take it, their plan holding fewer vectors), and the local part stays
    ``_chunk_local``."""
    q, v = (2, 16, 8192, 128), (2, 16, 2, 8192, 128)
    assert gdn_kernels.local_planned(q, v, 64, jnp.bfloat16)
    long = 64 * 2500
    q_long, v_long = q[:2] + (long, 128), v[:3] + (long, 128)
    assert not gdn_kernels.local_planned(q_long, v_long, 64, jnp.bfloat16)
    with pk.trace_scope(platform="tpu"):
        assert gdn_kernels.mode(q_long, v_long, 64, jnp.bfloat16) == "pallas"


WIDE = dict(
    hidden_size=32, vocab_size=50, num_hidden_layers=4,
    full_attention_interval=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, partial_rotary_factor=0.25, rope_theta=1e7,
    rms_norm_eps=1e-6, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=128, linear_value_head_dim=128,
    linear_conv_kernel_dim=4, num_experts=4, router_num_experts=16,
    first_expert=4, num_experts_per_tok=3, norm_topk_prob=True,
    moe_intermediate_size=16, shared_expert_intermediate_size=16)


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_program_counts_the_local_chunks_in_kernel(platform):
    """``_Program.gdn_chunk_steps``' third number: the chunk steps whose
    local part runs in ``gdn_local_fwd`` / ``gdn_local_bwd`` — all of them
    in a program traced for a TPU at the kernels' widths (three delta-rule
    layers, two chunks of 2 x 2 key heads, three passes), none on the
    CPU."""
    prog = _Program(models.qwen3_next.get_symbol(WIDE))
    shapes = {"data": (2, 70), "softmax_label": (2, 70)}
    dtypes = {"data": np.float32, "softmax_label": np.float32}
    with pk.trace_scope(platform=platform):
        steps, in_kernel, local = prog.gdn_chunk_steps(shapes, dtypes)
    assert steps == 3 * (3 * 2 * 4)
    assert (in_kernel, local) == ((steps, steps) if platform == "tpu"
                                  else (0, 0))
