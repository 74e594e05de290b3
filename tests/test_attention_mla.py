"""The flash kernels at a score width that is not the value width (latent
attention): forward and both backward kernels through the Pallas interpreter
against a float32 oracle written here, at 192 / 128 with the 64-wide key part
that all heads share and at a lane-multiple pair of plain widths, causal, with
lengths, at group 1 and group > 1; the plans' VMEM counts; the node's pair
counts."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.executor import _Program
from mxnet_tpu.ops import pallas_kernels as pk

# (query heads, K/V heads, own key width, shared key width, value width)
WIDTHS = [(4, 4, 128, 64, 128), (4, 2, 128, 64, 128), (4, 4, 256, 0, 128),
          (4, 2, 256, 0, 128), (2, 1, 128, 0, 256)]
IDS = ["192on128-g1", "192on128-g2", "256on128-g1", "256on128-g2",
       "128on256-g2"]


def _inputs(seed, b, s, heads, kv, d_k, d_s, d_v):
    r = np.random.RandomState(seed)
    arr = lambda *shape: jnp.asarray(r.normal(0, 1, shape), jnp.float32)
    return (arr(b, s, heads, d_k + d_s), arr(b, s, kv, d_k),
            arr(b, s, kv, d_v), arr(b, s, d_s) if d_s else None,
            arr(b, s, heads, d_v))


def _oracle(q, k, v, ks, lens, causal=True):
    """softmax(q [k | ks]^T / sqrt(d_qk) + mask) v in float64 numpy, one
    head at a time."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    b, s, heads, d = q.shape
    group = heads // k.shape[2]
    out = np.zeros(q.shape[:3] + v.shape[-1:])
    for i in range(b):
        for h in range(heads):
            key = k[i, :, h // group]
            if ks is not None:
                key = np.concatenate([key, np.asarray(ks, np.float64)[i]], -1)
            scores = q[i, :, h] @ key.T / np.sqrt(d)
            seen = np.arange(s)[None, :] < (s if lens is None else lens[i])
            if causal:
                seen = seen & (np.arange(s)[:, None] >= np.arange(s)[None, :])
            scores = np.where(seen, scores, -np.inf)
            p = np.exp(scores - scores.max(-1, keepdims=True))
            out[i, :, h] = (p / p.sum(-1, keepdims=True)) @ v[i, :, h // group]
    return out


def _flash(q, k, v, ks, lens=None, causal=True, **tiles):
    return pk.flash_attention(q, k, v, causal=causal, use_pallas=True,
                              interpret=True, kv_lens=lens, k_shared=ks,
                              **tiles)


@pytest.mark.parametrize("widths", WIDTHS, ids=IDS)
@pytest.mark.parametrize("lens", [None, (150, 61)], ids=["full", "lengths"])
def test_forward_is_the_oracle(widths, lens):
    q, k, v, ks, _ = _inputs(0, 2, 150, *widths)
    kvl = None if lens is None else jnp.asarray(lens, jnp.float32)
    got = _flash(q, k, v, ks, kvl, block_q=16, block_k=128)
    assert got.shape == q.shape[:3] + (widths[4],)
    np.testing.assert_allclose(got, _oracle(q, k, v, ks, lens), atol=2e-5)
    # ... and the XLA fallback, which copies the shared part to every head
    scale = 1.0 / np.sqrt(q.shape[-1])
    np.testing.assert_allclose(
        pk._reference_attention(q, k, v, True, scale, kvl, 0, ks),
        _oracle(q, k, v, ks, lens), atol=2e-5)


@pytest.mark.parametrize("widths", WIDTHS, ids=IDS)
@pytest.mark.parametrize("lens", [None, (256, 77)], ids=["full", "lengths"])
def test_both_backward_kernels_are_the_oracles_gradient(widths, lens):
    q, k, v, ks, w = _inputs(1, 2, 256, *widths)
    kvl = None if lens is None else jnp.asarray(lens, jnp.float32)
    scale = 1.0 / np.sqrt(q.shape[-1])
    args = (q, k, v) + ((ks,) if ks is not None else ())
    which = tuple(range(len(args)))

    def through(fn):
        return jax.grad(lambda *a: jnp.sum(w * fn(*a)), argnums=which)(*args)

    got = through(lambda q, k, v, ks=None: _flash(
        q, k, v, ks, kvl, block_q=32, block_k=128))
    want = through(lambda q, k, v, ks=None: pk._reference_attention(
        q, k, v, True, scale, kvl, 0, ks))
    for g, r in zip(got, want):
        assert g.shape == r.shape
        np.testing.assert_allclose(g, r, atol=3e-5 * float(jnp.abs(r).max()))
    if ks is not None:
        # the shared part's gradient is the sum over the heads that read it
        assert got[3].shape == ks.shape and float(jnp.abs(got[3]).max()) > 0


def test_full_attention_and_the_plans_own_tiles():
    """Not causal, and with the tiles the plan picks itself."""
    q, k, v, ks, _ = _inputs(2, 1, 300, 4, 2, 128, 64, 128)
    np.testing.assert_allclose(_flash(q, k, v, ks, causal=False),
                               _oracle(q, k, v, ks, None, causal=False),
                               atol=2e-5)
    np.testing.assert_allclose(_flash(q, k, v, ks),
                               _oracle(q, k, v, ks, None), atol=2e-5)


def test_bfloat16_stays_near_the_oracle():
    q, k, v, ks, _ = (None if a is None else a.astype(jnp.bfloat16)
                      for a in _inputs(3, 1, 256, 4, 4, 128, 64, 128))
    got = _flash(q, k, v, ks, block_q=32, block_k=128)
    assert got.dtype == jnp.bfloat16
    want = _oracle(*(np.asarray(a.astype(jnp.float32)) for a in (q, k, v, ks)),
                   None)
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)), want,
                               atol=0.03)


def test_widths_that_do_not_add_up_are_refused():
    q, k, v, ks, _ = _inputs(4, 1, 32, 2, 2, 128, 64, 128)
    with pytest.raises(ValueError, match="wide"):
        _flash(q, k, v, None)
    with pytest.raises(ValueError, match="wide"):
        _flash(q[..., :128], k, v, ks)


def test_eligibility_and_vmem_counts():
    f32 = jnp.float32
    assert pk._flash_eligible(128, 128, 64, f32)
    assert pk._flash_eligible(256, 128, 0, f32)
    assert not pk._flash_eligible(192, 128, 0, f32)     # no lane tile
    assert not pk._flash_eligible(128, 128, 32, f32)
    assert not pk._flash_eligible(128, 128, 64, jnp.int32)
    assert pk._vmem_width(128, 64) == 256 and pk._vmem_width(256) == 256
    # equal widths: the counts and plans the one-width callers had
    for args in ((256, 512, 128, 8, 2), (1024, 1024, 256, 8, 2)):
        assert pk._flash_vmem_bytes(*args) == pk._flash_vmem_bytes(
            *args, d_v=args[2])
        assert pk._flash_bwd_vmem_bytes(*args) == pk._flash_bwd_vmem_bytes(
            *args, d_v=args[2])
    # narrower values, fewer bytes
    assert pk._flash_vmem_bytes(512, 512, 256, 1, 2, d_v=128) \
        < pk._flash_vmem_bytes(512, 512, 256, 1, 2)
    # the latent attention of 32 heads of 192 / 128 over 8,192 tokens
    bq, bk = pk._flash_plan(8192, 8192, 256, 1, 2, True, 128)
    assert (bq, bk) == (2048, 1024)
    assert pk._flash_vmem_bytes(bq, bk, 256, 1, 2, 128) \
        <= pk._FLASH_VMEM_BUDGET
    bwd = pk._flash_bwd_plan(8192, 8192, bq, bk, 256, 1, 2, True, 128)
    assert pk._flash_bwd_vmem_bytes(*bwd, 256, 1, 2, 128) \
        <= pk._FLASH_BWD_VMEM_BUDGET


def test_pairs_of_a_two_width_node(monkeypatch):
    shape_q, shape_k = (1, 1024, 4, 192), (1, 1024, 4, 128)
    visible = 2 * 1024 * 1025 // 2
    # off the kernel: every pair both ways
    assert pk.attention_pairs(shape_q, shape_k, jnp.bfloat16, True,
                              v_width=128, shared_width=64) \
        == (2 * 1024 * 1024, visible)
    monkeypatch.setenv("MXNET_TPU_PALLAS_ATTN", "1")
    computed, seen = pk.attention_pairs(shape_q, shape_k, jnp.bfloat16, True,
                                        v_width=128, shared_width=64)
    assert seen == visible and visible < computed < 3 * 1024 * 1024
    # widths the kernels do not take fall back, and count so
    assert pk.attention_pairs((1, 1024, 4, 192), (1, 1024, 4, 192),
                              jnp.bfloat16, True, v_width=128) \
        == (2 * 1024 * 1024, visible)


def test_the_node_reaches_the_kernels_and_keeps_its_widths(monkeypatch):
    """``scaled_dot_product_attention`` with a shared key part through the
    executor, kernel flag on (interpreter) and off: the same numbers."""
    q, k, v, ks, _ = _inputs(5, 1, 64, 4, 2, 128, 64, 128)
    names = dict(q=q, k=k, v=v, ks=ks)
    node = mx.sym.scaled_dot_product_attention(
        *(mx.sym.Variable(n) for n in ("q", "k", "v")),
        key_shared=mx.sym.Variable("ks"), causal=True, use_shared_key=True)
    off = _Program(node).evaluate(names, {}, (), False)[0][0]
    monkeypatch.setenv("MXNET_TPU_PALLAS_ATTN", "1")
    on = _Program(node).evaluate(names, {}, (), False)[0][0]
    assert on.shape == (1, 64, 4, 128)
    np.testing.assert_allclose(on, off, atol=2e-5)
    np.testing.assert_allclose(on, _oracle(q, k, v, ks, None), atol=2e-5)
