"""The kvstore's 2-bit error-feedback wire format
(kvstore/gradient_compression.py) and the dist kvstore's batched
push/pull and psum cache (kvstore/dist.py)."""
from __future__ import annotations

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.kvstore import gradient_compression as gc


# -- 2-bit wire format -------------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 3, 5, 7, 8])
def test_quantize_flat_non_multiple_of_4(n):
    """Regression: the packed stream covers ceil(n/4) bytes for EVERY
    length — the flat-length contract lives in _pack2, not the caller."""
    import jax.numpy as jnp
    rng = np.random.RandomState(n)
    flat = jnp.asarray(rng.randn(n).astype(np.float32))
    res = jnp.asarray(rng.randn(n).astype(np.float32) * 0.1)
    packed, new_res = gc.quantize_flat(flat, res, 0.5)
    assert packed.shape == (gc.packed_nbytes(n),)
    deq = gc.dequantize_flat(packed, n, 0.5)
    assert deq.shape == (n,)
    # error feedback closes: dequantized + residual == input + old residual
    np.testing.assert_allclose(np.asarray(deq) + np.asarray(new_res),
                               np.asarray(flat) + np.asarray(res),
                               rtol=1e-6)
    # the reference coding: above +t -> +t, below -t -> -t, else 0
    g = np.asarray(flat) + np.asarray(res)
    expect = np.where(g >= 0.5, 0.5, np.where(g <= -0.5, -0.5, 0.0))
    np.testing.assert_allclose(np.asarray(deq), expect, rtol=1e-6)


def test_dequantize_sum_matches_sum_of_dequantized():
    """The compressed-sum oracle: dequantize_sum over every worker's
    packed rows == the sum of individually dequantized gradients."""
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    n, workers, t = 37, 5, 0.25
    rows, expect = [], np.zeros(n, np.float32)
    for w in range(workers):
        flat = jnp.asarray(rng.randn(n).astype(np.float32))
        packed, _ = gc.quantize_flat(flat, jnp.zeros(n, jnp.float32), t)
        rows.append(np.asarray(packed))
        expect += np.asarray(gc.dequantize_flat(packed, n, t))
    got = gc.dequantize_sum_flat(jnp.asarray(np.stack(rows)), n, t)
    np.testing.assert_array_equal(np.asarray(got), expect)


def test_class_quantize_arbitrary_length_roundtrip():
    """The kvstore GradientCompression path with a non-multiple-of-4
    gradient (shape (3, 5) -> 15 values)."""
    import jax.numpy as jnp
    g = jnp.asarray(np.linspace(-1, 1, 15, dtype=np.float32).reshape(3, 5))
    c = gc.GradientCompression(threshold=0.5)
    packed = c.quantize("k", g)
    assert packed.shape == (gc.packed_nbytes(15),)
    deq = c.dequantize(packed, (3, 5))
    assert deq.shape == (3, 5)
    s = c.dequantize_sum(np.asarray(packed)[None], (3, 5))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(deq))


# -- dist kvstore -----------------------------------------------------------

def test_dist_push_pull_list_single_process(monkeypatch):
    """Single-process degenerate path: batched push_pull_list applies
    the same per-key semantics as push+pull (the cross-host collective
    is a no-op without jax.distributed)."""
    from mxnet_tpu.kvstore.dist import DistKVStore
    kv = DistKVStore()
    a0 = mx.nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    b0 = mx.nd.array(np.ones((3,), np.float32))
    kv.init("a", a0)
    kv.init("b", b0)
    ga = mx.nd.array(np.full((2, 3), 2.0, np.float32))
    gb = mx.nd.array(np.full((3,), 3.0, np.float32))
    oa = mx.nd.zeros((2, 3))
    ob = mx.nd.zeros((3,))
    kv.push_pull_list(["a", "b"], [ga, gb], [oa, ob])
    # no updater: the pushed value replaces the stored one; pull reads it
    np.testing.assert_array_equal(oa.asnumpy(), ga.asnumpy())
    np.testing.assert_array_equal(ob.asnumpy(), gb.asnumpy())
    assert kv.wire_bytes_pushed == ga.asnumpy().nbytes + \
        gb.asnumpy().nbytes


def test_dist_psum_cache_lru_bound(monkeypatch):
    from mxnet_tpu.kvstore.dist import DistKVStore
    monkeypatch.setenv("MXNET_TPU_PSUM_CACHE_SIZE", "2")
    kv = DistKVStore()
    for i in range(4):
        kv._cached_fn(("t", i), lambda: i)
    assert len(kv._psum_cache) == 2
    assert ("t", 3) in kv._psum_cache and ("t", 2) in kv._psum_cache
    # hit refreshes recency
    kv._cached_fn(("t", 2), lambda: None)
    kv._cached_fn(("t", 9), lambda: None)
    assert ("t", 2) in kv._psum_cache and ("t", 3) not in kv._psum_cache
