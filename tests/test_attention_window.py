"""A causal window in attention (docs/kernels.md §flash-attention;
docs/trinity.md): query ``i`` sees keys ``i - window < j <= i``.

The Pallas kernel through the interpreter (the code path the chip compiles)
and the XLA reference against a float32 oracle written out by hand, forward
and all three gradients; what the forward's K/V index map and the
backward's q index map fetch, as plain integers; and what an attention node is
built to compute against what its mask lets through (the
``module.attn.pairs_*`` counters)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.executor import _Program
from mxnet_tpu.ops import pallas_kernels as pk

D = 128


def _normal(seed, shape, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).normal(0, 1, shape), dtype)


def _oracle(q, k, v, window, lens=None):
    """float32, every score written out; (output, rows that see a key)."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / D ** 0.5
    i = jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    seen = (j <= i) & (i - j < window)
    seen = jnp.broadcast_to(seen[None, None], s.shape)
    if lens is not None:
        seen &= j[None, None] < lens[:, None, None, None]
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1) * seen
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                     precision=jax.lax.Precision.HIGHEST)
    return out, seen.any(-1).transpose(0, 2, 1)[..., None]


# (seq, query heads, K/V heads, window, block_q, block_k, with lengths)
CASES = [
    (300, 8, 2, 1, 64, 128, False),         # itself alone
    (300, 8, 2, 128, 64, 128, False),       # one K/V tile
    (300, 8, 2, 192, 64, 128, False),       # a tile and a half
    (333, 4, 4, 100, 32, 128, False),       # equal heads, nothing divides
    (512, 8, 1, 130, 128, 64, False),       # block_q > block_k, one K/V head
    (300, 8, 2, 128, 64, 128, True),
    (333, 6, 2, 70, 32, 128, True),
    (100, 4, 2, 48, None, None, False),     # the planned (single) tile
    (700, 8, 2, 256, None, None, True),
]
IDS = ["s%d-h%dkv%d-w%d%s%s" % (c[0], c[1], c[2], c[3],
                               "" if c[4] is None else "-q%dk%d" % c[4:6],
                               "-lens" if c[6] else "") for c in CASES]


def _inputs(case, seed, dtype=jnp.float32):
    seq, heads, kv = case[:3]
    q = _normal(seed, (2, seq, heads, D), dtype)
    k, v = (_normal(seed + n, (2, seq, kv, D), dtype) for n in (1, 2))
    lens = jnp.asarray([seq, seq // 3], jnp.int32) if case[6] else None
    return q, k, v, lens


def _paths(case, lens):
    window, bq, bk = case[3:6]
    return {
        "flash": lambda q, k, v: pk.flash_attention(
            q, k, v, causal=True, use_pallas=True, interpret=True,
            kv_lens=lens, window=window, block_q=bq, block_k=bk),
        "xla": lambda q, k, v: pk._reference_attention(
            q, k, v, True, 1.0 / D ** 0.5, lens, window)}


@pytest.mark.parametrize("path", ["flash", "xla"])
@pytest.mark.parametrize("case", CASES, ids=IDS)
def test_window_forward_matches_the_oracle(case, path):
    q, k, v, lens = _inputs(case, seed=1)
    want, live = _oracle(q, k, v, case[3], lens)
    got = _paths(case, lens)[path](q, k, v)
    assert got.dtype == q.dtype and got.shape == q.shape
    # a row past a short sequence's window sees no key: nothing is asked
    np.testing.assert_allclose(np.asarray(got * live), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("path", ["flash", "xla"])
@pytest.mark.parametrize("case", CASES, ids=IDS)
def test_window_gradients_match_the_oracle(case, path):
    q, k, v, lens = _inputs(case, seed=4)
    _, live = _oracle(q, k, v, case[3], lens)
    w = _normal(7, q.shape) * live

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
                        argnums=(0, 1, 2))(q, k, v)

    want = grads(lambda *a: _oracle(*a, case[3], lens)[0])
    for g, r, name in zip(grads(_paths(case, lens)[path]), want, "qkv"):
        assert g.dtype == r.dtype and g.shape == r.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-4,
                                   atol=2e-4, err_msg="d%s diverged" % name)


def test_window_in_bfloat16_stays_within_its_rounding():
    case = (512, 8, 2, 192, 64, 128, True)
    q, k, v, lens = _inputs(case, seed=9, dtype=jnp.bfloat16)
    want, live = _oracle(q, k, v, 192, lens)
    w = _normal(10, q.shape) * live
    grad = lambda fn, args: jax.grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
        argnums=(0, 1, 2))(*args)
    want_g = grad(lambda *a: _oracle(*a, 192, lens)[0],
                  tuple(x.astype(jnp.float32) for x in (q, k, v)))
    for fn in _paths(case, lens).values():
        got = fn(q, k, v)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got * live, np.float32),
                                   np.asarray(want), rtol=2e-2, atol=2e-2)
        for g, r in zip(grad(fn, (q, k, v)), want_g):
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(r), rtol=0,
                atol=4 * 2.0 ** -8 * float(jnp.abs(r).max()))


@pytest.mark.parametrize("window", [300, 301, 5000])
def test_a_window_that_hides_no_key_is_plain_causal_to_the_bit(window):
    case = (300, 8, 2, window, 64, 128, True)
    q, k, v, lens = _inputs(case, seed=11)
    w = _normal(12, q.shape)
    both = lambda fn: jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for name, fn in _paths(case, lens).items():
        plain = _paths(case[:3] + (0,) + case[4:], lens)[name]
        assert np.array_equal(np.asarray(fn(q, k, v)),
                              np.asarray(plain(q, k, v))), name
        for a, b in zip(jax.tree_util.tree_leaves(both(fn)),
                        jax.tree_util.tree_leaves(both(plain))):
            assert np.array_equal(np.asarray(a), np.asarray(b)), name
    # the same program, not only the same numbers
    text = lambda w: str(jax.make_jaxpr(lambda *a: pk.flash_attention(
        *a, causal=True, use_pallas=True, interpret=True, window=w))(q, k, v))
    assert text(window) == text(0)


def test_a_window_needs_causal_and_a_positive_width():
    q, k, v, _ = _inputs((16, 2, 2, 4, None, None, False), seed=13)
    for kw in (dict(causal=False, window=4), dict(causal=True, window=-1)):
        with pytest.raises(ValueError, match="window"):
            pk.flash_attention(q, k, v, use_pallas=True, interpret=True, **kw)
        with pytest.raises(ValueError, match="window"):
            pk.attention(q, k, v, **kw)


# -- what is fetched, forward and backward: plain integers -----------------------

@pytest.mark.parametrize("blocks", [(64, 128), (256, 128), (128, 512),
                                    (256, 512), (256, 1024)],
                         ids=lambda b: "q%dk%d" % b)
@pytest.mark.parametrize("window", [1, 100, 512, 2048])
def test_kv_index_map_keeps_to_the_window(blocks, window):
    """The K/V tiles a q-block computes are exactly those that hold a key
    some row of it sees, and along its kv steps the mapped index changes
    once per such tile (an unchanged index is no DMA)."""
    bq, bk = blocks
    seq = 4096
    n_q, n_kv = seq // bq, seq // bk
    for qi in range(n_q):
        first = int(pk._first_kv_tile(qi, bq, bk, window))
        last = int(pk._last_kv_tile(qi, seq, bq, bk, True))
        rows = np.arange(qi * bq, qi * bq + bq)
        needed = [t for t in range(n_kv) if any(
            max(r - window + 1, 0) < (t + 1) * bk and t * bk <= r
            for r in (rows[0], rows[-1]))]
        assert list(range(first, last + 1)) == needed
        tiles = [max(min(ki, last), min(first, last)) for ki in range(n_kv)]
        assert 1 + sum(a != b for a, b in zip(tiles, tiles[1:])) \
            == len(needed)
    assert pk._first_kv_tile(3, bq, bk, 0) == 0


@pytest.mark.parametrize("blocks", [(64, 128), (256, 128), (128, 512),
                                    (256, 512), (512, 512)],
                         ids=lambda b: "q%dk%d" % b)
@pytest.mark.parametrize("window", [0, 1, 100, 512, 2048])
@pytest.mark.parametrize("kv_len", [2048, 700])
def test_q_index_map_keeps_to_the_window(blocks, window, kv_len):
    """The transpose of the test above, for the dk/dv kernel: the q tiles a
    K/V tile computes are exactly those that hold a query which sees one of
    its valid keys (every pair written out), and along its q steps the
    mapped index changes once per such tile."""
    bq, bk = blocks
    seq = 2048
    n_q, n_kv = seq // bq, seq // bk
    i = np.arange(seq)[:, None]
    j = np.arange(seq)[None, :]
    seen = (j <= i) & (j < kv_len)
    if window:
        seen &= i - j < window
    for ki in range(n_kv):
        needed = [qi for qi in range(n_q)
                  if seen[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk].any()]
        if ki * bk >= kv_len:     # the kernel's predicate skips the tile
            assert not needed
            continue
        first = int(pk._first_q_tile(ki, bq, bk, True))
        last = int(pk._last_q_tile(ki, kv_len, n_q, bq, bk, window))
        assert list(range(first, last + 1)) == needed
        tiles = [max(min(qi, last), min(first, last)) for qi in range(n_q)]
        assert 1 + sum(a != b for a, b in zip(tiles, tiles[1:])) \
            == len(needed)
        # what the forward's q-blocks compute of this K/V tile, seen from
        # the other side (the forward does not know the length's reach
        # through the window, so it may compute more, never less)
        fwd = [qi for qi in range(n_q)
               if int(pk._first_kv_tile(qi, bq, bk, window)) <= ki
               <= int(pk._last_kv_tile(qi, kv_len, bq, bk, True))]
        assert set(needed) <= set(fwd)
        assert kv_len < seq or needed == fwd
    # without a diagonal every q tile sees every valid K/V tile
    assert pk._first_q_tile(3, bq, bk, False) == 0
    assert int(pk._last_q_tile(0, kv_len, n_q, bq, bk, 0)) == n_q - 1


def test_flash_plan_at_the_window_cells_shape():
    """`trinity-mini-train-s8k-b1`: 32 query heads over 4 K/V heads of 128,
    bfloat16, 8,192 keys.  The plan does not know the window: its layers
    and the full ones take PR 31's tiles alike (narrower K/V tiles overhang
    the band less and were slower on the chip: PERF.md, PR 32)."""
    tiles = pk._flash_plan(8192, 8192, D, 8, 2, True)
    assert tiles == (256, 1024)
    assert pk._flash_vmem_bytes(*tiles, D, 8, 2) <= pk._FLASH_VMEM_BUDGET


# -- computed against visible ---------------------------------------------------------

CELL_Q, CELL_K = (1, 8192, 32, D), (1, 8192, 4, D)


def test_attention_pairs_of_the_cells_layers():
    """Visible: 14.7 M pairs a window layer, 33.6 M a full one, each way.
    Computed on the chip: the forward's needed 256 x 1,024 tiles once, and
    the backward's needed 512 x 512 tiles TWICE: its dq and its dk/dv kernel
    each score the pairs of their tiles."""
    with pk.trace_scope(platform="tpu"):
        wc, wv = pk.attention_pairs(CELL_Q, CELL_K, jnp.bfloat16, True, 2048)
        fc, fv = pk.attention_pairs(CELL_Q, CELL_K, jnp.bfloat16, True)
    assert wv == 2 * (2048 * 2049 // 2 + 6144 * 2048) == 2 * 14_681_088
    assert fv == 2 * (8192 * 8193 // 2) == 2 * 33_558_528
    assert 4 * wv + fv == 184_565_760
    assert pk._flash_bwd_plan(8192, 8192, 256, 1024, D, 8, 2, True) \
        == (512, 512)
    fwd_full = 256 * 1024 * sum(i // 4 + 1 for i in range(32))
    bwd_full = 512 * 512 * sum(i + 1 for i in range(16))
    assert fc == fwd_full + 2 * bwd_full == 109_051_904
    fwd_tiles = sum((256 * i + 255) // 1024 - max(256 * i - 2047, 0) // 1024
                    + 1 for i in range(32))
    bwd_tiles = sum(i - max(512 * i - 2047, 0) // 512 + 1 for i in range(16))
    assert wc == 256 * 1024 * fwd_tiles + 2 * 512 * 512 * bwd_tiles
    # one pass over the visible pairs each way would read 1; the forward's
    # overhang and the backward's second scoring pass make it 1.8
    assert 1.7 < (4 * wc + fc) / (4 * wv + fv) < 1.9
    # the window ignored in all three kernels would read 3.0
    assert 2.9 < 5 * fc / (4 * wv + fv) < 3.1
    # off the chip the XLA reference computes every pair both ways
    with pk.trace_scope(platform="cpu"):
        assert pk.attention_pairs(CELL_Q, CELL_K, jnp.bfloat16, True, 2048) \
            == (2 * 8192 * 8192, wv)
        # a head that is not lane-tiled takes the reference on the chip too
    with pk.trace_scope(platform="tpu"):
        assert pk.attention_pairs((2, 64, 4, 16), (2, 64, 2, 16),
                                  jnp.float32, True, 8) \
            == (2 * 2 * 64 * 64, 2 * 2 * (36 + 56 * 8))
        assert pk.attention_pairs((2, 64, 4, 16), (2, 48, 2, 16),
                                  jnp.float32, False) \
            == (2 * 2 * 64 * 48, 2 * 2 * 64 * 48)


def _sdpa_net(window, causal=True):
    q, k, v = (mx.sym.Variable(n) for n in "qkv")
    return mx.sym.scaled_dot_product_attention(q, k, v, causal=causal,
                                               window=window, name="attn")


def test_the_op_takes_a_window():
    q, k, v, _ = _inputs((40, 4, 2, 8, None, None, False), seed=20)
    net = _sdpa_net(8)
    shapes, out, _ = net.infer_shape(q=q.shape, k=k.shape, v=v.shape)
    assert out == [q.shape]
    prog = _Program(net)
    got = prog.evaluate(dict(q=q, k=k, v=v), {}, (), False)[0][0]
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_oracle(q, k, v, 8)[0]),
                               rtol=2e-5, atol=2e-5)
    args = ({n: x.shape for n, x in zip("qkv", (q, k, v))},
            {n: np.float32 for n in "qkv"})
    with pk.trace_scope(platform="cpu"):
        assert prog.attention_pairs(*args) \
            == (2 * 2 * 40 * 40, 2 * 2 * (36 + 32 * 8))
        assert _Program(mx.sym.exp(mx.sym.Variable("q"))).attention_pairs(
            *args) == (0, 0)
    with pytest.raises(ValueError, match="window"):
        _Program(_sdpa_net(8, causal=False)).evaluate(
            dict(q=q, k=k, v=v), {}, (), False)
    # the fused node counts too: 4 heads of 8 over [2, 40, 32], causal
    x = mx.sym.Variable("x")
    fused = _Program(mx.sym.multi_head_attention(x, x, x, num_heads=4,
                                                 causal=True, name="mha"))
    with pk.trace_scope(platform="cpu"):
        assert fused.attention_pairs({"x": (2, 40, 32)}, {"x": np.float32}) \
            == (2 * 2 * 40 * 40, 2 * 2 * 820)


def test_the_scopes_name_the_kind_of_layer():
    q, k, v, _ = _inputs((40, 4, 2, 8, None, None, False), seed=21)
    text = lambda w: jax.jit(lambda *a: _Program(_sdpa_net(w)).evaluate(
        dict(zip("qkv", a)), {}, (), False)[0][0]).lower(q, k, v).as_text(
            debug_info=True)
    assert "mx:attn/mx:attn:window" in text(8)
    assert "mx:attn/mx:attn:full" in text(0)
    assert "mx:attn:window" not in text(40)     # hides no key: a full layer
