"""Native attention subsystem tier (ISSUE 19; docs/kernels.md
§flash-attention).

Three contracts:

1. **Kernel parity.** The Pallas flash-attention kernel runs through the
   interpreter (the exact kernel code path the chip compiles) and must
   match the XLA reference — forward AND grads, f32 and bf16, causal /
   padding-mask / block-padded odd lengths.
2. **The flag contract.** ``MXNET_TPU_PALLAS_ATTN`` rides
   ``kernel_signature()`` into the executor-cache key: enabling costs
   exactly one retrace of a real transformer fwd_bwd program, disabling
   costs zero, and the off path is bitwise what it was before the round
   trip.
3. **The health tap.** With ``MXNET_TPU_HEALTH=1`` the packed summary
   carries a ``max_abs_attn_logit/<node>`` slot per attention node — an
   upper bound on the node's max |logit| (Cauchy-Schwarz, uniform across
   kernel modes); absent taps pack -1.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import executor_cache
from mxnet_tpu.observability import health
from mxnet_tpu.ops import pallas_kernels as pk


def _rng(seed=0):
    return np.random.RandomState(seed)


def _qkv(b, s, h, d, dtype=jnp.float32, seed=0):
    r = _rng(seed)
    mk = lambda: jnp.asarray(r.normal(0, 1, (b, s, h, d)), dtype)
    return mk(), mk(), mk()


# ---------------------------------------------------------------------------
# 1) Flash kernel (interpret mode) vs the XLA reference oracle
# ---------------------------------------------------------------------------

ATTN_CASES = [
    # (dtype, causal, with_lens, seq, block_q, block_k): None = planned
    (jnp.float32, False, False, 16, None, None),
    (jnp.float32, True, False, 16, None, None),
    (jnp.float32, False, True, 16, None, None),
    (jnp.float32, True, True, 13, None, None),   # odd: block padding + mask
    (jnp.bfloat16, False, False, 16, None, None),
    (jnp.bfloat16, True, True, 16, None, None),
    # several q- and kv-tiles a sequence, block_q != block_k
    (jnp.float32, True, True, 300, 64, 128),
    (jnp.bfloat16, False, True, 256, 128, 64),
]


def _blocks_id(bq, bk):
    return "" if bq is None else "-q%dk%d" % (bq, bk)


ATTN_IDS = ["%s-%s%s-s%d%s" % (np.dtype(c[0]).name,
                               "causal" if c[1] else "full",
                               "-lens" if c[2] else "", c[3],
                               _blocks_id(*c[4:]))
            for c in ATTN_CASES]


def _lens(seq, with_lens):
    """Valid K/V lengths of a batch of two: the whole sequence, and one cut
    by a few keys (a third of a long one, so whole K/V tiles lie past it)."""
    if not with_lens:
        return None
    return jnp.asarray([seq, max(1, seq - 5) if seq < 64 else seq // 3],
                       jnp.int32)


def _tols(dtype):
    return {"rtol": 2e-2, "atol": 2e-2} if dtype == jnp.bfloat16 \
        else {"rtol": 2e-5, "atol": 2e-5}


@pytest.mark.parametrize("case", ATTN_CASES, ids=ATTN_IDS)
def test_flash_forward_matches_reference(case):
    dtype, causal, with_lens, seq, block_q, block_k = case
    q, k, v = _qkv(2, seq, 2, 128, dtype, seed=1)
    lens = _lens(seq, with_lens)
    scale = 1.0 / 128 ** 0.5
    want = pk._reference_attention(q, k, v, causal, scale, lens)
    got = pk.flash_attention(q, k, v, causal=causal, use_pallas=True,
                             interpret=True, kv_lens=lens,
                             block_q=block_q, block_k=block_k)
    assert got.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tols(dtype))


@pytest.mark.parametrize("case", ATTN_CASES, ids=ATTN_IDS)
def test_flash_grads_match_reference(case):
    dtype, causal, with_lens, seq, block_q, block_k = case
    q, k, v = _qkv(2, seq, 2, 128, dtype, seed=2)
    lens = _lens(seq, with_lens)
    scale = 1.0 / 128 ** 0.5
    w = jnp.asarray(_rng(3).normal(0, 1, q.shape), jnp.float32)

    def loss(fn):
        def f(q_, k_, v_):
            o = fn(q_, k_, v_)
            return jnp.sum(o.astype(jnp.float32) * w)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    want = loss(lambda q_, k_, v_: pk._reference_attention(
        q_, k_, v_, causal, scale, lens))
    got = loss(lambda q_, k_, v_: pk.flash_attention(
        q_, k_, v_, causal=causal, use_pallas=True, interpret=True,
        kv_lens=lens, block_q=block_q, block_k=block_k))
    tol = {"rtol": 3e-2, "atol": 3e-2} if dtype == jnp.bfloat16 \
        else {"rtol": 2e-4, "atol": 2e-4}
    for g, r, name in zip(got, want, "qkv"):
        assert g.dtype == r.dtype
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(r, np.float32),
            err_msg="d%s diverged" % name, **tol)


# grouped-query attention: more query heads than K/V heads, each K/V head
# reached by index (never repeated in memory)
GQA_CASES = [
    # (dtype, causal, with_lens, seq, query heads, K/V heads, head size,
    #  block_q, block_k): None = planned
    (jnp.float32, True, False, 16, 4, 2, 128, None, None),
    (jnp.float32, False, True, 13, 6, 2, 128, None, None),
    (jnp.float32, True, True, 24, 4, 1, 128, None, None),
    (jnp.bfloat16, True, False, 16, 8, 2, 128, None, None),
    # a group's heads in one q tile over SEVERAL q- and kv-tiles: forced
    # small tiles, block_q != block_k, causal and full, lengths that leave
    # whole tiles out, an odd length, head 256, the cell's 16 heads over 2
    (jnp.float32, True, False, 512, 8, 2, 128, 64, 128),
    (jnp.float32, False, True, 512, 8, 2, 128, 128, 64),
    (jnp.float32, True, True, 333, 16, 2, 128, 32, 128),
    (jnp.bfloat16, True, True, 512, 16, 2, 256, 64, 256),
    (jnp.bfloat16, False, False, 512, 8, 2, 256, 128, 128),
    # ... and the planned tiles where the plan itself takes several
    (jnp.float32, True, False, 512, 8, 2, 128, None, None),
    (jnp.float32, True, True, 1100, 8, 2, 128, None, None),
]
GQA_IDS = ["%s-%s%s-s%d-h%dkv%d-d%d%s" % (
    np.dtype(c[0]).name, "causal" if c[1] else "full",
    "-lens" if c[2] else "", c[3], c[4], c[5], c[6], _blocks_id(*c[7:]))
    for c in GQA_CASES]


def _gqa_inputs(case, seed):
    dtype, causal, with_lens, seq, heads, kv, d = case[:7]
    q, _, _ = _qkv(2, seq, heads, d, dtype, seed=seed)
    _, k, v = _qkv(2, seq, kv, d, dtype, seed=seed + 10)
    return q, k, v, _lens(seq, with_lens)


def _gqa_flash(case, lens):
    dtype, causal = case[:2]
    block_q, block_k = case[7:]
    return lambda q, k, v: pk.flash_attention(
        q, k, v, causal=causal, use_pallas=True, interpret=True,
        kv_lens=lens, block_q=block_q, block_k=block_k)


def _repeated(fn, group):
    """The oracle: equal-heads attention on K/V heads repeated by hand."""
    return lambda q, k, v: fn(q, jnp.repeat(k, group, axis=2),
                              jnp.repeat(v, group, axis=2))


@pytest.mark.parametrize("case", GQA_CASES, ids=GQA_IDS)
def test_gqa_forward_matches_repeated_heads(case):
    dtype, causal, _, _, heads, kv, d = case[:7]
    q, k, v, lens = _gqa_inputs(case, seed=4)
    scale = 1.0 / d ** 0.5
    want = _repeated(lambda q_, k_, v_: pk._reference_attention(
        q_, k_, v_, causal, scale, lens), heads // kv)(q, k, v)
    for got in (pk._reference_attention(q, k, v, causal, scale, lens),
                _gqa_flash(case, lens)(q, k, v)):
        assert got.dtype == q.dtype and got.shape == q.shape
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **_tols(dtype))


@pytest.mark.parametrize("case", GQA_CASES, ids=GQA_IDS)
def test_gqa_grads_match_repeated_heads(case):
    dtype, causal, _, _, heads, kv, d = case[:7]
    q, k, v, lens = _gqa_inputs(case, seed=5)
    scale = 1.0 / d ** 0.5
    w = jnp.asarray(_rng(6).normal(0, 1, q.shape), jnp.float32)

    def grads(fn, args=(q, k, v)):
        return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
                        argnums=(0, 1, 2))(*args)

    # the oracle runs in float32 whatever the case's dtype: over hundreds
    # of keys a bfloat16 autodiff is the noisier side of the comparison
    want = grads(_repeated(lambda q_, k_, v_: pk._reference_attention(
        q_, k_, v_, causal, scale, lens), heads // kv),
        tuple(x.astype(jnp.float32) for x in (q, k, v)))
    for fn in (lambda q_, k_, v_: pk._reference_attention(
                   q_, k_, v_, causal, scale, lens),
               _gqa_flash(case, lens)):
        for g, x, r, name in zip(grads(fn), (q, k, v), want, "qkv"):
            assert g.dtype == x.dtype and g.shape == r.shape
            # bfloat16 keeps 8 bits: four of its roundings of the largest
            # gradient; float32 to the products' own round-off
            tol = {"rtol": 0, "atol": 4 * 2.0 ** -8 * float(jnp.abs(r).max())} \
                if dtype == jnp.bfloat16 else {"rtol": 2e-4, "atol": 2e-4}
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(r, np.float32),
                err_msg="d%s diverged" % name, **tol)


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, nested ones included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_pallas_calls(sub))
    return found


def test_equal_heads_program_is_what_it_was():
    """Equal heads are the group of one of the ONE forward kernel: the same
    ``flash_attn_fwd`` call whose grid runs over batch x K/V heads and
    whose q tile holds ``group`` heads — no second kernel, no switch.  The
    XLA reference for equal heads still has no group axis."""
    q, k, v = _qkv(1, 16, 2, 128, seed=7)

    def call(k_, v_):
        calls = _pallas_calls(jax.make_jaxpr(lambda *a: pk.flash_attention(
            *a, causal=True, use_pallas=True, interpret=True))(
            q, k_, v_).jaxpr)
        assert len(calls) == 1
        mapping = calls[0].params["grid_mapping"]
        return (calls[0].params["name"], mapping.grid,
                mapping.block_mappings[0].block_shape)

    name, grid, q_block = call(k, v)
    g_name, g_grid, g_q_block = call(k[:, :, :1], v[:, :, :1])
    assert name == g_name == "flash_attn_fwd"
    assert grid == (2, 1, 1) and g_grid == (1, 1, 1)
    assert [int(getattr(x, "block_size", x)) for x in q_block] \
        == [1, 1, 16, 128]
    assert [int(getattr(x, "block_size", x)) for x in g_q_block] \
        == [1, 2, 16, 128]
    ref = jax.make_jaxpr(lambda *a: pk._reference_attention(
        *a, True, 0.1))(q, k, v).pretty_print()
    assert "bqhgd" not in ref and ref.count("dot_general") == 2


def _primitives(jaxpr):
    """Names of every primitive of a jaxpr, nested ones included."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


@pytest.mark.parametrize("heads,kv,window,with_lens", [
    (2, 2, 0, False), (8, 1, 0, True), (8, 1, 48, False)],
    ids=["group1", "group8-lens", "group8-window"])
def test_the_backward_is_two_kernels_and_no_loop(heads, kv, window,
                                                 with_lens):
    """A differentiated call holds the forward's kernel and the backward's
    two, ``flash_attn_bwd_dq`` and ``flash_attn_bwd_dkv`` (no name of theirs
    holds the forward's: a metric finds the forward by it), whose grids are
    each other's transpose, and no XLA loop.  A group of one is the same
    pair of kernels."""
    q, _, _ = _qkv(1, 256, heads, 128, seed=8)
    _, k, v = _qkv(1, 256, kv, 128, seed=9)
    lens = jnp.asarray([200], jnp.int32) if with_lens else None
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(pk.flash_attention(
            *a, causal=True, use_pallas=True, interpret=True, window=window,
            kv_lens=lens, block_q=64, block_k=128)), argnums=(0, 1, 2)))(
        q, k, v).jaxpr
    calls = {c.params["name"]: c.params["grid_mapping"].grid
             for c in _pallas_calls(jaxpr)}
    assert calls == {"flash_attn_fwd": (kv, 4, 2),
                     "flash_attn_bwd_dq": (kv, 4, 2),
                     "flash_attn_bwd_dkv": (kv, 2, 4)}
    assert sum("flash_attn_fwd" in name for name in calls) == 1
    assert not {"while", "scan"} & _primitives(jaxpr)


@pytest.mark.parametrize("heads,kv", [(2, 2), (8, 1)],
                         ids=["group1", "group8"])
@pytest.mark.parametrize("window", [0, 40], ids=["full", "window"])
def test_rows_that_see_no_key_take_no_gradient(heads, kv, window):
    """Lengths that do not tile, one sequence cut short and one EMPTY: a
    query row with no valid key (every row of the empty sequence; under a
    window, the rows past the short one's reach) has p re-masked to 0 in
    the backward: its dq is zero, it adds nothing to dk and dv, and
    nothing is NaN, though its cotangent is not zero."""
    seq, d = 333, 128
    q, _, _ = _qkv(3, seq, heads, d, seed=11)
    _, k, v = _qkv(3, seq, kv, d, seed=12)
    lens = jnp.asarray([seq, 100, 0], jnp.int32)
    w = jnp.asarray(_rng(13).normal(0, 1, q.shape), jnp.float32)
    i = jnp.arange(seq)[:, None]
    j = jnp.arange(seq)[None, :]
    seen = (j <= i) & ((i - j < window) if window else True)
    live = (seen[None] & (j[None] < lens[:, None, None])).any(-1)  # [B, S]
    assert not bool(live[2].any()) and bool(live[0].all())
    assert bool(live[1].all()) == (window == 0)

    def grads(fn, weight):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * weight),
                        argnums=(0, 1, 2))(q, k, v)

    got = grads(lambda *a: pk.flash_attention(
        *a, causal=True, use_pallas=True, interpret=True, kv_lens=lens,
        window=window, block_q=32, block_k=128), w)
    # the oracle is asked about the live rows only
    want = grads(lambda *a: pk._reference_attention(
        *a, True, 1.0 / d ** 0.5, lens, window), w * live[:, :, None, None])
    for g, r, name in zip(got, want, "qkv"):
        assert bool(jnp.isfinite(g).all()), name
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-4,
                                   atol=2e-4, err_msg="d%s diverged" % name)
    dq = np.asarray(got[0])
    assert not dq[~np.asarray(live)].any() and dq[np.asarray(live)].any()
    assert not np.asarray(got[1])[2].any() and not np.asarray(got[2])[2].any()


# the tile plan as a pure function (docs/kernels.md §flash-attention)

def test_flash_plan_at_the_language_model_cells_shape():
    """8,192 tokens, 16 query heads over 2 K/V heads of 256, bf16, causal,
    batch 2: about a thousand grid steps where 128 x 128 tiles one query
    head at a time took 131,072, inside the stated VMEM budget."""
    bq, bk = pk._flash_plan(8192, 8192, 256, 8, 2, True)
    assert 8192 % bq == 0 and 8192 % bk == 0
    assert bq % 16 == 0 and bk % 128 == 0
    steps = (2 * 2) * (8192 // bq) * (8192 // bk)
    assert steps < 4096, (bq, bk, steps)
    assert 8 * bq >= 1024 and bk >= 512
    assert pk._flash_vmem_bytes(bq, bk, 256, 8, 2) <= pk._FLASH_VMEM_BUDGET \
        <= pk._FLASH_VMEM_LIMIT


@pytest.mark.parametrize("group", [1, 8])
@pytest.mark.parametrize("itemsize", [2, 4])
@pytest.mark.parametrize("seq", [16, 100, 256, 512, 4096])
def test_flash_plan_tiles_divide_the_padded_length(seq, itemsize, group):
    """Short and odd lengths keep the one or two tiles they always had:
    the plan's tiles are sublane-aligned for the element size, never
    longer than the sequence padded to that alignment, and divide it."""
    sub = {2: 16, 4: 8}[itemsize]
    padded = -(-seq // sub) * sub
    for causal in (False, True):
        bq, bk = pk._flash_plan(seq, seq, 128, group, itemsize, causal)
        assert bq % sub == 0 and bk % sub == 0
        assert bq <= padded and bk <= padded
        assert padded % bq == 0 and padded % bk == 0
        assert pk._flash_vmem_bytes(bq, bk, 128, group, itemsize) \
            <= pk._FLASH_VMEM_BUDGET


@pytest.mark.parametrize("d,want", [(128, (512, 512)), (256, (256, 512))],
                         ids=["trinity-head128", "qwen3next-head256"])
def test_backward_plan_at_the_language_model_cells_shapes(d, want):
    """Both cells: 8,192 tokens, eight query heads a K/V head, bf16, causal.
    The backward takes K/V tiles half the forward's and the longest q tile
    its own budget lets it (measured on the v5e: PERF.md, PR 33)."""
    fwd = pk._flash_plan(8192, 8192, d, 8, 2, True)
    assert fwd == (256, 1024)
    bq, bk = pk._flash_bwd_plan(8192, 8192, *fwd, d, 8, 2, True)
    assert (bq, bk) == want
    assert 8192 % bq == 0 and 8192 % bk == 0 and bk % 128 == 0
    assert 8 * bq <= pk._FLASH_BWD_MAX_ROWS and bk <= pk._FLASH_BWD_MAX_BLOCK_K
    assert pk._flash_bwd_vmem_bytes(bq, bk, d, 8, 2) \
        <= pk._FLASH_BWD_VMEM_BUDGET <= pk._FLASH_BWD_VMEM_LIMIT
    # one q tile more would not fit: the budget is what stops it
    assert d == 128 or pk._flash_bwd_vmem_bytes(2 * bq, bk, d, 8, 2) \
        > pk._FLASH_BWD_VMEM_BUDGET


@pytest.mark.parametrize("group", [1, 8])
@pytest.mark.parametrize("itemsize", [2, 4])
@pytest.mark.parametrize("seq", [16, 100, 256, 1100, 4096])
def test_backward_plan_tiles_divide_the_padded_length(seq, itemsize, group):
    """The backward works on operands padded by the forward's tiles: its
    own divide those lengths, keep the forward's alignment and fit its
    budget; under ``causal`` a q tile stays within a quarter of the keys
    (or 512)."""
    sub = {2: 16, 4: 8}[itemsize]
    for causal in (False, True):
        fq, fk = pk._flash_plan(seq, seq, 128, group, itemsize, causal)
        sq, sk = -(-seq // fq) * fq, -(-seq // fk) * fk
        bq, bk = pk._flash_bwd_plan(sq, sk, fq, fk, 128, group, itemsize,
                                    causal)
        assert sq % bq == 0 and sk % bk == 0
        assert bq % sub == 0 and (bk % 128 == 0 or bk == fk)
        assert group * bq <= max(pk._FLASH_BWD_MAX_ROWS, group * fq)
        assert not causal or bq <= max(512, sk // 4, fq)
        assert pk._flash_bwd_vmem_bytes(bq, bk, 128, group, itemsize) \
            <= pk._FLASH_BWD_VMEM_BUDGET


@pytest.mark.parametrize("blocks", [(64, 128), (256, 128), (128, 512),
                                    (256, 1024)],
                         ids=lambda b: "q%dk%d" % b)
def test_kv_index_map_stops_at_the_last_needed_tile(blocks):
    """The K/V index map of a causal grid, as plain Python: the mapped tile
    never lies above the diagonal, and along a q-block's kv steps it
    changes exactly once per needed tile (an unchanged index is no DMA)."""
    bq, bk = blocks
    seq = 2048
    n_q, n_kv = seq // bq, seq // bk
    for kv_len in (seq, 700):
        for qi in range(n_q):
            last = int(pk._last_kv_tile(qi, kv_len, bq, bk, True))
            tiles = [min(ki, last) for ki in range(n_kv)]
            # its first key is at or below the block's last row, and valid
            assert all(t * bk <= qi * bq + bq - 1 and t * bk < kv_len
                       for t in tiles)
            needed = [ki for ki in range(n_kv)
                      if ki * bk <= qi * bq + bq - 1 and ki * bk < kv_len]
            assert needed == list(range(last + 1))
            fetches = 1 + sum(a != b for a, b in zip(tiles, tiles[1:]))
            assert fetches == len(needed)
    # without a diagonal only the valid length bounds it
    assert int(pk._last_kv_tile(0, seq, bq, bk, False)) == n_kv - 1
    assert int(pk._last_kv_tile(0, 0, bq, bk, False)) == 0


def test_flash_refuses_heads_that_do_not_divide():
    q, _, _ = _qkv(1, 16, 3, 128)
    _, k, v = _qkv(1, 16, 2, 128)
    with pytest.raises(ValueError, match="no multiple"):
        pk.flash_attention(q, k, v, use_pallas=True, interpret=True)


def test_attention_dispatch_falls_back_when_ineligible():
    """head_dim that is not lane-tiled (not a multiple of 128) must take
    the reference path bit-for-bit, whatever the flag says."""
    q, k, v = _qkv(2, 8, 2, 32, seed=4)
    want = pk._reference_attention(q, k, v, True, 1.0 / 32 ** 0.5, None)
    saved = os.environ.get("MXNET_TPU_PALLAS_ATTN")
    os.environ["MXNET_TPU_PALLAS_ATTN"] = "1"
    try:
        got = pk.attention(q, k, v, causal=True)
    finally:
        if saved is None:
            os.environ.pop("MXNET_TPU_PALLAS_ATTN", None)
        else:
            os.environ["MXNET_TPU_PALLAS_ATTN"] = saved
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_kernel_signature_carries_attn_family():
    sig = dict(pk.kernel_signature())
    assert "attn" in sig
    assert sig["attn"] in ("off", "pallas", "interpret")


# ---------------------------------------------------------------------------
# 2) Graph ops: forward parity + the flag cache-key contract
# ---------------------------------------------------------------------------

def test_sdpa_op_forward_matches_reference():
    r = _rng(5)
    b, s, h, d = 2, 6, 2, 8
    x = {n: r.normal(0, 1, (b, s, h, d)).astype(np.float32)
         for n in ("query", "key", "value")}
    lens = np.asarray([6, 3], np.float32)
    sym = mx.sym.scaled_dot_product_attention(
        mx.sym.Variable("query"), mx.sym.Variable("key"),
        mx.sym.Variable("value"), mx.sym.Variable("kv_length"),
        causal=True, use_lengths=True, name="sdpa")
    exe = sym.simple_bind(mx.cpu(), grad_req="null",
                          query=x["query"].shape, key=x["key"].shape,
                          value=x["value"].shape, kv_length=lens.shape)
    for n, arr in x.items():
        exe.arg_dict[n][:] = mx.nd.array(arr)
    exe.arg_dict["kv_length"][:] = mx.nd.array(lens)
    out = exe.forward(is_train=False)[0].asnumpy()
    want = pk._reference_attention(
        jnp.asarray(x["query"]), jnp.asarray(x["key"]),
        jnp.asarray(x["value"]), True, 1.0 / d ** 0.5,
        jnp.asarray(lens))
    np.testing.assert_allclose(out, np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_mha_op_forward_matches_manual_projection():
    r = _rng(6)
    b, s, e, heads = 2, 5, 8, 2
    x = r.normal(0, 1, (b, s, e)).astype(np.float32)
    ws = {n: r.normal(0, 0.5, (e, e)).astype(np.float32)
          for n in ("query_weight", "key_weight", "value_weight",
                    "out_weight")}
    bs = {n: r.normal(0, 0.1, (e,)).astype(np.float32)
          for n in ("query_bias", "key_bias", "value_bias", "out_bias")}
    sym = mx.sym.multi_head_attention(
        mx.sym.Variable("data"), mx.sym.Variable("data"),
        mx.sym.Variable("data"), num_heads=heads, causal=True,
        name="attn0")
    exe = sym.simple_bind(mx.cpu(), grad_req="null", data=(b, s, e))
    exe.arg_dict["data"][:] = mx.nd.array(x)
    for n in ws:
        exe.arg_dict["attn0_" + n][:] = mx.nd.array(ws[n])
    for n in bs:
        exe.arg_dict["attn0_" + n][:] = mx.nd.array(bs[n])
    out = exe.forward(is_train=False)[0].asnumpy()
    # manual oracle: x @ W^T + b per side, reference core, out proj
    proj = {n: (x @ ws[n + "_weight"].T + bs[n + "_bias"])
            .reshape(b, s, heads, e // heads)
            for n in ("query", "key", "value")}
    core = pk._reference_attention(
        jnp.asarray(proj["query"]), jnp.asarray(proj["key"]),
        jnp.asarray(proj["value"]), True, 1.0 / (e // heads) ** 0.5, None)
    want = np.asarray(core).reshape(b, s, e) @ ws["out_weight"].T \
        + bs["out_bias"]
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    # auto-created parameter shapes follow the FC convention
    shapes = dict(zip(sym.list_arguments(), sym.infer_shape(
        data=(b, s, e))[0]))
    assert shapes["attn0_query_weight"] == (e, e)
    assert shapes["attn0_out_bias"] == (e,)


@pytest.fixture
def _attn_flag():
    saved = os.environ.pop("MXNET_TPU_PALLAS_ATTN", None)
    yield
    if saved is None:
        os.environ.pop("MXNET_TPU_PALLAS_ATTN", None)
    else:
        os.environ["MXNET_TPU_PALLAS_ATTN"] = saved


def _transformer_net(embed=128, heads=1):
    # head_dim = embed/heads = 128: lane-tiled, so the flag-on path
    # really routes through the (interpret-mode) flash kernel
    data = mx.sym.Variable("data")
    attn = mx.sym.multi_head_attention(
        data, data, data, num_heads=heads, causal=True, name="attn0")
    net = mx.sym.Flatten(data + attn, name="flat")
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def test_attn_flag_keys_the_program_cache(_attn_flag):
    """MXNET_TPU_PALLAS_ATTN obeys the kernel-flag contract through a
    real transformer fwd_bwd: enable = one retrace, disable = zero, and
    the off-path grads are bitwise untouched by the round trip."""
    from mxnet_tpu.io import DataBatch, DataDesc
    sym = _transformer_net()
    shape = (2, 4, 128)

    def run():
        r = _rng(7)
        mod = mx.mod.Module(sym, context=mx.cpu())
        mod.bind([("data", shape)], [("softmax_label", (shape[0],))])
        mx.random.seed(0)
        mod.init_params(mx.initializer.Xavier())
        batch = DataBatch(
            data=[mx.nd.array(r.normal(0, 1, shape).astype(np.float32))],
            label=[mx.nd.array(r.randint(0, 3, (shape[0],))
                               .astype(np.float32))],
            provide_data=[DataDesc("data", shape)],
            provide_label=[DataDesc("softmax_label", (shape[0],))])
        with executor_cache.watch_traces() as w:
            mod.forward_backward(batch)
        exe = mod._exec_group.execs[0]
        return w, {n: np.asarray(g._h.array)
                   for n, g in exe.grad_dict.items()}

    run()  # warm the off-path program
    w_off, g_off = run()
    assert w_off.total() == 0, w_off.delta()

    os.environ["MXNET_TPU_PALLAS_ATTN"] = "1"
    assert pk.kernel_mode("attn") in ("interpret", "pallas")
    w_on, g_on = run()
    assert w_on.total() == 1 \
        and w_on.delta().get("traces_fwd_bwd") == 1, w_on.delta()
    for n in g_off:
        np.testing.assert_allclose(g_on[n], g_off[n], rtol=1e-3,
                                   atol=1e-3, err_msg=n)

    del os.environ["MXNET_TPU_PALLAS_ATTN"]
    w_back, g_back = run()
    assert w_back.total() == 0, w_back.delta()
    assert all(np.array_equal(g_off[n], g_back[n]) for n in g_off), \
        "off-path gradients changed after a kernel-flag round trip"


# ---------------------------------------------------------------------------
# 3) The health tap: max_abs_attn_logit slots
# ---------------------------------------------------------------------------

def test_attention_tap_names_scans_the_graph():
    sym = _transformer_net()
    from mxnet_tpu.executor import _Program
    names = health.attention_tap_names(_Program(sym).order)
    assert names == ("attn0",)


def test_health_summary_carries_attention_logit_bound(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_HEALTH", "1")
    sym = _transformer_net(embed=8, heads=2)
    exe = sym.simple_bind(mx.cpu(), grad_req="write", data=(2, 4, 8),
                          softmax_label=(2,))
    r = _rng(8)
    x = r.normal(0, 1, (2, 4, 8)).astype(np.float32)
    exe.arg_dict["data"][:] = mx.nd.array(x)
    exe.arg_dict["softmax_label"][:] = mx.nd.array(
        r.randint(0, 3, (2,)).astype(np.float32))
    for n, a in exe.arg_dict.items():
        if n.startswith(("attn0_", "fc_")):  # simple_bind zero-inits
            a[:] = mx.nd.array(
                r.normal(0, 0.5, a.shape).astype(np.float32))
    exe.forward_backward(is_train=True)
    layout = exe.health_layout
    assert layout.tap_names == ["attn0"]
    assert layout.slots[-1] == "max_abs_attn_logit/attn0"
    summary = layout.unpack(np.asarray(exe._last_health))
    bound = summary["max_abs_attn_logit/attn0"]
    assert np.isfinite(bound) and bound > 0
    # it really bounds the logits: recompute them from the bound args
    args = {n: a.asnumpy() for n, a in exe.arg_dict.items()}
    d = 4  # head_dim = 8 / 2
    proj = {n: (x @ args["attn0_%s_weight" % n].T
                + args["attn0_%s_bias" % n]).reshape(2, 4, 2, d)
            for n in ("query", "key")}
    logits = np.einsum("bqhd,bkhd->bhqk", proj["query"],
                       proj["key"]) / d ** 0.5
    assert bound >= np.abs(logits).max() - 1e-5


def test_pack_summary_fills_missing_taps_with_minus_one():
    layout = health.HealthLayout(1, ["w"], tap_names=("attn0", "attn1"))
    assert layout.slots[-2:] == ["max_abs_attn_logit/attn0",
                                 "max_abs_attn_logit/attn1"]
    outs = [jnp.asarray([1.0])]
    params = [jnp.asarray([1.0])]
    grads = [jnp.asarray([0.5])]
    vec = np.asarray(health.pack_summary(layout, outs, params, grads,
                                         taps=[jnp.float32(2.5)]))
    summary = layout.unpack(vec)
    assert summary["max_abs_attn_logit/attn0"] == 2.5
    assert summary["max_abs_attn_logit/attn1"] == -1.0
    vec_none = np.asarray(health.pack_summary(layout, outs, params,
                                              grads, taps=None))
    s2 = layout.unpack(vec_none)
    assert s2["max_abs_attn_logit/attn0"] == -1.0


def test_note_tap_is_noop_without_open_frame():
    health.note_tap(jnp.float32(3.0))  # must not raise or leak
    with health.collect_taps() as frame:
        health.note_tap(jnp.float32(1.0))
        health.note_tap(jnp.float32(2.0))
    assert [float(t) for t in frame] == [1.0, 2.0]


# ---------------------------------------------------------------------------
# 4) Head width 64 (a block whose last dim is the whole head): forward and
#    both backward kernels against the reference, at a softmax scale given
#    (Granite's attention_multiplier, 1/64, not 1/sqrt(64))
# ---------------------------------------------------------------------------

HEAD64_CASES = [
    # (dtype, query heads, K/V heads, seq, block_q, block_k)
    (jnp.float32, 4, 2, 16, None, None),
    (jnp.float32, 4, 2, 300, 64, 128),
    (jnp.bfloat16, 4, 1, 256, 128, 128),
]
HEAD64_IDS = ["%s-h%dkv%d-s%d%s" % (np.dtype(c[0]).name, c[1], c[2], c[3],
                                    _blocks_id(*c[4:]))
              for c in HEAD64_CASES]


@pytest.mark.parametrize("case", HEAD64_CASES, ids=HEAD64_IDS)
def test_flash_at_head_64_matches_reference(case):
    dtype, heads, kv, seq, block_q, block_k = case
    r = _rng(5)
    mk = lambda h: jnp.asarray(r.normal(0, 1, (2, seq, h, 64)), dtype)
    q, k, v = mk(heads), mk(kv), mk(kv)
    w = jnp.asarray(r.normal(0, 1, q.shape), jnp.float32)
    scale = 1.0 / 64

    def run(fn):
        def f(q_, k_, v_):
            return jnp.sum(fn(q_, k_, v_).astype(jnp.float32) * w)
        return fn(q, k, v), jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    want, want_g = run(lambda *a: pk._reference_attention(*a, True, scale))
    got, got_g = run(lambda *a: pk.flash_attention(
        *a, causal=True, scale=scale, use_pallas=True, interpret=True,
        block_q=block_q, block_k=block_k))
    assert got.dtype == q.dtype and got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tols(dtype))
    tol = {"rtol": 3e-2, "atol": 3e-2} if dtype == jnp.bfloat16 \
        else {"rtol": 2e-4, "atol": 2e-4}
    for g, ref, name in zip(got_g, want_g, "qkv"):
        assert g.dtype == ref.dtype
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(ref, np.float32),
                                   err_msg="d%s diverged" % name, **tol)


def test_a_64_wide_head_takes_the_kernels_and_is_counted_at_128_lanes():
    assert pk._flash_eligible(64, 64, 0, jnp.bfloat16)
    assert not pk._flash_eligible(32, 32, 0, jnp.bfloat16)
    assert not pk._flash_eligible(96, 96, 0, jnp.bfloat16)
    assert not pk._flash_eligible(192, 64, 0, jnp.bfloat16)
    # VMEM holds a 64-wide value tile in 128 lanes: the plans count it so
    assert pk._flash_vmem_bytes(256, 512, 128, 4, 2, 64) \
        == pk._flash_vmem_bytes(256, 512, 128, 4, 2, 128)
    assert pk._flash_bwd_vmem_bytes(256, 512, 128, 4, 2, 64) \
        == pk._flash_bwd_vmem_bytes(256, 512, 128, 4, 2, 128)
