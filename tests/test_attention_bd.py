"""The block-diffusion mask in attention (docs/kernels.md §flash-attention;
docs/sdar.md): ``2 L`` positions, a noisy copy of a sequence and its clean
copy in blocks of ``B``; a noisy query sees its own block's noisy keys and the
clean keys of the blocks before it, a clean query the clean keys of its block
and those before.

The three Pallas kernels through the interpreter (the code path the chip
compiles) against a float32 oracle written out by hand, forward and all three
gradients; the two runs of tiles each kernel walks against the tiles an
explicit mask holds, as plain integers; and what an attention node is built to
compute against what the mask lets through (the ``module.attn.pairs_*``
counters)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.executor import _Program
from mxnet_tpu.ops import pallas_kernels as pk

D = 128


def _normal(seed, shape):
    return jnp.asarray(np.random.RandomState(seed).normal(0, 1, shape),
                       jnp.float32)


def _mask(n, block, leak=False):
    """The mask written out by hand over ``n = 2 L`` positions; ``leak`` lets
    a noisy query see its own block's clean keys too."""
    half = n // 2
    i, j = np.arange(n)[:, None], np.arange(n)[None, :]
    qb, kb = (i % half) // block, (j % half) // block
    noisy_q, noisy_k = i < half, j < half
    seen = (noisy_q & noisy_k & (qb == kb)) \
        | (noisy_q & ~noisy_k & ((kb <= qb) if leak else (kb < qb))) \
        | (~noisy_q & ~noisy_k & (kb <= qb))
    return seen


def _oracle(q, k, v, block, leak=False):
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / D ** 0.5
    seen = _mask(q.shape[1], block, leak)
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                      precision=jax.lax.Precision.HIGHEST)


# (L clean positions, block, query heads, K/V heads, block_q, block_k):
# K/V tiles across the two halves; several tiles a half each way; the
# planned (single) tile; a block that divides no tile
CASES = [(24, 4, 4, 2, 8, 16), (64, 4, 2, 1, 32, 16),
         (40, 4, 4, 2, None, None), (60, 3, 2, 2, 16, 8)]
IDS = ["L%d-B%d-h%dkv%d%s" % (c[:4] + ("" if c[4] is None
                                         else "-q%dk%d" % c[4:],))
       for c in CASES]


@pytest.mark.parametrize("case", CASES[1:3], ids=IDS[1:3])
def test_kernels_match_the_oracle(case):
    """Forward, dq and dk/dv of the kernels against the mask written out,
    the first block (which sees no clean key) included: several tiles a half
    each way, and one tile that holds both halves."""
    length, block, heads, kv, bq, bk = case
    n = 2 * length
    q = _normal(0, (1, n, heads, D))
    k, v = _normal(1, (1, n, kv, D)), _normal(2, (1, n, kv, D))
    w = _normal(3, (1, n, heads, D))
    flash = lambda *a: pk.flash_attention(  # noqa: E731
        *a, use_pallas=True, interpret=True, block_q=bq, block_k=bk,
        block_diffusion=block)
    got = flash(q, k, v)
    want = _oracle(q, k, v, block)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(
        pk._reference_attention(q, k, v, False, D ** -0.5,
                                diffusion=(block, length)), want,
        atol=2e-5, rtol=1e-5)
    grads = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    wants = jax.grad(lambda *a: jnp.sum(_oracle(*a, block) * w),
                     argnums=(0, 1, 2))(q, k, v)
    for g, want in zip(grads, wants):
        np.testing.assert_allclose(g, want, atol=5e-5, rtol=1e-4)


def test_a_leak_into_the_own_clean_block_is_caught_at_every_noisy_row():
    """A noisy query that saw its own block's clean keys: the kernel's rows
    agree with the mask to rounding and differ from the leaking one at every
    noisy row, and at no clean row."""
    length, block = 32, 4
    q = _normal(4, (1, 2 * length, 2, D))
    k, v = _normal(5, (1, 2 * length, 2, D)), _normal(6, (1, 2 * length, 2, D))
    got = pk.flash_attention(q, k, v, use_pallas=True, interpret=True,
                             block_q=16, block_k=16, block_diffusion=block)
    gap = np.abs(np.asarray(got - _oracle(q, k, v, block, leak=True)))
    assert float(np.abs(np.asarray(got - _oracle(q, k, v, block))).max()) \
        < 2e-5
    assert gap[:, :length].max(axis=(0, 2, 3)).min() > 1e-3
    assert gap[:, length:].max() < 2e-5


@pytest.mark.parametrize("case", CASES, ids=IDS)
def test_the_runs_hold_exactly_the_tiles_the_mask_needs(case):
    """Each q tile's runs of K/V tiles (the forward, dq) and each K/V tile's
    runs of q tiles (dk/dv) are exactly the tiles with a visible pair."""
    length, block, _, _, bq, bk = case
    n = 2 * length
    bq, bk = bq or n, bk or n
    seen = _mask(n, block)
    need = np.array([[seen[a * bq:(a + 1) * bq, b * bk:(b + 1) * bk].any()
                      for b in range(n // bk)] for a in range(n // bq)])
    diffusion = (block, length)
    walked, walked_t = np.zeros_like(need), np.zeros_like(need)
    for qi in range(n // bq):
        runs = pk._bd_kv_runs(qi, bq, bk, diffusion, np)
        for step in range(int(runs[1] + runs[3])):
            walked[qi, int(pk._bd_step_tile(step, *runs, xp=np))] = True
    for ki in range(n // bk):
        runs = pk._bd_q_runs(ki, n, n // bq, bq, bk, diffusion, np)
        for step in range(int(runs[1] + runs[3])):
            walked_t[int(pk._bd_step_tile(step, *runs, xp=np)), ki] = True
    assert (walked == need).all() and (walked_t == need).all()
    # a q tile of the first block alone sees no clean key: no second run
    assert not seen[:block, length:].any()
    assert int(pk._bd_kv_runs(0, block, bk, diffusion, np)[3]) == 0


@pytest.mark.parametrize("length,block", [(20, 4), (8192, 4), (30, 4),
                                          (21, 3)])
def test_visible_pairs_are_the_closed_form(length, block):
    """``B^2 n^2 + L B`` a head where the blocks are whole; the mask written
    out where it is small enough."""
    got = pk.bd_visible_pairs(2 * length, block)
    if length % block == 0:
        n = length // block
        assert got == block * block * n * n + length * block
    if length < 100:
        assert got == int(_mask(2 * length, block).sum())


def test_attention_pairs_of_the_cells_layer():
    """At the cell's shape (16,384 positions, 32 query heads over 4 of 128,
    bfloat16) the kernels' tiles compute at most 2.1 scored pairs a visible
    one, counting each backward kernel's; the XLA reference scores all."""
    q, k = (1, 16384, 32, 128), (1, 16384, 4, 128)
    visible = 8192 * 8192 + 8192 * 4
    with pk.trace_scope(platform="tpu"):
        computed, seen = pk.attention_pairs(q, k, jnp.bfloat16,
                                            block_diffusion=4)
        assert seen == 2 * visible
        assert 1.5 < computed / seen < 2.1
        assert computed == sum(pk._bd_pairs_scored(
            16384, 16384, *tiles, (4, 8192), keys_first=t)
            for tiles, t in (((256, 1024), False), ((512, 512), False),
                             ((512, 512), True)))
    off = pk.attention_pairs(q, k, jnp.bfloat16, block_diffusion=4)
    assert off == (2 * 16384 * 16384, 2 * visible)


def test_the_mask_takes_nothing_else():
    q = _normal(7, (1, 16, 2, D))
    for kw in ({"causal": True}, {"causal": True, "window": 4},
               {"kv_lens": jnp.array([8.0])}):
        with pytest.raises(ValueError, match="block_diffusion"):
            pk.flash_attention(q, q, q, use_pallas=True, interpret=True,
                               block_diffusion=4, **kw)
    with pytest.raises(ValueError, match="block_diffusion"):
        pk.attention(q[:, :15], q[:, :15], q[:, :15], block_diffusion=4)


def test_the_op_takes_the_mask_under_its_scope():
    """``scaled_dot_product_attention(block_diffusion=B)``: the reference's
    values, lowered under ``mx:attn/mx:attn:bd``, and counted by the
    program's pairs."""
    args = {n: _normal(8 + i, (1, 24, 2, 16)) for i, n in enumerate("qkv")}
    node = mx.sym.scaled_dot_product_attention(
        *(mx.sym.Variable(n) for n in "qkv"), block_diffusion=4)
    prog = _Program(node)
    run = lambda a: prog.evaluate(a, {}, (), False)[0][0]  # noqa: E731
    np.testing.assert_allclose(
        run(args), pk._reference_attention(*args.values(), False, 0.25,
                                           diffusion=(4, 12)), atol=1e-6)
    text = jax.jit(run).lower(args).as_text(debug_info=True)
    assert "mx:attn/mx:attn:bd" in text
    shapes = {n: a.shape for n, a in args.items()}
    assert prog.attention_pairs(shapes, {n: np.float32 for n in "qkv"}) \
        == (2 * 24 * 24, 2 * pk.bd_visible_pairs(24, 4))
