"""Pallas TPU kernels for hot ops.

Where the reference hand-writes CUDA for its hot paths (88 .cu files,
SURVEY.md §2.3) this framework leans on XLA fusion — and reaches for Pallas
only where a hand-scheduled kernel beats the compiler.  First citizen:
blocked flash attention (online-softmax over KV tiles staged through VMEM,
QK^T and PV on the MXU) — the single-chip building block under
parallel/ring.py's sequence-parallel ring.

All kernels ship with a pure-XLA fallback (`use_pallas=False` or non-TPU
backends run the same math via jnp) and are validated against it in tests.
"""
from __future__ import annotations

import contextlib
import functools
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np

from ..context import on_tpu

_enable_x64 = jax.enable_x64
_NEG_INF = -1e30


def _causal_mask(n_q, n_k, window=0):
    """[n_q, n_k] bool: key ``j`` is visible to query ``i`` iff ``j <= i``
    and, under a ``window``, ``i - j < window``."""
    mask = jnp.tril(jnp.ones((n_q, n_k), bool))
    if window:
        mask &= ~jnp.tril(jnp.ones((n_q, n_k), bool), -int(window))
    return mask


def _bd_div(x, block):
    """``x // block`` for non-negative ``x``: a shift where ``block`` is a
    power of two (what a vector unit does without a divider)."""
    if isinstance(x, (int, np.integer)) or block & (block - 1):
        return x // block
    return jax.lax.shift_right_logical(x, jnp.int32(block.bit_length() - 1))


def _bd_visible(pos, cols, diffusion):
    """Whether key ``cols`` is visible to query ``pos`` under the
    block-diffusion mask ``diffusion = (block, half)``: positions below
    ``half`` are the noisy copy of the sequence and the rest its clean copy,
    both counted from 0 in blocks of ``block``.  A noisy query sees the noisy
    keys of its own block and the clean keys of the blocks before it; a
    clean query sees the clean keys of its block and those before it; no
    query sees a noisy key of another block.  Plain integer arithmetic, on
    arrays or scalars."""
    block, half = diffusion
    q_noisy, k_noisy = pos < half, cols < half
    qb = _bd_div(jnp.where(q_noisy, pos, pos - half), block)
    kb = _bd_div(jnp.where(k_noisy, cols, cols - half), block)
    same = qb == kb
    # boolean algebra alone: Mosaic selects no boolean vectors
    return (k_noisy & q_noisy & same) \
        | (~k_noisy & ((kb < qb) | (same & ~q_noisy)))


def _bd_mask(n, block):
    """[n, n] bool: the block-diffusion mask of ``n = 2 * half`` positions."""
    pos = jnp.arange(n, dtype=jnp.int32)
    return _bd_visible(pos[:, None], pos[None, :], (block, n // 2))


def checked_diffusion(block, causal, window, kv_lens, sq, sk):
    """``(block, half)`` as the kernels take the block-diffusion mask, or None
    where ``block`` is 0.  The mask is a whole mask of its own: it takes no
    causal flag, window or padding mask, and q and k are one sequence of
    ``2 * half`` positions."""
    block = int(block or 0)
    if not block:
        return None
    if causal or window or kv_lens is not None or sq != sk or sk % 2:
        raise ValueError(
            "attention: block_diffusion takes one even sequence (%d queries, "
            "%d keys) and no causal flag, window or kv_lens" % (sq, sk))
    return block, sk // 2


def _reference_attention(q, k, v, causal, scale, kv_lens=None, window=0,
                         k_shared=None, diffusion=None):
    """[B, S, H, D] exact attention — the fallback + test oracle.  ``v``
    may be narrower or wider than ``q`` and ``k``: the result has its width.

    ``kv_lens``: optional (B,) per-sequence valid KV length (the padding
    mask); keys at positions >= the length never receive weight.
    ``window`` (needs ``causal``; 0 = none): a query sees its own position
    and the ``window - 1`` before it.  ``k_shared`` [B, S, d_s]: a part of
    the key that every head shares, scored against the LAST ``d_s`` columns
    of ``q``; here it is simply copied to every K/V head.  ``diffusion``
    ``(block, half)``: the block-diffusion mask (:func:`_bd_visible`) in
    place of any other."""
    if k_shared is not None:
        k = jnp.concatenate([k, jnp.broadcast_to(
            k_shared[:, :, None, :].astype(k.dtype),
            k.shape[:3] + k_shared.shape[-1:])], axis=-1)
    if q.shape[2] != k.shape[2]:
        return _reference_attention_grouped(q, k, v, causal, scale, kv_lens,
                                            window, diffusion)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    n_q, n_k = q.shape[1], k.shape[1]
    if diffusion:
        s = jnp.where(_bd_mask(n_k, diffusion[0])[None, None], s, _NEG_INF)
    if causal:
        mask = _causal_mask(n_q, n_k, window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    if kv_lens is not None:
        cols = jnp.arange(n_k)
        valid = cols[None, :] < kv_lens.astype(jnp.int32)[:, None]  # [B, Sk]
        s = jnp.where(valid[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _reference_attention_grouped(q, k, v, causal, scale, kv_lens=None,
                                 window=0, diffusion=None):
    """The same for grouped-query attention: ``q`` has a multiple of the
    K/V heads, and K/V head ``j`` serves query heads ``j*group ..
    (j+1)*group`` — by index, no head is repeated in memory."""
    b, n_q, h, d = q.shape
    n_k, kv = k.shape[1], k.shape[2]
    qg = q.reshape(b, n_q, kv, h // kv, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) * scale
    if diffusion:
        s = jnp.where(_bd_mask(n_k, diffusion[0]), s, _NEG_INF)
    if causal:
        s = jnp.where(_causal_mask(n_q, n_k, window), s, _NEG_INF)
    if kv_lens is not None:
        valid = jnp.arange(n_k)[None, :] < kv_lens.astype(jnp.int32)[:, None]
        s = jnp.where(valid[:, None, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p, v).reshape(b, n_q, h,
                                                          v.shape[-1])


def _last_kv_tile(q_idx, kv_len, block_q, block_k, causal):
    """Index of the last K/V tile the q-block ``q_idx`` needs: the one that
    holds key ``kv_len - 1`` and, under ``causal``, the one that holds the
    block's last row's own position.  The kernel computes tiles up to it
    and the K/V index map stops at it, so the tiles past it are neither
    computed nor fetched.  Plain integer arithmetic: traced scalars in the
    kernel and the index map, Python ints in the tests."""
    last = (jnp.maximum(kv_len, 1) - 1) // block_k
    if causal:
        last = jnp.minimum(last, (q_idx * block_q + block_q - 1) // block_k)
    return last


def _first_kv_tile(q_idx, block_q, block_k, window):
    """Index of the first K/V tile the q-block ``q_idx`` needs under a
    causal ``window`` (0 = none: tile 0): the one that holds the key
    ``window - 1`` before the block's first row.  The tiles before it are
    neither computed nor fetched, as those past ``_last_kv_tile``."""
    if not window:
        return 0
    return jnp.maximum(q_idx * block_q - (window - 1), 0) // block_k


# Under the block-diffusion mask a tile of one side needs the tiles of the
# other side in TWO runs: a noisy q tile needs the noisy keys of its own
# blocks and the clean keys of the blocks before them; a clean K/V tile is
# seen by the noisy queries of the later blocks and by the clean queries of
# its blocks and the later ones.  The kernels walk the first run and then the
# second along their innermost grid axis, which is as long as the longest
# pair of runs; a step past both stays on the last tile (no DMA) and computes
# nothing.  Each run holds exactly the tiles with a visible pair, and a tile
# that lies across the two halves is in the first run alone.  ``xp`` is
# ``jnp`` for the traced scalars of a kernel or an index map, ``np`` for the
# counts a plan makes in Python.

def _bd_kv_runs(q_idx, block_q, block_k, diffusion, xp=jnp):
    """(first tile, count, first tile, count) of the two runs of K/V tiles
    that q tile ``q_idx`` needs: the noisy keys of the blocks its noisy rows
    lie in, then the clean keys from the first one up to where its rows
    stop seeing them."""
    block, half = diffusion
    r0 = q_idx * block_q
    r1 = r0 + block_q - 1
    noisy = r0 < half
    last_noisy = xp.minimum(r1, half - 1)
    n_lo = (r0 // block) * block // block_k
    n_hi = (xp.minimum((last_noisy // block + 1) * block, half) - 1) // block_k
    # clean keys half .. half + ends - 1: a noisy row sees the blocks before
    # its own, a clean row its own too
    ends = xp.maximum(
        xp.where(noisy, (last_noisy // block) * block, 0),
        xp.where(r1 >= half, xp.minimum(
            (xp.maximum(r1 - half, 0) // block + 1) * block, half), 0))
    c_lo = xp.where(noisy, xp.maximum(half // block_k, n_hi + 1),
                    half // block_k)
    c_hi = (half + ends - 1) // block_k
    return (n_lo, xp.where(noisy, n_hi - n_lo + 1, 0), c_lo,
            xp.where(ends > 0, xp.maximum(c_hi - c_lo + 1, 0), 0))


def _bd_q_runs(kv_idx, kv_len, n_q, block_q, block_k, diffusion, xp=jnp):
    """(first tile, count, first tile, count) of the two runs of q tiles that
    see a key of K/V tile ``kv_idx`` (keys past ``kv_len`` are padding): the
    noisy queries of its noisy keys' blocks and of the blocks after its first
    clean key's, then the clean queries from that key's block on."""
    block, half = diffusion
    k0 = kv_idx * block_k
    k1 = xp.minimum(k0 + block_k, kv_len) - 1
    real = k0 < kv_len
    noisy = real & (k0 < half)
    clean = real & (k1 >= half)
    # noisy queries of the noisy keys' own blocks
    a_lo = (k0 // block) * block // block_q
    a_hi = (xp.minimum((xp.minimum(k1, half - 1) // block + 1) * block, half)
            - 1) // block_q
    # ... and of the blocks after the first clean key's (a tile across the
    # halves holds key half - 1, so the two ranges meet)
    first = (xp.maximum(k0, half) - half) // block
    after = (first + 1) * block
    later = clean & (after < half)
    lo1 = xp.where(noisy, xp.where(later, xp.minimum(a_lo, after // block_q),
                                   a_lo), after // block_q)
    hi1 = xp.where(later, (half - 1) // block_q, a_hi)
    n1 = xp.where(noisy | later, hi1 - lo1 + 1, 0)
    lo2 = (half + first * block) // block_q
    lo2 = xp.where(n1 > 0, xp.maximum(lo2, hi1 + 1), lo2)
    n2 = xp.where(clean, xp.maximum(n_q - lo2, 0), 0)
    return lo1, n1, lo2, n2


def _bd_step_tile(step, first1, n1, first2, n2, xp=jnp):
    """The tile of grid step ``step`` over two runs: the first's, then the
    second's, and past both the last one needed."""
    last = xp.where(n2 > 0, first2 + n2 - 1, first1 + xp.maximum(n1 - 1, 0))
    return xp.minimum(xp.where(step < n1, first1 + step, first2 + step - n1),
                      last)


def _kv_step(q_idx, kv_idx, kv_len, block_q, block_k, causal, window,
             diffusion):
    """(the K/V tile of grid step ``kv_idx`` of q tile ``q_idx``, whether the
    step computes it) in the forward and dq: under causal the steps stop at
    the diagonal's tile and under a window start at its first; under the
    block-diffusion mask they walk the two runs of :func:`_bd_kv_runs`.
    Tiles wholly past the valid length are not needed either."""
    if diffusion:
        runs = _bd_kv_runs(q_idx, block_q, block_k, diffusion)
        return (_bd_step_tile(kv_idx, *runs),
                (kv_len > 0) & (kv_idx < runs[1] + runs[3]))
    needed = (kv_len > 0) & (
        kv_idx <= _last_kv_tile(q_idx, kv_len, block_q, block_k, causal))
    if window:
        needed &= kv_idx >= _first_kv_tile(q_idx, block_q, block_k, window)
    return kv_idx, needed


def _bd_steps(n_tiles, runs):
    """The longest pair of runs over the ``n_tiles`` tiles of one side: the
    length of the grid axis that walks the other side's tiles."""
    counts = [runs(i) for i in range(n_tiles)]
    return max(1, max(int(r[1] + r[3]) for r in counts))


_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_NN = (((1,), (0,)), ((), ()))     # a @ b


def _tile_operands(q_ref, k_ref, ks_ref, rows):
    """(q parts, key parts) of one (q tile, K/V tile) pair, matched: the
    whole q tile as [rows, d] against the K/V head's key tile and, where
    ``ks_ref`` holds a key part that all heads share, q's first columns
    against the head's own keys and its remaining (last) columns against the
    shared part.  The two widths are each lane-aligned where their sum (192
    = 128 + 64) is not, so the parts are read as slices of the refs."""
    if ks_ref is None:
        return (q_ref[0].reshape(rows, q_ref.shape[-1]),), (k_ref[0],)
    d_k = k_ref.shape[-1]
    return ((q_ref[0, :, :, :d_k].reshape(rows, d_k),
             q_ref[0, :, :, d_k:].reshape(rows, q_ref.shape[-1] - d_k)),
            (k_ref[0], ks_ref[0]))


def _tile_scores(qs, ks, keys_first=False):
    """The raw float32 scores of :func:`_tile_operands`' parts, [rows, keys]
    or its transpose: one product a part, summed."""
    f32 = dict(preferred_element_type=jnp.float32)
    parts = [jax.lax.dot_general(k, q, _NT, **f32) if keys_first
             else jax.lax.dot_general(q, k, _NT, **f32)
             for q, k in zip(qs, ks)]
    return sum(parts[1:], parts[0])


def _flash_kernel(len_ref, q_ref, k_ref, v_ref, *rest,
                  causal, scale, block_q, block_k, group, n_kv_blocks,
                  emit_lse, window=0, shared=False, diffusion=None):
    """One (q-block, kv-block) grid step.  Grid = (B*KV, n_q, n_kv) with
    the kv dimension innermost; m/l/acc scratch persists across kv steps of
    the same q block (standard flash-attention accumulation).  The q tile
    holds ``block_q`` positions of ALL ``group`` query heads that share the
    step's K/V head, flattened to ``group * block_q`` rows: one K/V tile is
    read once for the whole group and both products see that many rows.
    ``len_ref`` is the scalar-prefetched int32 [B*KV] vector of valid KV
    lengths (SMEM): the padding mask, and the bound that makes block-padded
    sequences exact.  q and k share the score width, v and the output the
    value width; ``shared`` adds ``ks_ref``, the key part all heads share
    (:func:`_tile_scores`).  Under ``diffusion`` (the block-diffusion mask)
    the kv axis walks the two runs of :func:`_bd_kv_runs` and is
    ``n_kv_blocks`` steps long."""
    ks_ref = rest[0] if shared else None
    o_ref, *rest = rest[bool(shared):]
    if emit_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        lse_ref = None
        m_ref, l_ref, acc_ref = rest
    from jax.experimental import pallas as pl

    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)
    rows = group * block_q

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[pl.program_id(0)]
    # tiles wholly past the valid length and, under causal, wholly above
    # the diagonal (or wholly below the window) contribute no weight: the
    # body skips them here and the K/V index map (_call_flash) never moves
    # to them
    tile, needed = _kv_step(q_idx, kv_idx, kv_len, block_q, block_k, causal,
                            window, diffusion)

    @pl.when(needed)
    def _compute():
        v = v_ref[0]                                  # [block_k, d_v]
        # concrete f32 constants: the framework runs with x64 on and the
        # kernel must never see a 64-bit scalar (re-checked on jax 0.9: a
        # weak python float now survives the `_enable_x64(False)` window,
        # the explicit dtype costs nothing)
        s = _tile_scores(*_tile_operands(q_ref, k_ref, ks_ref, rows)) \
            * jnp.float32(scale)

        # a row's position is q_idx*block_q + (row mod block_q): the mask
        # is one [block_q, block_k] tile that every head of the group
        # shares, added as 0 / -1e30 (s - 1e30 rounds to -1e30 in float32)
        cols = tile * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = cols < kv_len
        if causal or diffusion:
            pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
        if diffusion:
            valid &= _bd_visible(pos, cols, diffusion)
        if causal:
            valid &= pos >= cols
            if window:
                valid &= pos - cols < window
        bias = jnp.where(valid, jnp.float32(0.0), jnp.float32(_NEG_INF))
        s = (s.reshape(group, block_q, block_k) + bias[None]).reshape(
            rows, block_k)

        # m/l scratch is lane-tiled [rows, 128] (TPU min tile); the
        # running stats live broadcast across lanes and are read back via
        # a 1-lane slice of the loaded value
        m_prev = m_ref[:][:, :1]      # [rows, 1]
        l_prev = l_ref[:][:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        lanes = m_ref.shape[1]
        m_ref[:] = jnp.broadcast_to(m_new, (rows, lanes))
        l_ref[:] = jnp.broadcast_to(l_new, (rows, lanes))

    @pl.when(kv_idx == n_kv_blocks - 1)
    def _finalize():
        l = l_ref[:][:, :1]
        l = jnp.where(l == 0, jnp.float32(1.0), l)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype).reshape(
            o_ref.shape[1:])
        if emit_lse:
            # per-row log-sum-exp residual for the custom backward.
            # Lane-broadcast [rows, 128]: Mosaic requires the last two
            # block dims be 8/128-divisible, which rules out a compact
            # (1, block_q) layout; the 128x write only happens on the
            # DIFFERENTIATED forward (inference skips lse entirely)
            lse = m_ref[:][:, :1] + jnp.log(l)
            lse_ref[0] = jnp.broadcast_to(
                lse, (rows, lse_ref.shape[-1])).reshape(lse_ref.shape[1:])


def _round_up(n, m):
    return ((n + m - 1) // m) * m


def _sublanes(itemsize):
    """Rows of one TPU tile for an element size: 8 of float32, 16 of
    bfloat16."""
    return 8 * max(1, 4 // itemsize)


# What one grid step of the flash kernel may hold in VMEM by the plan's own
# count, and the scoped limit the call asks Mosaic for.  The default scoped
# limit (16 MiB on a v5e, of 128 MiB physical) is the binding one for the
# large tiles, so the call states its own; the margin over the budget is
# for what the count leaves out (Mosaic's own temporaries, semaphores).
_FLASH_VMEM_BUDGET = 32 << 20
_FLASH_VMEM_LIMIT = 48 << 20
# past these a tile gains nothing: a step is already ~1 GFLOP against the
# pipeline's ~0.4 us, K/V are re-read once per 2,048 query rows, and the
# masked part of the diagonal tiles grows with both
_FLASH_MAX_ROWS = 2048
_FLASH_MAX_BLOCK_K = 1024


def _flash_vmem_bytes(block_q, block_k, d, group, itemsize, d_v=None):
    """VMEM one grid step holds, by operand: the double-buffered q, k, v,
    out and log-sum-exp tiles, the three float32 scratch arrays, and the
    float32 score and probability tiles with the probabilities' cast.  ``d``
    is the score width as VMEM holds it (:func:`_vmem_width`), ``d_v`` the
    value width where it differs."""
    rows = group * block_q
    d_v = _round_up(d_v or d, 128)          # a 64-wide tile takes 128 lanes
    piped = 2 * ((rows + block_k) * (d + d_v) * itemsize + rows * 128 * 4)
    scratch = rows * (128 + 128 + d_v) * 4
    scores = rows * block_k * (4 + 4 + itemsize)
    return piped + scratch + scores


def _vmem_width(d_k, d_shared=0):
    """Lanes a q or key tile of score width ``d_k + d_shared`` takes in
    VMEM: each part is padded to whole 128-lane tiles."""
    return _round_up(d_k, 128) + (_round_up(d_shared, 128) if d_shared else 0)


def _flash_plan(sq, sk, d, group, itemsize, causal, d_v=None):
    """(block_q, block_k) for a [sq] x [sk] attention at head size ``d``
    (the score width as VMEM holds it; values ``d_v`` wide where that
    differs) with ``group`` query heads a K/V head: the fewest equal tiles whose
    step fits ``_FLASH_VMEM_BUDGET``, a q tile of at most
    ``_FLASH_MAX_ROWS`` rows over the whole group and a K/V tile of at most
    ``_FLASH_MAX_BLOCK_K`` keys (under ``causal``, neither side longer than
    a quarter of the keys).  A causal ``window`` does not narrow them: at
    8,192 keys under a 2,048 window 256 x 1,024 tiles read 3.20 ms a call
    on the v5e against 4.31 for 256 x 512, whose smaller overhang of the
    band (1.25 against 1.40 computed pairs a visible one) does not pay for
    twice the steps (PERF.md, PR 32).  Tiles are sublane-aligned for the element
    size (8 rows of float32, 16 of bfloat16) and 128-aligned where a
    sequence takes several K/V tiles (keys lie on the score tile's lanes);
    a sequence shorter than a cap is one tile of its own padded length, so
    short and odd lengths are never padded past their alignment."""
    sub = _sublanes(itemsize)

    def block(n, cap, align):
        if _round_up(n, sub) <= cap:
            return _round_up(n, sub)
        n_tiles = -(-n // cap)
        return _round_up(-(-n // n_tiles), align)

    cap_q = max(_FLASH_MAX_ROWS // group // sub * sub, sub)
    cap_k = _FLASH_MAX_BLOCK_K
    if causal:
        # the masked part of the diagonal tiles is work thrown away, in
        # proportion to (block_q + block_k) / keys: a tile side stays
        # within a quarter of the keys, but not under 512, below which the
        # per-step cost outweighs it (measured on the v5e, PERF.md PR 31)
        cap_q, cap_k = (min(c, max(512, sk // 4)) for c in (cap_q, cap_k))
    while True:
        bq, bk = block(sq, cap_q, sub), block(sk, cap_k, 128)
        if _flash_vmem_bytes(bq, bk, d, group, itemsize, d_v) \
                <= _FLASH_VMEM_BUDGET or (cap_q == sub and cap_k == 128):
            return bq, bk
        # halve the longer side of the score tile
        if (bk >= group * bq and cap_k > 128) or cap_q == sub:
            cap_k //= 2
        else:
            cap_q = max(cap_q // 2 // sub * sub, sub)


def checked_window(window, causal, sk):
    """``window`` as the kernels take it: 0 where it hides no key."""
    window = int(window or 0)
    if window < 0 or (window and not causal):
        raise ValueError("attention: window %d needs causal=True and a "
                         "positive width" % window)
    return 0 if window >= sk else window


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, use_pallas=None, interpret=None,
                    kv_lens=None, window=0, k_shared=None, block_diffusion=0):
    """Blocked flash attention.  q/k/v: [batch, seq, heads, head_dim];
    ``k``/``v`` may hold fewer heads than ``q`` (grouped queries: K/V head
    ``j`` serves query heads ``j*group .. (j+1)*group``).

    Two widths: the SCORE width is q's last dim, which k shares, and the
    VALUE width is v's, which the result takes; they need not be equal.
    ``k_shared`` [batch, keys, d_s] is a part of the key that every head
    shares (latent attention's one rotary key): ``k`` is then ``d_s``
    narrower than ``q``, whose last ``d_s`` columns are scored against it,
    and the kernels read it once a tile instead of a copy a head.  ``scale``
    defaults to ``1 / sqrt(score width)``.

    ``kv_lens``: optional (batch,) valid KV lengths — the padding mask.
    Sequences that do not tile evenly are block-padded internally and
    bounded by the same per-row length the padding mask uses, so any
    seq length is exact.  ``window`` (needs ``causal``; 0 = none): query
    ``i`` sees keys ``i - window < j <= i``; the forward neither fetches
    nor computes the K/V tiles wholly below the window, nor do the
    backward's two kernels (a query row with no visible key at all, which
    only ``kv_lens`` can make, holds nothing meaningful and gives no
    gradient).  ``block_diffusion`` (0 = none; takes no ``causal``,
    ``window`` or ``kv_lens``): q and k are the ``2 * half`` positions of a
    noisy copy of a sequence and its clean copy, in blocks of that many
    positions, and the block-diffusion mask (:func:`_bd_visible`) decides;
    the three kernels walk the two runs of tiles that mask needs
    (:func:`_bd_kv_runs`, :func:`_bd_q_runs`) and neither fetch nor compute
    a tile it hides wholly.  use_pallas=None auto-selects: the Pallas
    kernel on TPU backends for lane-tiled head dims, the XLA reference
    otherwise.  ``block_q`` / ``block_k``: tile sizes, from
    :func:`_flash_plan` unless given (the tests give them).
    """
    b, sq, h, d = q.shape
    sk, kv, d_k, d_v = k.shape[1], k.shape[2], k.shape[3], v.shape[3]
    d_s = 0 if k_shared is None else k_shared.shape[-1]
    if h % kv:
        raise ValueError("flash_attention: %d query heads are no multiple "
                         "of %d K/V heads" % (h, kv))
    if d_k + d_s != d:
        raise ValueError("flash_attention: q is %d wide, its keys %d%s"
                         % (d, d_k, " + %d shared" % d_s if d_s else ""))
    group = h // kv
    window = checked_window(window, causal, sk)
    diffusion = checked_diffusion(block_diffusion, causal, window, kv_lens,
                                  sq, sk)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if use_pallas is None:
        use_pallas = on_tpu() and _flash_eligible(d_k, d_v, d_s, q.dtype)
    if not use_pallas:
        return _reference_attention(q, k, v, causal, scale, kv_lens, window,
                                    k_shared, diffusion)

    # tile sizes: the plan's, or the caller's held to the same alignment
    # and never beyond the padded sequence.  The block-diffusion mask is
    # planned as a causal one: each q tile needs about as many K/V tiles
    itemsize = jnp.dtype(q.dtype).itemsize
    sub = _sublanes(itemsize)
    wide = _vmem_width(d_k, d_s)
    tiled_as = causal or bool(diffusion)
    bq, bk = _flash_plan(sq, sk, wide, group, itemsize, tiled_as, d_v)
    if block_q is not None:
        bq = _round_up(min(block_q, sq), sub)
    if block_k is not None:
        bk = _round_up(min(block_k, sk), sub)
    sq_p, sk_p = _round_up(sq, bq), _round_up(sk, bk)

    # layout: fold heads into batch, [BH, S, D]; pad to block multiples
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kv, sk, d_k)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kv, sk, d_v)
    ksf = k_shared
    if sq_p != sq:
        qf = jnp.pad(qf, ((0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        pad_keys = lambda x: jnp.pad(x, ((0, 0), (0, sk_p - sk), (0, 0)))
        kf, vf = pad_keys(kf), pad_keys(vf)
        ksf = None if ksf is None else pad_keys(ksf)
    # per-K/V-row valid KV length, f32 [B*KV] (f32 so the custom_vjp can
    # hand back an ordinary zero cotangent; the kernel reads it as int32
    # from SMEM).  Block padding and the user's padding mask are the same
    # bound to the kernel.
    if kv_lens is None:
        lens = jnp.full((b,), sk, jnp.float32)
    else:
        lens = jnp.clip(kv_lens.astype(jnp.float32), 0, sk)
    lens = jnp.broadcast_to(lens[:, None], (b, kv)).reshape(b * kv)

    # dispatch through a jitted-callable cache: tracing a pallas_call is
    # hundreds of ms of host work, so eager per-call tracing would swamp
    # the kernel (measured 680 ms/call untraced vs 0.02 ms cached)
    # the backward's tiles: its own plan's, or the caller's where given
    bwd = (bq, bk) if (block_q, block_k) != (None, None) else \
        _flash_bwd_plan(sq_p, sk_p, bq, bk, wide, group, itemsize, tiled_as,
                        d_v)
    out = _flash_vjp_wrapped(qf, kf, vf, ksf, lens,
                             ((b * kv, group, sq_p, sk_p, (d_k, d_v, d_s, kv),
                               str(jnp.dtype(q.dtype)), causal, float(scale),
                               bq, bk, interpret, window, diffusion), bwd))
    out = out.reshape(b, h, sq_p, d_v)[:, :, :sq]
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _flash_vjp_wrapped(qf, kf, vf, ksf, lens, meta):
    """Differentiable flash attention over [BH, S, D] operands (``ksf`` the
    shared key part [B, S, d_s], or None; ``meta`` holds the widths as (own
    key, value, shared key, K/V heads a batch row)): forward is
    the Pallas kernel, backward is the standard flash backward from the
    saved row log-sum-exp as two more Pallas kernels (``flash_attn_bwd_dkv``
    and ``flash_attn_bwd_dq``: no S^2 materialization, no tile the mask
    hides).  The undifferentiated primal skips the lse output entirely."""
    out, _ = _flash_jitted(*meta[0], with_lse=False)(qf, kf, vf, ksf, lens)
    return out


def _flash_vjp_fwd(qf, kf, vf, ksf, lens, meta):
    out, lse = _flash_jitted(*meta[0], with_lse=True)(qf, kf, vf, ksf, lens)
    return out, (qf, kf, vf, ksf, lens, out, lse[:, :, 0])


def _flash_vjp_bwd(meta, res, d_out):
    (bkv, group, sq, sk, widths, _, causal, scale, _, _, interpret, window,
     diffusion), (block_q, block_k) = meta
    qf, kf, vf, ksf, lens, out, lse = res
    fn = _flash_bwd_jitted(bkv, group, sq, sk, widths, causal, scale, block_q,
                           block_k, interpret, window, diffusion)
    dq, dk, dv, dks = fn(qf, kf, vf, ksf, lens, out, lse, d_out)
    return dq, dk, dv, dks, jnp.zeros_like(lens)


_flash_vjp_wrapped.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _first_q_tile(kv_idx, block_q, block_k, causal):
    """Index of the first q tile that holds a query which sees a key of K/V
    tile ``kv_idx``: under ``causal`` the one that holds the tile's first
    key's own position, else tile 0.  The transpose of ``_last_kv_tile``:
    the dk/dv kernel walks q tiles where the forward walks K/V tiles, and
    neither computes nor fetches the q tiles before it.  Plain integer
    arithmetic, as the forward's."""
    return (kv_idx * block_k) // block_q if causal else 0


def _last_q_tile(kv_idx, kv_len, n_q, block_q, block_k, window):
    """Index of the last q tile that holds a query which sees a key of K/V
    tile ``kv_idx`` (the transpose of ``_first_kv_tile``): tile ``n_q - 1``
    without a ``window``; under one, the tile that holds the query
    ``window - 1`` past the tile's last VALID key (``kv_len`` bounds it)."""
    if not window:
        return n_q - 1
    last_key = jnp.minimum(kv_idx * block_k + block_k,
                           jnp.maximum(kv_len, 1)) - 1
    return jnp.minimum((last_key + window - 1) // block_q, n_q - 1)


def _flash_bwd_bias(q_idx, kv_idx, kv_len, block_q, block_k, causal, window,
                    keys_first, diffusion=None):
    """The mask of one (q tile, K/V tile) pair as a float32 0 / -1e30 tile
    that every head of the group shares: [block_q, block_k], or its
    transpose where the keys lie on the sublanes (the dk/dv kernel)."""
    shape = (block_k, block_q) if keys_first else (block_q, block_k)
    cols = kv_idx * block_k + jax.lax.broadcasted_iota(
        jnp.int32, shape, 0 if keys_first else 1)
    valid = cols < kv_len
    if causal or diffusion:
        pos = q_idx * block_q + jax.lax.broadcasted_iota(
            jnp.int32, shape, 1 if keys_first else 0)
    if diffusion:
        valid &= _bd_visible(pos, cols, diffusion)
    if causal:
        valid &= pos >= cols
        if window:
            valid &= pos - cols < window
    return jnp.where(valid, jnp.float32(0.0), jnp.float32(_NEG_INF))


def _flash_bwd_dq_kernel(len_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         dd_ref, *rest, causal, scale, block_q, block_k, group,
                         n_kv_blocks, window, shared=False, diffusion=None):
    """dq of one q tile: grid (B*KV, n_q, n_kv), the K/V tiles innermost
    and walked as the forward walks them (``_first_kv_tile`` ..
    ``_last_kv_tile``, or the two runs of :func:`_bd_kv_runs` over
    ``n_kv_blocks`` steps; the others neither computed nor fetched); dq
    accumulates in float32 scratch and is written once.  The q tile holds
    all ``group`` query heads of the K/V head, rows on the sublanes and
    keys on the lanes as in the forward.  ``lse`` and D = rowsum(dO * O)
    come as compact [1, rows] vectors and are turned once a q tile into
    the lane-broadcast [rows, 128] columns the forward keeps its running
    stats in.  Under ``shared`` dq's last columns come from the shared key
    part, as the scores' second product did."""
    from jax.experimental import pallas as pl

    ks_ref = rest[0] if shared else None
    dq_ref, acc_ref, lse_col, dd_col = rest[bool(shared):]
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)
    rows = group * block_q

    @pl.when(kv_idx == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        column = lambda ref: jnp.transpose(  # noqa: E731
            jnp.broadcast_to(ref[0, 0], (lse_col.shape[1], rows)))
        lse_col[:] = column(lse_ref)
        dd_col[:] = column(dd_ref)

    kv_len = len_ref[pl.program_id(0)]
    tile, needed = _kv_step(q_idx, kv_idx, kv_len, block_q, block_k, causal,
                            window, diffusion)

    @pl.when(needed)
    def _compute():
        qs, keys = _tile_operands(q_ref, k_ref, ks_ref, rows)
        do = do_ref[0].reshape(rows, do_ref.shape[-1])
        v = v_ref[0]
        f32 = dict(preferred_element_type=jnp.float32)
        s = _tile_scores(qs, keys) * jnp.float32(scale) - lse_col[:][:, :1]
        bias = _flash_bwd_bias(q_idx, tile, kv_len, block_q, block_k,
                               causal, window, keys_first=False,
                               diffusion=diffusion)
        p = jnp.exp((s.reshape(group, block_q, block_k) + bias[None])
                    .reshape(rows, block_k))
        dp = jax.lax.dot_general(do, v, _NT, **f32)
        ds = (p * (dp - dd_col[:][:, :1])).astype(v.dtype)
        if shared:
            d_k = k_ref.shape[-1]
            acc_ref[:, :d_k] += jax.lax.dot_general(ds, keys[0], _NN, **f32)
            acc_ref[:, d_k:] += jax.lax.dot_general(ds, keys[1], _NN, **f32)
        else:
            acc_ref[:] += jax.lax.dot_general(ds, keys[0], _NN, **f32)

    @pl.when(kv_idx == n_kv_blocks - 1)
    def _finalize():
        dq_ref[0] = (acc_ref[:] * jnp.float32(scale)).astype(
            dq_ref.dtype).reshape(dq_ref.shape[1:])


def _flash_bwd_dkv_kernel(len_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                          dd_ref, *rest, causal, scale, block_q, block_k,
                          group, n_q_blocks, window, shared=False,
                          diffusion=None, n_q_tiles=0):
    """dk and dv of one K/V tile: grid (B*KV, n_kv, n_q), the q tiles
    innermost and walked from ``_first_q_tile`` to ``_last_q_tile`` (or the
    two runs of :func:`_bd_q_runs` of the ``n_q_tiles`` over ``n_q_blocks``
    steps; the others neither computed nor fetched); dk and dv accumulate in
    float32 scratch across them and are written once.  The scores are computed
    TRANSPOSED, keys on the sublanes and the ``group * block_q`` rows of
    the q tile on the lanes: ``p.T @ dO`` and ``ds.T @ q`` are then plain
    products that sum over the group inside the MXU, and ``lse`` and D come
    as compact [1, rows] vectors that broadcast down the sublanes.  Under
    ``shared`` a third result, this K/V head's part of the shared key's
    gradient in float32: the caller sums the heads' parts."""
    from jax.experimental import pallas as pl

    if shared:
        ks_ref, dk_ref, dv_ref, dks_ref, dk_acc, dv_acc, dks_acc = rest
    else:
        ks_ref = dks_ref = dks_acc = None
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    step = pl.program_id(2)
    kv_idx = pl.program_id(1)
    rows = group * block_q

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        if shared:
            dks_acc[:] = jnp.zeros_like(dks_acc)

    kv_len = len_ref[pl.program_id(0)]
    if diffusion:
        runs = _bd_q_runs(kv_idx, kv_len, n_q_tiles, block_q, block_k,
                          diffusion)
        q_idx = _bd_step_tile(step, *runs)
        needed = step < runs[1] + runs[3]
    else:
        q_idx = step
        needed = (kv_idx * block_k < kv_len) \
            & (q_idx >= _first_q_tile(kv_idx, block_q, block_k, causal)) \
            & (q_idx <= _last_q_tile(kv_idx, kv_len, n_q_blocks, block_q,
                                     block_k, window))

    @pl.when(needed)
    def _compute():
        qs, keys = _tile_operands(q_ref, k_ref, ks_ref, rows)
        do = do_ref[0].reshape(rows, do_ref.shape[-1])
        v = v_ref[0]
        f32 = dict(preferred_element_type=jnp.float32)
        # one [block_k, block_q] tile, repeated along the lanes for each head
        bias = jnp.concatenate([_flash_bwd_bias(
            q_idx, kv_idx, kv_len, block_q, block_k, causal, window,
            keys_first=True, diffusion=diffusion)] * group, axis=1)
        p = jnp.exp(_tile_scores(qs, keys, keys_first=True)
                    * jnp.float32(scale) - lse_ref[0, 0] + bias)
        dv_acc[:] += jax.lax.dot_general(p.astype(do.dtype), do, _NN, **f32)
        dp = jax.lax.dot_general(v, do, _NT, **f32)
        ds = (p * (dp - dd_ref[0, 0])).astype(v.dtype)
        dk_acc[:] += jax.lax.dot_general(ds, qs[0], _NN, **f32)
        if shared:
            dks_acc[:] += jax.lax.dot_general(ds, qs[1], _NN, **f32)

    @pl.when(step == n_q_blocks - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[:] * jnp.float32(scale)).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)
        if shared:
            dks_ref[0] = dks_acc[:] * jnp.float32(scale)


# What one grid step of a backward kernel may hold in VMEM by the plan's own
# count, and the scoped limit both calls ask Mosaic for (a v5e core has 128
# MiB).  The count is generous (Mosaic reuses the score-sized temporaries it
# lists as all live: a 96 MiB count compiled under this limit), so the
# budget binds before the limit does.
_FLASH_BWD_VMEM_BUDGET = 50 << 20
_FLASH_BWD_VMEM_LIMIT = 64 << 20
# measured on the v5e at both language-model cells' shapes (PERF.md, PR 33):
# the backward wants K/V tiles HALF the forward's and q tiles twice as long,
# up to the budget: 512 x 512 at head 128 x group 8 (with and without the
# window), 256 x 512 at head 256 x group 8
_FLASH_BWD_MAX_ROWS = 4096
_FLASH_BWD_MAX_BLOCK_K = 512


def _flash_bwd_vmem_bytes(block_q, block_k, d, group, itemsize, d_v=None):
    """VMEM one grid step of the backward holds, the larger of its two
    kernels by operand: the double-buffered q, dO, K and V tiles and the
    compact ``lse`` and D rows (a [1, rows] block takes eight sublanes),
    the double-buffered results and their float32 scratch (dq's with the
    two lane-broadcast columns), and the float32 score, ``dp`` and ``ds``
    tiles with the casts of ``p`` and ``ds``.  ``d`` and ``d_v`` as
    :func:`_flash_vmem_bytes` takes them."""
    rows = group * block_q
    d_v = _round_up(d_v or d, 128)          # a 64-wide tile takes 128 lanes
    piped = 2 * (rows + block_k) * (d + d_v) * itemsize \
        + 2 * 2 * 8 * rows * 4
    scores = rows * block_k * (3 * 4 + 2 * itemsize)
    dq = 2 * rows * d * itemsize + rows * (d + 2 * 128) * 4
    dkv = block_k * (d + d_v) * (2 * itemsize + 4)
    return piped + max(dq, dkv) + scores


def _flash_bwd_plan(sq, sk, block_q, block_k, d, group, itemsize, causal,
                    d_v=None):
    """(block_q, block_k) of the backward's two kernels for operands padded
    to ``sq`` x ``sk`` by the forward's tiles ``block_q`` x ``block_k``,
    which the backward's must divide or multiply: the K/V tile is the
    forward's halved down to ``_FLASH_BWD_MAX_BLOCK_K`` keys, the q tile
    the longest run of the forward's q tiles that divides ``sq``, holds at
    most ``_FLASH_BWD_MAX_ROWS`` rows over the group (under ``causal``, as
    in the forward, no more than a quarter of the keys where that is over
    512: the masked part of the diagonal tiles is work thrown away) and
    fits ``_FLASH_BWD_VMEM_BUDGET`` by :func:`_flash_bwd_vmem_bytes`; where
    not even one does, the forward's q tile halved until it fits.  A side
    is never halved out of the forward's alignment (sublanes for q, 128
    lanes for keys)."""
    bk = block_k
    while bk > _FLASH_BWD_MAX_BLOCK_K and bk % 256 == 0:
        bk //= 2
    fits = lambda bq: _flash_bwd_vmem_bytes(  # noqa: E731
        bq, bk, d, group, itemsize, d_v) <= _FLASH_BWD_VMEM_BUDGET
    n = sq // block_q
    cap = _FLASH_BWD_MAX_ROWS // group
    if causal:
        cap = min(cap, max(512, sk // 4))
    runs = [block_q * m for m in range(n, 0, -1) if n % m == 0
            and block_q * m <= cap]
    bq = next((r for r in runs if fits(r)), block_q)
    while not fits(bq) and bq % (2 * _sublanes(itemsize)) == 0:
        bq //= 2
    return bq, bk


@functools.lru_cache(maxsize=512)
def _flash_bwd_jitted(bkv, group, sq, sk, widths, causal, scale, block_q,
                      block_k, interpret, window=0, diffusion=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    d_k, d_v, d_s, kv_heads = widths
    d = d_k + d_s
    shared = bool(d_s)
    n_q, n_kv = sq // block_q, sk // block_k
    # the innermost axes: as many steps as the longest pair of runs where
    # the block-diffusion mask decides (every key is valid there)
    kv_steps, q_steps = n_kv, n_q
    bd = {}
    if diffusion:
        kv_steps = _bd_steps(n_q, lambda qi: _bd_kv_runs(
            qi, block_q, block_k, diffusion, np))
        q_steps = _bd_steps(n_kv, lambda ki: _bd_q_runs(
            ki, 2 * diffusion[1], n_q, block_q, block_k, diffusion, np))
        bd = dict(diffusion=diffusion)
    rows = group * block_q
    static = dict(causal=causal, scale=scale, block_q=block_q,
                  block_k=block_k, group=group, window=window,
                  shared=shared, **bd)
    extra = {"interpret": interpret} if interpret is not None else {}
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_FLASH_BWD_VMEM_LIMIT)

    # dq: the forward's grid and its clamped K/V index map
    def kv_of_q(g, qi, ki, lens):
        if diffusion:
            return (g, jnp.minimum(_bd_step_tile(ki, *_bd_kv_runs(
                qi, block_q, block_k, diffusion)), n_kv - 1), 0)
        last = _last_kv_tile(qi, lens[g], block_q, block_k, causal)
        tile = jnp.minimum(ki, last)
        if window:
            tile = jnp.maximum(tile, jnp.minimum(
                _first_kv_tile(qi, block_q, block_k, window), last))
        return (g, tile, 0)

    q_of_q = lambda g, qi, ki, lens: (g, 0, qi, 0)  # noqa: E731
    row_of_q = lambda g, qi, ki, lens: (g, qi, 0, 0)  # noqa: E731

    # dk/dv: its transpose.  Past the last q tile that sees the K/V tile
    # (and before the first) the index stays where it is: no DMA
    def q_tile(g, ki, qi, lens):
        if diffusion:
            return jnp.minimum(_bd_step_tile(qi, *_bd_q_runs(
                ki, lens[g], n_q, block_q, block_k, diffusion)), n_q - 1)
        last = _last_q_tile(ki, lens[g], n_q, block_q, block_k, window)
        first = jnp.minimum(_first_q_tile(ki, block_q, block_k, causal), last)
        return jnp.maximum(jnp.minimum(qi, last), first)

    q_of_kv = lambda g, ki, qi, lens: (g, 0, q_tile(g, ki, qi, lens), 0)  # noqa: E731
    row_of_kv = lambda g, ki, qi, lens: (g, q_tile(g, ki, qi, lens), 0, 0)  # noqa: E731
    kv_of_kv = lambda g, ki, qi, lens: (g, ki, 0)  # noqa: E731
    # the shared key part has one row a batch row: every K/V head of it
    # reads the same tile
    shared_of_q = lambda g, qi, ki, lens: (  # noqa: E731
        g // kv_heads,) + kv_of_q(g, qi, ki, lens)[1:]
    shared_of_kv = lambda g, ki, qi, lens: (g // kv_heads, ki, 0)  # noqa: E731

    def run(qf, kf, vf, ksf, lens, out, lse, d_out):
        with _enable_x64(False):
            # D_i = rowsum(dO_i * O_i), in f32: it enters ds by cancellation
            # against dp, so bf16 rounding here would amplify
            dd = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32),
                         axis=-1)                               # [BH, Sq]
            # a row with NO valid key has lse == -1e30, and exp(s - lse)
            # would resurrect every masked column as weight 1: +1e30 there
            # makes its p zero, and so its gradient
            lse = jnp.where(lse > _NEG_INF / 2, lse, jnp.float32(-_NEG_INF))
            grouped = lambda x: x.reshape((bkv, group, sq) + x.shape[2:])
            # one q tile's rows (head-major, as the tile is flattened) as a
            # [1, rows] vector: [B*KV, n_q, 1, rows]
            row = lambda x: x.reshape(bkv, group, n_q, block_q).transpose(
                0, 2, 1, 3).reshape(bkv, n_q, 1, rows)
            lse, dd = row(lse), row(dd)
            qg, dog = grouped(qf), grouped(d_out)
            lens = lens.astype(jnp.int32)
            q_block, do_block = ((1, group, block_q, w) for w in (d, d_v))
            k_block, v_block, ks_block = ((1, block_k, w)
                                          for w in (d_k, d_v, d_s))
            row_block = (1, 1, 1, rows)
            more = (ksf,) if shared else ()
            dq = pl.pallas_call(
                functools.partial(_flash_bwd_dq_kernel, n_kv_blocks=kv_steps,
                                  **static),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=(bkv, n_q, kv_steps),
                    in_specs=[
                        pl.BlockSpec(q_block, q_of_q),
                        pl.BlockSpec(k_block, kv_of_q),
                        pl.BlockSpec(v_block, kv_of_q),
                        pl.BlockSpec(do_block, q_of_q),
                        pl.BlockSpec(row_block, row_of_q),
                        pl.BlockSpec(row_block, row_of_q),
                    ] + [pl.BlockSpec(ks_block, shared_of_q)] * shared,
                    out_specs=pl.BlockSpec(q_block, q_of_q),
                    scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32),
                                    pltpu.VMEM((rows, 128), jnp.float32),
                                    pltpu.VMEM((rows, 128), jnp.float32)]),
                out_shape=jax.ShapeDtypeStruct(qg.shape, qf.dtype),
                compiler_params=params, name="flash_attn_bwd_dq", **extra,
            )(lens, qg, kf, vf, dog, lse, dd, *more)
            dk, dv, *dks = pl.pallas_call(
                functools.partial(_flash_bwd_dkv_kernel, n_q_blocks=q_steps,
                                  **dict(static, n_q_tiles=n_q) if bd
                                  else static),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=(bkv, n_kv, q_steps),
                    in_specs=[
                        pl.BlockSpec(q_block, q_of_kv),
                        pl.BlockSpec(k_block, kv_of_kv),
                        pl.BlockSpec(v_block, kv_of_kv),
                        pl.BlockSpec(do_block, q_of_kv),
                        pl.BlockSpec(row_block, row_of_kv),
                        pl.BlockSpec(row_block, row_of_kv),
                    ] + [pl.BlockSpec(ks_block, shared_of_kv)] * shared,
                    out_specs=[pl.BlockSpec(k_block, kv_of_kv),
                               pl.BlockSpec(v_block, kv_of_kv)]
                    + [pl.BlockSpec(ks_block, kv_of_kv)] * shared,
                    scratch_shapes=[pltpu.VMEM((block_k, w), jnp.float32)
                                    for w in (d_k, d_v) + (d_s,) * shared]),
                out_shape=[jax.ShapeDtypeStruct(kf.shape, kf.dtype),
                           jax.ShapeDtypeStruct(vf.shape, vf.dtype)]
                + [jax.ShapeDtypeStruct((bkv, sk, d_s), jnp.float32)] * shared,
                compiler_params=params, name="flash_attn_bwd_dkv", **extra,
            )(lens, qg, kf, vf, dog, lse, dd, *more)
            if shared:
                # the K/V heads' parts of the shared key's gradient, summed
                dks = dks[0].reshape(-1, kv_heads, sk, d_s).sum(axis=1).astype(
                    ksf.dtype)
            return dq.reshape(qf.shape), dk, dv, dks if shared else None

    return jax.jit(run)


@functools.lru_cache(maxsize=512)
def _flash_jitted(bkv, group, sq, sk, widths, dtype, causal, scale, block_q,
                  block_k, interpret, window=0, diffusion=None,
                  with_lse=False):
    d_k, d_v, d_s, kv_heads = widths
    d = d_k + d_s
    steps, bd = sk // block_k, {}
    if diffusion:
        steps = _bd_steps(sq // block_q, lambda qi: _bd_kv_runs(
            qi, block_q, block_k, diffusion, np))
        bd = dict(diffusion=diffusion)
    kernel = functools.partial(
        _flash_kernel, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, group=group, n_kv_blocks=steps,
        emit_lse=with_lse, window=window, shared=bool(d_s), **bd)

    def run(qf, kf, vf, ksf, lens):
        # the framework enables jax x64 globally (float64 NDArray API
        # parity); Mosaic rejects 64-bit types, so trace under 32-bit rules
        with _enable_x64(False):
            # folded query head b*h + kvh*group + g: [B*KV, group, S, D] is
            # a view of [B*H, S, D], and so are the results' way back
            out, lse = _call_flash(
                kernel, qf.reshape(bkv, group, sq, d), kf, vf, lens,
                block_q, block_k, causal, interpret, with_lse, window,
                ksf, kv_heads, diffusion, steps)
            return (out.reshape(bkv * group, sq, d_v),
                    lse.reshape(bkv * group, sq, 128) if with_lse else None)

    return jax.jit(run)


def _call_flash(kernel, qg, kf, vf, lens, block_q, block_k, causal,
                interpret, with_lse, window=0, ksf=None, kv_heads=None,
                diffusion=None, n_kv=None):
    """``n_kv``: the steps of the kv axis (the K/V tiles where not given)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bkv, group, sq, d = qg.shape
    d_k, d_v = kf.shape[-1], vf.shape[-1]
    shared = ksf is not None
    n_q, n_kv = sq // block_q, n_kv or kf.shape[1] // block_k
    # index maps see the scalar-prefetch ref as a trailing argument
    q_map = lambda g, qi, ki, lens: (g, 0, qi, 0)  # noqa: E731

    def kv_map(g, qi, ki, lens):
        # past the last tile the q-block needs (and, under a window,
        # before the first) the index stays where it is: the pipeline sees
        # an unchanged block and issues no DMA
        if diffusion:
            return (g, jnp.minimum(_bd_step_tile(ki, *_bd_kv_runs(
                qi, block_q, block_k, diffusion)),
                kf.shape[1] // block_k - 1), 0)
        last = _last_kv_tile(qi, lens[g], block_q, block_k, causal)
        tile = jnp.minimum(ki, last)
        if window:
            tile = jnp.maximum(tile, jnp.minimum(
                _first_kv_tile(qi, block_q, block_k, window), last))
        return (g, tile, 0)

    # the shared key part has one row a batch row: every K/V head of it
    # reads the same tile
    shared_map = lambda g, qi, ki, lens: (  # noqa: E731
        g // kv_heads,) + kv_map(g, qi, ki, lens)[1:]
    out_specs = [pl.BlockSpec((1, group, block_q, d_v), q_map)]
    out_shape = [jax.ShapeDtypeStruct(qg.shape[:3] + (d_v,), qg.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, group, block_q, 128), q_map))
        out_shape.append(
            jax.ShapeDtypeStruct((bkv, group, sq, 128), jnp.float32))
    rows = group * block_q
    res = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bkv, n_q, n_kv),
            in_specs=[
                pl.BlockSpec((1, group, block_q, d), q_map),
                pl.BlockSpec((1, block_k, d_k), kv_map),
                pl.BlockSpec((1, block_k, d_v), kv_map),
            ] + [pl.BlockSpec((1, block_k, d - d_k), shared_map)] * shared,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, d_v), jnp.float32),
            ]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_FLASH_VMEM_LIMIT),
        name="flash_attn_fwd",
        **({"interpret": interpret} if interpret is not None else {}),
    )(lens.astype(jnp.int32), qg, kf, vf, *((ksf,) if shared else ()))
    return res if with_lse else (res[0], None)


# ---------------------------------------------------------------------------
# Kernel flags (docs/kernels.md).  Every kernel family resolves to one of
# three modes; the resolved tuple is part of the executor-cache signature
# (executor_cache._signature), so flipping a flag re-keys the program the
# same way MXNET_TPU_HEALTH does: enabling costs one retrace per program,
# disabling costs zero, and the off-path program is bit-identical to a
# build that never knew the kernel existed.
# ---------------------------------------------------------------------------

_KERNEL_ENV = {
    "pool": "MXNET_TPU_PALLAS_POOL",
    "bn": "MXNET_TPU_PALLAS_BN",
    "attn": "MXNET_TPU_PALLAS_ATTN",
}


_trace_scope = threading.local()


@contextlib.contextmanager
def trace_scope(platform=None, partitioned=False):
    """What the program being TRACED is for, where the process default
    backend cannot say — opened by whoever jits a graph (the executor
    cache, the fused step, ``ShardedModule``) inside the traced body.

    ``platform``: the platform of the program's devices.  An ``mx.cpu()``
    executor on a TPU host must not trace compiled Mosaic kernels.

    ``partitioned``: XLA partitions the program BY ITSELF — a jit whose
    shardings span a multi-device mesh.  Mosaic kernels cannot be
    auto-partitioned (the TPU lowering refuses a ``pallas_call`` outside
    a ``shard_map``), so unset/``auto`` resolves to ``off`` there; an
    explicit ``=1`` still selects the kernel and the lowering raises.
    Per-shard bodies already under ``shard_map`` are not partitioned."""
    prev = (getattr(_trace_scope, "platform", None),
            getattr(_trace_scope, "partitioned", False))
    _trace_scope.platform = platform or prev[0]
    _trace_scope.partitioned = bool(partitioned) or prev[1]
    try:
        yield
    finally:
        _trace_scope.platform, _trace_scope.partitioned = prev


def kernel_mode(kind, platform=None):
    """Resolved mode of kernel family ``kind`` ('pool' / 'bn' / 'attn'):

    - ``'off'``     — XLA fallback (env ``0``; unset on non-TPU platforms;
      unset inside a ``partitioned`` :func:`trace_scope`)
    - ``'pallas'``  — compiled Pallas kernel (TPU platform, unless env ``0``)
    - ``'interpret'`` — the same kernel code path through the Pallas
      interpreter (env ``1`` on a non-TPU platform: the CI form — the whole
      executor program runs with the kernel inlined, so parity and retrace
      contracts are testable without a chip).

    Resolved at TRACE time against ``platform``, else the enclosing
    :func:`trace_scope`, else the process default backend; the executor
    cache keys programs on the same resolution, so a flag flip takes
    effect at the next bind, never mid-program.
    """
    val = os.environ.get(_KERNEL_ENV[kind], "auto").strip().lower()
    if val in ("0", "off", "false"):
        return "off"
    explicit = val in ("1", "on", "true", "interpret")
    if _traced_for_tpu(platform):
        if getattr(_trace_scope, "partitioned", False) and not explicit:
            return "off"
        return "pallas"
    return "interpret" if explicit else "off"


def _traced_for_tpu(platform=None):
    platform = platform or getattr(_trace_scope, "platform", None)
    return platform == "tpu" if platform else on_tpu()


def traced_for_unpartitioned_tpu():
    """Whether the program being traced runs on a TPU (the enclosing
    :func:`trace_scope`'s platform, else the process default backend) and
    XLA does not partition it by itself: where a kernel with no flag of its
    own (``ops/gdn_kernels.py``) engages."""
    return _traced_for_tpu() and not getattr(_trace_scope, "partitioned",
                                             False)


def kernel_signature(platform=None):
    """The resolved mode of every kernel family for a program bound to
    ``platform`` (default: the process default backend), as a hashable
    tuple — the executor-cache key component that makes kernel flags obey
    the health-sentinel retrace contract."""
    return tuple((k, kernel_mode(k, platform)) for k in sorted(_KERNEL_ENV))


def attention(q, k, v, causal=False, scale=None, kv_lens=None, window=0,
              k_shared=None, block_diffusion=0):
    """Trace-time attention dispatch for the ``attn`` kernel family.

    q/k/v: [batch, seq, heads, head_dim]; ``window`` (needs ``causal``; 0 =
    none), the two widths, ``k_shared`` and ``block_diffusion`` as
    :func:`flash_attention` takes them.  Resolves
    ``kernel_mode('attn')`` at TRACE time (the executor cache keys on the
    same resolution): ``off`` returns the plain XLA reference — no
    custom_vjp, so the off-path program is bit-identical to one that
    never knew the kernel — while ``pallas``/``interpret`` route through
    the flash kernel when the shape is eligible (lane-tiled widths,
    floating dtype) and fall back to the reference otherwise.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    window = checked_window(window, causal, k.shape[1])
    diffusion = checked_diffusion(block_diffusion, causal, window, kv_lens,
                                  q.shape[1], k.shape[1])
    d_s = 0 if k_shared is None else k_shared.shape[-1]
    mode = _flash_mode(k.shape[-1], q.dtype, v.shape[-1], d_s)
    if mode is None:
        return _reference_attention(q, k, v, causal, float(scale), kv_lens,
                                    window, k_shared, diffusion)
    return flash_attention(q, k, v, causal=causal, scale=float(scale),
                           use_pallas=True,
                           interpret=(mode == "interpret") or None,
                           kv_lens=kv_lens, window=window, k_shared=k_shared,
                           block_diffusion=block_diffusion)


def _flash_eligible(d_k, d_v, d_shared, dtype):
    """Whether the flash kernels take these widths and element type: the
    heads' own key width and the value width whole 128-lane tiles or one
    half tile (a 64-wide head: a block whose last dim is the whole head,
    which VMEM pads to 128 lanes and the plans count so), a shared key part
    whole or half ones."""
    lanes = lambda w: w % 128 == 0 or w == 64
    return lanes(d_k) and lanes(d_v) and d_shared % 64 == 0 \
        and jnp.issubdtype(dtype, jnp.floating)


def _flash_mode(d_k, dtype, d_v=None, d_shared=0):
    """The resolved ``attn`` mode where :func:`attention` takes the flash
    kernel for these widths (the heads' own keys', the values', the shared
    key part's) and element type, else None (the XLA reference)."""
    mode = kernel_mode("attn")
    eligible = _flash_eligible(d_k, d_v or d_k, d_shared, dtype)
    return mode if mode != "off" and eligible else None


def bd_visible_pairs(seq, block):
    """(query, key) pairs the block-diffusion mask lets through over ``seq
    = 2 * half`` positions: a noisy row sees its own block's noisy keys and
    the clean keys before its block, a clean row the clean keys up to its
    block's end; ``block**2 * n**2 + half * block`` where ``n = half /
    block`` blocks are whole."""
    half = int(seq) // 2
    starts = np.arange(0, half, int(block), dtype=np.int64)
    sizes = np.minimum(starts + int(block), half) - starts
    # a block's noisy rows and its clean rows each see starts + sizes keys
    return int(2 * np.sum(sizes * (starts + sizes)))


def _bd_pairs_scored(sq, sk, bq, bk, diffusion, keys_first=False):
    """Pairs of the tiles a block-diffusion kernel computes over operands
    padded to ``sq`` x ``sk``: the runs of K/V tiles of each q tile (the
    forward, dq) or, ``keys_first``, of q tiles of each K/V tile (dk/dv)."""
    if keys_first:
        runs = [_bd_q_runs(ki, 2 * diffusion[1], sq // bq, bq, bk, diffusion,
                           np) for ki in range(sk // bk)]
    else:
        runs = [_bd_kv_runs(qi, bq, bk, diffusion, np)
                for qi in range(sq // bq)]
    return bq * bk * sum(int(r[1] + r[3]) for r in runs)


def attention_pairs(q_shape, k_shape, dtype, causal=False, window=0,
                    v_width=None, shared_width=0, block_diffusion=0):
    """What one :func:`attention` node of these shapes is built to do, as
    (computed, visible) counts of (query, key) pairs per head: the pairs
    whose score its forward and its backward compute, masked or not (the
    needed tiles of the forward and of EACH of the backward's two kernels,
    which both score the pairs of their tiles: a backward pair counts
    twice; the XLA reference computes every pair both ways), and the pairs
    the mask lets through, once each way.  ``k_shape``'s width is the heads'
    own keys' (``shared_width`` less than q's where a key part is shared),
    ``v_width`` the values' where it differs.  Static: shapes, the tile plans
    and the kernel mode of the enclosing :func:`trace_scope`; lengths
    (``kv_lens``) are not known here.  Under ``block_diffusion`` the three
    kernels' tiles are counted each (dk/dv walks the transpose of the
    forward's runs, tile for tile)."""
    b, sq = int(q_shape[0]), int(q_shape[1])
    sk, kv, d_k = (int(x) for x in k_shape[1:])
    d, d_v = _vmem_width(d_k, shared_width), int(v_width or d_k)
    group = int(q_shape[2]) // kv
    window = checked_window(window, causal, sk)
    diffusion = checked_diffusion(block_diffusion, causal, window, None, sq,
                                  sk)
    if diffusion:
        visible = bd_visible_pairs(sk, diffusion[0])
        if _flash_mode(d_k, dtype, d_v, shared_width) is None:
            return 2 * b * sq * sk, 2 * b * visible
        itemsize = jnp.dtype(dtype).itemsize
        bq, bk = _flash_plan(sq, sk, d, group, itemsize, True, d_v)
        sq_p, sk_p = _round_up(sq, bq), _round_up(sk, bk)
        bwd = _flash_bwd_plan(sq_p, sk_p, bq, bk, d, group, itemsize, True,
                              d_v)
        return b * (_bd_pairs_scored(sq_p, sk_p, bq, bk, diffusion)
                    + _bd_pairs_scored(sq_p, sk_p, *bwd, diffusion)
                    + _bd_pairs_scored(sq_p, sk_p, *bwd, diffusion,
                                       keys_first=True)), 2 * b * visible
    if causal:
        visible = sum(min(i, sk - 1) + 1 - (max(i - window + 1, 0)
                                            if window else 0)
                      for i in range(sq))
    else:
        visible = sq * sk
    if _flash_mode(d_k, dtype, d_v, shared_width) is None:
        return 2 * b * sq * sk, 2 * b * visible
    itemsize = jnp.dtype(dtype).itemsize
    bq, bk = _flash_plan(sq, sk, d, group, itemsize, causal, d_v)
    sq_p = _round_up(sq, bq)

    def scored(bq, bk):
        tiles = sum(max(0, int(_last_kv_tile(qi, sk, bq, bk, causal)) + 1
                        - int(_first_kv_tile(qi, bq, bk, window)))
                    for qi in range(sq_p // bq))
        return bq * bk * tiles

    backward = scored(*_flash_bwd_plan(sq_p, _round_up(sk, bk), bq, bk, d,
                                       group, itemsize, causal, d_v))
    return b * (scored(bq, bk) + 2 * backward), 2 * b * visible


# ---------------------------------------------------------------------------
# Max-pooling backward (ref: pool.h unpool kernels; XLA's lowering is
# select-and-scatter.11 = 423 us/step of the ResNet-50 train step,
# ROOFLINE_r05.json).  Strategy: recompute-argmax over input tiles staged
# through VMEM.  Stride-s pooling relates input lanes to output lanes at
# ratio s, which a TPU kernel cannot cross with strided lane access — so
# the input is viewed PHASE-MAJOR (space-to-depth by the stride, the same
# rewrite ops/nn.py uses for the conv stem): plane (i%sh)*sw + (j%sw) of
# ``xs[R, sh*sw, Hq, Wq]`` holds every input pixel congruent to that
# residue, and window tap (i, j) becomes a CONTIGUOUS (OH, OW) slice of
# its plane at offset (i//sh, j//sw).  The s2d view is built where XLA
# fuses it (the forward saves it as the vjp residual, so the transpose
# rides the producer fusion's epilogue; the inverse rides the consumer of
# dx), and the kernel itself touches x and dy exactly once.
# ---------------------------------------------------------------------------


def _pool_geometry(kernel, stride, out_shape):
    """(Hq, Wq, planes) of the s2d view: Hq = OH + (kh-1)//sh quotient
    rows cover every tap offset, exactly."""
    kh, kw = kernel
    sh, sw = stride
    oh, ow = out_shape
    return oh + (kh - 1) // sh, ow + (kw - 1) // sw, sh * sw


def _pool_taps(kernel, stride):
    """Window taps in row-major window order (the tie-break order of the
    recomputed argmax): (plane, dh, dw) per tap."""
    kh, kw = kernel
    sh, sw = stride
    return tuple(((i % sh) * sw + (j % sw), i // sh, j // sw)
                 for i in range(kh) for j in range(kw))


def pool_s2d(x, kernel, stride, pad, out_shape, pad_value):
    """Phase-major (space-to-depth by stride) view of the padded pooling
    input: (N, C, H, W) -> (N*C, sh*sw, Hq, Wq).  Input rows past the last
    window are cropped (they take zero gradient); short rows pad with
    ``pad_value`` (-inf for max so padding never wins the argmax, 0
    otherwise)."""
    n, c, h, w = x.shape
    sh, sw = stride
    ph, pw = pad
    hq, wq, _ = _pool_geometry(kernel, stride, out_shape)
    hp2, wp2 = hq * sh, wq * sw
    h_take = min(h, hp2 - ph)
    w_take = min(w, wp2 - pw)
    xp = jnp.full((n, c, hp2, wp2), jnp.asarray(pad_value, x.dtype), x.dtype)
    xp = xp.at[:, :, ph:ph + h_take, pw:pw + w_take].set(
        x[:, :, :h_take, :w_take])
    xs = xp.reshape(n * c, hq, sh, wq, sw)
    return xs.transpose(0, 2, 4, 1, 3).reshape(n * c, sh * sw, hq, wq)


def _pool_s2d_inverse(dxs, x_shape, kernel, stride, pad, out_shape):
    """Assemble (N, C, H, W) input gradients from the kernel's phase-major
    output (the inverse s2d view; XLA fuses it into dx's consumer)."""
    n, c, h, w = x_shape
    sh, sw = stride
    ph, pw = pad
    hq, wq, _ = _pool_geometry(kernel, stride, out_shape)
    hp2, wp2 = hq * sh, wq * sw
    dxp = dxs.reshape(n, c, sh, sw, hq, wq)
    dxp = dxp.transpose(0, 1, 4, 2, 5, 3).reshape(n, c, hp2, wp2)
    h_take = min(h, hp2 - ph)
    w_take = min(w, wp2 - pw)
    dx = dxp[:, :, ph:ph + h_take, pw:pw + w_take]
    if h_take < h or w_take < w:
        dx = jnp.pad(dx, ((0, 0), (0, 0),
                          (0, h - h_take), (0, w - w_take)))
    return dx


def _pool_block_rows(rows):
    """Largest power-of-two row block (<=8) dividing the flattened N*C
    extent — whole-spatial blocks keep VMEM per step in the hundreds of
    KB for real conv-net shapes."""
    for b in (8, 4, 2, 1):
        if rows % b == 0:
            return b
    return 1


def _max_pool_bwd_kernel(xs_ref, dy_ref, out_ref, acc_ref, *, taps, oh, ow):
    """One R-block: recompute the window max and its FIRST achieving tap
    (row-major window order — the same tie-break select-and-scatter's
    ``ge`` select applies in its iteration order), then route each output
    cotangent to that tap's plane slice.  All tap reads/writes are
    contiguous (OH, OW) slices of VMEM-resident planes; accumulation runs
    in a float32 scratch and casts once on the way out."""
    n_taps = len(taps)

    def tap_x(t):
        plane, dh, dw = taps[t]
        return xs_ref[:, plane, dh:dh + oh, dw:dw + ow].astype(jnp.float32)

    m = tap_x(0)
    for t in range(1, n_taps):
        m = jnp.maximum(m, tap_x(t))
    am = jnp.full(m.shape, n_taps, jnp.int32)
    for t in range(n_taps):
        hit = (tap_x(t) == m) & (am == n_taps)
        am = jnp.where(hit, jnp.int32(t), am)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    dyv = dy_ref[:].astype(jnp.float32)
    for t in range(n_taps):
        plane, dh, dw = taps[t]
        acc_ref[:, plane, dh:dh + oh, dw:dw + ow] += jnp.where(
            am == t, dyv, jnp.float32(0.0))
    out_ref[:] = acc_ref[:].astype(out_ref.dtype)


@functools.lru_cache(maxsize=512)
def _pool_bwd_jitted(rows, planes, hq, wq, oh, ow, taps, dtype, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    br = _pool_block_rows(rows)
    out_dtype = jnp.dtype(dtype)
    kernel = functools.partial(_max_pool_bwd_kernel, taps=taps, oh=oh,
                               ow=ow)
    in_specs = [
        pl.BlockSpec((br, planes, hq, wq), lambda r: (r, 0, 0, 0)),
        pl.BlockSpec((br, oh, ow), lambda r: (r, 0, 0)),
    ]

    def run(*operands):
        with _enable_x64(False):
            return pl.pallas_call(
                kernel,
                grid=(rows // br,),
                in_specs=in_specs,
                out_specs=pl.BlockSpec((br, planes, hq, wq),
                                       lambda r: (r, 0, 0, 0)),
                out_shape=jax.ShapeDtypeStruct((rows, planes, hq, wq),
                                               out_dtype),
                scratch_shapes=[
                    pltpu.VMEM((br, planes, hq, wq), jnp.float32)],
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel",)),
                name="max_pool_bwd",
                **({"interpret": interpret} if interpret is not None
                   else {}),
            )(*operands)

    return jax.jit(run)


def max_pool_backward(xs, dy, x_shape, x_dtype, kernel, stride, pad,
                      out_shape, interpret=None):
    """Input gradient of 2-D max pooling from the phase-major residual
    ``xs = pool_s2d(x, ..., -inf)`` and the output cotangent ``dy``
    (N, C, OH, OW).  Returns dx shaped/typed like x."""
    n, c = x_shape[:2]
    oh, ow = out_shape
    hq, wq, planes = _pool_geometry(kernel, stride, out_shape)
    fn = _pool_bwd_jitted(n * c, planes, hq, wq, oh, ow,
                          _pool_taps(kernel, stride),
                          str(jnp.dtype(x_dtype)), interpret)
    dxs = fn(xs, dy.reshape(n * c, oh, ow))
    return _pool_s2d_inverse(dxs, x_shape, kernel, stride, pad, out_shape)


# ---------------------------------------------------------------------------
# Fused BN-stats epilogue (ref: batch_norm-inl.h; XLA's lowering of the
# one-pass stats is the convert_reduce_fusion.* family — ~1 ms/step
# combined on the ResNet-50 train step, ROOFLINE_r05.json, because each
# reduction re-reads the bf16 activation and materializes an f32 convert).
# One Pallas kernel computes BOTH per-channel moments (sum and
# sum-of-squares) in a single pass over the activation, reading bf16 and
# accumulating f32 in VMEM — the same kernel shape serves the backward's
# (sum dy, sum dy*x) pair, so training BN costs two activation passes
# total instead of XLA's four-plus converts.
# ---------------------------------------------------------------------------


def _make_channel_sums_kernel(pair):
    from jax.experimental import pallas as pl

    def kernel(*refs):
        if pair:
            a_ref, b_ref, out1_ref, out2_ref, acc1_ref, acc2_ref = refs
        else:
            a_ref, out1_ref, out2_ref, acc1_ref, acc2_ref = refs
            b_ref = a_ref
        # reduction steps: batch (axis 1) x spatial chunks (axis 2)
        first = (pl.program_id(1) == 0) & (pl.program_id(2) == 0)
        last = (pl.program_id(1) == pl.num_programs(1) - 1) \
            & (pl.program_id(2) == pl.num_programs(2) - 1)

        @pl.when(first)
        def _init():
            acc1_ref[:] = jnp.zeros_like(acc1_ref)
            acc2_ref[:] = jnp.zeros_like(acc2_ref)

        av = a_ref[0].astype(jnp.float32)     # (block_c, block_s)
        bv = av if not pair else b_ref[0].astype(jnp.float32)
        acc1_ref[:] += av
        acc2_ref[:] += av * bv

        @pl.when(last)
        def _emit():
            # lane reduction with the channel axis staying on sublanes:
            # (block_c, block_s) -> (block_c, 1), no relayout
            out1_ref[:] = jnp.sum(acc1_ref[:], axis=1, keepdims=True)
            out2_ref[:] = jnp.sum(acc2_ref[:], axis=1, keepdims=True)

    return kernel


_BN_ACC_BYTES = 1 << 20     # per f32 accumulator; two live per kernel


def _bn_blocks(c, s):
    """(block_c, block_s) for a [N, C, S] activation (S = H*W flattened
    onto lanes, C on sublanes), or None when no Mosaic-legal block keeps
    an f32 accumulator under ``_BN_ACC_BYTES`` of VMEM.  Legal means each
    of the last two block dims is a tile multiple (16 sublanes covers
    bf16 packing, 128 lanes) or the whole axis."""
    def padded(bc, bs):
        return _round_up(bc, 8) * _round_up(bs, 128) * 4

    c_opts = [b for b in range(16, c, 16) if c % b == 0] + [c]
    s_opts = [b for b in range(128, s, 128) if s % b == 0] + [s]
    fits = [(bc * bs, bc, bs) for bc in c_opts for bs in s_opts
            if padded(bc, bs) <= _BN_ACC_BYTES]
    if not fits:
        return None
    _, bc, bs = max(fits)
    return bc, bs


def bn_sums_eligible(shape):
    """Static per-shape dispatch rule of the channel-sums kernel: NCHW
    with a block decomposition that fits VMEM."""
    return len(shape) == 4 and \
        _bn_blocks(shape[1], shape[2] * shape[3]) is not None


@functools.lru_cache(maxsize=512)
def _channel_sums_jitted(pair, n, c, s, dtype_a, dtype_b, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    block_c, block_s = _bn_blocks(c, s)
    kernel = _make_channel_sums_kernel(pair)
    x_spec = pl.BlockSpec((1, block_c, block_s),
                          lambda cb, i, sb: (i, cb, sb))
    in_specs = [x_spec, x_spec] if pair else [x_spec]
    out_spec = pl.BlockSpec((block_c, 1), lambda cb, i, sb: (cb, 0))
    out_shape = jax.ShapeDtypeStruct((c, 1), jnp.float32)

    def run(*operands):
        with _enable_x64(False):
            s1, s2 = pl.pallas_call(
                kernel,
                grid=(c // block_c, n, s // block_s),
                in_specs=in_specs,
                out_specs=[out_spec, out_spec],
                out_shape=[out_shape, out_shape],
                scratch_shapes=[
                    pltpu.VMEM((block_c, block_s), jnp.float32),
                    pltpu.VMEM((block_c, block_s), jnp.float32)],
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel", "arbitrary",
                                         "arbitrary")),
                name="bn_channel_sums",
                **({"interpret": interpret} if interpret is not None
                   else {}),
            )(*operands)
        return s1.reshape(c), s2.reshape(c)

    return jax.jit(run)


def bn_channel_sums(a, b=None, interpret=None):
    """Per-channel single-pass paired reduction over an NCHW tensor:
    returns float32 ``(sum_c a, sum_c a*b)`` with ``b = a`` when ``b`` is
    None (the stats epilogue: sum + sum-of-squares) — the backward pair
    is ``bn_channel_sums(dy, x)`` = (sum dy, sum dy*x)."""
    n, c, h, w = a.shape
    pair = b is not None
    fn = _channel_sums_jitted(pair, n, c, h * w, str(jnp.dtype(a.dtype)),
                              str(jnp.dtype(b.dtype)) if pair else "",
                              interpret)
    # H*W flattens onto the lane axis: a (H, W)-minor block would pad
    # every 7x7 plane to an (8, 128) tile
    a = a.reshape(n, c, h * w)
    return fn(a, b.reshape(n, c, h * w)) if pair else fn(a)
