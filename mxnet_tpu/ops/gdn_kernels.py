"""Pallas TPU kernels for the gated delta rule (``ops/lm_ops.py``;
docs/kernels.md): the chunk-local algebra and the recurrence over chunks.

The chunk-local algebra (``lm_ops._chunk_local``) needs a chunk's own
tokens alone; as XLA ops over all chunks at once every intermediate is a
float32 ``[chunks, c, c]`` array in HBM and the nilpotent inverse ten
passes over them.  Here it is one grid a pass, (key heads, chunks), each
step one chunk of a few key heads with everything in VMEM:

- ``gdn_local_fwd``: reads q, k, v, g and beta where they lie and writes
  ``_chunk_local``'s u, m, qk, grow and shrink in the layout the scan
  kernels read.  A key head's ``r`` value heads share q and k, so their
  ``[c, c]`` matrices stand side by side in one ``[c, r c]`` tile, and a
  product by a block-diagonal matrix works on all of them at once.
- ``gdn_local_bwd``: the same grid; recomputes the chunk (the inverse
  included: the program keeps no residual for it) and emits what
  ``jax.vjp(_chunk_local)`` emits, from the cotangents ``gdn_scan_bwd``
  returns.

The decays, the inverse and its pull-back stay float32 (the products inside
the inverse at ``HIGHEST``, as XLA's are); operands meet the MXU in the
compute dtype where ``_chunk_local`` casts them.

What is left is a recurrence: every chunk reads the float32 state ``[dk,
dv]`` of its value head, adds five small products and hands the state on.
As a ``lax.scan`` that is a dozen fusions a chunk with the state going
through HBM between each pair of them; here it is one grid a pass, (key
heads, chunks) with the chunk axis sequential, the state in a VMEM scratch
for all chunks of a head and each chunk's operands read where they lie by
the index map:

- ``gdn_scan_fwd``: ``lm_ops._chunk_step`` a grid step, from the first chunk
  to the last; emits the chunk's outputs and the state the chunk STARTED
  from, in the operands' dtype (the backward's residual).
- ``gdn_scan_bwd``: the same grid from the last chunk to the first, the
  state's cotangent in the scratch; recomputes ``v_new`` from the saved
  start state and emits what ``jax.vjp(_chunk_step)`` emits.

Both keep ``_chunk_step``'s precisions: operands in the compute dtype at the
MXU, float32 accumulation, float32 state and decays.  ``g_all``, the decay
over a whole chunk, is ``grow``'s last column (``_chunk_local`` computes both
as ``exp`` of the same number), so the kernels read it there and the
backward returns its cotangent inside ``grow``'s.

Without the delta rule's correction (Mamba-2's state-space layer,
``lm_ops._ssd``) a chunk's local part is q k^T under each value head's
decay, which costs less to compute again than to keep, so one kernel a pass
does the whole chunk, grid (key head x head block, chunks):

- ``ssd_scan_fwd``: ``lm_ops._chunk_plain`` and ``_chunk_step`` of one
  chunk of ``hb`` value heads.  The value heads stand side by side on the
  lanes as the op's input lays them (``[c, hb P]``: 64-wide heads make
  lane-dense tiles by twos), the float32 state ``[dk, hb P]`` in a VMEM
  scratch, q k^T computed once for the heads of a key head, the ``hb``
  decayed ``[c, c]`` matrices stacked on the sublanes so that one product
  gives every head's part (each head keeps its own lanes of it).
- ``ssd_scan_bwd``: the same grid from the last chunk to the first, the
  state's cotangent in the scratch; emits the cotangents of q, k (one a
  head block, summed after), v and the running log decay.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import pallas_kernels as _pk

_F32 = jnp.float32
_NN = (((1,), (0,)), ((), ()))     # a @ b
_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_TN = (((0,), (0,)), ((), ()))     # a.T @ b

# What one grid step may hold in VMEM by the plan's own count, and the
# scoped limit the calls ask Mosaic for (as the flash kernels do: the default
# scoped limit of a v5e is 16 MiB of 128 MiB physical).
_GDN_VMEM_BUDGET = 24 << 20
_GDN_VMEM_LIMIT = 48 << 20
# key heads a grid step: past a few, a step's independent chains already
# fill the units and the unrolled body only grows (PERF.md, PR 35)
_GDN_MAX_HEADS = 4


def _gdn_vmem_bytes(heads, r, n, c, dk, dv, itemsize):
    """VMEM one grid step of the BACKWARD kernel (the larger of the two)
    holds for ``heads`` key heads: the double-buffered per-chunk operands
    and results, the decay vectors of all ``n`` chunks (resident a head, in
    and out), and the float32 scratch.  A tile's last dim is padded to 128
    lanes."""
    lanes = lambda w: _pk._round_up(w, 128)
    per_chunk = (
        2 * c * lanes(dk)                     # q, k
        + 2 * r * c * lanes(dv)               # u, d_out
        + 2 * r * c * lanes(c)                # m, qk
        + r * dk * lanes(dv)) * itemsize      # the start state
    results = (2 * c * lanes(dk) + r * c * lanes(dv)
               + 2 * r * c * lanes(c)) * itemsize
    vectors = 4 * r * _pk._round_up(n, 8) * lanes(c) * 4
    scratch = r * dk * lanes(dv) * 4
    return heads * (2 * (per_chunk + results + vectors) + scratch)


def _local_vmem_bytes(heads, r, n, c, dk, dv, itemsize):
    """VMEM one grid step of ``gdn_local_bwd`` (the larger of the two local
    kernels) holds for ``heads`` key heads: the double-buffered per-chunk
    operands and results, the decay vectors and their cotangents (resident a
    head), and the float32 ``[c, r c]`` tiles the chunk's algebra keeps
    live, counted as 32 of them."""
    lanes = lambda w: _pk._round_up(w, 128)
    per_chunk = (2 * c * lanes(dk) + 2 * r * c * lanes(dv)
                 + 2 * r * c * lanes(c)) * itemsize \
        + c * lanes(r * c) * 4                # the inverses
    results = (2 * c * lanes(dk) + r * c * lanes(dv)) * itemsize
    vectors = 6 * r * _pk._round_up(n, 8) * lanes(c) * 4
    work = 32 * c * lanes(r * c) * 4
    return heads * (2 * (per_chunk + results + vectors) + work)


def _gdn_plan(bh, r, n, c, dk, dv, itemsize, vmem=_gdn_vmem_bytes):
    """Key heads a grid step takes for ``bh`` (batch x key heads) rows of
    ``n`` chunks of ``c`` tokens, ``r`` value heads a key head: the most, up
    to ``_GDN_MAX_HEADS``, that divide ``bh`` and whose ``vmem`` count (the
    scan kernels' by default, :func:`_local_vmem_bytes` for the local ones)
    fits ``_GDN_VMEM_BUDGET``; None where not even one head fits (the decay
    vectors of a head's ``n`` chunks stay resident: a very long sequence
    falls back to XLA)."""
    for heads in range(min(_GDN_MAX_HEADS, bh), 0, -1):
        if bh % heads == 0 and vmem(
                heads, r, n, c, dk, dv, itemsize) <= _GDN_VMEM_BUDGET:
            return heads
    return None


# the lanes of a state-space grid step's value heads, side by side: past
# 512 the stacked product of the heads' decayed matrices grows as their
# square and the steps are already few (1,024 a pass at 64 heads of 64 and
# 8,192 tokens)
_SSD_MAX_LANES = 512


def eligible(dk, dv, chunk, dtype, r=1, correction=True):
    """Whether the kernels take this shape: dk and dv whole 128-lane tiles
    (without the ``correction``: the ``r`` value heads of a key head make
    whole tiles side by side, 64-wide heads by twos), ``chunk`` whole
    sublane tiles of the element type, bfloat16 or float32."""
    dtype = jnp.dtype(dtype)
    wide = dv % 128 == 0 if correction else (r * dv) % 128 == 0
    return dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)) \
        and dk % 128 == 0 and wide \
        and chunk % _pk._sublanes(dtype.itemsize) == 0


def _ssd_vmem_bytes(hb, c, dk, dv, itemsize):
    """VMEM one grid step of ``ssd_scan_bwd`` (the larger of the two)
    holds for ``hb`` value heads of ``dv`` side by side: the
    double-buffered per-chunk operands and results, the float32 state
    scratch, and the working tiles (the stacked ``[hb c, c]`` matrices and
    their cotangents, the ``[hb c, hb dv]`` product, a dozen float32 ``[c,
    hb dv]`` tiles)."""
    lanes = lambda w: _pk._round_up(w, 128)
    w = hb * dv
    vec = _pk._round_up(hb, 8) * lanes(c) * 4
    piped = (2 * c * lanes(dk) + 2 * c * w + dk * w) * itemsize + vec \
        + 2 * c * lanes(dk) * 4 + c * w * itemsize + vec
    work = hb * c * lanes(c) * (itemsize + 4 + 4) + hb * c * w * 4 \
        + 12 * c * w * 4
    return 2 * piped + dk * w * 4 + work


def _ssd_plan(r, c, dk, dv, itemsize):
    """Value heads a grid step of the state-space kernels takes: the most
    that divide ``r``, make whole 128-lane tiles side by side, stay within
    ``_SSD_MAX_LANES`` and fit ``_GDN_VMEM_BUDGET``; None where none does."""
    for hb in range(r, 0, -1):
        if r % hb == 0 and (hb * dv) % 128 == 0 \
                and hb * dv <= max(_SSD_MAX_LANES, dv) \
                and _ssd_vmem_bytes(hb, c, dk, dv, itemsize) \
                <= _GDN_VMEM_BUDGET:
            return hb
    return None


def mode(q_shape, v_shape, chunk, dtype, correction=True):
    """How the recurrence of ``chunked_gated_delta_rule`` at q ``[b, hk, t,
    dk]``, v ``[b, hk, r, t, dv]`` runs in the program being traced:
    ``"pallas"`` where that program is for a TPU that XLA does not partition
    by itself (:func:`pallas_kernels.trace_scope`) and the shape is
    :func:`eligible` and has a plan (without the ``correction``:
    :func:`_ssd_plan`), else None: the ``lax.scan``.  No knob: the platform
    and the shape decide."""
    b, hk, t, dk = (int(x) for x in q_shape)
    r, dv = int(v_shape[2]), int(v_shape[-1])
    if not _pk.traced_for_unpartitioned_tpu() \
            or not eligible(dk, dv, chunk, dtype, r, correction):
        return None
    if not correction:
        planned = _ssd_plan(r, chunk, dk, dv, jnp.dtype(dtype).itemsize)
        return "pallas" if planned else None
    planned = _gdn_plan(b * hk, r, -(-t // chunk), chunk, dk, dv,
                        jnp.dtype(dtype).itemsize)
    return "pallas" if planned else None


def local_planned(q_shape, v_shape, chunk, dtype):
    """Whether, where :func:`mode` takes the recurrence, the chunk-local
    part runs in ``gdn_local_fwd`` / ``gdn_local_bwd`` too: the shape has a
    plan of theirs (else it stays ``lm_ops._chunk_local`` in XLA).  The
    shape alone decides."""
    b, hk, t, dk = (int(x) for x in q_shape)
    r, dv = int(v_shape[2]), int(v_shape[-1])
    return _gdn_plan(b * hk, r, -(-t // chunk), chunk, dk, dv,
                     jnp.dtype(dtype).itemsize, _local_vmem_bytes) is not None


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _dot_f32(a, b, dims=_NN):
    """A float32 product at float32 precision (``HIGHEST``: Mosaic's fp32
    contract precision, XLA's six bfloat16 passes)."""
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32,
                               precision=jax.lax.Precision.HIGHEST)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _eye(c):
    return _iota((c, c), 0) == _iota((c, c), 1)


def _last_column(rows, c):
    return _iota((rows, c), 1) == c - 1


def _picked(x, mask, axis):
    """``x`` broadcast to ``mask``'s shape, summed along ``axis`` where the
    mask holds.  With the identity as mask a [1, c] row becomes the [c, 1]
    column that scales a tile's rows (axis 1), exactly, and a column a row
    (axis 0); with the last column as mask a row's last element stands on
    every row of a column.  Mosaic broadcasts along sublanes or lanes, never
    both, and this is one of each."""
    return jnp.sum(jnp.where(mask, jnp.broadcast_to(x, mask.shape),
                             _F32(0.0)), axis=axis, keepdims=True)


# -- the chunk-local algebra: one chunk of a key head a grid step ------------------
#
# The r value heads of a key head share q and k, so their [c, c] matrices
# stand side by side in one [c, r c] tile (value head j in lanes j c ..
# (j + 1) c).  A product by the block-diagonal [r c, r c] matrix of such a
# tile multiplies each of them by its own, r at a time on the MXU; a
# product by a 0/1 placement matrix moves a [c, c] matrix into or out of its
# block, exactly (one term a sum).

def _dot_split(a, b, dims):
    """A float32 cotangent ``a`` by an operand ``b`` in the compute dtype:
    ``a`` as the sum of two parts in ``b``'s dtype, a product each (what XLA
    does with the float32 operand, to about 16 bits)."""
    hi = a.astype(b.dtype)
    if hi.dtype == a.dtype:
        return _dot(a, b, dims)
    return _dot(hi, b, dims) + _dot((a - hi.astype(_F32)).astype(b.dtype),
                                    b, dims)


class _Tiles:
    """The masks of a ``[c, r c]`` tile: ``blocks[j]`` (value head j's
    lanes), ``rows``, and ``cols`` (the column within a block)."""

    def __init__(self, c, r):
        self.c, self.r = c, r
        self.rows, lanes = _iota((c, r * c), 0), _iota((c, r * c), 1)
        self.blocks = [(lanes >= j * c) & (lanes < (j + 1) * c)
                       for j in range(r)]
        self.cols = lanes
        for j in range(1, r):
            self.cols = jnp.where(self.blocks[j], lanes - j * c, self.cols)
        self.eye = self.rows == self.cols

    def spread(self, columns):
        """r columns ``[c, 1]`` as one tile, column j across block j."""
        out = jnp.broadcast_to(columns[0], self.eye.shape)
        for col, block in zip(columns[1:], self.blocks[1:]):
            out = jnp.where(block, jnp.broadcast_to(col, block.shape), out)
        return out

    def by_column(self, tile):
        """``[1, r c]``: the value a tile made by :meth:`spread` holds on
        row ``i`` of block j, at column ``i`` of block j."""
        return _picked(tile, self.eye, 0)

    def column(self, row, j):
        """``[c, 1]``: block j of a ``[1, r c]`` row, as a column."""
        return _picked(row, self.eye & self.blocks[j], 1)

    def block_sum(self, tile, j):
        """``[c, 1]``: each row of a tile summed over block j."""
        return _picked(tile, self.blocks[j], 1)

    def diagonal(self, tile):
        """The ``[r c, r c]`` block-diagonal matrix of a tile's blocks."""
        if self.r == 1:
            return tile
        return jnp.concatenate([jnp.where(b, tile, _F32(0.0))
                                for b in self.blocks], axis=0)

    def fold(self, x):
        """The sum of the ``r`` row blocks of ``[r c, ...]``."""
        c, out = self.c, x[:self.c]
        for j in range(1, self.r):
            out = out + x[j * c:(j + 1) * c]
        return out

    def placement(self, j, dtype):
        """``[c, r c]`` in ``dtype``: ``x @ P`` puts a ``[., c]`` matrix
        into block j, ``t @ P.T`` takes block j out of a tile."""
        return jnp.where(self.eye & self.blocks[j], _F32(1.0),
                         _F32(0.0)).astype(dtype)

    def moved(self, a, b, dims, dtype):
        """A placement product, exact: one term a sum (float32 operands at
        float32 precision)."""
        if jnp.dtype(dtype) == jnp.dtype(_F32):
            return _dot_f32(a, b, dims)
        return _dot(a, b, dims)


def _unit_lower_inverse(nils, tiles):
    """``(I - n)^{-1}`` of the r strictly lower ``[c, c]`` matrices side by
    side in each tile of ``nils`` (one a key head): ``sum_{j < c} n^j`` by
    doubling, ``s <- s + s p`` and ``p <- p p`` in ONE float32 product of
    ``[s; p]`` by ``p``'s block diagonal: six products of the pair where
    ``lm_ops._unit_lower_inverse`` takes ten of each matrix.  The key
    heads' chains go in step, so that their products stand side by side."""
    c = tiles.c
    eye = jnp.where(tiles.eye, _F32(1.0), _F32(0.0))
    ss = [eye + n for n in nils]
    ps = [_dot_f32(n, tiles.diagonal(n)) for n in nils]     # n^2
    reach = 2
    while reach < c:
        if 2 * reach < c:
            both = [_dot_f32(jnp.concatenate([s, p], axis=0),
                             tiles.diagonal(p)) for s, p in zip(ss, ps)]
            ss = [s + x[:c] for s, x in zip(ss, both)]
            ps = [x[c:] for x in both]
        else:
            ss = [s + _dot_f32(s, tiles.diagonal(p)) for s, p in zip(ss, ps)]
        reach *= 2
    return ss


class _Chunk:
    """``lm_ops._chunk_local`` of one chunk of one key head in float32 up to
    the inverse, the r value heads side by side: from q, k ``[c, dk]`` and v
    ``[c, dv]`` (r of them) in the compute dtype and the rows g, beta ``[1,
    c]`` (r of each).  Attributes as the backward needs them: ``gc`` (r
    columns of the in-chunk cumulative log decay), the tiles ``gc_cols`` /
    ``beta_cols`` (row i of block j: value head j's at token i), the rows
    ``gc_row`` / ``beta_row`` (column l of block j: its at token l),
    ``decay``, ``kk``, ``qk`` (before the decay), ``nil`` (the strictly
    lower matrices :func:`_unit_lower_inverse` inverts) and ``vb`` (v times
    beta, stacked ``[r c, dv]``)."""

    def __init__(self, tiles, q, k, vs, gs, betas):
        c, r, cd = tiles.c, tiles.r, k.dtype
        eye = _eye(c)
        self.on_or_below = _iota((c, c), 0) >= _iota((c, c), 1)
        # the cumulative sum as a masked one: float32, like jnp.cumsum's
        self.gc = [jnp.sum(jnp.where(self.on_or_below,
                                     jnp.broadcast_to(g, (c, c)), _F32(0.0)),
                           axis=1, keepdims=True) for g in gs]
        self.gc_cols = tiles.spread(self.gc)
        self.gc_row = tiles.by_column(self.gc_cols)
        self.betas = [_picked(b, eye, 1) for b in betas]
        self.beta_cols = tiles.spread(self.betas)
        self.beta_row = tiles.by_column(self.beta_cols)
        self.lower = tiles.rows >= tiles.cols
        self.decay = jnp.exp(jnp.where(self.lower,
                                       self.gc_cols - self.gc_row, -jnp.inf))
        # q k^T and k k^T at once, r copies side by side
        self.k_stack = jnp.concatenate([k] * r, axis=0) if r > 1 else k
        both = _dot(jnp.concatenate([q, k], axis=0), self.k_stack, _NT)
        self.qk, self.kk = both[:c], both[c:]
        self.strict = tiles.rows > tiles.cols
        self.nil = -jnp.where(self.strict,
                              self.kk * self.decay * self.beta_cols,
                              _F32(0.0))
        vb = [(v.astype(_F32) * b.astype(cd).astype(_F32)).astype(cd)
              for v, b in zip(vs, self.betas)]
        self.vb = jnp.concatenate(vb, axis=0) if r > 1 else vb[0]

    def row(self, j):
        """``[1, c]``: value head j's cumulative log decay as a row."""
        return _picked(self.gc[j], _eye(self.gc[j].shape[0]), 0)


def _last(col):
    """``[1, 1]``: the last entry of a column ``[c, 1]``."""
    return _picked(col, _iota(col.shape, 0) == col.shape[0] - 1, 0)


def _chunks_of(refs, tiles, heads, i):
    """The :class:`_Chunk` of each of a grid step's key heads, from the
    refs q, k, v, g, beta (the decay vectors resident: chunk ``i``'s row)."""
    from jax.experimental import pallas as pl
    q_ref, k_ref, v_ref, g_ref, beta_ref = refs
    at = lambda ref, h: [ref[h, j, pl.ds(i, 1), :] for j in range(tiles.r)]
    return [_Chunk(tiles, q_ref[h, 0], k_ref[h, 0],
                   [v_ref[h, j, 0] for j in range(tiles.r)], at(g_ref, h),
                   at(beta_ref, h)) for h in range(heads)]


def _local_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, u_ref, m_ref,
                      qk_ref, grow_ref, shrink_ref, *inv_ref, heads, r, c):
    """One chunk of ``heads`` key heads: ``lm_ops._chunk_local``.  The
    decay vectors of all chunks of a head stay resident (in and out) and are
    indexed by the chunk here.  ``inv_ref``, where given, takes the
    inverses as they stand side by side (for :func:`_local_bwd_kernel`)."""
    from jax.experimental import pallas as pl
    i = pl.program_id(1)
    tiles = _Tiles(c, r)
    chunks = _chunks_of((q_ref, k_ref, v_ref, g_ref, beta_ref), tiles,
                        heads, i)
    invs = _unit_lower_inverse([ch.nil for ch in chunks], tiles)
    for h, (ch, inv) in enumerate(zip(chunks, invs)):
        cd = ch.vb.dtype
        if inv_ref:
            inv_ref[0][h, 0] = inv
        scale = ch.beta_row * jnp.exp(ch.gc_row)        # beta exp(gc), by column
        mats = jnp.concatenate([(inv * scale).astype(cd),
                                (ch.qk * ch.decay).astype(cd)], axis=0)
        for j in range(r):
            mine = jnp.where(tiles.blocks[j], inv, _F32(0.0)).astype(cd)
            u_ref[h, j, 0] = _dot(mine, ch.vb).astype(cd)
            both = tiles.moved(mats, tiles.placement(j, cd), _NT, cd)
            m_ref[h, j, 0] = both[:c].astype(cd)
            qk_ref[h, j, 0] = both[c:].astype(cd)
            gc = ch.row(j)
            grow_ref[h, j, pl.ds(i, 1), :] = jnp.exp(gc)
            shrink_ref[h, j, pl.ds(i, 1), :] = jnp.exp(
                _last(ch.gc[j]) - gc)


def _local_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, inv_ref, du_ref,
                      dm_ref, dqk_ref, dgrow_ref, dshrink_ref, dq_ref, dk_ref,
                      dv_ref, dg_ref, dbeta_ref, *, heads, r, c):
    """One chunk of ``heads`` key heads: the chunk again up to its inverse,
    which ``inv_ref`` hands in, then the transpose of
    :func:`_local_fwd_kernel`'s algebra.  Cotangents are float32 and meet
    the MXU in the compute dtype where the forward's operands did; the
    inverse's pull-back ``T^T dT T^T`` is float32 at float32 precision."""
    from jax.experimental import pallas as pl
    i = pl.program_id(1)
    tiles = _Tiles(c, r)
    eye = _eye(c)
    at = lambda ref, h, j: ref[h, j, pl.ds(i, 1), :]
    chunks = _chunks_of((q_ref, k_ref, v_ref, g_ref, beta_ref), tiles,
                        heads, i)
    d_invs, parts = [], []
    for h, ch in enumerate(chunks):
        cd = ch.vb.dtype
        inv = inv_ref[h, 0]
        inv_c = inv.astype(cd)
        grow_row = jnp.exp(ch.gc_row)
        scale = ch.beta_row * grow_row
        # the cotangents of m and qk, side by side
        dmq = None
        for j in range(r):
            put = tiles.moved(jnp.concatenate([dm_ref[h, j, 0],
                                               dqk_ref[h, j, 0]], axis=0),
                              tiles.placement(j, cd), _NN, cd)
            dmq = put if dmq is None else dmq + put
        dm, dqk = dmq[:c], dmq[c:]
        # m = inv * scale (by column)
        d_inv = dm * scale
        d_scale = jnp.sum(dm * inv, axis=0, keepdims=True)
        # u_j = inv_j (v_j beta_j)
        d_beta = []
        for j in range(r):
            du = du_ref[h, j, 0]
            d_inv = d_inv + jnp.where(tiles.blocks[j],
                                      _dot(du, ch.vb, _NT), _F32(0.0))
            d_vb = _dot(inv_c, du, _TN)[j * c:(j + 1) * c].astype(cd) \
                .astype(_F32)
            beta_c = ch.betas[j].astype(cd).astype(_F32)
            v = v_ref[h, j, 0].astype(_F32)
            dv_ref[h, j, 0] = (d_vb * beta_c).astype(cd)
            d_beta.append(jnp.sum(d_vb * v, axis=1,
                                  keepdims=True).astype(cd).astype(_F32))
        d_invs.append(d_inv)
        parts.append((dqk, d_scale, scale, grow_row, d_beta))
    # inv = (I - n)^-1: dn = inv^T d_inv inv^T, r at a time, heads in step
    diags = [tiles.diagonal(inv_ref[h, 0]) for h in range(heads)]
    rights = [_dot_f32(d, diag, _NT) for d, diag in zip(d_invs, diags)]
    d_nils = [tiles.fold(_dot_f32(diag, tiles.diagonal(x), _TN))
              for diag, x in zip(diags, rights)]
    for h, (ch, d_nil, part) in enumerate(zip(chunks, d_nils, parts)):
        dqk, d_scale, scale, grow_row, d_beta = part
        cd = ch.vb.dtype
        q, k = q_ref[h, 0], k_ref[h, 0]
        # n = -where(strict, kk * decay * beta (by row), 0)
        w = jnp.where(ch.strict, -d_nil, _F32(0.0))
        d_kkd = w * ch.beta_cols
        d_kk = d_kkd * ch.decay
        d_decay = d_kkd * ch.kk + dqk * ch.qk
        d_beta_cols = w * (ch.kk * ch.decay)
        # qk = (q k^T) * decay; q k^T and k k^T read q and k, r copies
        lhs = jnp.concatenate([dqk * ch.decay, d_kk], axis=0)   # [2c, r c]
        by_k = _dot_split(lhs, ch.k_stack, _NN)            # d_qk k, d_kk k
        by_rows = tiles.fold(_dot_split(
            lhs, jnp.concatenate([q, k], axis=0), _TN))    # d_qk^T q + d_kk^T k
        dq_ref[h, 0] = by_k[:c].astype(cd)
        dk_ref[h, 0] = (by_k[c:] + by_rows).astype(cd)
        # decay = exp(gc_i - gc_l) on and below the diagonal
        d_diff = jnp.where(ch.lower, d_decay * ch.decay, _F32(0.0))
        d_gc_row = d_scale * scale - jnp.sum(d_diff, axis=0, keepdims=True)
        d_beta_row = d_scale * grow_row
        for j in range(r):
            gc = ch.gc[j]
            d_gc = tiles.block_sum(d_diff, j) + tiles.column(d_gc_row, j)
            # grow = exp(gc); shrink = exp(gc_last - gc)
            d_gc = d_gc + _picked(at(dgrow_ref, h, j), eye, 1) * jnp.exp(gc)
            d_sh = _picked(at(dshrink_ref, h, j), eye, 1) \
                * jnp.exp(_last(gc) - gc)
            d_gc = d_gc - d_sh + jnp.where(
                _iota((c, 1), 0) == c - 1,
                jnp.sum(d_sh, axis=0, keepdims=True), _F32(0.0))
            # gc = cumsum(g): g's cotangent sums gc's from its token on
            dg_ref[h, j, pl.ds(i, 1), :] = jnp.sum(jnp.where(
                ch.on_or_below, jnp.broadcast_to(d_gc, (c, c)), _F32(0.0)),
                axis=0, keepdims=True)
            d_b = d_beta[j] + tiles.block_sum(d_beta_cols, j) \
                + tiles.column(d_beta_row, j)
            dbeta_ref[h, j, pl.ds(i, 1), :] = _picked(d_b, eye, 0)


def _fwd_kernel(q_ref, k_ref, u_ref, m_ref, qk_ref, grow_ref, shrink_ref,
                out_ref, *rest, heads, r, c):
    """One chunk of ``heads`` key heads: ``lm_ops._chunk_step``.  Grid =
    (key-head groups, chunks), the chunks innermost and in order; the float32
    state ``[heads, r, dk, dv]`` persists in ``state_ref`` across a head's
    chunks.  q and k are stacked so that their products with the state are
    one pass over it.  ``rest``: ``start_ref`` (the states a differentiated
    forward keeps) where asked for, then the scratch."""
    from jax.experimental import pallas as pl
    start_ref, state_ref = rest if len(rest) == 2 else (None,) + rest
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    eye = _eye(c)
    last = _last_column(state_ref.shape[2], c)
    for h in range(heads):
        k = k_ref[h, 0]                                     # [c, dk]
        cd = k.dtype
        rows = jnp.concatenate([q_ref[h, 0], k], axis=0)    # [2c, dk]
        for j in range(r):
            state = state_ref[h, j]                         # float32
            s = state.astype(cd)
            if start_ref is not None:
                start_ref[h, 0, j] = s
            both = _dot(rows, s)                            # [2c, dv]
            grow = grow_ref[h, j, pl.ds(i, 1), :]           # [1, c]
            shrink = _picked(shrink_ref[h, j, pl.ds(i, 1), :], eye, 1)
            v_new = u_ref[h, j, 0].astype(_F32) \
                - _dot(m_ref[h, j, 0], both[c:].astype(cd))
            out = _picked(grow, eye, 1) * both[:c] \
                + _dot(qk_ref[h, j, 0], v_new.astype(cd))
            out_ref[h, j, 0] = out.astype(cd)
            state_ref[h, j] = state * _picked(grow, last, 1) \
                + _dot(k, (v_new * shrink).astype(cd), _TN)


def _bwd_kernel(q_ref, k_ref, u_ref, m_ref, qk_ref, grow_ref, shrink_ref,
                start_ref, do_ref, dq_ref, dk_ref, du_ref, dm_ref, dqk_ref,
                dgrow_ref, dshrink_ref, dstate_ref, *, heads, r, c, n):
    """One chunk of ``heads`` key heads, walked from the last chunk to the
    first (the index maps hand chunk ``n - 1 - i``): the transpose of
    :func:`_fwd_kernel`'s step at the saved start state, with the cotangent
    of the state ``[heads, r, dk, dv]`` in ``dstate_ref``.  Cotangents are
    float32 and cast to the compute dtype at the MXU only."""
    from jax.experimental import pallas as pl
    i = pl.program_id(1)
    at = n - 1 - i

    @pl.when(i == 0)
    def _init():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    eye = _eye(c)
    last = _last_column(dstate_ref.shape[2], c)
    for h in range(heads):
        q, k = q_ref[h, 0], k_ref[h, 0]
        cd = k.dtype
        rows = jnp.concatenate([q, k], axis=0)              # [2c, dk]
        d_q = d_k = jnp.zeros(k.shape, _F32)
        for j in range(r):
            s = start_ref[h, 0, j]                          # [dk, dv]
            d_next = dstate_ref[h, j]                       # float32
            d_next_c = d_next.astype(cd)
            m, qk = m_ref[h, j, 0], qk_ref[h, j, 0]
            grow_row = grow_ref[h, j, pl.ds(at, 1), :]
            grow = _picked(grow_row, eye, 1)
            shrink = _picked(shrink_ref[h, j, pl.ds(at, 1), :], eye, 1)
            d_out = do_ref[h, j, 0].astype(_F32)            # [c, dv]
            # the chunk again, as far as its cotangents need it
            both = _dot(rows, s)
            qs, ks = both[:c], both[c:].astype(cd)
            v_new = u_ref[h, j, 0].astype(_F32) - _dot(m, ks)
            # next state = state * g_all + k^T (v_new * shrink)
            d_vs = _dot(k, d_next_c)                        # [c, dv]
            d_k_own = _dot((v_new * shrink).astype(cd), d_next_c, _NT)
            d_g_all = jnp.sum(jnp.sum(d_next * s.astype(_F32), axis=1,
                                      keepdims=True), axis=0, keepdims=True)
            # out = grow * (q s) + qk v_new
            d_v = _dot(qk, d_out.astype(cd), _TN) + d_vs * shrink
            d_v_c = d_v.astype(cd)
            # v_new = u - m (k s)
            d_both = jnp.concatenate([grow * d_out, -_dot(m, d_v_c, _TN)],
                                     axis=0).astype(cd)     # d(q s), d(k s)
            d_rows = _dot(d_both, s, _NT)                   # [2c, dk]
            d_q, d_k = d_q + d_rows[:c], d_k + d_rows[c:] + d_k_own
            du_ref[h, j, 0] = d_v_c
            dm_ref[h, j, 0] = (-_dot(d_v_c, ks, _NT)).astype(cd)
            dqk_ref[h, j, 0] = _dot(d_out.astype(cd), v_new.astype(cd),
                                    _NT).astype(cd)
            d_grow = _picked(jnp.sum(d_out * qs, axis=1, keepdims=True),
                             eye, 0)
            dgrow_ref[h, j, pl.ds(at, 1), :] = d_grow + jnp.where(
                _last_column(1, c), d_g_all, _F32(0.0))
            dshrink_ref[h, j, pl.ds(at, 1), :] = _picked(
                jnp.sum(d_vs * v_new, axis=1, keepdims=True), eye, 0)
            dstate_ref[h, j] = d_next * _picked(grow_row, last, 1) \
                + _dot(rows, d_both, _TN)
        dq_ref[h, 0] = d_q.astype(cd)
        dk_ref[h, 0] = d_k.astype(cd)


def _specs(heads, r, n, c, dk, dv, chunk_of):
    """(BlockSpecs by operand kind) of both kernels: ``chunk_of(i)`` is the
    chunk grid step ``i`` works on."""
    from jax.experimental import pallas as pl
    return {
        "qk_rows": pl.BlockSpec((heads, 1, c, dk),
                                lambda h, i: (h, chunk_of(i), 0, 0)),
        "values": pl.BlockSpec((heads, r, 1, c, dv),
                               lambda h, i: (h, 0, chunk_of(i), 0, 0)),
        "square": pl.BlockSpec((heads, r, 1, c, c),
                               lambda h, i: (h, 0, chunk_of(i), 0, 0)),
        # the decay vectors of all chunks of a head: fetched (and written
        # back) once a head, indexed by the chunk in the kernel
        "vectors": pl.BlockSpec((heads, r, n, c), lambda h, i: (h, 0, 0, 0)),
        "states": pl.BlockSpec((heads, 1, r, dk, dv),
                               lambda h, i: (h, chunk_of(i), 0, 0, 0)),
    }


def _params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_GDN_VMEM_LIMIT)


def _planned(q, u):
    """((b * hk, r, n, c, dk, dv), key heads a grid step) of operands q
    ``[b, hk, n, c, dk]`` and u ``[b, hk, r, n, c, dv]``."""
    b, hk, n, c, dk = q.shape
    dims = (b * hk, int(u.shape[2]), n, c, dk, int(u.shape[-1]))
    heads = _gdn_plan(*dims, jnp.dtype(u.dtype).itemsize)
    if not heads:
        raise ValueError("gdn_scan: no plan for %d rows of %d chunks at "
                         "%d x %d" % (dims[0], n, dk, dims[-1]))
    return dims, heads


@functools.lru_cache(maxsize=128)
def _fwd_jitted(bh, r, n, c, dk, dv, dtype, heads, interpret, keep_states):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    spec = _specs(heads, r, n, c, dk, dv, lambda i: i)
    extra = {"interpret": interpret} if interpret is not None else {}
    out_specs = [spec["values"]] + [spec["states"]] * keep_states
    out_shape = [jax.ShapeDtypeStruct((bh, r, n, c, dv), dtype)] \
        + [jax.ShapeDtypeStruct((bh, n, r, dk, dv), dtype)] * keep_states

    def run(q, k, u, m, qk, grow, shrink):
        # the framework runs with x64 on; Mosaic takes no 64-bit type
        with _pk._enable_x64(False):
            return pl.pallas_call(
                functools.partial(_fwd_kernel, heads=heads, r=r, c=c),
                grid=(bh // heads, n),
                in_specs=[spec["qk_rows"], spec["qk_rows"], spec["values"],
                          spec["square"], spec["square"], spec["vectors"],
                          spec["vectors"]],
                out_specs=out_specs, out_shape=out_shape,
                scratch_shapes=[pltpu.VMEM((heads, r, dk, dv), _F32)],
                compiler_params=_params(), name="gdn_scan_fwd", **extra,
            )(q, k, u, m, qk, grow, shrink)

    return jax.jit(run)


@functools.lru_cache(maxsize=128)
def _bwd_jitted(bh, r, n, c, dk, dv, dtype, heads, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    spec = _specs(heads, r, n, c, dk, dv, lambda i: n - 1 - i)
    extra = {"interpret": interpret} if interpret is not None else {}
    shape = lambda *s, dt=dtype: jax.ShapeDtypeStruct((bh,) + s, dt)

    def run(q, k, u, m, qk, grow, shrink, states, d_out):
        with _pk._enable_x64(False):
            return pl.pallas_call(
                functools.partial(_bwd_kernel, heads=heads, r=r, c=c, n=n),
                grid=(bh // heads, n),
                in_specs=[spec["qk_rows"], spec["qk_rows"], spec["values"],
                          spec["square"], spec["square"], spec["vectors"],
                          spec["vectors"], spec["states"], spec["values"]],
                out_specs=[spec["qk_rows"], spec["qk_rows"], spec["values"],
                           spec["square"], spec["square"], spec["vectors"],
                           spec["vectors"]],
                out_shape=[shape(n, c, dk), shape(n, c, dk),
                           shape(r, n, c, dv), shape(r, n, c, c),
                           shape(r, n, c, c), shape(r, n, c, dt=_F32),
                           shape(r, n, c, dt=_F32)],
                scratch_shapes=[pltpu.VMEM((heads, r, dk, dv), _F32)],
                compiler_params=_params(), name="gdn_scan_bwd", **extra,
            )(q, k, u, m, qk, grow, shrink, states, d_out)

    return jax.jit(run)


def _flat(x):
    """[b, hk, ...] as [b * hk, ...]: a view."""
    return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])


def scan_fwd(q, k, local, keep_states=True, interpret=None):
    """The recurrence over chunks, forward.  q, k ``[b, hk, n, c, dk]`` and
    ``local`` = ``lm_ops._chunk_local``'s (u, m, qk, grow, shrink, g_all),
    each ``[b, hk, r, n, c, ...]``.  Returns (outputs ``[b, hk, r, n, c,
    dv]``, the state every chunk starts from ``[b, hk, n, r, dk, dv]`` in
    the operands' dtype, or None unless ``keep_states``: a forward that
    nothing differentiates does not write them).  ``interpret`` (the
    tests'): run the kernel in the Pallas interpreter."""
    u, m, qk, grow, shrink, _ = local
    dims, heads = _planned(q, u)
    fn = _fwd_jitted(*dims, jnp.dtype(u.dtype), heads, interpret,
                     bool(keep_states))
    out, *states = fn(*(_flat(x) for x in (q, k, u, m, qk, grow, shrink)))
    return out.reshape(u.shape), states[0].reshape(
        q.shape[:2] + states[0].shape[1:]) if states else None


def scan_bwd(q, k, local, states, d_out, interpret=None):
    """The recurrence over chunks, backward: operands as :func:`scan_fwd`
    takes them, the ``states`` it returned and the outputs' cotangent
    ``[b, hk, r, n, c, dv]``.  Returns (d_q, d_k, the cotangents of
    ``local``), ``g_all``'s inside ``grow``'s (its last column) and zeros in
    its own place."""
    u, m, qk, grow, shrink, g_all = local
    dims, heads = _planned(q, u)
    fn = _bwd_jitted(*dims, jnp.dtype(u.dtype), heads, interpret)
    d_q, d_k, *d_local = fn(*(_flat(x) for x in (
        q, k, u, m, qk, grow, shrink, states, d_out.astype(u.dtype))))
    back = lambda x, like: x.reshape(like.shape)
    return back(d_q, q), back(d_k, k), tuple(
        back(x, like) for x, like in zip(d_local, local)) \
        + (jnp.zeros_like(g_all),)


def _local_planned(q, v):
    """((b * hk, r, n, c, dk, dv), key heads a grid step) of the local
    kernels' operands q ``[b, hk, n, c, dk]`` and v ``[b, hk, r, n, c,
    dv]``."""
    b, hk, n, c, dk = q.shape
    dims = (b * hk, int(v.shape[2]), n, c, dk, int(v.shape[-1]))
    heads = _gdn_plan(*dims, jnp.dtype(v.dtype).itemsize, _local_vmem_bytes)
    if not heads:
        raise ValueError("gdn_local: no plan for %d rows of %d chunks at "
                         "%d x %d" % (dims[0], n, dk, dims[-1]))
    return dims, heads


def _local_specs(heads, r, n, c, dk, dv):
    """:func:`_specs` of the local kernels (chunk ``i`` at grid step ``i``)
    with ``inverses``: a chunk's inverses side by side, ``[b * hk, n, c, r
    c]`` float32."""
    from jax.experimental import pallas as pl
    spec = _specs(heads, r, n, c, dk, dv, lambda i: i)
    spec["inverses"] = pl.BlockSpec((heads, 1, c, r * c),
                                    lambda h, i: (h, i, 0, 0))
    return spec


@functools.lru_cache(maxsize=128)
def _local_fwd_jitted(bh, r, n, c, dk, dv, dtype, heads, interpret,
                      keep_inverse):
    from jax.experimental import pallas as pl
    spec = _local_specs(heads, r, n, c, dk, dv)
    extra = {"interpret": interpret} if interpret is not None else {}
    shape = lambda *s, dt=dtype: jax.ShapeDtypeStruct((bh,) + s, dt)

    def run(q, k, v, g, beta):
        with _pk._enable_x64(False):
            return pl.pallas_call(
                functools.partial(_local_fwd_kernel, heads=heads, r=r, c=c),
                grid=(bh // heads, n),
                in_specs=[spec["qk_rows"], spec["qk_rows"], spec["values"],
                          spec["vectors"], spec["vectors"]],
                out_specs=[spec["values"], spec["square"], spec["square"],
                           spec["vectors"], spec["vectors"]]
                + [spec["inverses"]] * keep_inverse,
                out_shape=[shape(r, n, c, dv), shape(r, n, c, c),
                           shape(r, n, c, c), shape(r, n, c, dt=_F32),
                           shape(r, n, c, dt=_F32)]
                + [shape(n, c, r * c, dt=_F32)] * keep_inverse,
                compiler_params=_params(), name="gdn_local_fwd", **extra,
            )(q, k, v, g, beta)

    return jax.jit(run)


@functools.lru_cache(maxsize=128)
def _local_bwd_jitted(bh, r, n, c, dk, dv, dtype, heads, interpret):
    from jax.experimental import pallas as pl
    spec = _local_specs(heads, r, n, c, dk, dv)
    extra = {"interpret": interpret} if interpret is not None else {}
    shape = lambda *s, dt=dtype: jax.ShapeDtypeStruct((bh,) + s, dt)

    def run(q, k, v, g, beta, inv, d_u, d_m, d_qk, d_grow, d_shrink):
        with _pk._enable_x64(False):
            return pl.pallas_call(
                functools.partial(_local_bwd_kernel, heads=heads, r=r, c=c),
                grid=(bh // heads, n),
                in_specs=[spec["qk_rows"], spec["qk_rows"], spec["values"],
                          spec["vectors"], spec["vectors"], spec["inverses"],
                          spec["values"], spec["square"], spec["square"],
                          spec["vectors"], spec["vectors"]],
                out_specs=[spec["qk_rows"], spec["qk_rows"], spec["values"],
                           spec["vectors"], spec["vectors"]],
                out_shape=[shape(n, c, dk), shape(n, c, dk),
                           shape(r, n, c, dv), shape(r, n, c, dt=_F32),
                           shape(r, n, c, dt=_F32)],
                compiler_params=_params(), name="gdn_local_bwd", **extra,
            )(q, k, v, g, beta, inv, d_u, d_m, d_qk, d_grow, d_shrink)

    return jax.jit(run)


def local_fwd(q, k, v, g, beta, keep_inverse=False, interpret=None):
    """``lm_ops._chunk_local`` in ``gdn_local_fwd``: q, k ``[b, hk, n, c,
    dk]`` and v ``[b, hk, r, n, c, dv]`` in the compute dtype, g, beta
    ``[b, hk, r, n, c]`` float32.  Returns (``_chunk_local``'s (u, m, qk,
    grow, shrink, g_all), ``g_all`` being ``grow``'s last column; the
    chunks' inverses for :func:`local_bwd`, ``[b * hk, n, c, r c]`` float32
    with a key head's side by side, or None unless ``keep_inverse``)."""
    dims, heads = _local_planned(q, v)
    fn = _local_fwd_jitted(*dims, jnp.dtype(v.dtype), heads, interpret,
                           bool(keep_inverse))
    u, m, qk, grow, shrink, *inv = fn(*(_flat(x) for x in (q, k, v, g, beta)))
    u, m, qk, grow, shrink = (x.reshape(q.shape[:2] + x.shape[1:])
                              for x in (u, m, qk, grow, shrink))
    return (u, m, qk, grow, shrink, grow[..., -1]), \
        inv[0] if inv else None


def local_bwd(q, k, v, g, beta, inv, d_local, interpret=None):
    """The cotangents of :func:`local_fwd`'s operands (what
    ``jax.vjp(_chunk_local)`` pulls back) in ``gdn_local_bwd``, from the
    inverses ``local_fwd`` kept and the cotangents of (u, m, qk, grow,
    shrink, g_all) as :func:`scan_bwd` returns them: ``g_all``'s inside
    ``grow``'s, so its own place is not read."""
    dims, heads = _local_planned(q, v)
    fn = _local_bwd_jitted(*dims, jnp.dtype(v.dtype), heads, interpret)
    d_u, d_m, d_qk, d_grow, d_shrink = d_local[:5]
    out = fn(*(_flat(x) for x in (q, k, v, g, beta)), inv,
             *(_flat(x) for x in (d_u.astype(v.dtype), d_m.astype(v.dtype),
                                  d_qk.astype(v.dtype), d_grow, d_shrink)))
    return tuple(x.reshape(like.shape)
                 for x, like in zip(out, (q, k, v, g, beta)))


# -- without the correction: one kernel a pass (Mamba-2's state-space layer) --
#
# A grid step holds one chunk of ``hb`` value heads of one key head: q, k
# ``[c, dk]``, v ``[c, hb dv]`` with head j in lanes j dv .. (j + 1) dv, and
# the running log decay of each head as a row ``[hb, c]``.

def _row(j):
    """Sublane ``j`` of a block, as a ``[1, ...]`` slice."""
    from jax.experimental import pallas as pl
    return pl.ds(j, 1)


def _spread(parts, masks, shape):
    """One ``shape`` tile holding ``parts[j]`` broadcast where ``masks[j]``
    holds (a head's lanes), 0 elsewhere."""
    out = jnp.zeros(shape, _F32)
    for part, mask in zip(parts, masks):
        out = jnp.where(mask, jnp.broadcast_to(part, shape), out)
    return out


class _Heads:
    """One chunk of ``hb`` value heads side by side: the masks of their
    lanes, the decay as each head's column and row, the decayed ``[c, c]``
    matrices (float32, and stacked on the sublanes in the compute dtype), and
    the ``[c, hb dv]`` / ``[1, hb dv]`` tiles of grow, shrink and g_all."""

    def __init__(self, gc_ref, qk, hb, dv, c, cd):
        eye = _eye(c)
        lower = _iota((c, c), 0) >= _iota((c, c), 1)
        w = hb * dv
        lanes, row_lanes = _iota((c, w), 1), _iota((1, w), 1)
        self.masks = [(lanes >= j * dv) & (lanes < (j + 1) * dv)
                      for j in range(hb)]
        self.row_masks = [(row_lanes >= j * dv) & (row_lanes < (j + 1) * dv)
                          for j in range(hb)]
        self.rows = [gc_ref[0, 0, _row(j), :] for j in range(hb)]    # [1, c]
        self.cols = [_picked(row, eye, 1) for row in self.rows]     # [c, 1]
        self.decays = [jnp.exp(jnp.where(lower, col - row, -jnp.inf))
                       for col, row in zip(self.cols, self.rows)]
        self.stacked = jnp.concatenate([(qk * d).astype(cd)
                                        for d in self.decays], axis=0)
        self.last = [_last(col) for col in self.cols]               # [1, 1]
        self.grow = _spread([jnp.exp(col) for col in self.cols], self.masks,
                            (c, w))
        self.shrink = _spread([jnp.exp(g - col) for g, col in
                               zip(self.last, self.cols)], self.masks, (c, w))
        self.g_all = _spread([jnp.exp(g) for g in self.last], self.row_masks,
                             (1, w))

    def own(self, stacked_product, c):
        """``[c, hb dv]``: each head's lanes of its own block of a product
        ``[hb c, hb dv]`` of the stacked matrices."""
        out = None
        for j, mask in enumerate(self.masks):
            part = jnp.where(mask, stacked_product[j * c:(j + 1) * c],
                             _F32(0.0))
            out = part if out is None else out + part
        return out


def _ssd_fwd_kernel(q_ref, k_ref, v_ref, gc_ref, out_ref, *rest, hb, dv, c):
    """One chunk of ``hb`` value heads: ``lm_ops._chunk_plain`` and
    ``_chunk_step``.  Grid = (key head x head block, chunks), the chunks
    innermost and in order; the float32 state ``[dk, hb dv]`` persists in
    ``state_ref`` across them.  ``rest``: ``start_ref`` (the states a
    differentiated forward keeps) where asked for, then the scratch."""
    from jax.experimental import pallas as pl
    start_ref, state_ref = rest if len(rest) == 2 else (None,) + rest

    @pl.when(pl.program_id(1) == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    q, k, v = q_ref[0], k_ref[0], v_ref[0]
    cd = v.dtype
    state = state_ref[...]
    s = state.astype(cd)
    if start_ref is not None:
        start_ref[0, 0] = s
    heads = _Heads(gc_ref, _dot(q, k, _NT), hb, dv, c, cd)
    intra = heads.own(_dot(heads.stacked, v), c)
    out_ref[0] = (heads.grow * _dot(q, s) + intra).astype(cd)
    state_ref[...] = state * heads.g_all + _dot(
        k, (v.astype(_F32) * heads.shrink).astype(cd), _TN)


def _ssd_bwd_kernel(q_ref, k_ref, v_ref, gc_ref, start_ref, do_ref, dq_ref,
                    dk_ref, dv_ref, dgc_ref, dstate_ref, *, hb, dv, c):
    """One chunk of ``hb`` value heads, walked from the last chunk to the
    first (the index maps hand chunk ``n - 1 - i``): the transpose of
    :func:`_ssd_fwd_kernel`'s step at the saved start state, the state's
    cotangent ``[dk, hb dv]`` in ``dstate_ref``.  Cotangents are float32
    and meet the MXU in the compute dtype; a float32 one against an operand
    in the compute dtype goes as two parts (:func:`_dot_split`)."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _init():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    q, k, v = q_ref[0], k_ref[0], v_ref[0]
    cd = v.dtype
    s = start_ref[0, 0]                                  # [dk, w]
    d_next = dstate_ref[...]
    d_next_c = d_next.astype(cd)
    d_out = do_ref[0].astype(_F32)                       # [c, w]
    qk = _dot(q, k, _NT)
    heads = _Heads(gc_ref, qk, hb, dv, c, cd)
    v32 = v.astype(_F32)
    qs = _dot(q, s)
    # next state = state * g_all + k^T (v * shrink)
    d_vs = _dot(k, d_next_c)                             # [c, w]
    d_k = _dot((v32 * heads.shrink).astype(cd), d_next_c, _NT)
    d_g_all = jnp.sum(d_next * s.astype(_F32), axis=0, keepdims=True)
    # out = grow * (q s) + own(stacked v)
    d_qs = (heads.grow * d_out).astype(cd)
    d_grow = d_out * qs
    dstate_ref[...] = d_next * heads.g_all + _dot(q, d_qs, _TN)
    d_q = _dot(d_qs, s, _NT)                             # [c, dk]
    d_r = jnp.concatenate([jnp.where(m, d_out, _F32(0.0)) for m in
                           heads.masks], axis=0).astype(cd)   # [hb c, w]
    d_stacked = _dot(d_r, v, _NT)                        # [hb c, c]
    dv_ref[0] = (_dot(heads.stacked, d_r, _TN)
                 + d_vs * heads.shrink).astype(cd)
    d_shrink = d_vs * v32
    eye = _eye(c)
    at_last = _iota((c, 1), 0) == c - 1
    d_qk = None
    for j in range(hb):
        mask, decay = heads.masks[j], heads.decays[j]
        d_att = d_stacked[j * c:(j + 1) * c]
        d_qk = d_att * decay if d_qk is None else d_qk + d_att * decay
        # decay = exp(col - row) on and below the diagonal
        d_seg = d_att * qk * decay
        sh = jnp.sum(jnp.where(mask, d_shrink * heads.shrink, _F32(0.0)),
                     axis=1, keepdims=True)              # [c, 1]
        d_last = jnp.sum(sh, axis=0, keepdims=True) + jnp.sum(
            jnp.where(heads.row_masks[j], d_g_all * heads.g_all, _F32(0.0)),
            axis=1, keepdims=True)                       # [1, 1]
        d_col = jnp.sum(d_seg, axis=1, keepdims=True) + jnp.sum(
            jnp.where(mask, d_grow * heads.grow, _F32(0.0)), axis=1,
            keepdims=True) - sh + jnp.where(at_last, d_last, _F32(0.0))
        dgc_ref[0, 0, _row(j), :] = _picked(d_col, eye, 0) \
            - jnp.sum(d_seg, axis=0, keepdims=True)
    dq_ref[0, 0] = d_q + _dot_split(d_qk, k, _NN)
    dk_ref[0, 0] = d_k + _dot_split(d_qk, q, _TN)


def _ssd_specs(nb, hb, c, dk, dv, chunk_of):
    """BlockSpecs of the state-space kernels over q, k ``[b hk, t, dk]``, v
    ``[b hk, t, r dv]``, the running decay ``[b hk nb, n, hb, c]``, the
    states ``[b hk, n, dk, r dv]`` and the per-head-block cotangents of q
    and k ``[b hk nb, n, c, dk]``; grid row ``h`` is key head ``h // nb``'s
    head block ``h % nb``."""
    from jax.experimental import pallas as pl
    w = hb * dv
    return {
        "rows": pl.BlockSpec((1, c, dk),
                             lambda h, i: (h // nb, chunk_of(i), 0)),
        "values": pl.BlockSpec((1, c, w),
                               lambda h, i: (h // nb, chunk_of(i), h % nb)),
        "decay": pl.BlockSpec((1, 1, hb, c),
                              lambda h, i: (h, chunk_of(i), 0, 0)),
        "states": pl.BlockSpec((1, 1, dk, w),
                               lambda h, i: (h // nb, chunk_of(i), 0, h % nb)),
        "partial": pl.BlockSpec((1, 1, c, dk),
                                lambda h, i: (h, chunk_of(i), 0, 0)),
    }


@functools.lru_cache(maxsize=128)
def _ssd_fwd_jitted(bh, r, n, c, dk, dv, dtype, hb, interpret, keep_states):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    nb = r // hb
    spec = _ssd_specs(nb, hb, c, dk, dv, lambda i: i)
    extra = {"interpret": interpret} if interpret is not None else {}
    out_specs = [spec["values"]] + [spec["states"]] * keep_states
    out_shape = [jax.ShapeDtypeStruct((bh, n * c, r * dv), dtype)] \
        + [jax.ShapeDtypeStruct((bh, n, dk, r * dv), dtype)] * keep_states

    def run(q, k, v, gc):
        with _pk._enable_x64(False):
            return pl.pallas_call(
                functools.partial(_ssd_fwd_kernel, hb=hb, dv=dv, c=c),
                grid=(bh * nb, n),
                in_specs=[spec["rows"], spec["rows"], spec["values"],
                          spec["decay"]],
                out_specs=out_specs, out_shape=out_shape,
                scratch_shapes=[pltpu.VMEM((dk, hb * dv), _F32)],
                compiler_params=_params(), name="ssd_scan_fwd", **extra,
            )(q, k, v, gc)

    return jax.jit(run)


@functools.lru_cache(maxsize=128)
def _ssd_bwd_jitted(bh, r, n, c, dk, dv, dtype, hb, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    nb = r // hb
    spec = _ssd_specs(nb, hb, c, dk, dv, lambda i: n - 1 - i)
    extra = {"interpret": interpret} if interpret is not None else {}

    def run(q, k, v, gc, states, d_out):
        with _pk._enable_x64(False):
            return pl.pallas_call(
                functools.partial(_ssd_bwd_kernel, hb=hb, dv=dv, c=c),
                grid=(bh * nb, n),
                in_specs=[spec["rows"], spec["rows"], spec["values"],
                          spec["decay"], spec["states"], spec["values"]],
                out_specs=[spec["partial"], spec["partial"], spec["values"],
                           spec["decay"]],
                out_shape=[
                    jax.ShapeDtypeStruct((bh * nb, n, c, dk), _F32),
                    jax.ShapeDtypeStruct((bh * nb, n, c, dk), _F32),
                    jax.ShapeDtypeStruct((bh, n * c, r * dv), dtype),
                    jax.ShapeDtypeStruct((bh * nb, n, hb, c), _F32)],
                scratch_shapes=[pltpu.VMEM((dk, hb * dv), _F32)],
                compiler_params=_params(), name="ssd_scan_bwd", **extra,
            )(q, k, v, gc, states, d_out)

    return jax.jit(run)


def _ssd_layout(q, k, v, gc):
    """The kernels' operands: q, k ``[b hk, t, dk]``; v with a key head's
    value heads side by side ``[b hk, t, r dv]``; the running log decay
    ``[b, hk, r, n, c]`` as rows ``[b hk nb, n, hb, c]``.  Returns them and
    the dims (b hk, r, n, c, dk, dv, hb)."""
    b, hk, t, dk = q.shape
    r, n, c = int(v.shape[2]), int(gc.shape[3]), int(gc.shape[4])
    dv = int(v.shape[-1])
    hb = _ssd_plan(r, c, dk, dv, jnp.dtype(v.dtype).itemsize)
    if not hb:
        raise ValueError("ssd_scan: no plan for %d heads of %d at chunk %d"
                         % (r, dv, c))
    bh, nb = b * hk, r // hb
    lay_v = jnp.swapaxes(v, 2, 3).reshape(bh, t, r * dv)
    lay_gc = gc.reshape(b, hk, nb, hb, n, c).transpose(0, 1, 2, 4, 3, 5) \
        .reshape(bh * nb, n, hb, c)
    return (_flat(q), _flat(k), lay_v, lay_gc), (bh, r, n, c, dk, dv, hb)


def ssd_fwd(q, k, v, gc, keep_states=True, interpret=None):
    """The recurrence without the correction, forward: q, k ``[b, hk, t,
    dk]``, v ``[b, hk, r, t, dv]`` in the compute dtype and ``gc`` the
    running log decay within each chunk ``[b, hk, r, n, c]`` float32 (``t
    = n c``).  Returns (outputs ``[b, hk, r, t, dv]``, the state every chunk
    starts from ``[b hk, n, dk, r dv]`` in the operands' dtype, or None
    unless ``keep_states``).  ``interpret`` (the tests'): the Pallas
    interpreter."""
    ops, dims = _ssd_layout(q, k, v, gc)
    fn = _ssd_fwd_jitted(*dims[:6], jnp.dtype(v.dtype), dims[6], interpret,
                         bool(keep_states))
    out, *states = fn(*ops)
    b, hk, r, t, dv = v.shape
    out = jnp.swapaxes(out.reshape(b, hk, t, r, dv), 2, 3)
    return out, states[0] if states else None


def ssd_bwd(q, k, v, gc, states, d_out, interpret=None):
    """The cotangents of q, k, v and ``gc`` of :func:`ssd_fwd` from the
    ``states`` it kept and the outputs' cotangent ``[b, hk, r, t, dv]``."""
    ops, dims = _ssd_layout(q, k, v, gc)
    bh, r, n, c, dk, dv, hb = dims
    fn = _ssd_bwd_jitted(*dims[:6], jnp.dtype(v.dtype), hb, interpret)
    d_o = jnp.swapaxes(d_out.astype(v.dtype), 2, 3).reshape(bh, n * c,
                                                            r * dv)
    d_q, d_k, d_v, d_gc = fn(*ops, states, d_o)
    b, hk = q.shape[:2]
    summed = lambda x: jnp.sum(x.reshape(bh, r // hb, n * c, dk), axis=1) \
        .reshape(q.shape).astype(q.dtype)
    d_v = jnp.swapaxes(d_v.reshape(b, hk, n * c, r, dv), 2, 3)
    d_gc = d_gc.reshape(b, hk, r // hb, n, hb, c).transpose(0, 1, 2, 4, 3, 5) \
        .reshape(gc.shape)
    return summed(d_q), summed(d_k), d_v, d_gc
