"""Pallas TPU kernels for the gated delta rule's recurrence over chunks
(``ops/lm_ops.py``; docs/kernels.md).

The chunk-local algebra (``lm_ops._chunk_local``) is one piece over all
chunks and stays with XLA.  What is left is a recurrence: every chunk reads
the float32 state ``[dk, dv]`` of its value head, adds five small products
and hands the state on.  As a ``lax.scan`` that is a dozen fusions a chunk
with the state going through HBM between each pair of them; here it is one
grid a pass, (key heads, chunks) with the chunk axis sequential, the state in
a VMEM scratch for all chunks of a head and each chunk's operands read where
they lie by the index map:

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


def _gdn_plan(bh, r, n, c, dk, dv, itemsize):
    """Key heads a grid step takes for ``bh`` (batch x key heads) rows of
    ``n`` chunks of ``c`` tokens, ``r`` value heads a key head: the most, up
    to ``_GDN_MAX_HEADS``, that divide ``bh`` and fit ``_GDN_VMEM_BUDGET``;
    None where not even one head fits (the decay vectors of a head's ``n``
    chunks stay resident: a very long sequence falls back to the scan)."""
    for heads in range(min(_GDN_MAX_HEADS, bh), 0, -1):
        if bh % heads == 0 and _gdn_vmem_bytes(
                heads, r, n, c, dk, dv, itemsize) <= _GDN_VMEM_BUDGET:
            return heads
    return None


def eligible(dk, dv, chunk, dtype):
    """Whether the kernels take this shape: dk and dv whole 128-lane tiles,
    ``chunk`` whole sublane tiles of the element type, bfloat16 or
    float32."""
    dtype = jnp.dtype(dtype)
    return dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)) \
        and dk % 128 == 0 and dv % 128 == 0 \
        and chunk % _pk._sublanes(dtype.itemsize) == 0


def mode(q_shape, v_shape, chunk, dtype):
    """How the recurrence of ``chunked_gated_delta_rule`` at q ``[b, hk, t,
    dk]``, v ``[b, hk, r, t, dv]`` runs in the program being traced:
    ``"pallas"`` where that program is for a TPU that XLA does not partition
    by itself (:func:`pallas_kernels.trace_scope`) and the shape is
    :func:`eligible` and has a plan, else None: the ``lax.scan``.  No knob:
    the platform and the shape decide."""
    b, hk, t, dk = (int(x) for x in q_shape)
    r, dv = int(v_shape[2]), int(v_shape[-1])
    if not _pk.traced_for_unpartitioned_tpu() \
            or not eligible(dk, dv, chunk, dtype):
        return None
    planned = _gdn_plan(b * hk, r, -(-t // chunk), chunk, dk, dv,
                        jnp.dtype(dtype).itemsize)
    return "pallas" if planned else None


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _eye(c):
    return jax.lax.broadcasted_iota(jnp.int32, (c, c), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)


def _last_column(rows, c):
    return jax.lax.broadcasted_iota(jnp.int32, (rows, c), 1) == c - 1


def _picked(x, mask, axis):
    """``x`` broadcast to ``mask``'s shape, summed along ``axis`` where the
    mask holds.  With the identity as mask a [1, c] row becomes the [c, 1]
    column that scales a tile's rows (axis 1), exactly, and a column a row
    (axis 0); with the last column as mask a row's last element stands on
    every row of a column.  Mosaic broadcasts along sublanes or lanes, never
    both, and this is one of each."""
    return jnp.sum(jnp.where(mask, jnp.broadcast_to(x, mask.shape),
                             _F32(0.0)), axis=axis, keepdims=True)


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
