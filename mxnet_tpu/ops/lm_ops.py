"""Graph ops of the modern decoder block (ROADMAP M1, M3, M6, M17):
zero-centred RMSNorm, partial rotary positions, SwiGLU, causal depth-wise
conv1d, the gated delta rule as a chunked scan and Mamba-2's state-space
layer on the same scan, a dropless top-k expert layer that is
told which experts it holds, and a per-sequence softmax cross-entropy.

Each is a pure JAX function registered like every other op, so a model is
ONE ``Symbol`` that ``Module.fit`` trains through the fused step
(docs/qwen3_next.md).  Activations come in the storage dtype (bfloat16 in
mixed precision); norms, the router's softmax, the decay and the loss are
computed in float32 inside the ops and cast back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..observability import instrument as _instrument
from . import gdn_kernels
from .registry import register, pBool, pFloat, pInt, pStr

_F32 = jnp.float32


# -- RMSNorm ------------------------------------------------------------------

def _rms_norm(data, gamma, eps=1e-6, zero_centered=True):
    """``x / rms(x) * (1 + w)`` over the last axis (``w`` alone when not
    zero-centred), in float32."""
    x = data.astype(_F32)
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                      + _F32(eps))
    w = gamma.astype(_F32)
    return (y * (1.0 + w if zero_centered else w)).astype(data.dtype)


def _last_dim_param_shape(in_shapes, attrs):
    filled = list(in_shapes)
    if in_shapes[0] is None:
        return filled, [None]
    filled[1] = (int(in_shapes[0][-1]),)
    return filled, [tuple(in_shapes[0])]


register("RMSNorm", _rms_norm, input_names=("data", "gamma"),
         infer_shape=_last_dim_param_shape,
         params={"eps": (pFloat, 1e-6), "zero_centered": (pBool, True)})


# -- rotary positions -----------------------------------------------------------

def _rotary_embedding(data, rotary_dim=0, base=10000.0, interleaved=False,
                      offset=0, segments=1):
    """Rotary positions on ``rotary_dim`` dims of every head of ``[batch,
    seq, heads, head_dim]``, from dim ``offset`` on (all dims from there
    when 0); the position of a row is its index along ``seq`` or, where the
    sequence is ``segments`` equal parts (a noisy copy of a sequence and its
    clean copy are two), its index within its part.  Frequency ``i`` turns
    the pair of dims ``(i, i + rotary_dim / 2)`` (rotate-half) or, where
    ``interleaved``, the neighbours ``(2i, 2i + 1)``."""
    d = int(data.shape[-1])
    lo = int(offset)
    rd = int(rotary_dim) or d - lo
    half = rd // 2
    # graftlint: disable=GL003 — the angles depend on shapes and attributes
    # alone: a float64 table made at trace time, a constant of the program
    inv_freq = 1.0 / (float(base) ** (np.arange(0, rd, 2, dtype=np.float64)
                                      / rd))
    seq, parts = int(data.shape[1]), int(segments)
    if seq % parts:
        raise ValueError("rotary_embedding: %d rows are not %d equal parts"
                         % (seq, parts))
    # graftlint: disable=GL003 — as above
    ang = (np.arange(seq) % (seq // parts)).astype(np.float64)[:, None] \
        * inv_freq[None, :]
    cos = jnp.asarray(np.cos(ang), _F32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), _F32)[None, :, None, :]
    x = data.astype(_F32)
    before, turned, rest = x[..., :lo], x[..., lo:lo + rd], x[..., lo + rd:]
    if interleaved:
        pairs = turned.reshape(turned.shape[:-1] + (half, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        turned = [jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                            axis=-1).reshape(turned.shape)]
    else:
        x1, x2 = turned[..., :half], turned[..., half:]
        turned = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    parts = [p for p in (before, *turned, rest) if p.shape[-1]]
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)
    return out.astype(data.dtype)


register("rotary_embedding", _rotary_embedding, num_inputs=1,
         params={"rotary_dim": (pInt, 0), "base": (pFloat, 10000.0),
                 "interleaved": (pBool, False), "offset": (pInt, 0),
                 "segments": (pInt, 1)})


# -- SwiGLU -------------------------------------------------------------------

def _swiglu(gate, up):
    """``SiLU(gate) * up``."""
    return (jax.nn.silu(gate.astype(_F32)) * up.astype(_F32)).astype(up.dtype)


register("SwiGLU", _swiglu, input_names=("gate", "up"))


# -- causal depth-wise conv1d -------------------------------------------------

def _causal_conv1d(data, weight, *bias, kernel=4, activation="silu",
                   use_bias=False):
    """``y[t, c] = sum_j w[c, j] x[t - (kernel-1) + j, c] (+ b[c])`` on
    ``[batch, seq, channels]`` (zeros before the sequence; the ``bias``
    input where ``use_bias``), accumulated in float32, then SiLU unless
    ``activation`` is ``'none'``."""
    k = int(kernel)
    x = data.astype(_F32)
    w = weight.astype(_F32)
    s = int(x.shape[1])
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(xp[:, j:j + s, :] * w[:, j] for j in range(k))
    if use_bias:
        y = y + bias[0].astype(_F32)
    if activation == "silu":
        y = jax.nn.silu(y)
    return y.astype(data.dtype)


def _conv1d_infer_shape(in_shapes, attrs):
    filled = list(in_shapes)
    if in_shapes[0] is None:
        return filled, [None]
    filled[1] = (int(in_shapes[0][-1]), int(attrs["kernel"]))
    if len(filled) > 2:
        filled[2] = (int(in_shapes[0][-1]),)
    return filled, [tuple(in_shapes[0])]


register("causal_conv1d", _causal_conv1d,
         input_names=("data", "weight", "bias"),
         num_inputs=lambda attrs: 2 + bool(attrs.get("use_bias")),
         infer_shape=_conv1d_infer_shape,
         params={"kernel": (pInt, 4), "activation": (pStr, "silu"),
                 "use_bias": (pBool, False)})


# -- the gated delta rule, chunk-wise --------------------------------------------
#
# Per value head with state S in R^{dk x dv}, S_0 = 0:
#   S' = gamma_t S_{t-1};  S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T;
#   o_t = S_t^T q_t.
# Within a chunk that starts from state S: with G_t the running product of
# gamma, u_t = beta_t (v_t - G_t S^T k_t - sum_{j<t} (G_t/G_j)(k_j.k_t) u_j),
# i.e. (I + L) U = V_beta - diag(beta G) K S with L strictly lower.  With
# T = (I + L)^-1:  U = T V_beta - M (K S),  M = T diag(beta G);
# o = diag(G) (Q S) + tril(Q K^T * D) U;  S_next = G_C S + K^T diag(G_C/G) U.
# What a chunk needs beside q, k and the state is thus per value head a
# [c, dv] matrix, two [c, c] matrices and three vectors — kept in the
# operands' dtype (bfloat16 in mixed precision, as the products read them);
# the decay, the solve and the carried state are float32.
#
# Without the correction (``correction=False``: Mamba-2's state-space
# duality, below) the state takes ``k_t v_t^T`` as it stands: U = V, there is
# no L, no T and no M, and the chunk needs q k^T under the decay alone.

# the two parts of the op under its ``mx:gdn`` scope, in the forward and in
# the backward rule alike (docs/observability.md: device time by mechanism);
# the state-space op's under ``mx:ssm``
LOCAL_SCOPE = "mx:gdn:local"
SCAN_SCOPE = "mx:gdn:scan"
SSM_LOCAL_SCOPE = "mx:ssm:local"
SSM_SCAN_SCOPE = "mx:ssm:scan"


def _scopes(correction):
    """(local, scan) scope names of the recurrence with or without the
    delta rule's correction."""
    return (LOCAL_SCOPE, SCAN_SCOPE) if correction \
        else (SSM_LOCAL_SCOPE, SSM_SCAN_SCOPE)


def _l2norm(x, eps=1e-6):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                         + _F32(eps))


def _mm32(a, b):
    return jnp.matmul(a, b, preferred_element_type=_F32)


@jax.custom_vjp
def _unit_lower_inverse(n):
    """``(I - n)^{-1}`` of a strictly lower triangular (nilpotent) ``n``
    over its last two axes: ``(I + n)(I + n^2)(I + n^4)...`` — log2(C)
    batched products in place of a row-by-row substitution."""
    c = int(n.shape[-1])
    mm = lambda a, b: jnp.matmul(a, b, precision=lax.Precision.HIGHEST)
    eye = jnp.eye(c, dtype=n.dtype)
    inv, power, reach = eye + n, n, 2
    while reach < c:
        power = mm(power, power)
        inv = mm(inv, eye + power)
        reach *= 2
    return inv


def _unit_lower_inverse_bwd(inv, d_inv):
    # d(I - n)^-1 = T dn T: the products' factors are not kept
    mm = lambda a, b: jnp.matmul(a, b, precision=lax.Precision.HIGHEST)
    t = jnp.swapaxes(inv, -1, -2)
    return (mm(mm(t, d_inv), t),)


_unit_lower_inverse.defvjp(lambda n: (_unit_lower_inverse(n),) * 2,
                           _unit_lower_inverse_bwd)


def _chunk_local(q, k, v, g, beta):
    """What every chunk computes from its own tokens alone, all chunks at
    once.  q, k: [b, hk, n, c, dk]; v: [b, hk, r, n, c, dv] (``r`` value
    heads to a key head); g (log decay), beta: [b, hk, r, n, c] float32.
    Returns (u, m, qk, grow, shrink, g_all) as the header above names
    them."""
    c, cd = int(q.shape[-2]), v.dtype
    gc = jnp.cumsum(g, axis=-1)
    # graftlint: disable=GL003 — static index grids for the triangular masks
    rows = np.arange(c)[:, None]
    # graftlint: disable=GL003 — as above
    cols = np.arange(c)[None, :]
    diff = gc[..., :, None] - gc[..., None, :]
    decay = jnp.exp(jnp.where(rows >= cols, diff, -jnp.inf))   # i >= j
    kk = jnp.einsum("bhnid,bhnjd->bhnij", k, k,
                    preferred_element_type=_F32)[:, :, None]
    inv = _unit_lower_inverse(-jnp.where(
        rows > cols, kk * decay * beta[..., :, None], 0.0))
    u = _mm32(inv.astype(cd), (v * beta[..., None].astype(cd)))
    m = inv * (beta * jnp.exp(gc))[..., None, :]
    qk = jnp.einsum("bhnid,bhnjd->bhnij", q, k,
                    preferred_element_type=_F32)[:, :, None] * decay
    g_last = gc[..., -1:]
    return (u.astype(cd), m.astype(cd), qk.astype(cd), jnp.exp(gc),
            jnp.exp(g_last - gc), jnp.exp(g_last[..., 0]))


def _chunk_plain(q, k, v, g):
    """:func:`_chunk_local` without the correction: u is v itself, there is
    no m (None), and qk is q k^T under the decay.  Same layouts."""
    cd = v.dtype
    c = int(q.shape[-2])
    gc = jnp.cumsum(g, axis=-1)
    # graftlint: disable=GL003 — static index grids for the triangular mask
    rows = np.arange(c)[:, None]
    # graftlint: disable=GL003 — as above
    cols = np.arange(c)[None, :]
    decay = jnp.exp(jnp.where(rows >= cols,
                              gc[..., :, None] - gc[..., None, :], -jnp.inf))
    qk = jnp.einsum("bhnid,bhnjd->bhnij", q, k,
                    preferred_element_type=_F32)[:, :, None] * decay
    g_last = gc[..., -1:]
    return (v, None, qk.astype(cd), jnp.exp(gc), jnp.exp(g_last - gc),
            jnp.exp(g_last[..., 0]))


def _chunk_step(state, xs):
    """One chunk given the float32 state [b, hk, r, dk, dv] it starts from:
    (next state, outputs [b, hk, r, c, dv]).  ``m`` None: no correction,
    ``v_new`` is ``u``."""
    q, k, u, m, qk, grow, shrink, g_all = xs
    cd = u.dtype
    s = state.astype(cd)
    v_new = u.astype(_F32) if m is None else \
        u.astype(_F32) - _mm32(m, _mm32(k[:, :, None], s).astype(cd))
    out = grow[..., None] * _mm32(q[:, :, None], s) \
        + _mm32(qk, v_new.astype(cd))
    nxt = state * g_all[..., None, None] + jnp.einsum(
        "bhcd,bhrce->bhrde", k, (v_new * shrink[..., None]).astype(cd),
        preferred_element_type=_F32)
    return nxt, out.astype(cd)


def _split(x, axis, n):
    """Cut ``axis`` (the sequence) into ``n`` chunks."""
    return x.reshape(x.shape[:axis] + (n, x.shape[axis] // n)
                     + x.shape[axis + 1:])


def _chunks(q, k, v, g, beta, chunk):
    """The operands cut into chunks: q, k [b, hk, n, c, dk]; v, g, beta [b,
    hk, r, n, c, ...].  ``beta`` None (no correction): left out."""
    n = int(q.shape[2]) // chunk
    return (_split(q, 2, n), _split(k, 2, n), _split(v, 3, n),
            _split(g, 3, n)) + ((_split(beta, 3, n),) if beta is not None
                                else ())


def _scan_inputs(q, k, v, g, beta, chunk):
    """(per-chunk operands with the chunk axis leading, the vjp of the
    chunk-local part: :func:`_chunk_plain`'s where ``beta`` is None)."""
    args = _chunks(q, k, v, g, beta, chunk)
    with jax.named_scope(_scopes(beta is not None)[0]):
        local, pull = jax.vjp(_chunk_local if beta is not None
                              else _chunk_plain, *args)
    lead = lambda x, axis: jnp.moveaxis(x, axis, 0)
    xs = (lead(args[0], 2), lead(args[1], 2)) \
        + tuple(None if x is None else lead(x, 3) for x in local)
    return xs, pull


def _local_part(args, kernel, keep_inverse=True):
    """(``local``, its pull-back) of the ``kernel`` path on the chunked
    operands: ``gdn_kernels.local_fwd`` / ``local_bwd`` where the shape has
    their plan (``gdn_kernels.local_planned``), else ``_chunk_local`` in
    XLA.  The backward rule's call keeps the chunks' inverses for
    ``local_bwd``; the differentiated forward's asks the same, so that XLA
    merges the call the mirror stage recomputes with it (a forward that
    nothing differentiates does not write them)."""
    q, v = args[0], args[2]
    b, hk, n, c, dk = q.shape
    interpret = (kernel == "interpret") or None
    if not gdn_kernels.local_planned((b, hk, n * c, dk), v.shape, c,
                                     v.dtype):
        return jax.vjp(_chunk_local, *args)
    local, inv = gdn_kernels.local_fwd(*args, keep_inverse=keep_inverse,
                                       interpret=interpret)
    return local, functools.partial(gdn_kernels.local_bwd, *args, inv,
                                    interpret=interpret)


def _ssd_cumulative(g, chunk):
    """The running sum of the log decay within each chunk, [b, hk, r, n,
    c]: all the state-space kernels need of the chunk-local part."""
    return jnp.cumsum(_split(g, 3, int(g.shape[3]) // chunk), axis=-1)


def _gdr_forward(q, k, v, g, beta, chunk, kernel=None, keep_states=True):
    """q, k: [b, hk, t, dk]; v: [b, hk, r, t, dv]; g, beta: [b, hk, r, t];
    t a multiple of ``chunk``.  Returns (outputs [b, hk, r, t, dv], the
    state each chunk starts from, kept in the operands' dtype: [n, b, hk, r,
    dk, dv], or [b, hk, n, r, dk, dv] from the ``kernel``, which writes them
    only where ``keep_states``; XLA drops the scan's by itself).  ``beta``
    None: no correction (``gdn_kernels.ssd_fwd`` on the kernel path, whose
    states are [b * hk, n, dk, r * dv])."""
    if kernel and beta is None:
        interpret = (kernel == "interpret") or None
        with jax.named_scope(SSM_LOCAL_SCOPE):
            gc = _ssd_cumulative(g, chunk)
        with jax.named_scope(SSM_SCAN_SCOPE):
            return gdn_kernels.ssd_fwd(q, k, v, gc, keep_states,
                                       interpret=interpret)
    if kernel:
        args = _chunks(q, k, v, g, beta, chunk)
        with jax.named_scope(LOCAL_SCOPE):
            local, _ = _local_part(args, kernel, keep_states)
        with jax.named_scope(SCAN_SCOPE):
            outs, states = gdn_kernels.scan_fwd(
                args[0], args[1], local, keep_states,
                interpret=(kernel == "interpret") or None)
        return outs.reshape(v.shape), states
    xs, _ = _scan_inputs(q, k, v, g, beta, chunk)

    def body(state, x):
        nxt, out = _chunk_step(state, x)
        return nxt, (state.astype(v.dtype), out)

    start = jnp.zeros(v.shape[:3] + (q.shape[-1], v.shape[-1]), _F32)
    with jax.named_scope(_scopes(beta is not None)[1]):
        _, (states, outs) = lax.scan(body, start, xs)
    outs = jnp.moveaxis(outs, 0, 3)
    return outs.reshape(v.shape), states


@functools.lru_cache(maxsize=None)
def _make_gdr(chunk, kernel=None, correction=True):
    """The differentiable recurrence at one ``chunk``.  ``kernel``: None for
    the ``lax.scan`` over chunks and ``_chunk_local`` in XLA (the CPU path,
    the fallback, the tests' oracle), ``"pallas"`` for ``ops/gdn_kernels.py``'s
    scan kernels in its place and, where the shape has their plan, its
    local kernels in place of ``_chunk_local`` and its pull-back
    (``"interpret"``: the same through the Pallas interpreter, the
    tests').  Without the ``correction`` beta is None, the XLA path's local
    part is ``_chunk_plain`` and the kernel path's the running sum of g
    before ``gdn_kernels.ssd_fwd`` / ``ssd_bwd``."""
    @jax.custom_vjp
    def gdr(q, k, v, g, beta):
        return _gdr_forward(q, k, v, g, beta, chunk, kernel,
                            keep_states=False)[0]

    def fwd(q, k, v, g, beta):
        out, states = _gdr_forward(q, k, v, g, beta, chunk, kernel)
        return out, (q, k, v, g, beta, states)

    def bwd(res, d_out):
        """Recomputes every chunk: the local part again in one piece with
        its pull-back, then the chunks in reverse, each from the state it
        started from, carrying the state's cotangent."""
        q, k, v, g, beta, states = res
        n = int(q.shape[2]) // chunk
        local_scope, scan_scope = _scopes(correction)
        if kernel and not correction:
            with jax.named_scope(local_scope):
                gc, pull_gc = jax.vjp(
                    functools.partial(_ssd_cumulative, chunk=chunk), g)
            with jax.named_scope(scan_scope):
                d_q, d_k, d_v, d_gc = gdn_kernels.ssd_bwd(
                    q, k, v, gc, states, d_out,
                    interpret=(kernel == "interpret") or None)
            with jax.named_scope(local_scope):
                return d_q, d_k, d_v, pull_gc(d_gc)[0], None
        if kernel:
            args = _chunks(q, k, v, g, beta, chunk)
            with jax.named_scope(LOCAL_SCOPE):
                local, pull = _local_part(args, kernel)
            with jax.named_scope(SCAN_SCOPE):
                d_q, d_k, d_local = gdn_kernels.scan_bwd(
                    args[0], args[1], local, states, _split(d_out, 3, n),
                    interpret=(kernel == "interpret") or None)
        else:
            xs, pull = _scan_inputs(q, k, v, g, beta, chunk)
            d_outs = jnp.moveaxis(_split(d_out, 3, n), 3, 0)

            def body(d_state, item):
                state, x, d_o = item
                _, step_vjp = jax.vjp(_chunk_step, state.astype(_F32), x)
                d_prev, d_x = step_vjp((d_state, d_o))
                return d_prev, d_x

            zero = jnp.zeros(states.shape[1:], _F32)
            with jax.named_scope(scan_scope):
                _, d_xs = lax.scan(body, zero, (states, xs, d_outs),
                                   reverse=True)
            d_q, d_k = (jnp.moveaxis(x, 0, 2) for x in d_xs[:2])
            d_local = tuple(None if x is None else jnp.moveaxis(x, 0, 3)
                            for x in d_xs[2:])
        with jax.named_scope(local_scope):
            grads = list(pull(d_local)) + [None] * (beta is None)
        grads[0], grads[1] = grads[0] + d_q, grads[1] + d_k
        return tuple(None if r is None else x.reshape(r.shape)
                     for x, r in zip(grads, (q, k, v, g, beta)))

    gdr.defvjp(fwd, bwd)
    return gdr


def chunked_gated_delta_rule(q, k, v, g, beta, chunk=64, correction=True):
    """The recurrence above for q, k [batch, key heads, seq, dk], v [batch,
    key heads, r, seq, dv] (``r`` value heads share a key head) and float32
    g (the log of the decay), beta [batch, key heads, r, seq], computed
    chunk by chunk; any ``seq``: a tail chunk is padded with tokens that
    leave the state as it is (beta 0 or v 0, decay 1).  The recurrence over
    the chunks, and the chunk-local part where the shape has the local
    kernels' plan, are ``ops/gdn_kernels.py``'s Pallas kernels where the
    program is traced for a TPU and the shape is eligible
    (``gdn_kernels.mode``: no knob), else a ``lax.scan`` after
    ``_chunk_local``.  ``correction=False``: ``S_t = gamma_t S_{t-1} + k_t
    v_t^T`` (beta is not read), through ``_chunk_plain`` or the kernels'
    ``ssd_scan_fwd`` / ``ssd_scan_bwd``; each lowering is counted
    (``ops.ssm.lowered_kernel`` / ``ops.ssm.lowered_xla``)."""
    t = int(q.shape[2])
    pad = (-t) % chunk
    beta = beta if correction else None
    if pad:
        at = lambda x, axis: jnp.pad(
            x, [(0, pad if i == axis else 0) for i in range(x.ndim)])
        q, k, v, g = at(q, 2), at(k, 2), at(v, 3), at(g, 3)
        beta = None if beta is None else at(beta, 3)
    kernel = gdn_kernels.mode(q.shape, v.shape, chunk, v.dtype, correction)
    if not correction:
        _instrument.note_ssm_lowering(bool(kernel))
    return _make_gdr(chunk, kernel, bool(correction))(
        q, k, v, g, beta)[:, :, :, :t]


def _gated_delta_rule(query, key, value, a, b, A_log, dt_bias, chunk=64):
    """Gated DeltaNet's token mixer on ``query``/``key`` [batch, seq, key
    heads, dk], ``value`` [batch, seq, value heads, dv], ``a``/``b`` [batch,
    seq, value heads]: q and k are L2-normalised over the head (q also
    scaled by dk^-1/2), key head ``j`` serves value heads ``j*r .. (j+1)*r``,
    ``beta = sigmoid(b)`` and the decay is ``exp(-exp(A_log) softplus(a +
    dt_bias))``, in float32; the products read ``value``'s dtype."""
    with jax.named_scope("mx:gdn"):
        cd = value.dtype
        bsz, t, hk, dk = query.shape
        hv, dv = int(value.shape[2]), int(value.shape[3])
        r = hv // hk
        heads_first = lambda x: jnp.swapaxes(x, 1, 2)
        q = heads_first(_l2norm(query.astype(_F32)) * _F32(dk ** -0.5))
        k = heads_first(_l2norm(key.astype(_F32)))
        grouped = lambda x: heads_first(x).reshape(
            (bsz, hk, r, t) + x.shape[3:])
        g = -jnp.exp(A_log.astype(_F32)) \
            * jax.nn.softplus(a.astype(_F32) + dt_bias.astype(_F32))
        out = chunked_gated_delta_rule(
            q.astype(cd), k.astype(cd), grouped(value), grouped(g),
            grouped(jax.nn.sigmoid(b.astype(_F32))), int(chunk))
        return heads_first(out.reshape(bsz, hv, t, dv)).astype(cd)


def _gdr_infer_shape(in_shapes, attrs):
    filled = list(in_shapes)
    v = in_shapes[2]
    if v is None:
        return filled, [None]
    filled[3] = filled[4] = tuple(v[:3])
    filled[5] = filled[6] = (int(v[2]),)
    return filled, [tuple(v)]


register("gated_delta_rule", _gated_delta_rule,
         input_names=("query", "key", "value", "a", "b", "A_log", "dt_bias"),
         infer_shape=_gdr_infer_shape, params={"chunk": (pInt, 64)})


# -- Mamba-2's state-space layer (SSD) on the same recurrence -------------------

def _ssd(x, B, C, dt, A_log, dt_bias, D, chunk=64):
    """Mamba-2's selective state space (the SSD form) on ``x`` [batch, seq,
    heads, P], ``B`` / ``C`` [batch, seq, groups, N] and ``dt`` [batch,
    seq, heads]: per head ``h`` with state S [P, N] from zero,
    ``delta = softplus(dt + dt_bias)``, ``S_t = exp(-exp(A_log_h) delta_t)
    S_{t-1} + delta_t x_t B_t^T`` and ``y_t = S_t C_t + D_h x_t``; group
    ``j`` (its B and C) serves heads ``j*r .. (j+1)*r``.  That is
    :func:`chunked_gated_delta_rule` without its correction, C its q, B its
    k and ``delta x`` its v.  The decay, ``delta``, the state and the skip
    are float32; the products read ``x``'s dtype."""
    with jax.named_scope("mx:ssm"):
        cd = x.dtype
        bsz, t, h, p = x.shape
        groups = int(B.shape[2])
        r = h // groups
        heads_first = lambda a: jnp.swapaxes(a, 1, 2)
        grouped = lambda a: heads_first(a).reshape(
            (bsz, groups, r, t) + a.shape[3:])
        delta = jax.nn.softplus(dt.astype(_F32) + dt_bias.astype(_F32))
        g = -jnp.exp(A_log.astype(_F32)) * delta
        u = (x.astype(_F32) * delta[..., None]).astype(cd)
        out = chunked_gated_delta_rule(
            heads_first(C).astype(cd), heads_first(B).astype(cd), grouped(u),
            grouped(g), None, int(chunk), correction=False)
        y = heads_first(out.reshape(bsz, h, t, p)).astype(_F32) \
            + D.astype(_F32)[:, None] * x.astype(_F32)
        return y.astype(cd)


def _ssd_infer_shape(in_shapes, attrs):
    filled = list(in_shapes)
    x = in_shapes[0]
    if x is None:
        return filled, [None]
    filled[3] = tuple(x[:3])
    filled[4] = filled[5] = filled[6] = (int(x[2]),)
    return filled, [tuple(x)]


register("ssd", _ssd,
         input_names=("data", "B", "C", "dt", "A_log", "dt_bias", "D"),
         infer_shape=_ssd_infer_shape, params={"chunk": (pInt, 64)})


# -- dropless top-k experts, this chip's share ---------------------------------

# A round of ``moe_experts`` holds this many times the choices a uniform
# router would put on the held experts.  2: a layer at its expectation, or
# up to twice it, takes one round, and moves an eighth of the rows where a
# sixteenth of the experts is held.  One chip's share trains the router
# towards the experts it holds (PERF.md, question 15: over a 51 s run layers
# reach 2 to 15 times their expectation), and the rounds carry that: a layer
# at 7 times takes four.  At 1 a layer at its expectation would already
# take two rounds; at 4 every layer would move twice the rows it needs.
_MOE_HEADROOM = 2
_MOE_ROW_TILE = 1024            # a round is whole row tiles of the product


def moe_capacity(choices, held, num_experts):
    """The rows one round of ``moe_experts`` gathers, computes and combines,
    of ``choices`` (tokens x top-k) routed over ``num_experts`` of which this
    chip holds ``held``: ``_MOE_HEADROOM`` times the held experts' expected
    share, in whole row tiles; all the choices where every expert is held
    (``held`` 0 or ``num_experts``).  From shapes and attributes alone."""
    choices, held, num_experts = int(choices), int(held), int(num_experts)
    if not 0 < held < num_experts:
        return choices
    expected = -(-_MOE_HEADROOM * choices * held // num_experts)
    return min(choices, -(-expected // _MOE_ROW_TILE) * _MOE_ROW_TILE)


def moe_rounds(live, capacity):
    """The rounds of ``capacity`` rows that ``live`` held choices take (a
    number on the host, or a traced one)."""
    return -(-live // capacity)


def _moe_part(name):
    return jax.named_scope("mx:moe:" + name)


def _moe_round(r, k, cap, order, sizes, data, weight, gate_weight, up_weight,
               down_weight):
    """(tokens [cap], their weighted expert outputs [cap, h] in float32) of
    the sorted choices ``r * cap .. (r + 1) * cap``.  ``order`` lists the
    choices (token * k + slot) by held expert, other chips' last; ``sizes``
    counts each held expert's; ``weight`` [n * k] is each choice's."""
    lo = r * cap
    window = lax.dynamic_slice(order, (lo,), (cap,))
    tokens = window // k
    ends = jnp.cumsum(sizes)
    within = lambda edge: jnp.clip(edge, lo, lo + cap)
    groups = within(ends) - within(ends - sizes)    # each expert's rows here
    # the rows past the held groups are other chips': the grouped product
    # leaves them UNWRITTEN on the TPU, in the backward pass too, so each of
    # its operands and its result is selected, never scaled, to nought there
    # (PERF.md, PR 27)
    live = (lo + jnp.arange(cap) < ends[-1])[:, None]
    mine_only = lambda x: jnp.where(live, x, jnp.zeros((), x.dtype))
    with _moe_part("gather"):
        rows = mine_only(data[tokens])              # [cap, h], by expert
    with _moe_part("experts"):
        mid = mine_only(_swiglu(lax.ragged_dot(rows, gate_weight, groups),
                                lax.ragged_dot(rows, up_weight, groups)))
        out = mine_only(lax.ragged_dot(mid, down_weight, groups)
                        .astype(_F32)) * weight[window][:, None]
    return tokens, out


def _moe_in_rounds(cap, order, sizes, body, carry):
    """``carry`` through ``body(r, carry)`` for every round of ``cap`` sorted
    choices that the live ones reach, and no further; no loop where one
    round holds every choice."""
    if cap == order.shape[0]:
        return body(0, carry)
    return lax.fori_loop(0, moe_rounds(jnp.sum(sizes), cap), body, carry)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _moe_held(k, cap, order, sizes, data, weight, gate_weight, up_weight,
              down_weight):
    """``[n, h]``: each token's held choices, weighted and summed in float32
    (cast once, to ``data``'s dtype), in rounds of ``cap`` sorted choices, as
    many as the live ones reach: dropless at any load, and only the rows of
    the rounds taken are moved.  ``order`` holds a whole number of rounds.
    The backward takes the same rounds, each recomputed from the inputs and
    pulled back, so what a round makes does not outlive it."""
    def added(r, y):
        tokens, out = _moe_round(r, k, cap, order, sizes, data, weight,
                                 gate_weight, up_weight, down_weight)
        with _moe_part("scatter"):
            return y.at[tokens].add(out)
    return _moe_in_rounds(cap, order, sizes, added,
                          jnp.zeros(data.shape, _F32)).astype(data.dtype)


def _moe_held_fwd(k, cap, *args):
    return _moe_held(k, cap, *args), args


def _moe_held_bwd(k, cap, args, d_y):
    order, sizes, *into = args

    def added(r, d):
        _, pull, tokens = jax.vjp(
            lambda *into: _moe_round(r, k, cap, order, sizes, *into)[::-1],
            *into, has_aux=True)
        with _moe_part("scatter"):
            d_out = d_y[tokens].astype(_F32)    # what the sum's rows got
        return jax.tree_util.tree_map(jnp.add, d, pull(d_out))

    d = _moe_in_rounds(cap, order, sizes, added,
                       tuple(jnp.zeros_like(x) for x in into))
    # graftlint: disable=GL003 — float0 is numpy's alone: the cotangent of
    # the integer operands, never on the device
    none = lambda x: np.zeros(x.shape, jax.dtypes.float0)
    return (none(order), none(sizes)) + d


_moe_held.defvjp(_moe_held_fwd, _moe_held_bwd)


def _moe_experts(data, router_weight, gate_weight, up_weight, down_weight,
                 *rest, num_experts=1, num_hidden=0, experts_held=0,
                 first_expert=0, top_k=1, norm_topk_prob=True,
                 score_func="softmax", route_scale=1.0,
                 use_expert_bias=False):
    """``sum_{e in top-k and held} p_e F_e(x)`` for tokens ``data`` [n, h],
    ``F_e(x) = (SiLU(x W_gate,e) * x W_up,e) W_down,e``.

    The router (``router_weight`` [num_experts, h]) scores ALL experts in
    float32 — ``score_func`` ``"softmax"`` over the experts, or
    ``"sigmoid"`` of each logit alone — and takes the ``top_k`` by score;
    with ``use_expert_bias`` by score plus ``expert_bias`` [num_experts],
    which takes part in the choice alone, never in a weight, and gets no
    gradient.  ``norm_topk_prob`` renormalises the chosen scores over the k
    chosen wherever those live (plus 1e-20 under ``sigmoid``, whose scores
    need not add up to anything), and ``route_scale`` scales them.  This op
    holds experts ``first_expert .. first_expert + experts_held`` (weights
    [held, h, num_hidden] twice and [held, num_hidden, h]) and computes
    their part alone — the rest is other chips'.  The choices are sorted by
    expert, the held ones first, and the first ``moe_capacity`` of them are
    gathered, multiplied by one grouped product and added into their tokens
    in float32; where more are live than that, further rounds of as many
    follow (``_moe_held``).  Dropless: every (token, held expert) choice is
    computed, whatever the load.  Second output: how many tokens chose each
    of the ``num_experts`` (no gradient)."""
    if score_func not in ("softmax", "sigmoid"):
        raise ValueError("moe_experts: score_func %r is neither 'softmax' "
                         "nor 'sigmoid'" % (score_func,))
    with jax.named_scope("mx:moe"):
        n, h = data.shape
        held = int(experts_held) or int(num_experts)
        k = int(top_k)
        cap = moe_capacity(n * k, held, num_experts)
        with _moe_part("route"):
            logits = jnp.matmul(data.astype(_F32),
                                router_weight.astype(_F32).T)
            sigmoid = score_func == "sigmoid"
            probs = jax.nn.sigmoid(logits) if sigmoid \
                else jax.nn.softmax(logits, axis=-1)
            if use_expert_bias:
                _, top_e = lax.top_k(
                    probs + lax.stop_gradient(rest[0].astype(_F32)), k)
                top_p = jnp.take_along_axis(probs, top_e, axis=-1)
            else:
                top_p, top_e = lax.top_k(probs, k)
            if norm_topk_prob:
                total = jnp.sum(top_p, axis=-1, keepdims=True)
                top_p = top_p / (total + _F32(1e-20) if sigmoid else total)
            if float(route_scale) != 1.0:
                top_p = top_p * _F32(route_scale)
            chosen = lax.stop_gradient(top_e).reshape(-1)
            counts = jnp.zeros((int(num_experts),), _F32).at[chosen].add(1.0)
            local = chosen - int(first_expert)
            mine = (local >= 0) & (local < held)
            group = jnp.where(mine, local, held)   # the others sort last
            order = jnp.argsort(group, stable=True)
            sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
            # whole rounds: what pads the last one lies past every live row
            order = jnp.pad(order, (0, -(n * k) % cap))
        y = _moe_held(k, cap, order, sizes, data, top_p.reshape(-1),
                      gate_weight, up_weight, down_weight)
        return y, lax.stop_gradient(counts)


def _moe_infer_shape(in_shapes, attrs):
    filled = list(in_shapes)
    x = in_shapes[0]
    if x is None:
        return filled, [None, None]
    h, e, i = int(x[-1]), int(attrs["num_experts"]), int(attrs["num_hidden"])
    held = int(attrs.get("experts_held") or 0) or e
    filled[1] = (e, h)
    filled[2] = filled[3] = (held, h, i)
    filled[4] = (held, i, h)
    if attrs.get("use_expert_bias") and len(filled) > 5:
        filled[5] = (e,)
    return filled, [tuple(x), (e,)]


register("moe_experts", _moe_experts, num_outputs=2,
         input_names=("data", "router_weight", "gate_weight", "up_weight",
                      "down_weight", "expert_bias"),
         num_inputs=lambda attrs: 5 + bool(attrs.get("use_expert_bias")),
         infer_shape=_moe_infer_shape,
         params={"num_experts": (pInt, 1), "num_hidden": (pInt, 0),
                 "experts_held": (pInt, 0),
                 "first_expert": (pInt, 0), "top_k": (pInt, 1),
                 "norm_topk_prob": (pBool, True),
                 "score_func": (pStr, "softmax"),
                 "route_scale": (pFloat, 1.0),
                 "use_expert_bias": (pBool, False)})


# -- softmax cross-entropy, one number a sequence ---------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _seq_ce(logits, label, weight, shift):
    return _seq_ce_fwd(logits, label, weight, shift)[0]


def _seq_ce_targets(label, weight, shift):
    """(target ids [.., seq], the float32 weight of each position or None
    where all count alike, the count the sum is divided by): position
    ``i``'s target is ``label[i + shift]``, and the last ``shift`` positions
    have none; ``weight`` [.., seq] (or None) weighs each position."""
    idx = label.astype(jnp.int32)
    n = idx.shape[-1]
    live = None if weight is None else weight.astype(_F32)
    if not shift:
        return idx, live, n
    idx = jnp.concatenate([idx[..., shift:],
                           jnp.zeros_like(idx[..., :shift])], axis=-1)
    keep = (jnp.arange(n) < n - shift).astype(_F32)
    return idx, keep if live is None else live * keep, n - shift


def _seq_ce_fwd(logits, label, weight, shift):
    x = logits.astype(_F32)
    idx, live, n = _seq_ce_targets(label, weight, shift)
    lse = jax.nn.logsumexp(x, axis=-1)
    picked = jnp.take_along_axis(x, idx[..., None], axis=-1)[..., 0]
    loss = jnp.mean(lse - picked, axis=-1) if live is None \
        else jnp.sum((lse - picked) * live, axis=-1) / n
    return loss, (logits, label, weight, lse)


def _no_cotangent(x):
    """The zero cotangent of an input that gets no gradient: zeros of a
    float, numpy's float0 of an integer (never on the device)."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.zeros_like(x)
    # graftlint: disable=GL003 — float0 is numpy's alone: an integer input's
    # cotangent, never on the device
    return np.zeros(x.shape, jax.dtypes.float0)


def _seq_ce_bwd(shift, res, g):
    """softmax minus one-hot, times each position's weight, straight into the
    logits' dtype: no float32 copy of the probabilities is kept between the
    passes.  The labels and the weights get no gradient."""
    logits, label, weight, lse = res
    idx, live, n = _seq_ce_targets(label, weight, shift)
    scale = (g / n).astype(_F32)[..., None, None]
    if live is not None:
        scale = scale * live[..., None]
    p = jnp.exp(logits.astype(_F32) - lse[..., None])
    hot = idx[..., None] == jnp.arange(logits.shape[-1], dtype=jnp.int32)
    d = ((p - hot.astype(_F32)) * scale).astype(logits.dtype)
    return (d, _no_cotangent(label),
            None if weight is None else _no_cotangent(weight))


_seq_ce.defvjp(_seq_ce_fwd, _seq_ce_bwd)


def _sequence_cross_entropy(data, label, *rest, shift=0, use_weight=False):
    """Mean over positions of ``-log softmax(data)[label]`` for ``data``
    [batch, seq, vocab] and integer-valued ``label`` [batch, seq]: float32
    [batch], whatever the logits' dtype.  Under ``shift`` position ``i`` is
    held against ``label[i + shift]`` and the mean is over the ``seq -
    shift`` positions that have such a target (a head that predicts further
    ahead, on the same labels).  ``use_weight`` adds the input ``weight``
    [batch, seq]: each position's term is multiplied by it (0 leaves the
    position out) and the sum still divided by the positions, the weighted
    mean a masked diffusion loss takes; the weight gets no gradient."""
    weight = lax.stop_gradient(rest[0]) if use_weight else None
    return _seq_ce(data, lax.stop_gradient(label), weight, int(shift))


def _seq_ce_infer_shape(in_shapes, attrs):
    filled = list(in_shapes)
    d = in_shapes[0]
    if d is None:
        return filled, [None]
    filled[1:] = [tuple(d[:-1])] * (len(filled) - 1)
    return filled, [(int(d[0]),)]


def _seq_ce_infer_type(in_dtypes, attrs):
    filled = list(in_dtypes)
    if len(filled) > 2 and filled[2] is None:
        filled[2] = np.float32
    return filled, [np.float32]


register("sequence_cross_entropy", _sequence_cross_entropy,
         input_names=("data", "label", "weight"),
         num_inputs=lambda attrs: 2 + bool(attrs.get("use_weight")),
         infer_shape=_seq_ce_infer_shape, infer_type=_seq_ce_infer_type,
         params={"shift": (pInt, 0), "use_weight": (pBool, False)})
