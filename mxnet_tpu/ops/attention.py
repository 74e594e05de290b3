"""Native attention operators (the transformer fast path).

TPU-native analog of the reference's attention stack: where MXNet 1.x
composes attention from batch_dot + softmax + batch_dot at the Gluon
layer (incubator-mxnet gluon/model_zoo + contrib attention cells), these
register first-class graph ops so the executor can route the whole
softmax(QK^T)V contraction through the Pallas flash-attention kernel
(ops/pallas_kernels.py) — online-softmax over VMEM-resident tiles, no
S^2 materialization, recompute-based backward.

Two ops:

- ``scaled_dot_product_attention``: pre-split heads, q/k/v as
  [batch, seq, heads, head_dim] (K/V may hold fewer heads: grouped
  queries; v may be narrower or wider than q and k; a part of the key may
  be one that all heads share); causal + padding masks, a causal
  ``window``, and the block-diffusion mask.
- ``multi_head_attention``: fused qkv/out projections around the same
  core — one node carries the full attention block so the kernel flag
  (``MXNET_TPU_PALLAS_ATTN``) swaps the entire fast path at bind time.

Both resolve the kernel family at TRACE time via
``pallas_kernels.attention``; the resolved mode rides
``kernel_signature()`` into the executor-cache key, so the flag obeys
the established contract (enable = one retrace, disable = zero,
off-path bitwise).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..observability import health as _health
from . import pallas_kernels as _pk
from .registry import register, pBool, pFloat, pInt


def _note_logit_bound(q, k, scale, k_shared=None):
    """Health tap: an upper bound on max|logit| for this node, by
    Cauchy-Schwarz — scale * max_row||q|| * max_row||k||.  O(BSHD), so
    it is uniform across kernel modes (the flash path never
    materializes the S^2 logits this would otherwise read), and a
    no-op tracing-wise unless the executor opened a tap frame
    (MXNET_TPU_HEALTH=1)."""
    if not _health.enabled():
        return
    s = scale if scale else 1.0 / float(int(q.shape[-1])) ** 0.5
    qn = jnp.max(jnp.sqrt(jnp.sum(
        jnp.square(q.astype(jnp.float32)), axis=-1)))
    k2 = jnp.sum(jnp.square(k.astype(jnp.float32)), axis=-1)
    if k_shared is not None:
        k2 = k2 + jnp.sum(jnp.square(k_shared.astype(jnp.float32)),
                          axis=-1)[:, :, None]
    kn = jnp.max(jnp.sqrt(k2))
    _health.note_tap(jnp.float32(s) * qn * kn)


def _sdpa(query, key, value, *rest, causal=False, scale=0.0,
          use_lengths=False, window=0, use_shared_key=False,
          block_diffusion=0):
    """``softmax(q k^T * scale + mask) v`` for q [batch, seq, heads,
    d_qk] on k [batch, keys, kv heads, d_qk] and v [batch, keys, kv heads,
    d_v]: the result is [batch, seq, heads, d_v], and ``scale`` 0 means
    ``1 / sqrt(d_qk)``.  ``causal``: key ``j`` is visible to query ``i``
    iff ``j <= i``; ``window`` (needs ``causal``; 0 = none) narrows that to
    ``0 <= i - j < window``, and the kernels neither fetch nor compute what
    lies wholly outside it; ``use_lengths`` adds the (batch,) ``kv_length``
    input, the padding mask; ``use_shared_key`` adds ``key_shared`` [batch,
    keys, d_s], a part of the key that every head shares: ``key`` is then
    ``d_s`` narrower than ``query``, whose last ``d_s`` columns are scored
    against it, and the kernels read it once instead of a copy a head.
    ``block_diffusion`` (0 = none; takes no ``causal``, ``window`` or
    lengths): the sequence is a noisy copy and a clean copy of ``seq / 2``
    positions, in blocks of ``block_diffusion``, under the block-diffusion
    mask (``pallas_kernels._bd_visible``)."""
    kv_lens = rest[0] if use_lengths else None
    k_shared = rest[-1] if use_shared_key else None
    _note_logit_bound(query, key, scale, k_shared)
    window = _pk.checked_window(window, causal, key.shape[1])
    kind = "mx:attn:bd" if block_diffusion else (
        "mx:attn:window" if window else "mx:attn:full")
    with jax.named_scope("mx:attn"), jax.named_scope(kind):
        return _pk.attention(query, key, value, causal=causal,
                             scale=(scale if scale else None),
                             kv_lens=kv_lens, window=window,
                             k_shared=k_shared,
                             block_diffusion=block_diffusion)


def _sdpa_infer_shape(in_shapes, attrs, out_shapes=None):
    """k and v share batch, keys and heads but not their width: the output
    is q's shape with v's last dim, and neither of k and v is healed from
    the other."""
    filled = list(in_shapes)
    q, k, v = filled[0], filled[1], filled[2]
    batch = None
    for s in (q, k, v):
        if s is not None and len(s) == 4 and int(s[0]) != 0:
            batch = int(s[0])
    if attrs.get("use_lengths") and filled[3] is None and batch is not None:
        filled[3] = (batch,)
    if q is None or v is None:
        return filled, [None]
    return filled, [tuple(q[:-1]) + (v[-1],)]


def _sdpa_infer_type(in_dtypes, attrs):
    filled = list(in_dtypes)
    d = next((t for t in filled[:3] if t is not None), None)
    if d is None:
        return filled, None
    for i in range(3):
        if filled[i] is None:
            filled[i] = d
    # kv_length keeps its own dtype (an int/float index vector, never
    # coerced to the activation dtype); the shared key part takes the
    # activations'
    if attrs.get("use_shared_key") and filled[-1] is None:
        filled[-1] = d
    return filled, [d]


register("scaled_dot_product_attention", _sdpa,
         input_names=("query", "key", "value", "kv_length", "key_shared"),
         num_inputs=lambda attrs: 3 + bool(attrs.get("use_lengths"))
         + bool(attrs.get("use_shared_key")),
         infer_shape=_sdpa_infer_shape, bidirectional_infer=True,
         infer_type=_sdpa_infer_type,
         params={"causal": (pBool, False), "scale": (pFloat, 0.0),
                 "use_lengths": (pBool, False), "window": (pInt, 0),
                 "use_shared_key": (pBool, False),
                 "block_diffusion": (pInt, 0)})


def _mha(query, key, value, q_weight, q_bias, k_weight, k_bias, v_weight,
         v_bias, out_weight, out_bias, *rest, num_heads=1, num_hidden=0,
         causal=False, scale=0.0, use_lengths=False):
    b, sq = query.shape[0], query.shape[1]
    sk = key.shape[1]
    h = int(num_heads)
    # MXNet weight convention (num_hidden, in_dim): project via x @ W^T
    q = (jnp.matmul(query, q_weight.T) + q_bias).reshape(b, sq, h, -1)
    k = (jnp.matmul(key, k_weight.T) + k_bias).reshape(b, sk, h, -1)
    v = (jnp.matmul(value, v_weight.T) + v_bias).reshape(b, sk, h, -1)
    kv_lens = rest[0] if use_lengths else None
    _note_logit_bound(q, k, scale)
    with jax.named_scope("mx:attn"):
        o = _pk.attention(q, k, v, causal=causal,
                          scale=(scale if scale else None), kv_lens=kv_lens)
    return jnp.matmul(o.reshape(b, sq, -1), out_weight.T) + out_bias


def _mha_infer_shape(in_shapes, attrs, out_shapes=None):
    heads = int(attrs.get("num_heads", 1))
    units = int(attrs.get("num_hidden", 0))
    filled = list(in_shapes)
    q, k, v = filled[0], filled[1], filled[2]
    # heal query from a known output (backward inference, like FC)
    out = out_shapes[0] if out_shapes else None
    if q is None and out is not None:
        filled[0] = q = tuple(out)
    embed = int(q[-1]) if q is not None and int(q[-1]) != 0 else 0
    if not units:
        units = embed  # default projection width = query embed dim
    if units:
        if units % heads:
            raise ValueError(
                "multi_head_attention: num_hidden %d not divisible by "
                "num_heads %d" % (units, heads))
        ek = int(k[-1]) if k is not None and int(k[-1]) != 0 else embed
        ev = int(v[-1]) if v is not None and int(v[-1]) != 0 else embed
        if embed:
            filled[3] = (units, embed)         # q_weight
            filled[9] = (embed, units)         # out_weight
            filled[10] = (embed,)              # out_bias
        if ek:
            filled[5] = (units, ek)            # k_weight
        if ev:
            filled[7] = (units, ev)            # v_weight
        filled[4] = (units,)                   # q_bias
        filled[6] = (units,)                   # k_bias
        filled[8] = (units,)                   # v_bias
    if attrs.get("use_lengths") and len(filled) > 11 and filled[11] is None \
            and q is not None and int(q[0]) != 0:
        filled[11] = (int(q[0]),)
    if q is None:
        return filled, [None]
    return filled, [tuple(q)]


def _mha_infer_type(in_dtypes, attrs):
    filled = list(in_dtypes)
    d = next((t for t in filled[:11] if t is not None), None)
    if d is None:
        return filled, None
    for i in range(11):
        if filled[i] is None:
            filled[i] = d
    return filled, [d]


register("multi_head_attention", _mha,
         input_names=("query", "key", "value", "query_weight", "query_bias",
                      "key_weight", "key_bias", "value_weight", "value_bias",
                      "out_weight", "out_bias", "kv_length"),
         num_inputs=lambda attrs: 11 + bool(attrs.get("use_lengths")),
         infer_shape=_mha_infer_shape, bidirectional_infer=True,
         infer_type=_mha_infer_type,
         params={"num_heads": (pInt, 1), "num_hidden": (pInt, 0),
                 "causal": (pBool, False), "scale": (pFloat, 0.0),
                 "use_lengths": (pBool, False)})
