"""Ring attention: exact attention over sequences sharded across chips.

Long-context support is first-class here (the reference predates attention
entirely; its long-sequence story was BucketingModule + fused cuDNN RNN,
SURVEY.md §5.7).  The sequence axis is sharded over the mesh's `sp` axis;
each chip holds a Q/K/V block.  K/V blocks rotate around the ICI ring with
`lax.ppermute` while each chip accumulates its Q block's attention in
online-softmax (flash) form — memory stays O(seq_local), communication
overlaps with compute, and the result is exact (matches single-chip
attention to float tolerance).

Layout: [batch, seq, heads, head_dim]; inside shard_map seq is the local
shard. Blockwise accumulation follows the standard online-softmax recurrence
(running max m, normalizer l, weighted sum acc).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map


def _block_attn(q, k, v, bias, scale):
    """One q-block x kv-block attention, returning (scores_max, exp_sums,
    weighted_values) for online-softmax accumulation.
    q: [B, Sq, H, D], k/v: [B, Sk, H, D]."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)                        # [B, H, Sq]
    p = jnp.exp(s - m[..., None])                  # [B, H, Sq, Sk]
    l = jnp.sum(p, axis=-1)                        # [B, H, Sq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)        # [B, Sq, H, D]
    return m, l, o


def _ring_attn_local(q, k, v, axis_name, causal, scale):
    """Runs inside shard_map: q/k/v are the local sequence blocks."""
    sp = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    seq_local = q.shape[1]
    neg_inf = jnp.finfo(q.dtype).max * jnp.asarray(-1.0, q.dtype)

    def causal_bias(q_idx, kv_idx):
        # global positions: row = q_idx*seq_local + i, col = kv_idx*seq_local + j
        rows = q_idx * seq_local + jnp.arange(seq_local)
        cols = kv_idx * seq_local + jnp.arange(k.shape[1])
        mask = rows[:, None] >= cols[None, :]
        return jnp.where(mask, 0.0, neg_inf)[None, None]

    def step(carry, _):
        m_acc, l_acc, o_acc, kv_idx, k_cur, v_cur = carry
        bias = causal_bias(idx, kv_idx) if causal else None
        m_blk, l_blk, o_blk = _block_attn(q, k_cur, v_cur, bias, scale)
        m_new = jnp.maximum(m_acc, m_blk)
        alpha = jnp.exp(m_acc - m_new)             # rescale old accumulator
        beta = jnp.exp(m_blk - m_new)              # rescale new block
        l_new = l_acc * alpha + l_blk * beta
        o_new = (o_acc * alpha.transpose(0, 2, 1)[..., None]
                 + o_blk * beta.transpose(0, 2, 1)[..., None])
        # rotate kv around the ring: chip i sends to chip i+1
        perm = [(j, (j + 1) % sp) for j in range(sp)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        kv_nxt = (kv_idx - 1) % sp
        return (m_new, l_new, o_new, kv_nxt, k_nxt, v_nxt), None

    b, _, h, d = q.shape
    m0 = jnp.full((b, h, seq_local), neg_inf, q.dtype)
    l0 = jnp.zeros((b, h, seq_local), q.dtype)
    o0 = jnp.zeros_like(q)
    carry, _ = lax.scan(step, (m0, l0, o0, idx, k, v), None, length=sp)
    _, l_fin, o_fin, _, _, _ = carry
    l_fin = jnp.where(l_fin == 0, 1.0, l_fin)      # fully-masked rows
    return o_fin / l_fin.transpose(0, 2, 1)[..., None]


def ring_attention(q, k, v, mesh=None, axis_name="sp", causal=False,
                   scale=None, batch_axis=None):
    """Exact multi-head attention with the sequence dim sharded over
    `axis_name`.  q/k/v: [batch, seq, heads, head_dim] global arrays.
    batch_axis optionally shards dim 0 (e.g. 'dp') so data parallelism
    composes with the sequence ring.

    Single-device fallback (axis size 1) is plain attention — same code path,
    the ring degenerates to one block.
    """
    from .mesh import current_mesh
    mesh = mesh or current_mesh()
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)

    spec = P(batch_axis, axis_name, None, None)
    fn = shard_map(
        functools.partial(_ring_attn_local, axis_name=axis_name,
                          causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def ring_self_attention(x, wq, wk, wv, wo, num_heads, mesh=None,
                        axis_name="sp", causal=True, batch_axis=None):
    """Fused qkv-projection + ring attention + output projection.
    x: [batch, seq, model_dim]; w*: [model_dim, model_dim]."""
    b, s, dm = x.shape
    dh = dm // num_heads

    def proj(w):
        return jnp.einsum("bsm,mn->bsn", x, w).reshape(b, s, num_heads, dh)

    q, k, v = proj(wq), proj(wk), proj(wv)
    o = ring_attention(q, k, v, mesh=mesh, axis_name=axis_name, causal=causal,
                       batch_axis=batch_axis)
    return jnp.einsum("bsn,nm->bsm", o.reshape(b, s, dm), wo)
