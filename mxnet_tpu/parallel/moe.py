"""Expert parallelism: mixture-of-experts FFN with all_to_all dispatch.

Not present in the reference (SURVEY.md §2.5 item 5 confirms the absence);
included because expert parallelism is a first-class mesh axis here.  Experts
are sharded over `ep`; tokens route to their top-1 expert via all_to_all over
the ICI, the expert FFN runs as one batched matmul per chip (MXU-friendly),
and results route back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map


def _moe_local(x, gate_w, w1, w2, axis_name, capacity_factor):
    """Inside shard_map: x [tokens_local, d], experts sharded on dim 0 of
    w1 [e_local, d, hidden], w2 [e_local, hidden, d]."""
    ep = lax.psum(1, axis_name)
    e_local = w1.shape[0]
    n_exp = ep * e_local
    t_local, d = x.shape
    cap = max(1, int(capacity_factor * t_local // n_exp))

    # top-1 gating
    logits = x @ gate_w                               # [t, n_exp]
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)           # [t]
    gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=-1)[:, 0]

    # position of each token within its expert's capacity buffer
    onehot = jax.nn.one_hot(expert_idx, n_exp, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) * onehot        # 1-based slot per token
    slot = jnp.sum(pos, axis=-1) - 1                  # [t]
    keep = slot < cap                                  # overflow tokens drop

    # scatter tokens into [n_exp, cap, d] dispatch buffer
    buf = jnp.zeros((n_exp, cap, d), x.dtype)
    tok_target = jnp.where(keep, expert_idx, 0)
    slot_c = jnp.clip(slot, 0, cap - 1)
    buf = buf.at[tok_target, slot_c].add(
        jnp.where(keep[:, None], x, 0.0))

    # all_to_all: exchange so each chip holds its local experts' buffers
    # from every source chip: [ep(target), e_local, cap, d] ->
    # [ep(source), e_local, cap, d] -> [e_local, ep*cap, d]
    buf = buf.reshape(ep, e_local, cap, d)
    buf = lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0)
    buf = buf.transpose(1, 0, 2, 3).reshape(e_local, ep * cap, d)

    # expert FFN: batched matmul over local experts
    h = jax.nn.relu(jnp.einsum("ecd,edh->ech", buf, w1))
    y = jnp.einsum("ech,ehd->ecd", h, w2)

    # route back: inverse exchange
    y = y.reshape(e_local, ep, cap, d).transpose(1, 0, 2, 3)
    y = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0)
    y = y.reshape(n_exp, cap, d)

    out = y[tok_target, slot_c] * keep[:, None] * gate[:, None]
    return out.astype(x.dtype)


def moe_ffn(x, gate_w, w1, w2, mesh=None, axis_name="ep",
            capacity_factor=1.25, batch_axis=None):
    """MoE FFN over a token batch.

    x: [tokens, d] (or [b, s, d], flattened internally); batch_axis
    optionally shards the token dim (e.g. 'dp');
    gate_w: [d, n_experts] replicated; w1: [n_experts, d, hidden] and
    w2: [n_experts, hidden, d], sharded over experts (dim 0) on `ep`.
    """
    from .mesh import current_mesh
    mesh = mesh or current_mesh()
    orig_shape = x.shape
    if x.ndim == 3:
        x = x.reshape(-1, x.shape[-1])
    fn = shard_map(
        functools.partial(_moe_local, axis_name=axis_name,
                          capacity_factor=capacity_factor),
        mesh=mesh,
        in_specs=(P(batch_axis), P(), P(axis_name), P(axis_name)),
        out_specs=P(batch_axis), check_vma=False)
    out = fn(x, gate_w, w1, w2)
    return out.reshape(orig_shape)


class MoELayer:
    """Parameter container for moe_ffn (gluon-free; used by parallel tests
    and the multichip dry-run)."""

    def __init__(self, n_experts, d_model, d_hidden, key, dtype=jnp.float32):
        k1, k2, k3 = jax.random.split(key, 3)
        s1 = (2.0 / d_model) ** 0.5
        self.gate_w = jax.random.normal(k1, (d_model, n_experts), dtype) * s1
        self.w1 = jax.random.normal(k2, (n_experts, d_model, d_hidden),
                                    dtype) * s1
        self.w2 = jax.random.normal(k3, (n_experts, d_hidden, d_model),
                                    dtype) * (2.0 / d_hidden) ** 0.5

    def __call__(self, x, mesh=None):
        return moe_ffn(x, self.gate_w, self.w1, self.w2, mesh=mesh)
