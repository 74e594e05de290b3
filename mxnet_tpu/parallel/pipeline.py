"""Pipeline parallelism: GPipe-style microbatching over the `pp` mesh axis.

The reference's closest capability is manual per-layer ctx_group placement
(SURVEY.md §2.5 item 3: PlaceDevice + _CrossDeviceCopy); here the schedule is
explicit and compiled: every stage holds its layer stack shard, microbatch
activations flow stage-to-stage with `lax.ppermute` inside one `lax.scan` —
one XLA computation, ICI transfers overlapped by XLA's scheduler.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map


def _pipeline_local(stage_params, x_micro, stage_fn, axis_name):
    """Inside shard_map.  stage_params: this stage's params (pytree, leading
    layer dim already sharded away); x_micro: [n_micro_local, mb, ...] this
    chip's microbatch stream — when the caller runs data parallelism over
    the leading dim, n_micro_local is the per-replica share, not the
    caller's n_micro.  Returns [n_micro_local, mb, ...] outputs."""
    pp = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    n_micro = x_micro.shape[0]
    total_steps = n_micro + pp - 1
    perm = [(j, (j + 1) % pp) for j in range(pp)]

    def step(carry, t):
        state, outputs = carry
        # stage 0 injects microbatch t; later stages take the incoming state
        mb_in = jnp.clip(t, 0, n_micro - 1)
        x_in = jnp.where(idx == 0, x_micro[mb_in], state)
        y = stage_fn(stage_params, x_in)
        # the last stage completes microbatch t-(pp-1) at step t
        out_mb = t - (pp - 1)
        oc = jnp.clip(out_mb, 0, n_micro - 1)
        write = (idx == pp - 1) & (out_mb >= 0)
        outputs = outputs.at[oc].set(jnp.where(write, y, outputs[oc]))
        state_next = lax.ppermute(y, axis_name, perm)
        return (state_next, outputs), None

    state0 = jnp.zeros_like(x_micro[0])
    out0 = jnp.zeros_like(x_micro)
    (_, outputs), _ = lax.scan(step, (state0, out0),
                               jnp.arange(total_steps))
    # only the last stage holds real outputs; broadcast them to all stages
    outputs = lax.psum(jnp.where(idx == pp - 1, outputs, 0.0), axis_name)
    return outputs


def pipeline_stages(stage_params, x, stage_fn, n_micro, mesh=None,
                    axis_name="pp", params_spec=None, batch_axis=None,
                    tail_spec=None):
    """Run x through pp pipeline stages.

    stage_params: pytree whose leaves have a leading `n_stages` dim, sharded
    over `axis_name` (each chip gets its stage's slice).
    x: [batch, ...] input; split into n_micro microbatches.
    stage_fn(params_slice, x_mb) -> y_mb, same shape as x_mb.
    tail_spec: PartitionSpec entries for x's trailing (non-batch) dims —
    pass the sharding those dims already carry (e.g. ("sp", None) for
    [b, seq, d] with sequence parallelism) so the shard_map boundary does
    not force a reshard.
    """
    from .mesh import current_mesh
    mesh = mesh or current_mesh()
    b = x.shape[0]
    assert b % n_micro == 0, "batch %d not divisible by n_micro %d" % (
        b, n_micro)
    x_micro = x.reshape((n_micro, b // n_micro) + x.shape[1:])

    if params_spec is None:
        params_spec = jax.tree_util.tree_map(
            lambda _: P(axis_name), stage_params)
    tail = tuple(tail_spec) if tail_spec else (None,) * (x.ndim - 1)

    # the [b] -> [n_micro, mb] reshape lands the batch sharding on the
    # LEADING (microbatch-count) dim; keeping dp there makes the shard_map
    # boundary match the surrounding layout (no SPMD full-remat copy), but
    # shrinks each replica's stream to n_micro/dp — at pp>1 that inflates
    # the pipeline bubble (pp-1)/(n_local+pp-1).  Heuristic: take the
    # aligned layout when there is no bubble to inflate (pp==1) or each
    # replica still pipelines >=2 microbatches; callers who want it at
    # deeper pipelines should raise n_micro (e.g. 2*dp).
    dp_size = mesh.shape.get(batch_axis, 1) if batch_axis else 1
    pp_size = mesh.shape.get(axis_name, 1)
    if (batch_axis and n_micro % dp_size == 0
            and (pp_size == 1 or n_micro // dp_size >= 2)):
        x_spec = P(batch_axis, None, *tail)
    else:
        x_spec = P(None, batch_axis, *tail)

    def local(params, xm):
        # shard_map hands each chip params with the stage dim = 1; drop it
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        return _pipeline_local(params, xm, stage_fn, axis_name)

    fn = shard_map(local, mesh=mesh,
                   in_specs=(params_spec, x_spec),
                   out_specs=x_spec,
                   check_vma=False)
    y_micro = fn(stage_params, x_micro)
    return y_micro.reshape((b,) + y_micro.shape[2:])
