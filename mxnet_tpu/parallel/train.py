"""Sharded training step: the whole-step-as-one-XLA-program builder.

Replaces the reference's per-batch choreography (executor_group scatter →
per-device forward/backward → kvstore push/pull → optimizer, SURVEY.md §3.2)
with a single jitted computation: loss + grads + optimizer update, input
batch sharded over dp (and optionally sp), params sharded by rule, gradient
reduction inserted by XLA from the sharding annotations (psum over ICI —
no explicit kvstore traffic on the hot path).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import batch_sharding, replicated_sharding, shard_params_rule


class ShardedTrainStep:
    """Compile loss_fn(params, batch) into a sharded SGD-momentum step.

    params: dict name -> jax.Array.  The optimizer state (momentum) shards
    identically to its parameter — the analog of update_on_kvstore's
    server-side state, but sharded instead of centralized (SURVEY.md §5.8).
    """

    def __init__(self, loss_fn, params, mesh, lr=0.01, momentum=0.9, wd=0.0,
                 param_sharding=None, batch_spec=None, donate=True,
                 remat=False):
        self.mesh = mesh
        if param_sharding is None:
            param_sharding = {
                name: shard_params_rule(mesh, name, p.shape)
                for name, p in params.items()}
        self.param_sharding = param_sharding
        if batch_spec is None:
            batch_spec = NamedSharding(mesh, P("dp"))
        self.batch_spec = batch_spec
        self.params = {
            name: jax.device_put(p, param_sharding[name])
            for name, p in params.items()}
        # Build momentum zeros from host numpy, not jnp.zeros_like: an eager
        # jnp call would allocate on the *default* backend (which may not be
        # the mesh's backend, or may not even be usable) before re-placement.
        self.momentum_buf = {
            name: jax.device_put(np.zeros(p.shape, p.dtype),
                                 param_sharding[name])
            for name, p in self.params.items()}
        if remat:
            loss_fn = jax.checkpoint(loss_fn)

        def step(params, mom, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            new_params, new_mom = {}, {}
            for k in params:
                g = grads[k] + wd * params[k]
                m = momentum * mom[k] + g
                new_params[k] = params[k] - lr * m
                new_mom[k] = m
            return new_params, new_mom, loss

        in_shardings = (param_sharding, param_sharding, batch_spec)
        out_shardings = (param_sharding, param_sharding,
                         replicated_sharding(mesh))
        self._step = jax.jit(
            step, in_shardings=in_shardings, out_shardings=out_shardings,
            donate_argnums=(0, 1) if donate else ())

    def __call__(self, batch):
        batch = jax.device_put(batch, self.batch_spec)
        self.params, self.momentum_buf, loss = self._step(
            self.params, self.momentum_buf, batch)
        return loss

    def lower(self, batch_struct):
        """Return the lowered (pre-compile) step for inspection/AOT."""
        return self._step.lower(
            {k: jax.ShapeDtypeStruct(p.shape, p.dtype)
             for k, p in self.params.items()},
            {k: jax.ShapeDtypeStruct(p.shape, p.dtype)
             for k, p in self.momentum_buf.items()},
            batch_struct)
