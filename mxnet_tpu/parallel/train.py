"""Sharded training step: the whole-step-as-one-XLA-program builder.

Replaces the reference's per-batch choreography (executor_group scatter →
per-device forward/backward → kvstore push/pull → optimizer, SURVEY.md §3.2)
with a single jitted computation: loss + grads + optimizer update, input
batch sharded over dp (and optionally sp), params sharded by rule, gradient
reduction inserted by XLA from the sharding annotations (psum over ICI —
no explicit kvstore traffic on the hot path).

Overlapped collectives (``MXNET_TPU_COMM_BUCKET_MB`` /
``MXNET_TPU_GRAD_COMPRESS``, parallel/comm.py): on a pure data-parallel
mesh (dp > 1, every other axis 1, params replicated) the gradient
computation runs per shard under ``shard_map`` and the reduction becomes
one explicit collective per reverse-order bucket — schedulable against
the still-running backward — optionally 2-bit compressed with the
error-feedback residual carried next to the momentum state.  The
overlap contract assumes ``loss_fn`` returns a MEAN over batch examples
(the standard form; gradients are combined with ``pmean``).  Meshes
with model-parallel axes (tp/pp/ep/sp) or sharded parameters keep the
monolithic GSPMD path — see docs/distributed.md for why overlap cannot
help there.
"""
from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from . import comm as _comm
from jax import shard_map
from .mesh import batch_sharding, replicated_sharding, shard_params_rule

_logger = logging.getLogger("mxnet_tpu")


def _overlap_viable(mesh, param_sharding):
    """None when the bucketed-overlap path applies, else the reason it
    cannot (documented in docs/distributed.md)."""
    sizes = dict(mesh.shape)
    if sizes.get("dp", 1) <= 1:
        return "no data-parallel axis (dp<=1): no gradient collective " \
               "to overlap"
    if any(v > 1 for k, v in sizes.items() if k != "dp"):
        return "model-parallel axes present (%s): gradient flow is not " \
               "a plain dp psum" % ({k: v for k, v in sizes.items()
                                     if k != "dp" and v > 1},)
    if any(tuple(s.spec) != () for s in param_sharding.values()):
        return "sharded parameters: their gradients are not replicated " \
               "dp partial sums"
    return None


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

        # -- overlapped gradient collectives (resolved at construction) --
        self.comm_plan = None
        self.overlap_off_reason = None
        cfg = _comm.comm_config()
        if cfg is not None:
            self.overlap_off_reason = _overlap_viable(mesh, param_sharding)
            if self.overlap_off_reason is not None:
                _logger.warning(
                    "gradient-collective overlap requested but "
                    "unavailable for this step (%s); using the monolithic "
                    "GSPMD reduction", self.overlap_off_reason)
            else:
                # reverse declaration order stands in for reverse
                # autodiff order on an opaque loss_fn: later-declared
                # params sit deeper in the model by convention
                self._grad_order = list(params)
                dp = int(dict(mesh.shape)["dp"])
                self.comm_plan = _comm.CommPlan(
                    [tuple(self.params[n].shape) for n in self._grad_order],
                    [self.params[n].dtype for n in self._grad_order],
                    cfg, scale=1.0 / dp)
        self.residuals = []
        if self.comm_plan is not None and self.comm_plan.compress:
            dp = int(dict(mesh.shape)["dp"])
            res_sh = NamedSharding(mesh, P("dp"))
            self.residuals = [
                jax.device_put(np.zeros((dp,) + s, np.float32), res_sh)
                for s in self.comm_plan.residual_shapes()]
            self._res_sharding = [res_sh] * len(self.residuals)
        else:
            self._res_sharding = []

        plan = self.comm_plan
        grad_order = getattr(self, "_grad_order", None)

        def step(params, mom, residuals, batch):
            if plan is None:
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
                new_residuals = list(residuals)
            else:
                def _shard(params_l, batch_l, res_in):
                    loss, grads = jax.value_and_grad(loss_fn)(params_l,
                                                              batch_l)
                    glist = [grads[k] for k in grad_order]
                    red, new_res = _comm.reduce_buckets(
                        glist, "dp", plan, [r[0] for r in res_in])
                    # plan.scale = 1/dp: psum of per-shard mean-loss
                    # grads == the global mean-loss gradient (the
                    # documented mean-loss contract)
                    return (jax.lax.pmean(loss, "dp"),
                            dict(zip(grad_order, red)),
                            [r[None] for r in new_res])

                batch_specs = jax.tree_util.tree_map(
                    lambda s: s.spec, self.batch_spec,
                    is_leaf=lambda x: isinstance(x, NamedSharding))
                n_res = len(plan.residual_shapes())
                loss, grads, new_residuals = shard_map(
                    _shard, mesh=self.mesh,
                    in_specs=({k: P() for k in params}, batch_specs,
                              [P("dp")] * n_res),
                    out_specs=(P(), {k: P() for k in params},
                               [P("dp")] * n_res),
                    check_vma=False)(params, batch, residuals)
            new_params, new_mom = {}, {}
            for k in params:
                g = grads[k] + wd * params[k]
                m = momentum * mom[k] + g
                new_params[k] = params[k] - lr * m
                new_mom[k] = m
            return new_params, new_mom, new_residuals, loss

        in_shardings = (param_sharding, param_sharding, self._res_sharding,
                        batch_spec)
        out_shardings = (param_sharding, param_sharding, self._res_sharding,
                         replicated_sharding(mesh))
        self._step = jax.jit(
            step, in_shardings=in_shardings, out_shardings=out_shardings,
            donate_argnums=(0, 1, 2) if donate else ())

    def __call__(self, batch):
        batch = jax.device_put(batch, self.batch_spec)
        self.params, self.momentum_buf, self.residuals, loss = self._step(
            self.params, self.momentum_buf, self.residuals, batch)
        if self.comm_plan is not None:
            from ..observability.instrument import note_comm_overlapped
            note_comm_overlapped(self.comm_plan)
        return loss

    def lower(self, batch_struct):
        """Return the lowered (pre-compile) step for inspection/AOT."""
        return self._step.lower(
            {k: jax.ShapeDtypeStruct(p.shape, p.dtype)
             for k, p in self.params.items()},
            {k: jax.ShapeDtypeStruct(p.shape, p.dtype)
             for k, p in self.momentum_buf.items()},
            [jax.ShapeDtypeStruct(r.shape, r.dtype)
             for r in self.residuals],
            batch_struct)
