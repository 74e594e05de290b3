"""ShardedModule: the Module API over a jax.sharding.Mesh.

The TPU-first generalization of the reference's manual model parallelism
(`group2ctx` + PlaceDevice, graph_executor.cc:406; the user-facing shape
of it: example/model-parallel/lstm/lstm.py:65): instead of assigning
layers to devices, the user hands the module a *mesh* and (optionally)
per-parameter partition specs; the whole training step compiles to ONE
SPMD program per device with XLA inserting the collectives — gradient
psum over dp, megatron-style activation all-reduce over tp, sequence
shards over sp.

Partition resolution per parameter, first match wins:
  1. ``param_partition={name: PartitionSpec}`` ctor argument,
  2. a ``__shard__`` attr on the variable (``mx.sym.var(name,
     __shard__="tp,None")`` — the mesh analog of the reference's
     ``ctx_group`` attr),
  3. the default rule (parallel/mesh.py shard_params_rule): 2-D and conv
     weights split over tp when divisible, everything else replicated.

Batch inputs shard over dp on dim 0; pass ``sequence_axis=1`` to also
shard that dim over sp (sequence/context parallelism for long inputs).
Pipeline (pp) and expert (ep) axes are served by the stacked-stage and
MoE primitives in mxnet_tpu.parallel (see parallel/pipeline.py — those
need homogeneous stage structure a generic symbol graph doesn't have).

Usage (train_imagenet.py style)::

    mesh = mx.parallel.create_mesh(dp=2, tp=2, devices=jax.devices())
    mod = mx.mod.ShardedModule(sym, mesh=mesh)
    mod.fit(train_iter, num_epoch=..., optimizer='sgd')
"""
from __future__ import annotations

import logging

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError, np_dtype
from ..context import cpu
from ..initializer import Uniform, InitDesc
from ..io import DataDesc
from ..ndarray import NDArray, zeros as nd_zeros
from .. import optimizer as opt
from .. import random as _random
from ..ops import pallas_kernels as _pallas_kernels
from ..optimizer import _is_low_precision
from ..parallel.mesh import create_mesh, shard_params_rule, MeshSpec
from .base_module import BaseModule, _check_input_names
from .module import _parse_data_desc
from .fused_step import _map_state, _map2_state


def _parse_shard_attr(text):
    """'tp,None' / '(dp, tp)' / 'None' -> PartitionSpec."""
    cleaned = text.strip().strip("()")
    parts = []
    for tok in cleaned.split(","):
        tok = tok.strip().strip("'\"")
        if not tok:
            continue
        parts.append(None if tok.lower() in ("none", "") else tok)
    return P(*parts)


def _as_mesh(mesh):
    if mesh is None:
        from ..parallel.mesh import current_mesh
        return current_mesh()
    if isinstance(mesh, Mesh):
        return mesh
    if isinstance(mesh, MeshSpec):
        return create_mesh(mesh)
    if isinstance(mesh, dict):
        return create_mesh(**mesh)
    raise MXNetError("mesh must be a jax Mesh, MeshSpec, or axis dict; "
                     "got %r" % (mesh,))


class ShardedModule(BaseModule):
    """BaseModule over one mesh-sharded XLA program per step."""

    def __init__(self, symbol, mesh=None, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 param_partition=None, sequence_axis=None,
                 fixed_param_names=None):
        super().__init__(logger=logger)
        self._symbol = symbol
        self.mesh = _as_mesh(mesh)
        self._param_partition = dict(param_partition or {})
        self._sequence_axis = sequence_axis
        self._data_names = list(data_names or [])
        self._label_names = list(label_names or [])
        self._fixed_param_names = list(fixed_param_names or [])
        self._output_names = symbol.list_outputs()
        self._aux_names = symbol.list_auxiliary_states()
        inputs = set(self._data_names) | set(self._label_names)
        self._param_names = [a for a in symbol.list_arguments()
                             if a not in inputs
                             and a not in self._fixed_param_names]
        _check_input_names(symbol, self._data_names, "data", True)
        _check_input_names(symbol, self._label_names, "label", False)

        self._reset_bind()

    def _reset_bind(self):
        """Pristine unbound state — everything keyed to one bind's
        shapes/shardings (also used by bind(force_rebind=True) so a
        rebind can never train through stale compiled closures)."""
        self._host_args = None     # name -> cpu NDArray (masters' source)
        self._host_aux = None
        self._optimizer = None
        self._step = None
        self._fwd = None
        self._outputs = []
        self.optimizer_initialized = False
        self.params_initialized = False

    # -- introspection -------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        return self._output_shapes

    # -- binding -------------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        if self.binded and not force_rebind:
            self.logger.warning("Already bound, ignoring bind()")
            return
        if inputs_need_grad or shared_module is not None:
            raise MXNetError("ShardedModule does not support inputs_need_"
                             "grad or shared_module")
        preserved = None
        if self.binded:
            # force_rebind: drop everything compiled against the old
            # shapes/shardings (stale jitted closures would silently
            # train the old program), but carry the trained parameter
            # masters across — param shapes are batch-independent, and
            # the reference Module preserves them too (module.py:196)
            if self.params_initialized:
                preserved = self.get_params()
            self._reset_bind()
        self.for_training = for_training
        self.binded = True

        self._data_shapes, self._label_shapes = _parse_data_desc(
            self._data_names, self._label_names, data_shapes, label_shapes)

        from ..executor import _Program
        self._prog = _Program(self._symbol)
        prog = self._prog

        known = {d.name: tuple(d.shape) for d in self._data_shapes}
        if self._label_shapes:
            known.update((l.name, tuple(l.shape))
                         for l in self._label_shapes)
        arg_shapes, out_shapes, aux_shapes = \
            self._symbol.infer_shape(**known)
        arg_types, _, aux_types = self._symbol.infer_type()
        prog.finalize_shapes(known)
        self._output_shapes = list(zip(self._output_names, out_shapes))

        names = self._symbol.list_arguments()
        self._arg_shape = dict(zip(names, arg_shapes))
        self._arg_type = {n: np_dtype(t or np.float32)
                          for n, t in zip(names, arg_types)}
        self._aux_shape = dict(zip(self._aux_names, aux_shapes))
        self._aux_type = {n: np_dtype(t or np.float32)
                          for n, t in zip(self._aux_names,
                                          aux_types or [None] * len(
                                              self._aux_names))}

        # partition spec per parameter: ctor dict > __shard__ attr > rule
        attr_dict = self._symbol.attr_dict()
        self._pspec = {}
        for n in self._param_names + self._fixed_param_names:
            if n in self._param_partition:
                spec = self._param_partition[n]
                if not isinstance(spec, P):
                    spec = P(*spec) if isinstance(spec, (tuple, list)) \
                        else _parse_shard_attr(str(spec))
            elif "__shard__" in (attr_dict.get(n) or {}):
                spec = _parse_shard_attr(attr_dict[n]["__shard__"])
            else:
                spec = shard_params_rule(
                    self.mesh, n, self._arg_shape[n]).spec
            self._pspec[n] = spec
        self._param_sharding = {
            n: NamedSharding(self.mesh, s) for n, s in self._pspec.items()}
        self._repl = NamedSharding(self.mesh, P())

        def batch_spec(name, shape):
            parts = [("dp",)]
            if self._sequence_axis is not None and \
                    len(shape) > self._sequence_axis:
                while len(parts) < self._sequence_axis:
                    parts.append(None)
                parts.append(("sp",))
            return NamedSharding(self.mesh, P(*parts))

        self._batch_sharding = {
            d.name: batch_spec(d.name, d.shape) for d in self._data_shapes}
        if self._label_shapes:
            self._batch_sharding.update(
                (l.name, batch_spec(l.name, l.shape))
                for l in self._label_shapes)
        self._full_batch = int(self._data_shapes[0].shape[0])
        batch_set = set(self._data_names) | set(self._label_names)
        self._batch_arg_names = [n for n in prog.arg_names
                                 if n in batch_set]

        if preserved is not None:
            # re-upload the carried masters under the NEW shardings
            self.init_params(initializer=None, arg_params=preserved[0],
                             aux_params=preserved[1], force_init=True)

    def _check_divisibility(self):
        """Clear errors beat XLA's at trace time."""
        dp = self.mesh.shape.get("dp", 1)
        if self._full_batch % dp:
            raise MXNetError(
                "batch %d does not divide over dp=%d"
                % (self._full_batch, dp))
        sp = self.mesh.shape.get("sp", 1)
        if self._sequence_axis is not None and sp > 1:
            for d in self._data_shapes:
                if len(d.shape) > self._sequence_axis and \
                        d.shape[self._sequence_axis] % sp:
                    raise MXNetError(
                        "sequence dim %d of %s does not divide over sp=%d"
                        % (d.shape[self._sequence_axis], d.name, sp))

    # -- parameters ----------------------------------------------------------
    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before init_params"
        self._check_divisibility()
        attrs = self._symbol.attr_dict()
        batch_names = set(self._data_names) | set(self._label_names)

        def fill(name, shape, dtype, provided):
            host = nd_zeros(shape, cpu(), dtype=dtype)
            if provided and name in provided:
                provided[name].copyto(host)
            elif provided is not None and not allow_missing:
                raise RuntimeError("%s is not presented" % name)
            elif initializer is not None:
                initializer(InitDesc(name, attrs.get(name, None)), host)
            return host

        self._host_args = {
            n: fill(n, self._arg_shape[n], self._arg_type[n], arg_params)
            for n in self._symbol.list_arguments() if n not in batch_names}
        self._host_aux = {
            n: fill(n, self._aux_shape[n], self._aux_type[n], aux_params)
            for n in self._aux_names}

        # device placement: params by their partition, aux replicated
        self._dev_params = {
            n: jax.device_put(np.asarray(self._host_args[n].asnumpy()),
                              self._param_sharding[n])
            for n in self._param_names}
        self._dev_fixed = {
            n: jax.device_put(np.asarray(self._host_args[n].asnumpy()),
                              self._param_sharding.get(n, self._repl))
            for n in self._fixed_param_names}
        self._dev_aux = {
            n: jax.device_put(np.asarray(self._host_aux[n].asnumpy()),
                              self._repl)
            for n in self._aux_names}
        self.params_initialized = True

    def get_params(self):
        assert self.binded and self.params_initialized
        args = {n: NDArray(jax.device_put(np.asarray(v), cpu().jax_device()))
                for n, v in self._dev_params.items()}
        args.update((n, NDArray(jax.device_put(np.asarray(v),
                                               cpu().jax_device())))
                    for n, v in self._dev_fixed.items())
        auxs = {n: NDArray(jax.device_put(np.asarray(v), cpu().jax_device()))
                for n, v in self._dev_aux.items()}
        return args, auxs

    def init_params_from(self, arg_params, aux_params):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, force_init=True)

    # -- optimizer + step ----------------------------------------------------
    def init_optimizer(self, kvstore=None, optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        """kvstore is accepted for API parity and ignored: gradient
        aggregation is the dp-axis psum XLA inserts inside the step."""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            return
        if isinstance(optimizer, str):
            optimizer_params = dict(optimizer_params)
            optimizer_params.setdefault("rescale_grad",
                                        1.0 / self._full_batch)
            optimizer = opt.create(
                optimizer, sym=self._symbol,
                param_idx2name=dict(enumerate(self._param_names)),
                **optimizer_params)
        if not optimizer._fused_ok():
            raise MXNetError(
                "%s lacks fused_update; ShardedModule needs a fused-capable "
                "optimizer" % type(optimizer).__name__)
        self._optimizer = optimizer

        prog = self._prog
        mesh = self.mesh
        param_names = list(self._param_names)
        fixed_names = list(self._fixed_param_names)
        aux_names = list(prog.aux_names)
        batch_names = self._batch_arg_names

        # f32 masters for half-width params under multi_precision —
        # sharded exactly like their parameter
        mp = bool(getattr(optimizer, "multi_precision", False))
        self._store_dtypes = {n: self._arg_type[n] for n in param_names}
        self._mixed = {n: mp and _is_low_precision(self._arg_type[n])
                       for n in param_names}
        self._masters = {
            n: (jax.device_put(
                np.asarray(self._dev_params[n]).astype(np.float32),
                self._param_sharding[n]) if self._mixed[n]
                else self._dev_params[n])
            for n in param_names}

        def init_state(n):
            st_nd = optimizer.create_state(
                param_names.index(n),
                NDArray(jax.device_put(np.asarray(self._masters[n]),
                                       cpu().jax_device())))
            return _map_state(
                lambda a: jax.device_put(
                    np.asarray(a._h.array if isinstance(a, NDArray) else a),
                    self._param_sharding[n]),
                st_nd)

        self._states = {n: init_state(n) for n in param_names}
        n_extra = int(getattr(optimizer, "fused_n_scalars", 0))
        needs_rng = bool(getattr(optimizer, "fused_needs_rng", False))
        self._n_extra, self._needs_rng = n_extra, needs_rng
        store_dtypes, mixed = self._store_dtypes, self._mixed

        def _step(masters, fixed_vals, batch_vals, states, aux_vals, keys,
                  lrs, wds, extras, opt_key):
            amap = dict(zip(fixed_names, fixed_vals))
            amap.update(zip(batch_names, batch_vals))
            aux_map = dict(zip(aux_names, aux_vals))

            def f(mvals):
                m = dict(amap)
                m.update(
                    (n, v.astype(store_dtypes[n]) if mixed[n] else v)
                    for n, v in zip(param_names, mvals))
                outs, new_aux = prog.evaluate(m, aux_map, keys, True)
                return outs, [new_aux[n] for n in aux_names]

            mvals = [masters[n] for n in param_names]
            # XLA partitions this graph over the mesh by itself, which
            # no Mosaic kernel survives (ops/pallas_kernels.py)
            with _pallas_kernels.trace_scope(partitioned=True):
                (outs, new_aux), vjp_fn = jax.vjp(f, mvals)
                heads = [jnp.ones_like(o) for o in outs]
                zeros_aux = [jnp.zeros_like(a) for a in new_aux]
                (grads,) = vjp_fn((heads, zeros_aux))

            opt_keys = jax.random.split(opt_key, len(param_names)) \
                if needs_rng else [None] * len(param_names)
            new_masters, new_states = {}, {}
            for j, n in enumerate(param_names):
                ex = extras[j] if n_extra else ()
                nw, nst = optimizer.fused_update(
                    masters[n], grads[j], states[n], lrs[j], wds[j], ex,
                    key=opt_keys[j])
                new_masters[n] = nw.astype(masters[n].dtype)
                new_states[n] = _map2_state(
                    lambda a, old: a.astype(old.dtype), nst, states[n])
            return outs, new_masters, new_states, dict(zip(aux_names,
                                                           new_aux))

        param_sh = {n: self._param_sharding[n] for n in param_names}
        state_sh = {n: _map_state(lambda _a, _n=n: self._param_sharding[_n],
                                  self._states[n]) for n in param_names}
        repl = self._repl
        # outs keep XLA's choice (they only feed metrics host-side);
        # params/states/aux must round-trip bit-stable into the next call
        outs_sh = jax.sharding.UNCONSTRAINED \
            if hasattr(jax.sharding, "UNCONSTRAINED") else None
        self._step = jax.jit(
            _step,
            in_shardings=(
                param_sh,
                [self._param_sharding.get(n, repl) for n in fixed_names],
                [self._batch_sharding[n] for n in batch_names],
                state_sh,
                [repl] * len(aux_names),
                (repl,) * len(prog.rng_nodes),
                repl, repl, repl, repl),
            out_shardings=(None, param_sh, state_sh,
                           {n: repl for n in aux_names}))

        self._build_fwd()
        self.optimizer_initialized = True

    def _build_fwd(self):
        """The eval-mode program; optimizer-independent, so forward()
        can build it lazily after a rebind with no optimizer."""
        prog = self._prog
        param_names = list(self._param_names)
        fixed_names = list(self._fixed_param_names)
        aux_names = list(prog.aux_names)
        batch_names = self._batch_arg_names

        def _fwd(params, fixed_vals, batch_vals, aux_vals, keys):
            amap = dict(zip(fixed_names, fixed_vals))
            amap.update(zip(batch_names, batch_vals))
            amap.update(zip(param_names, params))
            aux_map = dict(zip(aux_names, aux_vals))
            with _pallas_kernels.trace_scope(partitioned=True):
                outs, _ = prog.evaluate(amap, aux_map, keys, False)
            return outs

        self._fwd = jax.jit(_fwd)

    def _per_step_scalars(self):
        optimizer = self._optimizer
        lrs, wds, extras = [], [], []
        for i, n in enumerate(self._param_names):
            optimizer._update_count(i)
            lrs.append(optimizer._get_lr(i) * 1.0)
            wds.append(optimizer._get_wd(i) * 1.0)
            extras.append(optimizer.fused_scalars(i))
        ex = np.asarray(extras, np.float32) if self._n_extra \
            else np.zeros((len(lrs), 1), np.float32)
        # host numpy -> explicit mesh placement; an eager jnp.zeros here
        # would allocate on the default backend, which the driver's
        # poisoned-backend gate (tests/test_graft_entry.py) forbids
        okey = np.asarray(_random.next_key()) if self._needs_rng \
            else np.zeros((2,), np.uint32)
        put = lambda a: jax.device_put(np.asarray(a), self._repl)
        return (put(np.asarray(lrs, np.float32)),
                put(np.asarray(wds, np.float32)), put(ex), put(okey))

    def _batch_vals(self, data_batch):
        vals = dict(zip(self._data_names, data_batch.data))
        if self._label_names and data_batch.label:
            vals.update(zip(self._label_names, data_batch.label))
        out = []
        for n in self._batch_arg_names:
            arr = vals[n]._h.array
            want = self._arg_type[n]
            sharding = self._batch_sharding[n]
            if getattr(arr, "sharding", None) == sharding and \
                    arr.dtype == want:
                out.append(arr)  # already resident on the mesh
                continue
            # stage through the host: casting or resharding a foreign
            # committed array eagerly would dispatch through default-
            # backend resolution (poisoned under the driver gate)
            host = np.asarray(arr)
            if host.dtype != want:
                host = host.astype(want)
            out.append(jax.device_put(host, sharding))
        return out

    # -- computation ---------------------------------------------------------
    def forward_backward(self, data_batch):
        assert self.optimizer_initialized, \
            "init_optimizer before training (the step is fused)"
        batch_vals = self._batch_vals(data_batch)
        lrs, wds, extras, opt_key = self._per_step_scalars()
        keys = tuple(_random.next_key()
                     for _ in range(len(self._prog.rng_nodes)))
        fixed_vals = [self._dev_fixed[n] for n in self._fixed_param_names]
        outs, self._masters, self._states, self._dev_aux = self._step(
            self._masters, fixed_vals, batch_vals,
            self._states, [self._dev_aux[n] for n in self._prog.aux_names],
            keys, lrs, wds, extras, opt_key)
        self._dev_params = {
            n: (self._masters[n].astype(self._store_dtypes[n])
                if self._mixed[n] else self._masters[n])
            for n in self._param_names}
        self._outputs = [NDArray(o) for o in outs]

    def update(self):
        pass  # the fused step already applied the optimizer

    def forward(self, data_batch, is_train=None):
        if is_train:
            raise MXNetError(
                "ShardedModule trains through forward_backward (one fused "
                "program); forward(is_train=True) alone has no step to "
                "attach to")
        assert self.binded and self.params_initialized
        if self._fwd is None:
            self._build_fwd()
        batch_vals = self._batch_vals(data_batch)
        keys = tuple(_random.next_key()
                     for _ in range(len(self._prog.rng_nodes)))
        fixed_vals = [self._dev_fixed[n] for n in self._fixed_param_names]
        outs = self._fwd([self._dev_params[n] for n in self._param_names],
                         fixed_vals, batch_vals,
                         [self._dev_aux[n] for n in self._prog.aux_names],
                         keys)
        self._outputs = [NDArray(o) for o in outs]

    def backward(self, out_grads=None):
        raise MXNetError("ShardedModule fuses backward into "
                         "forward_backward")

    def get_outputs(self, merge_multi_context=True):
        return list(self._outputs)

    def get_input_grads(self, merge_multi_context=True):
        raise MXNetError("inputs_need_grad is not supported")

    def update_metric(self, eval_metric, labels):
        eval_metric.update(labels, self._outputs)

    def install_monitor(self, mon):
        raise MXNetError("monitors need per-op values; use Module on one "
                         "device for monitoring")

    def save_checkpoint(self, prefix, epoch):
        from ..model import save_checkpoint
        arg_params, aux_params = self.get_params()
        save_checkpoint(prefix, epoch, self._symbol, arg_params, aux_params)
