"""Module: the symbolic training interface.

API parity with the reference Module contract (python/mxnet/module/
module.py) built around this package's executor design: bind() compiles
the whole symbol into one XLA program per context via
DataParallelExecutorGroup, and init_optimizer() upgrades the step to a
single fused fwd+bwd+update dispatch (module/fused_step.py) whenever the
configuration allows — the reference needed separate engine pushes per
op; here one jitted program per batch is the fast path, with the generic
forward/backward/update methods as the escape hatch.
"""
from __future__ import annotations

import logging
import pickle
import warnings

import numpy as np

from ..context import cpu
from ..observability import health as _health
from ..observability import instrument as _instrument
from ..initializer import Uniform, InitDesc
from ..io import DataDesc
from ..ndarray import zeros as nd_zeros
from .. import optimizer as opt
from ..model import (_create_kvstore, _initialize_kvstore, _update_params,
                     _update_params_on_kvstore, load_checkpoint)
from .base_module import BaseModule, _check_input_names
from .executor_group import DataParallelExecutorGroup


def _normalize_descs(names, shapes, kind, strict):
    """Coerce shape specs to DataDesc and verify they cover ``names``."""
    descs = [d if isinstance(d, DataDesc) else DataDesc(*d)
             for d in (shapes or [])]
    if sorted(names) != sorted(d[0] for d in descs):
        msg = ("%s_shapes %s does not provide exactly the declared "
               "%s_names %s" % (kind, descs, kind, list(names)))
        if strict:
            raise ValueError(msg)
        warnings.warn(msg)
    return descs


def _parse_data_desc(data_names, label_names, data_shapes, label_shapes):
    """Normalize data/label shape specs into DataDesc lists."""
    data = _normalize_descs(data_names, data_shapes, "data", strict=True)
    if label_shapes is None:
        _normalize_descs(label_names, None, "label", strict=False)
        return data, None
    return data, _normalize_descs(label_names, label_shapes, "label",
                                  strict=False)


class Module(BaseModule):
    """BaseModule implementation over a Symbol bound to explicit contexts."""

    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging, context=None,
                 work_load_list=None, fixed_param_names=None,
                 state_names=None, compression_params=None):
        super().__init__(logger=logger)
        self._compression_params = compression_params
        self._symbol = symbol
        if context is None:
            context = cpu()
        self._context = (list(context) if isinstance(context, (list, tuple))
                         else [context])
        self._work_load_list = work_load_list or [1] * len(self._context)
        assert len(self._work_load_list) == len(self._context)

        self._data_names = list(data_names or [])
        self._label_names = list(label_names or [])
        self._state_names = list(state_names or [])
        self._fixed_param_names = list(fixed_param_names or [])
        self._output_names = symbol.list_outputs()
        self._aux_names = symbol.list_auxiliary_states()
        # every argument that is not fed as data/label/state is a parameter
        inputs = set(self._data_names + self._label_names + self._state_names)
        self._param_names = [a for a in symbol.list_arguments()
                             if a not in inputs]

        for group, kind, strict in (
                (self._data_names, "data", True),
                (self._label_names, "label", False),
                (self._state_names, "state", True),
                (self._fixed_param_names, "fixed_param", True)):
            _check_input_names(symbol, group, kind, strict)

        # host-side master copies (the checkpoint representation)
        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False

        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None
        self._grad_req = None

        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None

    # -- checkpointing -------------------------------------------------------
    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params, mod._aux_params = args, auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        self._symbol.save("%s-symbol.json" % prefix)
        param_file = "%s-%04d.params" % (prefix, epoch)
        self.save_params(param_file)
        self.logger.info('Saved checkpoint to "%s"', param_file)
        if save_optimizer_states:
            state_file = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(state_file)
            self.logger.info('Saved optimizer state to "%s"', state_file)

    # -- introspection -------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

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
        return self._exec_group.get_output_shapes()

    # -- binding -------------------------------------------------------------
    def _reset_bind(self):
        self.binded = False
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already bound, ignoring bind()")
            return
        if not for_training:
            assert not inputs_need_grad

        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True
        self._grad_req = grad_req
        self._data_shapes, self._label_shapes = _parse_data_desc(
            self.data_names, self.label_names, data_shapes, label_shapes)

        shared_group = None
        if shared_module is not None:
            assert isinstance(shared_module, Module) and \
                shared_module.binded and shared_module.params_initialized
            shared_group = shared_module._exec_group

        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list,
            self._data_shapes, self._label_shapes, self._param_names,
            for_training, inputs_need_grad, shared_group, logger=self.logger,
            fixed_param_names=self._fixed_param_names, grad_req=grad_req,
            state_names=self._state_names)
        self._total_exec_bytes = 0

        if shared_module is not None:
            # adopt the sharer's masters outright (bucketing reuses them)
            self.params_initialized = True
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
            if shared_module.optimizer_initialized:
                self.borrow_optimizer(shared_module)
        elif self.params_initialized:
            # rebind after load(): push the preloaded masters to devices
            self._exec_group.set_params(self._arg_params, self._aux_params)
        else:
            self._arg_params, self._aux_params = self._allocate_masters()

    def _allocate_masters(self):
        """Fresh zeroed host arrays shaped like the bound device params."""
        args = {name: nd_zeros(replicas[0].shape, dtype=replicas[0].dtype)
                for name, replicas in zip(self._param_names,
                                          self._exec_group.param_arrays)}
        auxs = {name: nd_zeros(replicas[0].shape, dtype=replicas[0].dtype)
                for name, replicas in zip(self._aux_names,
                                          self._exec_group.aux_arrays)}
        return args, auxs

    def reshape(self, data_shapes, label_shapes=None):
        assert self.binded
        self._data_shapes, self._label_shapes = _parse_data_desc(
            self.data_names, self.label_names, data_shapes, label_shapes)
        self._exec_group.reshape(self._data_shapes, self._label_shapes)

    # -- parameters ----------------------------------------------------------
    def get_params(self):
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def _fill_master(self, desc, arr, provided, initializer, allow_missing):
        """Resolve one master array from ``provided`` or the initializer."""
        if provided is None:
            initializer(desc, arr)
            return
        source = provided.get(str(desc))
        if source is not None:
            if source is not arr:
                source.copyto(arr)
        elif not allow_missing:
            raise RuntimeError("%s is not presented" % desc)
        elif initializer is not None:
            initializer(desc, arr)

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        if self.params_initialized and not force_init:
            warnings.warn("Parameters already initialized and "
                          "force_init=False. init_params call ignored.",
                          stacklevel=2)
            return
        assert self.binded, "call bind before initializing the parameters"

        attrs = self._symbol.attr_dict()
        for masters, provided in ((self._arg_params, arg_params),
                                  (self._aux_params, aux_params)):
            for name in sorted(masters):
                desc = InitDesc(name, attrs.get(name, None))
                self._fill_master(desc, masters[name], provided,
                                  initializer, allow_missing)

        self.params_initialized = True
        self._params_dirty = False
        self._exec_group.set_params(self._arg_params, self._aux_params)

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        if not allow_missing:
            self.init_params(initializer=None, arg_params=arg_params,
                             aux_params=aux_params,
                             allow_missing=allow_missing,
                             force_init=force_init, allow_extra=allow_extra)
            return
        if self.params_initialized and not force_init:
            warnings.warn("Parameters already initialized and "
                          "force_init=False. set_params call ignored.",
                          stacklevel=2)
            return
        # partial update: push straight to devices, masters refresh lazily
        self._exec_group.set_params(arg_params, aux_params,
                                    allow_extra=allow_extra)
        self._params_dirty = True
        self.params_initialized = True

    def _sync_params_from_devices(self):
        fs = getattr(self, "_fused_step", None)
        if fs is not None and fs.ran:
            # the fused step's masters ARE the trained state: copy them
            # out bitwise.  The general path's cross-device AVERAGE of
            # replicas rounds (a running sum of 8 identical f32 values
            # passes through 3x/5x/7x, each up to 1 ulp off), which
            # would make a checkpoint differ from the live state —
            # breaking the elastic resume contract that a resumed run
            # replays the uninterrupted one bitwise.
            fs.sync_masters(self._arg_params, self._aux_params)
        else:
            self._exec_group.get_params(self._arg_params, self._aux_params)
        self._params_dirty = False

    # -- optimizer -----------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        if self._params_dirty:
            self._sync_params_from_devices()

        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), self._arg_params)
        # TPU-first: with one process and a non-distributed store there is
        # nothing to synchronize — updating through the host-side store
        # would stage every parameter through CPU each batch.  Update
        # locally on device instead (same math: one optimizer application
        # to the summed gradient).
        if (kvstore is not None and len(self._context) == 1
                and "dist" not in kvstore.type
                and kvstore.num_workers == 1):
            kvstore = None
            update_on_kvstore = False
        batch_size = self._exec_group.batch_size
        if kvstore and "dist" in kvstore.type and "_sync" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        if isinstance(optimizer, str):
            # index→name map lets per-param lr/wd multipliers resolve
            names = self._exec_group.param_names
            if update_on_kvstore:
                idx2name = dict(enumerate(names))
            else:
                ndev = len(self._context)
                idx2name = {i * ndev + k: n
                            for i, n in enumerate(names)
                            for k in range(ndev)}
            optimizer_params = dict(optimizer_params)
            optimizer_params.setdefault("rescale_grad", rescale_grad)
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name,
                                   **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)
            if optimizer.rescale_grad != rescale_grad:
                warnings.warn(
                    "Optimizer created manually outside Module but "
                    "rescale_grad is not normalized to "
                    "1.0/batch_size/num_workers (%s vs. %s). Is this "
                    "intended?" % (optimizer.rescale_grad, rescale_grad),
                    stacklevel=2)

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None

        if kvstore:
            if self._compression_params:
                kvstore.set_gradient_compression(self._compression_params)
            _initialize_kvstore(kvstore=kvstore,
                                param_arrays=self._exec_group.param_arrays,
                                arg_params=self._arg_params,
                                param_names=self._param_names,
                                update_on_kvstore=update_on_kvstore)
        if update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
        else:
            self._updater = opt.get_updater(optimizer)

        self.optimizer_initialized = True

        # one-dispatch-per-batch fused fwd+bwd+update (north star); an
        # unsupported configuration trains on the general path, but a
        # step that fails to trace, lower or compile raises
        from .fused_step import FusedStepUnsupported, FusedTrainStep
        try:
            self._fused_step = FusedTrainStep(self) \
                if FusedTrainStep.supports(self) else None
        except FusedStepUnsupported as e:
            self.logger.warning(
                "fused train step unavailable (%s); using the general "
                "path", e)
            self._fused_step = None
        self._fused_pending = False

        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    def borrow_optimizer(self, shared_module):
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self.optimizer_initialized = True

    # -- computation ---------------------------------------------------------
    def _rebind_for_batch(self, data_batch):
        """Reshape the bound program when a batch arrives with new shapes."""
        incoming = tuple(arr.shape for arr in data_batch.data)
        if incoming == tuple(d.shape for d in self._data_shapes):
            return
        dshapes = getattr(data_batch, "provide_data", None) or [
            DataDesc(d.name, shape, d.dtype, d.layout)
            for d, shape in zip(self._data_shapes, incoming)]
        lshapes = getattr(data_batch, "provide_label", None)
        if not lshapes and getattr(data_batch, "label", None):
            lshapes = [DataDesc(d.name, arr.shape, d.dtype, d.layout)
                       for d, arr in zip(self._label_shapes,
                                         data_batch.label)]
        self.reshape(dshapes, lshapes or None)

    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        self._rebind_for_batch(data_batch)
        self._exec_group.forward(data_batch, is_train)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec_group.backward(out_grads=out_grads)

    def forward_backward(self, data_batch):
        if getattr(self, "_fused_step", None) is not None:
            # the fused program IS forward+backward+update: outputs are
            # available immediately (update_metric may run before update()),
            # and the matching update() call becomes a no-op.  Loops that
            # deviate from the one-fb-one-update contract, or that change
            # batch shapes mid-stream, retire the fused path.
            batch_shapes = tuple(tuple(d.shape) for d in data_batch.data)
            bound_shapes = tuple(tuple(d.shape) for d in self._data_shapes)
            if self._fused_pending or batch_shapes != bound_shapes:
                self.logger.warning(
                    "non-canonical training loop (repeated forward_backward "
                    "or batch shape change); disabling the fused train "
                    "step. Note: any update already applied by a prior "
                    "fused forward_backward stands; momentum carries over "
                    "to the local updater.")
                self._fused_step.transfer_to_updater(self._updater)
                self._fused_step = None
                self._fused_pending = False
            else:
                # host-side span around the one-program dispatch, the
                # parent of the fused:* phases inside run() (outside the
                # jitted body: zero effect on tracing)
                with _instrument.phase("fused_train_step"):
                    self._fused_step.run(data_batch)
                self._fused_pending = True
                self._params_dirty = True
                return
        # general path: ONE fused fwd+bwd program per exec per step
        # (executor_cache fused dispatch) instead of a forward plus a
        # recompute-forward vjp — half the dispatches, no double forward
        assert self.binded and self.params_initialized
        # this dispatch did NOT apply an update: a stale pending flag
        # (fused step retired between its forward_backward and update(),
        # e.g. by install_monitor) must not eat the next update()
        self._fused_pending = False
        self._rebind_for_batch(data_batch)
        self._exec_group.forward_backward(data_batch)
        # aux states advanced on device (BatchNorm moving stats):
        # get_params() must re-sync the masters
        self._params_dirty = True

    def update(self):
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        self._params_dirty = True
        if getattr(self, "_fused_pending", False):
            # the matching fused forward_backward already applied this
            # update — checked before the _fused_step test so the no-op
            # survives the step being retired in between (install_monitor)
            self._fused_pending = False
            return
        if getattr(self, "_fused_step", None) is not None:
            # update() without a fused forward_backward: the caller drives
            # forward/backward explicitly — retire the fused path so there
            # is exactly one optimizer-state store (momentum carried over)
            self.logger.info("explicit forward/backward detected; "
                             "disabling the fused train step")
            self._fused_step.transfer_to_updater(self._updater)
            self._fused_step = None
        if self._update_on_kvstore:
            _update_params_on_kvstore(self._exec_group.param_arrays,
                                      self._exec_group.grad_arrays,
                                      self._kvstore,
                                      self._exec_group.param_names)
        else:
            _update_params(self._exec_group.param_arrays,
                           self._exec_group.grad_arrays,
                           updater=self._updater,
                           num_device=len(self._context),
                           kvstore=self._kvstore,
                           param_names=self._exec_group.param_names)

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return self._exec_group.get_outputs(
            merge_multi_context=merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and \
            self.inputs_need_grad
        return self._exec_group.get_input_grads(
            merge_multi_context=merge_multi_context)

    def update_metric(self, eval_metric, labels):
        self._exec_group.update_metric(eval_metric, labels)

    # -- optimizer state persistence -----------------------------------------
    def save_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if getattr(self, "_fused_step", None) is not None \
                and self._fused_step.ran:
            # self-describing container so load works regardless of which
            # path the restoring process ends up using
            with open(fname, "wb") as fout:
                pickle.dump({"format": "fused_v2",
                             "states": self._fused_step.export_states()},
                            fout)
        elif self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            with open(fname, "wb") as fout:
                fout.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        with open(fname, "rb") as f:
            raw = f.read()
        payload = None
        try:
            obj = pickle.loads(raw)
            # only the explicit format tag identifies fused states — a bare
            # str-keyed dict is ambiguous with kvstore updater states and
            # must fall through to the kvstore/updater restore path
            if isinstance(obj, dict) and obj.get("format") in ("fused_v1",
                                                               "fused_v2"):
                payload = obj["states"]
        except Exception:
            pass
        if payload is not None:
            if getattr(self, "_fused_step", None) is not None:
                self._fused_step.load_states(payload)
            else:
                self.logger.warning(
                    "fused-format optimizer states loaded without a fused "
                    "step; momentum not restored")
            return
        if getattr(self, "_fused_step", None) is not None:
            self.logger.warning(
                "updater-format optimizer states with a fused step active; "
                "disabling the fused step to restore them faithfully")
            self._fused_step = None
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            self._updater.set_states(raw)

    def install_monitor(self, mon):
        assert self.binded
        if getattr(mon, "stats", "tensors") == "health":
            self._install_health_monitor(mon)
            return
        # legacy tensor-tap mode: per-op stats need the uncompiled
        # evaluate pass — the separate-path warning belongs HERE only
        self._exec_group.install_monitor(mon)
        if getattr(self, "_fused_step", None) is not None:
            # the fused one-program step has no per-op tap points — a
            # monitor needs the uncompiled evaluate pass, so retire the
            # fused path (optimizer state carries over to the updater)
            self.logger.warning(
                "monitor installed: leaving the fused train-step path for "
                "the tap-capable separate-dispatch path (per-op stats "
                "require the uncompiled monitor pass; expect slower steps "
                "while the monitor is active)")
            self._fused_step.transfer_to_updater(self._updater)
            self._fused_step = None
            # _fused_pending is left alone: a fused forward_backward that
            # already applied its update must still turn the matching
            # update() into a no-op (update() checks the flag first)

    def _take_health_vector(self):
        """Consume this step's packed health vector: ``(np_vector,
        layout)`` or None when the sentinel is off / nothing was
        dispatched.  ONE tiny device->host transfer per step — the
        whole point of the in-program sentinel (contrast the legacy
        monitor's per-tensor taps)."""
        fs = getattr(self, "_fused_step", None)
        if fs is not None and getattr(fs, "last_health", None) is not None:
            vec = fs.last_health
            fs.last_health = None
            return np.asarray(vec), fs.health_layout
        group = self._exec_group
        if group is None or not group.execs:
            return None
        vecs, layout = [], None
        for exe in group.execs:
            vec = getattr(exe, "_last_health", None)
            if vec is None:
                return None  # health off, or no fused dispatch yet
            vecs.append(np.asarray(vec))
            layout = exe.health_layout
            exe._last_health = None
        if len(vecs) == 1:
            return vecs[0], layout
        return _health.combine(vecs, layout), layout

    def prepare(self, data_batch):
        """Start ``data_batch``, the one the next ``forward_backward``
        will be handed, on its way to the fused step's devices while the
        step in flight runs (``FusedTrainStep.stage``).  The general
        path has nothing to do ahead of its dispatch."""
        fused = getattr(self, "_fused_step", None)
        if fused is not None:
            fused.stage(data_batch)
