"""DataParallelExecutorGroup (ref: python/mxnet/module/executor_group.py).

Splits each batch across a list of contexts (TPU cores / virtual devices),
binds one whole-graph XLA executor per context, and merges outputs.  Gradient
reduction across the group happens in the KVStore/updater layer exactly like
the reference (§2.5 of SURVEY.md).
"""
from __future__ import annotations

import functools
import logging

import jax
import numpy as np

from ..base import MXNetError
from ..io import DataDesc
from ..ndarray import NDArray, zeros as nd_zeros, array, concatenate
from ..executor import Executor
from ..observability import instrument as _instrument


def _split_input_slice(batch_size, work_load_list):
    """Decide batch slices per device (ref: executor_group.py:266
    decide_slices / mxnet.executor_manager._split_input_slice)."""
    total_work_load = sum(work_load_list)
    batch_num_list = [round(work_load * batch_size / total_work_load)
                      for work_load in work_load_list]
    batch_num_sum = sum(batch_num_list)
    if batch_num_sum != batch_size:
        batch_num_list[-1] += batch_size - batch_num_sum
    slices = []
    end = 0
    for batch_num in batch_num_list:
        begin = int(min(end, batch_size))
        end = int(min(begin + batch_num, batch_size))
        if begin >= end:
            raise ValueError("Too many slices. Some splits are empty.")
        slices.append(slice(begin, end))
    return slices


def _load_general(data, targets):
    for d_src, d_targets in zip(data, targets):
        if isinstance(d_targets, NDArray):
            d_src.copyto(d_targets)
        else:
            for slice_idx, d_dst in d_targets:
                d_src[slice_idx.start:slice_idx.stop].copyto(d_dst)


def _load_data(batch, targets):
    _load_general(batch.data, targets)


def _load_label(batch, targets):
    _load_general(batch.label, targets)


def _merge_multi_context(outputs, major_axis):
    """Concat per-device outputs along the batch axis."""
    rets = []
    for tensors, axis in zip(outputs, major_axis):
        if axis >= 0 and len(tensors) > 1:
            rets.append(concatenate(tensors, axis=axis))
        else:
            rets.append(tensors[0])
    return rets


class DataParallelExecutorGroup:
    def __init__(self, symbol, contexts, workload, data_shapes, label_shapes,
                 param_names, for_training, inputs_need_grad,
                 shared_group=None, logger=logging, fixed_param_names=None,
                 grad_req="write", state_names=None):
        self.param_names = param_names
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.symbol = symbol
        self._counter_notes = None
        self.contexts = contexts
        self.workload = workload or [1] * len(contexts)
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.logger = logger
        self.fixed_param_names = fixed_param_names or []
        self.state_names = state_names or []
        if not for_training:
            grad_req = "null"
        data_names = [x.name if isinstance(x, DataDesc) else x[0]
                      for x in data_shapes]
        if isinstance(grad_req, str):
            self.grad_req = {}
            for k in self.arg_names:
                if k in self.param_names:
                    self.grad_req[k] = "null" if k in self.fixed_param_names \
                        else grad_req
                elif k in data_names:
                    self.grad_req[k] = grad_req if inputs_need_grad else "null"
                else:
                    self.grad_req[k] = "null"
        elif isinstance(grad_req, (list, tuple)):
            self.grad_req = dict(zip(self.arg_names, grad_req))
        elif isinstance(grad_req, dict):
            self.grad_req = {k: "null" for k in self.arg_names}
            self.grad_req.update(grad_req)
        else:
            raise ValueError("invalid grad_req")
        self.execs = []
        self.data_shapes = None
        self.label_shapes = None
        self.data_layouts = None
        self.label_layouts = None
        self.output_layouts = [
            DataDesc.get_batch_axis(self.symbol[i].attr("__layout__"))
            for i in range(len(self.symbol.list_outputs()))]
        self.bind_exec(data_shapes, label_shapes, shared_group)

    def decide_slices(self, data_shapes):
        """(ref: executor_group.py:266)"""
        assert len(data_shapes) > 0
        major_axis = [DataDesc.get_batch_axis(x.layout
                                              if isinstance(x, DataDesc) else "NCHW")
                      for x in data_shapes]
        for (name, shape), axis in zip(data_shapes, major_axis):
            if axis == -1:
                continue
            batch_size = shape[axis]
            if self.batch_size is not None:
                assert batch_size == self.batch_size, \
                    ("all data must have the same batch size: batch_size = %d,"
                     " but %s has shape %s" % (self.batch_size, name, shape))
            else:
                self.batch_size = batch_size
                self.slices = _split_input_slice(self.batch_size, self.workload)
        return major_axis

    def bind_exec(self, data_shapes, label_shapes, shared_group=None,
                  reshape=False):
        self.batch_size = None
        self.data_layouts = self.decide_slices(data_shapes)
        if label_shapes is not None:
            self.label_layouts = self.decide_slices(label_shapes)
        # a reshape rebind shares the old executors' parameter/aux
        # buffers (values survive; only data/label reallocate) — the
        # same sharing path bucketing uses, with the retiring execs as
        # the sharers (ref: graph_executor's shared memory pools)
        old_execs = list(self.execs) if reshape and shared_group is None \
            else []
        self.execs = []
        for i in range(len(self.contexts)):
            shared_exec = None
            if shared_group is not None:
                shared_exec = shared_group.execs[i]
            elif i < len(old_execs):
                shared_exec = old_execs[i]
            self.execs.append(self._bind_ith_exec(i, data_shapes, label_shapes,
                                                  shared_exec))
        self.data_shapes = data_shapes
        self.label_shapes = label_shapes
        self.data_names = [i.name if isinstance(i, DataDesc) else i[0]
                           for i in self.data_shapes]
        if label_shapes is not None:
            self.label_names = [i.name if isinstance(i, DataDesc) else i[0]
                                for i in self.label_shapes]
        self._collect_arrays()

    def reshape(self, data_shapes, label_shapes):
        if data_shapes == self.data_shapes and label_shapes == self.label_shapes:
            return
        self.bind_exec(data_shapes, label_shapes, reshape=True)

    def _sliced_shape(self, shapes, i, major_axis):
        sliced = []
        for (desc, axis) in zip(shapes, major_axis):
            name = desc.name if isinstance(desc, DataDesc) else desc[0]
            shape = list(desc.shape if isinstance(desc, DataDesc) else desc[1])
            if axis >= 0:
                shape[axis] = self.slices[i].stop - self.slices[i].start
            sliced.append(DataDesc(name, tuple(shape),
                                   getattr(desc, "dtype", np.float32)))
        return sliced

    def _bind_ith_exec(self, i, data_shapes, label_shapes, shared_exec):
        data_shapes_i = self._sliced_shape(data_shapes, i, self.data_layouts)
        if label_shapes is not None:
            label_shapes_i = self._sliced_shape(label_shapes, i,
                                                self.label_layouts)
        else:
            label_shapes_i = []
        ctx = self.contexts[i]
        shape_kwargs = {x.name: x.shape for x in data_shapes_i + label_shapes_i}
        type_kwargs = {x.name: x.dtype for x in data_shapes_i + label_shapes_i}
        if shared_exec is not None:
            # share parameter arrays with the shared executor (bucketing,
            # and the same-group reshape rebind)
            arg_shapes, _, aux_shapes = self.symbol.infer_shape(**shape_kwargs)
            arg_dict, grad_dict = {}, {}
            for name, shape in zip(self.arg_names, arg_shapes):
                if name in self.param_names \
                        and name in shared_exec.arg_dict:
                    cur = shared_exec.arg_dict[name]
                    if tuple(cur.shape) == tuple(shape):
                        arg_dict[name] = cur
                        if name in shared_exec.grad_dict and \
                                shared_exec.grad_dict[name] is not None:
                            grad_dict[name] = shared_exec.grad_dict[name]
                        continue
                    # a parameter whose shape changed cannot share its
                    # buffer; its learned values are discarded — loud,
                    # because that usually means a mis-specified bucket
                    self.logger.warning(
                        "parameter %r changed shape %s -> %s across the "
                        "shared bind; reallocating it ZEROED (its values "
                        "cannot carry over)", name, tuple(cur.shape),
                        tuple(shape))
                arg_dict[name] = nd_zeros(shape, ctx,
                                          dtype=type_kwargs.get(name, np.float32))
                if self.grad_req.get(name, "null") != "null":
                    grad_dict[name] = nd_zeros(shape, ctx)
            # aux states share only when the inferred shape still fits
            # (shape-dependent aux reallocates, mirroring the arg path)
            aux_dict = {}
            for name, shape in zip(self.aux_names, aux_shapes):
                cur = shared_exec.aux_dict.get(name)
                if cur is not None and tuple(cur.shape) == tuple(shape):
                    aux_dict[name] = cur
                else:
                    if cur is not None:
                        self.logger.warning(
                            "auxiliary state %r changed shape %s -> %s "
                            "across the shared bind; reallocating it "
                            "ZEROED", name, tuple(cur.shape), tuple(shape))
                    aux_dict[name] = nd_zeros(
                        shape, ctx,
                        dtype=cur.dtype if cur is not None else np.float32)
            return Executor(self.symbol, ctx, arg_dict, grad_dict, aux_dict,
                            self.grad_req)
        return self.symbol.simple_bind(ctx=ctx, grad_req=self.grad_req,
                                       type_dict=type_kwargs, **shape_kwargs)

    def _collect_arrays(self):
        self.data_arrays = [
            [(self.slices[i], e.arg_dict[name]) for i, e in enumerate(self.execs)]
            for name in self.data_names]
        if self.label_shapes is not None:
            self.label_arrays = [
                [(self.slices[i], e.arg_dict[name])
                 for i, e in enumerate(self.execs) if name in e.arg_dict]
                for name in self.label_names]
        else:
            self.label_arrays = None
        self.param_arrays = [
            [e.arg_dict[name] for e in self.execs]
            for name in self.param_names if name in self.arg_names]
        if self.for_training:
            self.grad_arrays = [
                [e.grad_dict[name] for e in self.execs
                 if e.grad_dict.get(name) is not None]
                for name in self.param_names
                if self.grad_req.get(name, "null") != "null"]
            self.grad_arrays = [g for g in self.grad_arrays if g]
        else:
            self.grad_arrays = []
        self.aux_arrays = [
            [e.aux_dict[name] for e in self.execs]
            for name in self.aux_names]
        if self.inputs_need_grad:
            self.input_grad_arrays = [
                [e.grad_dict[name] for e in self.execs
                 if e.grad_dict.get(name) is not None]
                for name in self.data_names]
        else:
            self.input_grad_arrays = []

    def set_params(self, arg_params, aux_params, allow_extra=False):
        for exc in self.execs:
            exc.copy_params_from(arg_params, aux_params,
                                 allow_extra_params=allow_extra)

    def get_params(self, arg_params, aux_params):
        """Average params across devices into the given dicts."""
        for name, block in zip(self.param_names, self.param_arrays):
            weight = sum(w.copyto(block[0].context) for w in block) / len(block)
            weight.astype(arg_params[name].dtype).copyto(arg_params[name])
        for name, block in zip(self.aux_names, self.aux_arrays):
            weight = sum(w.copyto(block[0].context) for w in block) / len(block)
            weight.astype(aux_params[name].dtype).copyto(aux_params[name])

    def forward(self, data_batch, is_train=None):
        _load_data(data_batch, self.data_arrays)
        if is_train is None:
            is_train = self.for_training
        if self.label_arrays is not None and data_batch.label:
            _load_label(data_batch, self.label_arrays)
        for e in self.execs:
            e.forward(is_train=is_train)

    def forward_backward(self, data_batch):
        """One fused fwd+bwd XLA dispatch per exec (outputs, gradients
        and aux updates from a single jitted program) — the general
        training step of the north-star dispatch model."""
        assert self.for_training, \
            "re-bind with for_training=True to run backward"
        from .. import profiler as _profiler
        # batch upload + per-exec dispatch under one nested span (the
        # per-exec executor_fwd_bwd spans become its children); the span
        # is a no-op flag check while the profiler is stopped
        with _profiler.record_span("exec_group_fwd_bwd",
                                   category="symbolic"):
            _load_data(data_batch, self.data_arrays)
            if self.label_arrays is not None and data_batch.label:
                _load_label(data_batch, self.label_arrays)
            for e in self.execs:
                e.forward_backward(is_train=True)

    def get_output_shapes(self):
        outputs = self.execs[0].outputs
        if outputs:
            shapes = [out.shape for out in outputs]
        else:
            # before the first forward (SequentialModule binds stage i+1
            # off stage i's output shapes): infer from the bound inputs
            known = {d[0]: tuple(d[1] if not hasattr(d, "shape")
                                 else d.shape) for d in self.data_shapes}
            if self.label_shapes:
                known.update((l[0], tuple(l[1] if not hasattr(l, "shape")
                                          else l.shape))
                             for l in self.label_shapes)
            _, shapes, _ = self.symbol.infer_shape(**known)
        concat_shapes = []
        for key, the_shape, axis in zip(self.symbol.list_outputs(), shapes,
                                        self.output_layouts):
            the_shape = list(the_shape)
            if axis >= 0:
                the_shape[axis] = self.batch_size
            concat_shapes.append((key, tuple(the_shape)))
        return concat_shapes

    def get_outputs(self, merge_multi_context=True):
        outputs = [[exc.outputs[i] for exc in self.execs]
                   for i in range(len(self.execs[0].outputs))]
        if merge_multi_context:
            return _merge_multi_context(outputs, self.output_layouts)
        return outputs

    def get_input_grads(self, merge_multi_context=True):
        assert self.inputs_need_grad
        if merge_multi_context:
            return _merge_multi_context(self.input_grad_arrays,
                                        self.data_layouts)
        return self.input_grad_arrays

    def backward(self, out_grads=None):
        assert self.for_training, "re-bind with for_training=True to run backward"
        if out_grads is None:
            out_grads = []
        elif isinstance(out_grads, NDArray):
            out_grads = [out_grads]
        for i, exc in enumerate(self.execs):
            out_grads_slice = []
            for grad, axis in zip(out_grads, self.output_layouts):
                if axis >= 0:
                    og_my_slice = grad[self.slices[i].start:self.slices[i].stop] \
                        if axis == 0 else grad
                    out_grads_slice.append(og_my_slice.as_in_context(
                        self.contexts[i]))
                else:
                    out_grads_slice.append(grad.copyto(self.contexts[i]))
            exc.backward(out_grads=out_grads_slice if out_grads_slice else None)

    def _counter_outputs(self):
        """{output index: what takes its value} of the outputs a model marks
        for the counters and not for the metric: its routing counts
        (``__moe_counts__`` = first expert, experts held, on the head node)
        and rows that name their own counters (``__counters__``)."""
        if self._counter_notes is None:
            self._counter_notes = {}
            for i, (node, _) in enumerate(self.symbol._entries):
                if "__moe_counts__" in node.attrs:
                    first, held = (int(v) for v in
                                   node.attrs["__moe_counts__"].split(","))
                    self._counter_notes[i] = functools.partial(
                        _instrument.note_moe_counts, first_expert=first,
                        experts_held=held)
                elif "__counters__" in node.attrs:
                    self._counter_notes[i] = functools.partial(
                        _instrument.note_counter_rows,
                        names=node.attrs["__counters__"].split(","))
        return self._counter_notes

    def update_metric(self, eval_metric, labels):
        counts = self._counter_outputs()
        for texec, islice in zip(self.execs, self.slices):
            labels_slice = []
            for label, axis in zip(labels, self.label_layouts or [0] * len(labels)):
                if axis == 0:
                    label_my_slice = label[islice.start:islice.stop]
                    labels_slice.append(label_my_slice)
                elif axis > 0:
                    labels_slice.append(label)
                else:
                    labels_slice.append(label)
            if not counts:
                eval_metric.update(labels_slice, texec.outputs)
                continue
            eval_metric.update(labels_slice, [
                o for i, o in enumerate(texec.outputs) if i not in counts])
            # one transfer for all of them, once the loss is on the host
            fetched = jax.device_get(
                [texec.outputs[i]._h.array for i in counts])
            for note, value in zip(counts.values(), fetched):
                note(value)

    def install_monitor(self, mon):
        for exe in self.execs:
            mon.install(exe)
