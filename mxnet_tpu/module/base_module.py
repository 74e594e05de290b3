"""BaseModule: the high-level train/score/predict interface.

API parity with the reference module contract (python/mxnet/module/
base_module.py) with this package's own training-loop construction: the
epoch loop fetches the NEXT batch mid-step (one-batch *lookahead*) so
its host→device transfer (``prepare``) overlaps the current step — the
same latency-hiding job the reference's ``next_data_batch`` juggling
does — and decomposes each step into instrumented components
(observability.instrument.StepTracker).  Subclasses provide
bind/forward/backward/update; Module's fused path collapses those into
one jitted XLA program per step.
"""
from __future__ import annotations

import logging
import math
import time

from .. import metric as metric_mod
from ..context import cpu
from ..initializer import Uniform
from ..io import DataIter
from ..log import module_logger as _module_logger
from ..observability import flight_recorder as _flight
from ..observability import health as _health
from ..observability import instrument as _instrument
from ..observability import memprof as _memprof
from ..observability import telemetry as _telemetry
from ..observability import tracing as _tracing
from ..observability.instrument import StepTracker


class BatchEndParam:
    """The object handed to batch-end callbacks (Speedometer et al.)."""

    def __init__(self, epoch, nbatch, eval_metric, locals=None):
        self.epoch = epoch
        self.nbatch = nbatch
        self.eval_metric = eval_metric
        self.locals = locals


def _each_callback(callbacks, arg):
    """Invoke one callback or a list of them with a single argument."""
    if callbacks is None:
        return
    if not isinstance(callbacks, (list, tuple)):
        callbacks = [callbacks]
    for cb in callbacks:
        cb(arg)


def _as_list(obj):
    return obj if isinstance(obj, (list, tuple)) else [obj]


def _trim_pad(outputs, pad):
    """Drop the iterator's pad rows from each output array."""
    if not pad:
        return list(outputs)
    return [out[:out.shape[0] - pad] for out in outputs]


_PARAM_SUFFIXES = ("_weight", "_bias", "_gamma", "_beta")


def _check_input_names(symbol, names, typename, throw):
    """Warn/raise when a declared data/label name is not a symbol input."""
    args = symbol.list_arguments()
    for name in names:
        if name in args:
            continue
        likely_inputs = [a for a in args
                        if not a.endswith(_PARAM_SUFFIXES)]
        msg = ("the Module was created with %s_names=%s, but %r is not an "
               "argument of the symbol. Inputs the symbol does declare: %s"
               % (typename, list(names), name, ", ".join(likely_inputs)))
        if throw:
            raise ValueError(msg)
        _module_logger(__name__).warning(msg)


class BaseModule:
    """Abstract train/predict driver over a bound computation.

    Concrete subclasses (Module, BucketingModule, SequentialModule,
    PythonModule) implement the abstract computation methods; everything
    layered on top of them — ``fit``, ``score``, ``predict`` — lives here.
    """

    def __init__(self, logger=logging):
        # the historical default was the bare `logging` MODULE (the root
        # logger) — route it under the package root instead so one
        # handler (the flight recorder's) captures every module record
        self.logger = _module_logger("module") if logger is logging \
            else logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    def _ready(self):
        if not (self.binded and self.params_initialized):
            raise AssertionError(
                "this call needs bind() and init_params() to have run")

    # -- high-level API ------------------------------------------------------
    def forward_backward(self, data_batch):
        self.forward(data_batch, is_train=True)
        self.backward()

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        """Evaluate on a data iterator; returns name/value pairs."""
        self._ready()
        if reset:
            eval_data.reset()
        eval_metric = metric_mod.create(eval_metric)
        eval_metric.reset()
        seen = 0
        for nbatch, batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(batch, is_train=False)
            self.update_metric(eval_metric, batch.label)
            _each_callback(batch_end_callback, BatchEndParam(
                epoch=epoch, nbatch=nbatch, eval_metric=eval_metric,
                locals=locals()))
            seen += 1
        _each_callback(score_end_callback, BatchEndParam(
            epoch=epoch, nbatch=seen, eval_metric=eval_metric,
            locals=locals()))
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        """Generator over (outputs, nbatch, batch) for each batch."""
        self._ready()
        if reset:
            eval_data.reset()
        for nbatch, batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                return
            self.forward(batch, is_train=False)
            yield _trim_pad(self.get_outputs(), batch.pad), nbatch, batch

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """Run inference over an iterator and collect the outputs."""
        per_batch = [
            [o.copy() for o in outs]
            for outs, _, _ in self.iter_predict(eval_data, num_batch, reset)]
        if not per_batch:
            return per_batch
        if not merge_batches:
            return per_batch
        widths = {len(outs) for outs in per_batch}
        if len(widths) != 1:
            raise AssertionError(
                "cannot merge: batches produced differing output counts %s "
                "(bucketing?); pass merge_batches=False" % sorted(widths))
        from ..ndarray import concatenate
        merged = [concatenate([outs[i] for outs in per_batch])
                  for i in range(widths.pop())]
        if len(merged) == 1 and not always_output_list:
            return merged[0]
        return merged

    # -- the training loop ---------------------------------------------------
    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=Uniform(0.01), arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None):
        """Bind, initialize, and train for ``num_epoch`` epochs.

        ``train_data``/``eval_data`` may be any ``DataIter`` — including
        an ``io_pipeline.PipelineDataIter`` — or a raw
        ``io_pipeline.Pipeline``, which is adapted (and closed when fit
        returns) automatically; the epoch loop's lookahead + ``prepare``
        contract is what the pipeline's double-buffered device transfer
        overlaps against."""
        if num_epoch is None:
            raise AssertionError("fit() needs num_epoch")

        owned_iters = []
        try:
            # adapt INSIDE the try: if the second adaptation (or the
            # fit itself) raises, the first adapter's already-running
            # workers still get torn down.  The eval adapter skips the
            # warm start — score(reset=True) discards the armed epoch
            # unconsumed anyway.
            train_data = self._adapt_data(train_data, owned_iters)
            eval_data = self._adapt_data(eval_data, owned_iters,
                                         warm_start=False)
            self._fit_impl(
                train_data, eval_data, eval_metric, epoch_end_callback,
                batch_end_callback, kvstore, optimizer, optimizer_params,
                eval_end_callback, eval_batch_end_callback, initializer,
                arg_params, aux_params, allow_missing, force_rebind,
                force_init, begin_epoch, num_epoch, validation_metric,
                monitor)
        finally:
            self._capture_op_scopes()
            for it in owned_iters:
                try:
                    it.close()
                except Exception:
                    pass

    def _capture_op_scopes(self):
        """Under an open profiler session (and telemetry on), hand the fused
        step's table from compiled op to ``mx:`` scope to ``instrument``, so
        that whoever reads the trace can turn its device events into time by
        mechanism after this module is gone
        (``instrument.device_seconds_by_scope``).  Off the hot path: the
        fit is over; with no session this is one attribute read."""
        if not _tracing.device_trace_open() or not _telemetry.enabled():
            return
        step = getattr(self, "_fused_step", None)
        if step is None or not step.ran:
            return
        try:
            tic = time.time()
            _instrument.capture_device_op_scopes(step._memprof_label,
                                                 step.op_scopes())
            self.logger.info("device trace open: the step program's op "
                             "scopes captured in %.2f s", time.time() - tic)
        except Exception:   # never take a finished fit down for a table
            self.logger.warning("could not capture the step program's op "
                                "scopes", exc_info=True)

    @staticmethod
    def _adapt_data(data, owned_iters, warm_start=True):
        """A raw Pipeline is adapted here and registered in
        ``owned_iters`` for fit's teardown; an already-built iterator
        passes through and belongs to the caller."""
        if data is not None and not isinstance(data, DataIter) \
                and hasattr(data, "as_dataiter"):
            it = data.as_dataiter(warm_start=warm_start)
            owned_iters.append(it)
            return it
        return data

    def _fit_impl(self, train_data, eval_data, eval_metric,
                  epoch_end_callback, batch_end_callback, kvstore,
                  optimizer, optimizer_params, eval_end_callback,
                  eval_batch_end_callback, initializer, arg_params,
                  aux_params, allow_missing, force_rebind, force_init,
                  begin_epoch, num_epoch, validation_metric, monitor):
        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)

        validation_metric = metric_mod.create(
            validation_metric if validation_metric is not None
            else eval_metric)
        eval_metric = metric_mod.create(eval_metric)

        try:
            for epoch in range(begin_epoch, num_epoch):
                self._run_epoch(epoch, train_data, eval_metric,
                                batch_end_callback, monitor)

                # sync the trained values back into the module's param
                # dicts so callbacks and the next epoch observe the same
                # tensors
                arg_now, aux_now = self.get_params()
                self.set_params(arg_now, aux_now)
                if epoch_end_callback is not None:
                    for cb in _as_list(epoch_end_callback):
                        cb(epoch, self.symbol, arg_now, aux_now)

                if eval_data:
                    for name, val in self.score(
                            eval_data, validation_metric,
                            score_end_callback=eval_end_callback,
                            batch_end_callback=eval_batch_end_callback,
                            epoch=epoch):
                        self.logger.info("Epoch[%d] Validation-%s=%f",
                                         epoch, name, val)
                train_data.reset()
        except _health.TrainingDivergedError:
            # the raise action already wrote the flight dump (black box
            # first); an attached elastic checkpointer leaves a final
            # snapshot behind before the error propagates, positioned
            # at the diverged step so a resume continues the stream
            ckpt = getattr(self, "_elastic_ckpt", None)
            if ckpt is not None:
                pos = getattr(self, "_elastic_position", None)
                ckpt.on_diverged(self, epoch=pos[0] if pos else 0,
                                 batch=pos[1] if pos else None)
            raise
        except Exception as exc:
            # OOM black box, unconditional: on async backends an
            # execution-time RESOURCE_EXHAUSTED surfaces at whatever
            # sync point consumes the step's results (metric update,
            # grad read) — not at the guarded dispatch — so the fit
            # loop is the one frame that always sees it
            oomed = _memprof.maybe_record_oom("fit", exc) is not None \
                or (_memprof.is_oom(exc)
                    and _flight.get_recorder().has_dumped("oom"))
            # black-box hook: an unattended run dying mid-fit leaves its
            # last-N-steps record behind (opt-in with the sentinel).
            # Skipped when THIS error already wrote the augmented oom
            # dump: with a fixed MXNET_TPU_FLIGHT_PATH a second dump
            # would overwrite the memory post-mortem
            if _health.enabled():
                _flight.note_exception(exc)
                if not oomed:
                    _flight.dump_once(reason="fit_exception")
            raise

    def _run_epoch(self, epoch, train_data, eval_metric,
                   batch_end_callback, monitor):
        """One pass over train_data: step on each batch, prefetch the next.

        Each step is decomposed into the telemetry components
        (data_wait / fwd_bwd_dispatch / update / metric / sync) as
        nested profiler spans + registry histograms — the per-step
        breakdown `tools/traceview.py` tabulates; what ``sync`` lumps
        together is split into its ``sync:*`` phases.  The lookahead:
        once this step is dispatched the NEXT batch is fetched and
        handed to ``prepare`` — ``Module.prepare`` starts its
        host->device transfer there (``FusedTrainStep.stage``, phase
        ``fused:stage``), so the copy runs under this step and the next
        dispatch finds its inputs on the device.  The epoch's first
        batch has no step to hide under and is loaded at dispatch."""
        tic = time.time()
        eval_metric.reset()
        tracker = StepTracker(epoch=epoch)
        # health sentinel (MXNET_TPU_HEALTH=1): consume the per-step
        # packed vector the in-program summary produced — one tiny
        # device->host fetch per step, evaluated by the rolling rules
        health_mon = self._ensure_health_monitor() \
            if _health.enabled() else None
        it = iter(train_data)
        with tracker.component("data_wait"):
            batch = next(it, None)
        nbatch = 0
        while batch is not None:
            if monitor is not None:
                with tracker.component("sync"), \
                        tracker.phase("sync:monitor"):
                    monitor.tic()
            with tracker.component("fwd_bwd_dispatch"):
                self.forward_backward(batch)
            with tracker.component("update"):
                self.update()
            with tracker.component("data_wait"):
                upcoming = next(it, None)
            if upcoming is not None:
                # start the next batch's transfer while the step executes
                with tracker.component("sync"), \
                        tracker.phase("sync:prepare"):
                    self.prepare(upcoming)
            pending_health = None
            if health_mon is not None:
                # AFTER the next batch's fetch/prepare: this blocks on
                # the in-flight step, so capturing it earlier would
                # serialize data loading behind device compute.  prepare
                # never changes the active program for the in-flight
                # step (BucketingModule switches back), so the stashed
                # vector is still this step's.
                with tracker.component("sync"), \
                        tracker.phase("sync:health"):
                    pending_health = self._capture_health()
            with tracker.component("metric"):
                self.update_metric(eval_metric, batch.label)
            if monitor is not None:
                with tracker.component("sync"), \
                        tracker.phase("sync:monitor"):
                    monitor.toc_print()
            with tracker.component("sync"), \
                    tracker.phase("sync:callbacks"):
                _each_callback(batch_end_callback, BatchEndParam(
                    epoch=epoch, nbatch=nbatch, eval_metric=eval_metric,
                    locals=locals()))
            timings = tracker.step_end(nbatch)
            ckpt = getattr(self, "_elastic_ckpt", None)
            if ckpt is not None:
                # stash the completed step's position BEFORE the health
                # judgment: a raise-action rule unwinds past the
                # on_step hook below, and the diverged snapshot must
                # still record where the data stream stands (this
                # step's update is already applied)
                self._elastic_position = (epoch, nbatch)
            if pending_health is not None:
                # record first, judge second: a raising rule's flight
                # dump must already contain the offending step — and
                # carry the latest device-memory sample so the dump
                # shows the memory trend leading into an anomaly
                step, summary = pending_health
                _flight.record_step(
                    step, epoch=epoch, batch=nbatch, health=summary,
                    timings=timings,
                    mem=_instrument.last_memory_sample())
                health_mon.observe(step, summary)
            if ckpt is not None:
                # AFTER the health judgment: an anomaly marked by the
                # monitor's callback snapshots here, strictly after its
                # flight dump (black box first); schedule/preemption
                # triggers also fire at this completed-step boundary
                with tracker.component("sync"), \
                        tracker.phase("sync:checkpoint"):
                    ckpt.on_step(self, epoch=epoch, batch=nbatch)
            batch = upcoming
            nbatch += 1
        tracker.close()
        for name, val in eval_metric.get_name_value():
            self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
        self.logger.info("Epoch[%d] Time cost=%.3f",
                         epoch, time.time() - tic)

    # -- health sentinel plumbing --------------------------------------------
    def _take_health_vector(self):
        """Subclasses with a bound exec group override this to hand the
        sentinel its per-step packed vector as ``(np_vector, layout)``;
        the base implementation opts out."""
        return None

    def _ensure_health_monitor(self):
        """One rolling-rule monitor per module, shared across epochs so
        EMAs and windows span the whole run."""
        mon = getattr(self, "_health_mon", None)
        if mon is None:
            mon = self._health_mon = _health.HealthMonitor(
                logger=self.logger)
            ckpt = getattr(self, "_elastic_ckpt", None)
            if ckpt is not None and ckpt.note_anomaly not in mon.callbacks:
                # an attached elastic checkpointer snapshots on anomaly
                # (at the next step boundary, after the monitor's dump)
                mon.add_callback(ckpt.note_anomaly)
        return mon

    def _capture_health(self):
        """Fetch + unpack this step's health vector.  Returns
        ``(global_step, summary_dict)`` or None; also stashes the
        summary for a ``Monitor(stats='health')`` to render and fills
        the update/param ratio estimate on the general path (the fused
        step computes the exact ratio in-program)."""
        step = getattr(self, "_health_step", 0)
        self._health_step = step + 1
        taken = self._take_health_vector()
        if taken is None:
            return None
        vec, layout = taken
        summary = layout.unpack(vec)
        opt = getattr(self, "_optimizer", None)
        if summary.get("update_ratio", -1.0) < 0 and opt is not None:
            gn = summary.get("grad_norm", float("nan"))
            pn = summary.get("param_norm", 0.0)
            if pn > 0 and math.isfinite(gn):
                summary["update_ratio"] = \
                    opt.health_update_scale() * gn / pn
        self._last_health_summary = (step, summary)
        return step, summary

    def _install_health_monitor(self, mon):
        """Bind a ``Monitor(stats='health')``: readings come from the
        in-program sentinel summaries the fit loop stashes on THIS
        module, so nothing is tapped and the fused one-program step
        stays active — no separate-path fallback, no retrace
        (regression-tested against the exec-cache trace counters)."""
        mon.install_module(self)
        if not getattr(self, "_health_mon_announced", False):
            self._health_mon_announced = True
            if _health.enabled():
                self.logger.info(
                    "monitor(stats='health') installed: per-step "
                    "stats come from the in-program health sentinel;"
                    " the fused train step stays active")
            else:
                self.logger.warning(
                    "monitor(stats='health') installed but "
                    "MXNET_TPU_HEALTH is not 1: the sentinel is off "
                    "and the monitor will report nothing")

    # -- parameter persistence -----------------------------------------------
    def save_params(self, fname):
        from ..ndarray import save
        arg_params, aux_params = self.get_params()
        blob = {"arg:" + k: v.as_in_context(cpu())
                for k, v in arg_params.items()}
        blob.update({"aux:" + k: v.as_in_context(cpu())
                     for k, v in aux_params.items()})
        save(fname, blob)

    def load_params(self, fname):
        from ..ndarray import load
        split = {"arg": {}, "aux": {}}
        for key, value in load(fname).items():
            kind, _, name = key.partition(":")
            if kind not in split or not name:
                raise ValueError(
                    "%s is not a Module param file (bad key %r)"
                    % (fname, key))
            split[kind][name] = value
        self.set_params(split["arg"], split["aux"])

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    # -- state passthrough (stateless by default) ------------------------------
    def get_states(self, merge_multi_context=True):
        self._ready()
        assert not merge_multi_context
        return []

    def set_states(self, states=None, value=None):
        self._ready()
        assert not states and not value

    def prepare(self, data_batch):
        pass

    # -- abstract surface ------------------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()

    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        raise NotImplementedError()

    def install_monitor(self, mon):
        raise NotImplementedError()

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError()
