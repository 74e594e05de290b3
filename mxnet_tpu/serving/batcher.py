"""Dynamic batcher: single requests in, bucket-padded batches out.

The TPU economics this implements: one compiled program per (graph,
shape) signature is expensive to create and free to reuse (PR 2's
executor cache), so online traffic must be funneled through a FIXED set
of batch shapes.  The batcher queues single requests, concatenates them
up to ``max_batch_size`` rows, pads the concat to the smallest
power-of-two bucket, dispatches ONE forward for the whole batch, and
splits the outputs back per request — BucketingModule's amortization
argument applied to inference.  After ``Server.warmup`` every bucket's
program is cached, so steady state serves arbitrary request mixes with
zero recompiles.

The dispatch thread is the service's heart and must never die: every
per-batch failure (a model raising, a shape mismatch that slipped
through validation) is caught and distributed to that batch's futures
as the error result, then the loop continues.  Padding rows are zeros;
the graph evaluates row-wise (no cross-row ops in inference graphs this
serves), so real rows are bitwise-identical to any run of the SAME
bucket shape — XLA specializes row blocking per program shape, so
across shapes equality holds only up to float reassociation.
``tests/test_serving.py`` asserts exactly that (each request replayed
at its ``dispatch_bucket`` through a plain Predictor, compared
bitwise).
"""
from __future__ import annotations

import logging
import threading
import time
from concurrent.futures import InvalidStateError

import numpy as np

from .. import threads as _threads
from ..analysis import locksan as _locksan
from ..observability import flight_recorder as _flight
from ..observability import health as _health
from ..observability import memprof as _memprof
from ..observability import reqtrace as _reqtrace
from ..observability import tracing
from . import metrics
from .registry import bucket_for

_log = logging.getLogger(__name__)


def _fail_future(future, exc):
    """Deliver ``exc`` to ``future`` if it is still pending.  Returns
    True when THIS call resolved it.  A pending concurrent Future can be
    cancel()ed by its client at any instant, so a ``done()`` pre-check
    is inherently racy — the InvalidStateError from losing that race
    must not escape into the dispatch thread."""
    try:
        future.set_exception(exc)
        return True
    except InvalidStateError:
        return False


def _resolve_future(future, result):
    """set_result with the same cancel-race protection."""
    try:
        future.set_result(result)
        return True
    except InvalidStateError:
        return False


# -- the shared batch-running core (DynamicBatcher + fleet Replica) ----------
#
# One place owns "run an assembled group through a ServedModel": the
# single-process DynamicBatcher below and every fleet Replica worker
# (serving/router.py) dispatch through these functions, so padding,
# splitting, metrics and failure accounting cannot drift between the
# one-replica and N-replica paths.

def assemble_padded(model, batch, bucket):
    """Concat the requests' input arrays and zero-pad to ``bucket``
    rows.  One allocation per input: rows copy in-place."""
    padded = {}
    for input_name, feature in model.input_shapes.items():
        buf = np.zeros((bucket,) + feature, dtype=np.float32)
        off = 0
        for r in batch:
            buf[off:off + r.n_rows] = r.inputs[input_name]
            off += r.n_rows
        padded[input_name] = buf
    return padded


def split_results(batch, outs, bucket):
    """Slice each request's rows back out of the batched outputs and
    resolve its future (list of per-output host arrays)."""
    off = 0
    t_split = time.monotonic()
    for r in batch:
        # copy, not view: a retained response must not pin the whole
        # bucket-sized output (nor expose co-batched rows via .base)
        result = [o[off:off + r.n_rows].copy() for o in outs]
        off += r.n_rows
        r.dispatch_bucket = bucket
        _resolve_future(r.future, result)
        t_done = time.monotonic()
        metrics.record_request_done(r, t_done)
        if r.ctx is not None:
            # split + future resolution is the waterfall's last hop;
            # finish() decides the record's fate (tail-pin on an SLO
            # breach, sampled ring otherwise)
            r.ctx.seg("split", t_split, t_done)
            r.ctx.bucket = bucket
            _reqtrace.finish(r.ctx, status="ok")
        t_split = t_done


def run_group(model, batch, rows, replica=None):
    """Run one same-model group end to end: bucket, pad, dispatch,
    record, split.  RAISES on failure — the caller owns the failure
    policy (``DynamicBatcher`` fails the futures and continues; a fleet
    ``Replica`` additionally quarantines itself).  ``replica`` tags the
    dispatch span + per-replica telemetry with the serving replica
    index."""
    name = model.name
    t_a0 = time.monotonic()
    bucket = bucket_for(rows, model.buckets)
    padded = assemble_padded(model, batch, bucket)
    t_a1 = time.monotonic()
    traced = [r for r in batch if r.ctx is not None]
    if traced:
        # co-batching facts every rider of this batch records: who it
        # shared the program shape with, and the padding it paid for
        ids = [r.ctx.trace_id for r in traced]
        for r in traced:
            r.ctx.seg("assemble", t_a0, t_a1, bucket=bucket,
                      cobatched=len(batch), padded_rows=bucket - rows,
                      neighbours=[i for i in ids if i != r.ctx.trace_id])
    span_args = {"model": name, "bucket": bucket, "rows": rows,
                 "requests": len(batch)}
    if replica is not None:
        span_args["replica"] = int(replica)
    with tracing.span("serving:batch", category="serving",
                      pid="serving", args=span_args):
        t0 = time.monotonic()
        dispatch_args = {"replica": int(replica)} \
            if replica is not None else None
        with tracing.span("serving:dispatch", category="serving",
                          pid="serving", args=dispatch_args):
            # locksan (MXNET_TPU_LOCKSAN=1): a package lock held here
            # would serialize device work behind host bookkeeping
            _locksan.check_dispatch_clear("serving.run_group")
            outs = model.run_batch(bucket, padded)
        t1 = time.monotonic()
        ms = (t1 - t0) * 1e3
        metrics.record_dispatch_ms(ms)
        for r in traced:
            r.ctx.seg("dispatch", t0, t1, bucket=bucket,
                      **({"replica": int(replica)}
                         if replica is not None else {}))
            if replica is not None:
                r.ctx.replica = int(replica)
        if replica is not None:
            metrics.record_replica_dispatch(replica, name, rows, ms)
    metrics.record_batch(name, bucket, rows)
    if _health.enabled():
        _note_output_health(name, bucket, outs)
    split_results(batch, outs, bucket)
    return bucket


def _note_output_health(model_name, bucket, outs):
    """Served-output numerics check (opt-in with the health sentinel):
    host-side isfinite over the already-fetched output arrays — no
    device sync, no program change.  Warn-only; the batch still
    ships."""
    bad = [i for i, o in enumerate(outs)
           if not np.all(np.isfinite(np.asarray(o)))]
    if bad:
        metrics.record_nonfinite_response(model_name, len(bad))
        _flight.note("serving_nonfinite",
                     {"model": model_name, "bucket": bucket,
                      "outputs": bad})


def fail_batch(batch, exc, model_name):
    """Deliver ``exc`` to every request of a failed batch, counting
    one rejection PER REQUEST actually failed (the reconciliation
    contract: requests_total minus rejected_total equals responses,
    so a 4-request batch failure must count 4, not 1)."""
    reason = getattr(exc, "reason", "dispatch_error")
    # OOM black box (unconditional — a serving process out of HBM
    # must leave the memory post-mortem behind even without the
    # health sentinel): one augmented dump per process, before the
    # clients see their errors
    _memprof.maybe_record_oom("serving:%s" % model_name, exc)
    if _health.enabled():
        # black-box hook BEFORE the futures resolve: by the time a
        # client sees the error, the dump exists.  dump_once — a
        # persistently failing model must not write a file per
        # batch, so only the process's FIRST failure pays the write.
        # An OOM skips the generic dump: the augmented oom dump
        # already exists, and with a fixed MXNET_TPU_FLIGHT_PATH a
        # second dump would overwrite its memory post-mortem
        _flight.note("serving_dispatch_error",
                     {"model": model_name,
                      "error": "%s: %s" % (type(exc).__name__, exc),
                      "requests": len(batch)})
        if not (_memprof.is_oom(exc)
                and _flight.get_recorder().has_dumped("oom")):
            _flight.dump_once(reason="serving_exception")
    for r in batch:
        if _fail_future(r.future, exc):
            metrics.record_rejection(reason, model=model_name)
        # the trace closes regardless of who resolved the future: a
        # typed error is exactly the journey tail capture exists for
        _reqtrace.finish_rejected(r.ctx, exc)


class DynamicBatcher:
    """Consumes an :class:`AdmissionController`, dispatches through a
    :class:`ModelRegistry`."""

    def __init__(self, registry, admission, max_batch_size=8,
                 batch_window_ms=2.0):
        self.registry = registry
        self.admission = admission
        self.max_batch_size = int(max_batch_size)
        self.batch_window_ms = float(batch_window_ms)
        self._thread = None
        # optional per-loop-iteration hook, run on the dispatch thread
        # AFTER a batch completes (never between assembly and dispatch):
        # the server's autotune cadence (MXNET_TPU_AUTOTUNE_EVERY_S)
        # hangs here.  Exceptions are contained by the loop's catch-all.
        self.cadence = None

    # -- lifecycle -----------------------------------------------------------

    def start(self):
        if self._thread is not None:
            return
        self._thread = _threads.spawn(self._loop, "serving",
                                      "batcher")

    @property
    def started(self):
        return self._thread is not None

    @property
    def alive(self):
        return self._thread is not None and self._thread.is_alive()

    def join(self, timeout=None):
        """Wait for the dispatch thread to drain and exit (the admission
        controller must be closed first)."""
        if self._thread is not None:
            self._thread.join(timeout)

    # -- the dispatch loop ---------------------------------------------------

    def _loop(self):
        while True:
            try:
                batch = self.admission.take_batch(
                    self.max_batch_size, self.batch_window_ms, self.reject)
                if batch is None:
                    return  # closed and drained
                self._dispatch(batch)
                if self.cadence is not None:
                    self.cadence()
            except Exception:  # the dispatch thread must never die
                _log.exception("serving dispatch loop survived an "
                               "unexpected error; continuing")
                # bound the spin if the failure is persistent (e.g. the
                # admission controller itself is broken)
                time.sleep(0.05)

    def reject(self, request, exc):
        """Fail one request with a typed error (deadline sweeps route
        through here).  Counts the rejection only when this call
        delivered it — a client that already cancel()ed its future was
        never rejected, and double-counting would break
        admitted-vs-rejected reconciliation."""
        now = time.monotonic()
        if _fail_future(request.future, exc):
            metrics.record_rejection(getattr(exc, "reason", "serving_error"),
                                     model=request.model)
            # a queued-stage rejection spent its whole life waiting:
            # its accrued wait belongs in serving.queue_ms, or the
            # queue histogram sees only survivors and reads healthiest
            # exactly while the server sheds its slowest waiters
            metrics.record_queue_wait((now - request.t_submit) * 1e3)
        if request.ctx is not None:
            request.ctx.seg("queue", request.t_submit, now)
            _reqtrace.finish_rejected(request.ctx, exc)

    def _dispatch(self, batch):
        """Run one assembled batch, split into sub-batches when the
        model's own ``max_batch_size`` is tighter than the assembly cap
        (a registry can hold models bucketed below the server's max).
        Any failure lands on the batch's futures, never on the thread."""
        name = batch[0].model
        try:
            model = self.registry.get(name)
        except Exception as exc:
            self._fail_batch(batch, exc, name)
            return
        group, group_rows = [], 0
        for r in batch:
            if group and group_rows + r.n_rows > model.max_batch_size:
                self._run_group(model, group, group_rows)
                group, group_rows = [], 0
            group.append(r)
            group_rows += r.n_rows
        if group:
            self._run_group(model, group, group_rows)

    def _run_group(self, model, batch, rows):
        try:
            run_group(model, batch, rows)
        except Exception as exc:  # the dispatch thread must survive
            fail_batch(batch, exc, model.name)

    # kept as a method for callers (Server.close's drain shed) that fail
    # a batch through the batcher object
    _fail_batch = staticmethod(fail_batch)
