"""Model registry: named checkpoints loaded into bound predict executors.

A :class:`ServedModel` is the serving-side view of one checkpoint: the
symbol + params bound through :class:`mxnet_tpu.predict.Predictor` (the
C-predict contract — loss heads run their inference forward, outputs are
positionally ordered, ``get_output_shape`` valid before the first
forward), with ONE predictor per batch-size bucket.  Bucket predictors
share the base predictor's weights (``Predictor.reshaped``), and every
bucket binds the same structural graph at a distinct batch shape — so
after :meth:`ServedModel.warmup` each bucket's forward program sits in
the process-wide executor cache and steady-state dispatches never
retrace (verified via ``executor_cache.watch_traces``).

The registry is the lookup half of admission: an unknown model name is a
typed ``ModelNotFound`` at submit time, not a KeyError in the dispatch
thread.
"""
from __future__ import annotations

import os
import threading
import time

import numpy as np

from .. import context as _context
from .. import executor_cache
from .. import threads as _threads
from ..observability import memprof as _memprof
from ..predict import Predictor
from .errors import ModelNotFound, RequestTooLarge


def bucket_sizes(max_batch_size):
    """The fixed batch-size buckets for ``max_batch_size``: powers of two
    up to it, plus the max itself when it is not a power of two.  Every
    dispatch pads to one of these, so the executor cache holds exactly
    ``len(bucket_sizes(m))`` forward programs per model after warmup
    (BucketingModule's amortization argument, applied to inference)."""
    if max_batch_size < 1:
        raise ValueError("max_batch_size must be >= 1, got %r"
                         % (max_batch_size,))
    out = []
    b = 1
    while b < max_batch_size:
        out.append(b)
        b *= 2
    out.append(max_batch_size)
    return out


def bucket_for(n_rows, buckets):
    """Smallest bucket holding ``n_rows`` (buckets ascending)."""
    for b in buckets:
        if n_rows <= b:
            return b
    raise RequestTooLarge(
        "batch of %d rows exceeds max_batch_size %d"
        % (n_rows, buckets[-1]))


class ServedModel:
    """One model's serving state: per-bucket predictors over shared
    weights, plus the metadata the batcher and HTTP front-end need."""

    def __init__(self, name, symbol, arg_params, aux_params, input_shapes,
                 max_batch_size=8, ctx=None, quantize=None,
                 calibration=None, slo_ms=None):
        self.name = name
        self.symbol = symbol
        self.buckets = bucket_sizes(max_batch_size)
        self.max_batch_size = max_batch_size
        # declared per-model latency SLO (p99 target, ms): the contract
        # the traceview attainment table judges observed latency
        # against.  None = no declared target; the env default covers
        # fleets whose deploy config owns the number.
        if slo_ms is None:
            env = os.environ.get("MXNET_TPU_SERVING_SLO_MS", "").strip()
            try:
                slo_ms = float(env) if env else None
            except ValueError:
                slo_ms = None
        self.slo_ms = float(slo_ms) if slo_ms else None
        if self.slo_ms:
            from . import metrics as _metrics
            _metrics.record_slo(name, self.slo_ms)
        # int8 serving (docs/serving.md §int8): quantize=None defers to
        # the MXNET_TPU_QUANTIZE env default; the rewrite happens once in
        # the base predictor and every bucket shares its int8 weights
        if quantize is None:
            env = os.environ.get("MXNET_TPU_QUANTIZE", "").strip().lower()
            quantize = env if env not in ("", "0", "off", "none") else None
        self.quantize = quantize
        # feature shapes EXCLUDE the batch dim: {"data": (8,)} serves
        # requests shaped (rows, 8)
        self.input_shapes = {k: tuple(int(d) for d in v)
                             for k, v in input_shapes.items()}
        params = {"arg:%s" % k: v for k, v in arg_params.items()}
        params.update({"aux:%s" % k: v for k, v in (aux_params or {}).items()})
        base_shapes = self._bind_shapes(self.buckets[0])
        if ctx is None:
            # the chip when the host has one; never silently the host CPU
            ctx = _context.accelerator()
        self._base = Predictor(symbol.tojson(), params, base_shapes,
                               ctx=ctx, quantize=quantize,
                               calibration=calibration)
        self.output_names = self._base.output_names
        # filled by warmup() under MXNET_TPU_MEMPROF=1: per-bucket
        # program byte footprints from XLA's memory_analysis
        self.bucket_memory = {}
        # a bucket set staged by the ServingBucketTuner (or an
        # operator) for adoption at the next warmup()/prewarm()
        # boundary — never swapped mid-traffic, where an untraced
        # bucket would retrace in the dispatch thread
        self._pending_buckets = None
        self._by_bucket = {self.buckets[0]: self._base}
        self._lock = _threads.package_lock("ServedModel._lock")
        # serializes run_batch: predictors are forward()+get_output()
        # pairs, not atomic — warmup from the caller thread must not
        # interleave with the dispatch thread on the same bucket
        self._run_lock = _threads.package_lock("ServedModel._run_lock")

    def _bind_shapes(self, bucket):
        return {k: (bucket,) + v for k, v in self.input_shapes.items()}

    def predictor_for(self, bucket):
        """The bucket's bound predictor, creating it on first use
        (weights shared with the base — ``Predictor.reshaped``)."""
        with self._lock:
            p = self._by_bucket.get(bucket)
            if p is None:
                p = self._base.reshaped(self._bind_shapes(bucket))
                self._by_bucket[bucket] = p
            return p

    def stage_buckets(self, buckets):
        """Stage a replacement bucket set, adopted at the START of the
        next :meth:`warmup` (which `Server.warmup`/`prewarm` drive), so
        every new bucket is traced inside the warmup sweep and
        steady-state serving never retraces.  The set is normalized —
        ints, deduped, clamped to [1, max_batch_size], and always
        topped by ``max_batch_size`` so ``bucket_for`` can place every
        admissible request.  Returns the normalized set.

        Run the warmup at a low-traffic moment: from the swap until the
        sweep finishes, a request routed to a not-yet-traced bucket
        would compile in the dispatch thread (the same window any cold
        model has)."""
        norm = sorted({min(self.max_batch_size, max(1, int(b)))
                       for b in buckets})
        if not norm:
            raise ValueError("bucket set must be non-empty")
        if norm[-1] != self.max_batch_size:
            norm.append(self.max_batch_size)
        with self._lock:
            self._pending_buckets = norm
        return list(norm)

    def pending_buckets(self):
        """The staged-but-not-yet-adopted bucket set, or None."""
        with self._lock:
            return list(self._pending_buckets) \
                if self._pending_buckets else None

    def _adopt_pending_buckets(self):
        """Swap in a staged bucket set (warmup-boundary only).  Old
        buckets' predictors stay in ``_by_bucket`` — their programs are
        already cached and shared weights make them cheap — but routing
        (``self.buckets``) moves to the new set atomically."""
        with self._lock:
            if not self._pending_buckets:
                return False
            self.buckets = self._pending_buckets
            self._pending_buckets = None
        return True

    def run_batch(self, bucket, inputs):
        """Run one padded batch: ``inputs`` maps input name -> np array
        with leading dim == ``bucket``.  Returns the outputs as a list
        of host arrays (positional, matching ``output_names``)."""
        p = self.predictor_for(bucket)
        with self._run_lock:
            p.forward(**inputs)
            # holding _run_lock across the device sync is the point:
            # predictors are forward()+get_output() pairs, not atomic,
            # so warmup from the caller thread must not interleave with
            # the dispatch thread on the same bucket (see __init__)
            # graftlint: disable=GL008
            return [p.get_output(i).asnumpy()
                    for i in range(len(self.output_names))]

    def warmup(self):
        """Pre-trace every bucket's forward program so steady-state
        serving recompiles nothing.  Returns {bucket: traces_added} from
        the executor-cache retrace counters — the verification pass in
        ``Server.warmup`` asserts a second sweep adds zero.

        Under ``MXNET_TPU_MEMPROF=1`` the programs traced here carry
        XLA's ``memory_analysis``; the per-bucket byte footprints land
        in ``self.bucket_memory`` ({bucket: {argument/output/temp/
        total_bytes}}), which ``Server.warmup`` sums against device
        capacity.  A bucket whose program was already cached (a second
        model over the same graph) traces nothing and so attributes
        nothing — only measured programs are reported.

        A bucket set staged by :meth:`stage_buckets` (the
        ServingBucketTuner's apply path) is adopted HERE, before the
        sweep — the warmup that follows traces every new bucket, so the
        applied change never retraces in steady state."""
        self._adopt_pending_buckets()
        traced = {}
        # bucket_memory accumulates rather than resets: the verify
        # sweep (and any later warm re-warmup) traces nothing and must
        # not erase the footprints the first pass measured
        #
        # attribution filter: records are matched by THIS model's bound
        # graph fingerprint (the entry label suffix — the predictor's
        # symbol, so the int8 rewrite attributes too), not just by time
        # window; a concurrent training thread compiling its own
        # programs mid-warmup must not be charged to the bucket
        label_suffix = "@" + self._base._symbol.structural_hash()[:10]
        for b in self.buckets:
            t0 = time.time()
            with executor_cache.watch_traces() as w:
                zeros = {k: np.zeros((b,) + v, dtype=np.float32)
                         for k, v in self.input_shapes.items()}
                self.run_batch(b, zeros)
            traced[b] = w.total()
            mems = [r["memory"] for r in _memprof.program_records()
                    if r["t"] >= t0 and r.get("memory")
                    and str(r.get("label", "")).endswith(label_suffix)]
            if mems:
                self.bucket_memory[b] = {
                    "argument_bytes": sum(m.get("argument_bytes", 0)
                                          for m in mems),
                    "output_bytes": sum(m.get("output_bytes", 0)
                                        for m in mems),
                    "temp_bytes": sum(m.get("temp_bytes", 0)
                                      for m in mems),
                    "total_bytes": sum(m.get("total_bytes", 0)
                                       for m in mems)}
        return traced

    def prewarm(self):
        """Deploy-time population of the persistent program-cache dir
        (``MXNET_TPU_PROGRAM_CACHE_DIR`` — mxnet_tpu/program_cache.py):
        compiles every bucket program (a plain :meth:`warmup` sweep) and
        reports what the sweep wrote to disk, so the deploy pipeline can
        ship a cache volume and a fresh replica serves in seconds
        instead of recompiling (docs/serving.md §prewarm).  Raises
        ``MXNetError`` when the disk tier is off: a prewarm that
        silently persists nothing is a broken deploy."""
        from .. import program_cache
        from ..base import MXNetError
        if not program_cache.enabled():
            raise MXNetError(
                "ServedModel.prewarm() needs the persistent program "
                "cache: set MXNET_TPU_PROGRAM_CACHE_DIR to the cache "
                "volume the replicas will mount")
        if program_cache.read_only():
            raise MXNetError(
                "ServedModel.prewarm() under MXNET_TPU_PROGRAM_CACHE_RO"
                "=1 would persist nothing (the read-only mode is for "
                "replicas CONSUMING a prewarmed volume) — unset it in "
                "the deploy pipeline that populates the cache")
        before = program_cache.stats()
        traced = self.warmup()
        after = program_cache.stats()
        return {"buckets": list(self.buckets),
                "traces": sum(traced.values()),
                "disk_writes": after["writes"] - before["writes"],
                "disk_hits": after["hits"] - before["hits"],
                "disk_bytes_written": (after["bytes_written"]
                                       - before["bytes_written"])}


class ModelRegistry:
    """Name -> :class:`ServedModel` map shared by a :class:`Server`."""

    def __init__(self):
        self._models = {}
        self._lock = _threads.package_lock("ModelRegistry._lock")

    def register(self, name, symbol, arg_params, aux_params, input_shapes,
                 max_batch_size=8, ctx=None, quantize=None,
                 calibration=None, slo_ms=None):
        """Register a live symbol + params under ``name`` (replacing any
        previous registration) and return its :class:`ServedModel`."""
        model = ServedModel(name, symbol, arg_params, aux_params,
                            input_shapes, max_batch_size=max_batch_size,
                            ctx=ctx, quantize=quantize,
                            calibration=calibration, slo_ms=slo_ms)
        with self._lock:
            self._models[name] = model
        return model

    def load(self, name, prefix, epoch, input_shapes, max_batch_size=8,
             ctx=None, quantize=None, calibration=None, slo_ms=None):
        """Register from ``save_checkpoint`` artifacts (prefix-symbol.json
        + prefix-%04d.params — the two-artifact reference format)."""
        from ..model import load_checkpoint
        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
        return self.register(name, symbol, arg_params, aux_params,
                             input_shapes, max_batch_size=max_batch_size,
                             ctx=ctx, quantize=quantize,
                             calibration=calibration, slo_ms=slo_ms)

    def get(self, name):
        with self._lock:
            model = self._models.get(name)
            have = sorted(self._models) if model is None else None
        if model is None:
            raise ModelNotFound(
                "no model registered as %r (have: %s)"
                % (name, have or "none"))
        return model

    def names(self):
        with self._lock:
            return sorted(self._models)

    def __contains__(self, name):
        with self._lock:
            return name in self._models
