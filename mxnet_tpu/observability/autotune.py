"""Telemetry-driven auto-tuning: close the observability loop into control.

PRs 3/5/9/10 built the READ side — step-breakdown spans, starvation
ratios, ``comm.exposed_ms``, per-bucket HBM footprints, request/batch
histograms — but every knob those signals inform was still hand-set.
This module turns recorded telemetry into *bounded, auditable*
configuration changes (the reference framework's profiler→operator-
tuning feedback loop, SURVEY.md L2 + ``src/profiler/``, grown into
fleet behavior):

- :class:`ServingBucketTuner` derives a TRAFFIC-SHAPED bucket set from
  the observed per-request row histogram (``serving.request_rows``,
  recorded at admission) via the shared log2-bucket quantile estimator
  (``telemetry.quantile_from_snapshot``), validates the candidate set
  against the per-bucket memprof footprints vs device ``bytes_limit``
  BEFORE it is ever applied, and — in apply mode — only *stages* it:
  the swap happens at the next ``warmup()``/``prewarm()`` boundary
  (``ServedModel.stage_buckets``), so steady-state serving never
  retraces.
- :class:`IoWorkerTuner` recommends io-pipeline worker counts from the
  measured starvation ratio (pipeline queue-wait — or the fit loop's
  ``data_wait`` — over measured step time).

Safety rails, enforced rather than hoped for:

- ``MXNET_TPU_AUTOTUNE`` gates everything: ``recommend`` (the default)
  logs decisions but changes nothing, ``apply`` lets controllers act,
  ``0`` disables them outright — ``run()`` returns None before reading
  a signal or creating a telemetry series, so a disabled process is
  bitwise-identical to one where this module never existed.
- Every decision — inputs read, candidates considered, action taken,
  cost paid — is a structured record appended to the process decision
  log AND the flight recorder's tuning ring, so every applied change is
  recoverable from a flight dump (``tools/traceview.py --tuning``
  renders it; docs/autotune.md pins the schema).
- A controller that cannot justify a change (insufficient samples,
  candidate == incumbent, footprint over capacity) says so with a
  logged decision instead of acting.
"""
from __future__ import annotations

import math
import os
import threading

from .. import threads as _threads
import time
from collections import deque

from ..log import module_logger as _module_logger
from . import flight_recorder as _flight
from . import telemetry as _telemetry
from . import tracing as _tracing

MODE_ENV = "MXNET_TPU_AUTOTUNE"

# actions a decision record may carry (docs/autotune.md):
#   apply     - a change was made (env set / bucket set staged)
#   recommend - report-only: the change the controller would make
#   hold      - signals read, incumbent kept (in band / already optimal)
#   reject    - candidate failed validation (e.g. footprint > capacity)
#   skip      - not enough signal to decide (insufficient samples)
ACTIONS = ("apply", "recommend", "hold", "reject", "skip")

_warned_mode = set()
_log_lock = _threads.package_lock("autotune._log_lock")
_decisions = deque(maxlen=256)


def mode():
    """The resolved ``MXNET_TPU_AUTOTUNE`` mode: ``recommend`` (default
    — controllers report what they would do), ``apply`` (controllers
    act), or ``off`` (``0``/``off`` — controllers are inert).  Malformed
    values warn once and read as the report-only default."""
    raw = os.environ.get(MODE_ENV, "").strip().lower()
    if raw in ("", "recommend"):
        return "recommend"
    if raw == "apply":
        return "apply"
    if raw in ("0", "off", "false", "none"):
        return "off"
    if raw not in _warned_mode:
        _warned_mode.add(raw)
        _module_logger(__name__).warning(
            "ignoring malformed %s=%r (want recommend|apply|0); running "
            "report-only", MODE_ENV, raw)
    return "recommend"


def enabled():
    return mode() != "off"


def decision_log():
    """The process decision log, newest last (bounded at 256 records;
    the flight recorder keeps its own ring so dumps carry them too)."""
    with _log_lock:
        return [dict(r) for r in _decisions]


def clear_decisions():
    """Drop the in-module log (tests; the flight recorder's tuning ring
    is owned — and reset — by ``flight_recorder.reset``)."""
    with _log_lock:
        _decisions.clear()


class Controller:
    """Base of the tuners: mode resolution + the decision log.

    ``mode`` precedence: the env kill switch (``MXNET_TPU_AUTOTUNE=0``)
    always wins; otherwise an explicit constructor ``mode=`` overrides
    the env, and the env's ``recommend``/``apply`` is the default.
    """

    name = "controller"

    def __init__(self, mode=None):
        if mode is not None and mode not in ("recommend", "apply"):
            raise ValueError("mode must be 'recommend' or 'apply', got %r"
                             % (mode,))
        self._mode = mode

    @property
    def mode(self):
        env = globals()["mode"]()
        if env == "off":
            return "off"
        return self._mode or env

    @property
    def active(self):
        return self.mode != "off"

    def _record(self, action, inputs, candidates, decision, cost, reason):
        """Append one structured decision record to the process log,
        the flight recorder's tuning ring, telemetry, and the trace
        timeline, then return it.  This is the ONLY way a controller
        reports — a decision that is not recorded did not happen."""
        rec = {
            "kind": "autotune_decision",
            "controller": self.name,
            "t": time.time(),
            "mode": self.mode,
            "action": action,
            "inputs": dict(inputs),
            "candidates": list(candidates),
            "decision": dict(decision),
            "cost": dict(cost),
            "reason": str(reason),
        }
        with _log_lock:
            _decisions.append(rec)
        _flight.get_recorder().note_decision(rec)
        _telemetry.counter(
            "autotune.decisions.%s.%s" % (self.name, action),
            help="autotune decisions by controller and action").inc()
        if _tracing.is_recording():
            _tracing.emit_instant(
                "autotune:%s" % self.name, category="autotune",
                args={"action": action, "reason": rec["reason"]})
        _module_logger(__name__).info(
            "autotune[%s] %s (%s): %s", self.name, action, rec["mode"],
            rec["reason"])
        return rec


# -- 1. serving bucket set ----------------------------------------------------

def expected_padded_rows(rows_hist, buckets):
    """Estimated padding rows PER REQUEST if traffic shaped like
    ``rows_hist`` (a ``serving.request_rows`` histogram snapshot) were
    dispatched one request per batch through ``buckets``.  Each
    histogram bucket's observations are represented by the clamped
    midpoint of its (lo, hi] range — an estimate by construction, used
    to rank candidate bucket sets, while the smoke measures the real
    ``serving.padded_rows_total`` delta."""
    total = rows_hist.get("count", 0)
    if not total or not buckets:
        return None
    mn = _telemetry._snap_bound(rows_hist, "min")
    mx = _telemetry._snap_bound(rows_hist, "max")
    top = sorted(buckets)
    padded = 0.0
    for lo, hi, n in _telemetry.iter_bucket_ranges(rows_hist):
        rep = (lo + hi) / 2.0
        if mn is not None:
            rep = max(rep, mn)
        if mx is not None:
            rep = min(rep, mx)
        target = next((b for b in top if rep <= b), top[-1])
        padded += n * max(0.0, target - rep)
    return padded / total


class ServingBucketTuner(Controller):
    """Traffic-shaped serving buckets from the admission row histogram.

    Reads ``serving.request_rows`` (recorded per admitted request),
    places candidate bucket edges at the configured quantiles of the
    observed distribution (shared estimator:
    ``telemetry.quantile_from_snapshot``), always topped by the model's
    ``max_batch_size`` so every admissible request still fits.  The
    candidate set is validated against the per-bucket memprof
    footprints (``ServedModel.bucket_memory``, scaled per row) vs the
    device ``bytes_limit`` BEFORE it can be applied; an over-capacity
    set is rejected with a logged decision, never staged.  Apply mode
    stages the set via :meth:`ServedModel.stage_buckets` — the swap
    happens inside the next ``warmup()``/``prewarm()``, which traces
    every new bucket, so steady-state serving never retraces.
    """

    name = "serving_buckets"

    QUANTILES = (0.25, 0.5, 0.75, 0.9, 0.99)

    def __init__(self, mode=None, quantiles=QUANTILES, min_samples=16):
        super().__init__(mode=mode)
        self._quantiles = tuple(float(q) for q in quantiles)
        self._min_samples = int(min_samples)

    def run(self, model, rows_hist=None, bytes_limit=None):
        if not self.active:
            return None
        if rows_hist is None:
            # the per-model series is the honest input on a shared
            # server (another model's traffic must not shape this
            # model's buckets); the process-wide series is the
            # single-model fallback
            snap = _telemetry.snapshot()
            rows_hist = snap.get("serving.request_rows.%s" % model.name) \
                or snap.get("serving.request_rows") or {}
        count = rows_hist.get("count", 0) or 0
        current = [int(b) for b in model.buckets]
        inputs = {"model": model.name, "requests": int(count),
                  "rows_min": rows_hist.get("min"),
                  "rows_max": rows_hist.get("max"),
                  "current_buckets": current,
                  "max_batch_size": int(model.max_batch_size)}
        if count < self._min_samples:
            return self._record(
                "skip", inputs, [], {"buckets": current, "staged": False},
                {"retraces": 0},
                "insufficient traffic: %d admitted request(s) recorded, "
                "need >= %d" % (count, self._min_samples))
        qvals = {("q%g" % q): _telemetry.quantile_from_snapshot(
            rows_hist, q) for q in self._quantiles}
        inputs["quantiles"] = {k: round(v, 3) for k, v in qvals.items()}
        # several quantiles can interpolate into ONE log2 histogram
        # bucket and propose near-adjacent edges (e.g. 5/6/7/8 all from
        # (4, 8]).  That ladder is kept deliberately: the histogram
        # cannot say WHERE inside the bucket the mass sits, and each
        # rung bounds the worst-case padding for that uncertainty at
        # one row — insurance priced at one compiled program per edge,
        # bounded by len(quantiles)+1 total and charged against device
        # capacity by the footprint validation below.
        proposed = sorted({
            min(int(model.max_batch_size), max(1, int(math.ceil(v))))
            for v in qvals.values() if v > 0})
        if not proposed or proposed[-1] != int(model.max_batch_size):
            proposed.append(int(model.max_batch_size))
        est_cur = expected_padded_rows(rows_hist, current)
        est_new = expected_padded_rows(rows_hist, proposed)
        footprint = self._estimate_footprint(model, proposed)
        if bytes_limit is None:
            from . import memprof as _memprof
            limits = [d["bytes_limit"] for d in _memprof.device_memory()
                      if d.get("bytes_limit")]
            bytes_limit = int(limits[0]) if limits else None
        inputs["bytes_limit"] = bytes_limit
        candidate = {"buckets": proposed,
                     "est_padded_rows_per_request": est_new,
                     "estimated_footprint_bytes": footprint}
        reduction = None
        if est_cur and est_new is not None:
            reduction = round(1.0 - est_new / est_cur, 4)
        decision = {"buckets": current, "staged": False,
                    "est_padded_rows_per_request_current": est_cur,
                    "est_padding_reduction_frac": reduction}
        if proposed == current:
            # ordered before the footprint rail: a no-op candidate is a
            # hold, not a capacity rejection an auditor would act on
            return self._record(
                "hold", inputs, [candidate], decision, {"retraces": 0},
                "traffic-shaped set equals the current bucket set %s"
                % (current,))
        if bytes_limit and footprint and footprint > bytes_limit:
            return self._record(
                "reject", inputs, [candidate], decision,
                {"retraces": 0},
                "candidate bucket set %s estimated at %d bytes exceeds "
                "device bytes_limit %d — not applied"
                % (proposed, footprint, bytes_limit))
        if est_cur is not None and est_new is not None \
                and est_new >= est_cur:
            # a change the evidence cannot justify is not made: the
            # incumbent (possibly hand-tuned) set already pads less
            return self._record(
                "hold", inputs, [candidate], decision, {"retraces": 0},
                "shaped set %s would not beat the current set %s "
                "(estimated padding %.2f vs %.2f rows/request)"
                % (proposed, current, est_new, est_cur))
        decision["buckets"] = proposed
        if self.mode == "apply":
            model.stage_buckets(proposed)
            decision["staged"] = True
            action = "apply"
            reason = ("staged bucket set %s (from %s) for adoption at "
                      "the next warmup()/prewarm(); estimated padding "
                      "%.2f -> %.2f rows/request"
                      % (proposed, current, est_cur or 0.0,
                         est_new or 0.0))
        else:
            action = "recommend"
            reason = ("bucket set %s would cut estimated padding %.2f "
                      "-> %.2f rows/request vs %s"
                      % (proposed, est_cur or 0.0, est_new or 0.0,
                         current))
        return self._record(action, inputs, [candidate], decision,
                            {"retraces": 0}, reason)

    @staticmethod
    def _estimate_footprint(model, buckets):
        """Estimated device bytes of ``buckets`` from the measured
        per-bucket footprints (warmup under ``MXNET_TPU_MEMPROF=1``):
        widest argument block once (bucket predictors share weights) +
        per-row temp+output scaled to each candidate bucket.  None when
        nothing was measured — validation then has no evidence and the
        candidate proceeds (the warmup footprint-vs-capacity report is
        the backstop)."""
        bm = getattr(model, "bucket_memory", None) or {}
        measured = {int(b): v for b, v in bm.items()
                    if v.get("total_bytes")}
        if not measured:
            return None
        per_row = max(
            (v.get("temp_bytes", 0) + v.get("output_bytes", 0))
            / float(b) for b, v in measured.items())
        arg = max(v.get("argument_bytes", 0) for v in measured.values())
        return int(arg + sum(b * per_row for b in buckets))


# -- 2. io-pipeline worker count ----------------------------------------------

class IoWorkerTuner(Controller):
    """Recommend io-pipeline worker counts from the starvation ratio.

    Numerator preference: ``io_pipeline.queue_wait_ms`` (the pipeline's
    own consumer wait), else ``io.next_batch_wait_ms`` (plain DataIter
    consumers), else the fit loop's ``module.step.data_wait_ms``;
    denominator ``module.step.total_ms``.  Above ``high`` (default 5%)
    the step is input-bound: double the workers (capped at the core
    count — workers beyond cores only thrash, docs/io_pipeline.md).
    Below ``low`` (default 0.5%) with more than one worker, release one
    core back to compute.  Apply mode sets ``MXNET_TPU_IO_WORKERS``,
    which the next pipeline construction reads — no live pipeline is
    ever resized (that would reorder its deterministic batch sequence).
    """

    name = "io_workers"

    WAIT_SOURCES = ("io_pipeline.queue_wait_ms", "io.next_batch_wait_ms",
                    "module.step.data_wait_ms")

    def __init__(self, mode=None, high=0.05, low=0.005):
        super().__init__(mode=mode)
        self._high = float(high)
        self._low = float(low)

    def run(self, snapshot=None, current_workers=None, cores=None):
        if not self.active:
            return None
        snap = snapshot if snapshot is not None else _telemetry.snapshot()
        step = snap.get("module.step.total_ms") or {}
        step_ms = step.get("sum", 0.0) or 0.0
        steps = step.get("count", 0) or 0
        wait_ms, source = 0.0, None
        for name in self.WAIT_SOURCES:
            h = snap.get(name)
            if h and h.get("count"):
                wait_ms, source = h.get("sum", 0.0) or 0.0, name
                break
        if current_workers is None:
            from ..io_pipeline.executor import default_num_workers
            current_workers = default_num_workers()
        current_workers = max(1, int(current_workers))
        cores = max(1, int(cores if cores is not None
                           else (os.cpu_count() or 1)))
        inputs = {"wait_ms": round(wait_ms, 3),
                  "step_ms": round(step_ms, 3), "steps": int(steps),
                  "signal": source, "current_workers": current_workers,
                  "cores": cores, "high": self._high, "low": self._low}
        if not steps or not step_ms or source is None:
            return self._record(
                "skip", inputs, [],
                {"workers": current_workers, "applied": False},
                {"retraces": 0},
                "no step/io-wait telemetry recorded — run a training "
                "window first")
        ratio = wait_ms / step_ms
        inputs["starvation_ratio"] = round(ratio, 5)
        decision = {"workers": current_workers, "applied": False}
        if ratio > self._high:
            target = min(cores, max(current_workers + 1,
                                    current_workers * 2))
            if target <= current_workers:
                return self._record(
                    "hold", inputs, [], decision, {"retraces": 0},
                    "starvation %.1f%% but already at the core count "
                    "(%d workers / %d cores)"
                    % (ratio * 100.0, current_workers, cores))
            reason = ("starvation %.1f%% > %.1f%%: %d -> %d workers"
                      % (ratio * 100.0, self._high * 100.0,
                         current_workers, target))
        elif ratio < self._low and current_workers > 1:
            target = current_workers - 1
            reason = ("starvation %.2f%% < %.2f%%: release one worker "
                      "core back to compute (%d -> %d)"
                      % (ratio * 100.0, self._low * 100.0,
                         current_workers, target))
        elif ratio < self._low:
            return self._record(
                "hold", inputs, [], decision, {"retraces": 0},
                "starvation %.2f%% below %.2f%% but already at a "
                "single worker — nothing to release"
                % (ratio * 100.0, self._low * 100.0))
        else:
            return self._record(
                "hold", inputs, [], decision, {"retraces": 0},
                "starvation %.2f%% within the [%.2f%%, %.1f%%] band"
                % (ratio * 100.0, self._low * 100.0, self._high * 100.0))
        candidate = {"workers": target}
        decision["workers"] = target
        if self.mode == "apply":
            os.environ["MXNET_TPU_IO_WORKERS"] = str(target)
            decision["applied"] = True
            return self._record("apply", inputs, [candidate], decision,
                                {"retraces": 0},
                                reason + " (MXNET_TPU_IO_WORKERS set; "
                                "takes effect at the next pipeline)")
        return self._record("recommend", inputs, [candidate], decision,
                            {"retraces": 0}, reason)
