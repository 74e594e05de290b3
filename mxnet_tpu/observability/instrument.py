"""Hot-path instrumentation used by the framework itself.

Everything here runs on the host, OUTSIDE jitted bodies — adding or
removing instrumentation must never change a traced program (the
exec-cache trace counters in ``tests/test_step_phases.py`` hold that
line).

- ``StepTracker``: the per-step breakdown behind ``BaseModule.fit``.
  Each training step decomposes into the five components a production
  stack asks about first — ``data_wait`` (input starvation),
  ``fwd_bwd_dispatch``, ``update``, ``metric``, ``sync`` — each emitted
  as a child span of an enclosing ``step`` span and observed into
  fixed-bucket histograms.  The step span's extent is [first component
  start, last component end], so the components cover it up to pure
  python glue.  Inside the components, ``phase`` names where the work
  happens (``fused:load``, ``sync:callbacks``; ``decode:*`` in
  ``PagedTransformerDecoder.step``, which drives a tracker of its own);
  every live component and phase is an ``mx:``-prefixed annotation on
  the profiler's clock; the tracker counts the time the device had
  nothing to run under each (``starved``) and keeps one record a step in
  memory (``recent_steps``).
- ``note_io_wait``: every ``DataIter.__next__`` reports how long the
  consumer waited for the batch (the numerator of the input-starvation
  ratio ``tools/traceview.py`` prints).
- ``record_kv``: kvstore push/pull bytes + latency.
- ``sample_device_memory``: the live-bytes + peak-bytes gauges, sampled
  every ``MXNET_TPU_MEM_SAMPLE_STEPS`` steps (default 10) by the
  tracker (and on demand); the latest sample is kept host-side
  (``last_memory_sample``) so flight-recorder step records carry the
  memory trend into post-mortem dumps.
"""
from __future__ import annotations

import collections
import logging
import os
import re
import threading
import time

import numpy as np

from . import telemetry
from . import tracing

# device-memory gauge sampling cadence, in training steps (the
# MXNET_TPU_MEM_SAMPLE_STEPS default)
DEFAULT_MEM_SAMPLE_STEPS = 10
_MEM_STEPS_ENV = "MXNET_TPU_MEM_SAMPLE_STEPS"
_mem_env_warned = False


def mem_sample_steps():
    """The device-memory sampling cadence in training steps: the
    ``MXNET_TPU_MEM_SAMPLE_STEPS`` env (clamped to >= 1), default 10.
    A malformed value warns once and falls back to the default — the
    same never-take-the-run-down posture as ``MXNET_TPU_FLIGHT_STEPS``.
    Re-read per ``StepTracker`` (i.e. per epoch), so tests and tools
    can flip it without a process restart."""
    global _mem_env_warned
    raw = os.environ.get(_MEM_STEPS_ENV, "")
    if not raw:
        return DEFAULT_MEM_SAMPLE_STEPS
    try:
        return max(1, int(raw))
    except ValueError:
        if not _mem_env_warned:
            _mem_env_warned = True
            logging.getLogger("mxnet_tpu").warning(
                "ignoring malformed %s=%r (want an integer); using %d",
                _MEM_STEPS_ENV, raw, DEFAULT_MEM_SAMPLE_STEPS)
        return DEFAULT_MEM_SAMPLE_STEPS

# tools/traceview.py carries an import-free pinned copy of this tuple —
# keep the two in sync when adding a component
STEP_COMPONENTS = ("data_wait", "fwd_bwd_dispatch", "update", "metric",
                   "sync")

# how many closed steps (iterations) each ring of ``recent_steps`` keeps
RECENT_STEPS = 4096

TrackerNames = collections.namedtuple(
    "TrackerNames", "series counters step glue components sample_memory")
TrackerNames.__doc__ = """What one host loop calls its tracker's output:
``series`` prefixes the histograms (``<series>.<component>_ms``,
``.phase.<phase>_ms``, ``.total_ms``, ``.starved_ms``,
``.starved.<name>_ms``), ``counters`` the counters (``<counters>.steps``,
``.steps_run_ahead``); ``step`` names the enclosing span (Chrome event,
``mx:<step>`` annotation), ``glue`` the starved time outside every
component and phase; ``components`` are the top-level names
``component()`` accepts."""

FIT = TrackerNames("module.step", "module", "step", "step:glue",
                   STEP_COMPONENTS, True)
# ``decode:between_calls`` is the caller's loop between two ``step()``s
DECODE = TrackerNames("serving.decode", "serving.decode", "decode:iter",
                      "decode:between_calls", (), False)

_recent = {}                # tracker pid -> deque of step records
_tls = threading.local()    # .tracker: the one with a span open here


def recent_steps(kind="train"):
    """The last ``RECENT_STEPS`` step records of the trackers whose
    ``pid`` is ``kind`` (``"train"``: ``BaseModule.fit``; ``"serving"``:
    ``PagedTransformerDecoder.step``), oldest first.  One record a step:
    ``{step, epoch, end_s, total_ms, components_ms, phases_ms,
    starved_ms, starved_by_ms, ran_ahead}`` — ``end_s`` is the
    ``time.perf_counter()`` instant the step's last component or phase
    closed; ``starved_ms`` is None for a loop that notes no dispatch.
    Kept in memory only; nothing on the hot path touches the disk."""
    return list(_recent.get(kind, ()))


def phase(name, dispatches=False, drains=False):
    """A phase of whichever tracker has a span open on this thread (the
    fit loop's, reached from ``FusedTrainStep.run``); the shared no-op
    when there is none or both its sinks are off."""
    tracker = getattr(_tls, "tracker", None)
    if tracker is None:
        return _NOOP_CM
    return tracker.phase(name, dispatches, drains)


class _NoopSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def watch(self, outputs, inputs=()):
        pass


_NOOP_CM = _NoopSpan()


def _ms(ns):
    return round(ns / 1e6, 4)


def _all_ready(arrays):
    """Non-blocking: every array's buffer is there.  A deleted (donated)
    array has been consumed, which is as good as ready."""
    try:
        return all(a.is_ready() for a in arrays)
    except RuntimeError:
        return True


class _Span:
    """One occurrence of a component or a phase: accumulates into the
    tracker, is an ``mx:<name>`` annotation on the profiler's clock, and
    emits a Chrome complete-event when ``mx.profiler`` is recording."""

    __slots__ = ("_tracker", "name", "_key", "_dispatches", "_drains",
                 "_t0", "_ann", "_parent", "_watch", "_inputs")

    def __init__(self, tracker, name, key, dispatches, drains):
        self._tracker = tracker
        self.name = name
        self._key = key             # the component's bare name, or None
        self._dispatches = dispatches
        self._drains = drains
        self._watch = self._inputs = ()

    def watch(self, outputs, inputs=()):
        """Inside a ``dispatches`` phase: the arrays whose ``is_ready()``
        says the dispatched program has finished (``outputs``) and has
        what it needs to start (``inputs``, uploads still in flight)."""
        self._watch, self._inputs = tuple(outputs), tuple(inputs)

    def __enter__(self):
        self._tracker._enter(self)
        return self

    def __exit__(self, exc_type, exc, tb):
        self._tracker._exit(self, exc_type is not None)
        return False


class StepTracker:
    """Per-step breakdown of one host loop that feeds the device: one
    epoch of ``BaseModule.fit`` (``names=FIT``), or the life of a
    ``PagedTransformerDecoder`` (``names=DECODE``).

    Usage (the shape ``BaseModule._run_epoch`` drives)::

        tracker = StepTracker(epoch=epoch)
        with tracker.component("data_wait"):
            batch = next(it)
        with tracker.component("fwd_bwd_dispatch"):
            module.forward_backward(batch)   # opens instrument.phase(..)
        ...
        tracker.step_end(nbatch)

    ``component`` calls may repeat within a step ("sync" does); the
    durations accumulate.  A ``phase`` is a named stretch inside a
    component (or straight under the step) where the work happens:
    ``fused:load``, ``sync:callbacks``, ``decode:tables``.  Both are
    ``mx:``-prefixed annotations on the profiler's clock, nested inside
    ``mx:<step>``.  ``step_end`` emits the enclosing ``step`` span
    (first-component-start to last-component-end, per-component
    millisecond args), feeds the histograms, appends the step's record
    to ``recent_steps`` and samples the device-memory gauges every
    ``MXNET_TPU_MEM_SAMPLE_STEPS`` steps (default 10).

    **Device starvation.**  The tracker keeps the step programs in
    flight: one more when a ``dispatches`` phase returns, none once the
    newest one's watched output reads ready at a component or phase
    boundary (a non-blocking ``is_ready()``: no sync is added) or a
    ``drains`` phase (the host fetched the results) returns.  Time with
    nothing in flight is *starved*: the device had nothing to run.  It
    is charged to the innermost span open, or to ``names.glue`` between
    spans.  Readiness is only seen at boundaries, so a starved stretch
    opens at the first boundary after the device finished and — where
    the dispatched program still waits for an upload — closes at the
    last boundary that saw the upload unfinished: the figure is a lower
    bound by at most the two spans the device's edges fell in.  A step
    dispatched while an earlier one is in flight ran ahead
    (``steps_run_ahead``): it adds no starved time, and the starved
    figure is then a lower bound for a second reason — the host cannot
    see the device drain and refill between the two.
    """

    def __init__(self, epoch=0, pid="train", names=FIT,
                 clock_ns=time.perf_counter_ns):
        self.epoch = epoch
        self.pid = pid
        self.names = names
        self._clock = clock_ns
        # Chrome events carry wall-clock microseconds: one anchor maps
        # the monotonic clock onto them, so a boundary reads one clock
        self._wall0_us = tracing.now_us() - clock_ns() / 1e3
        self._mem_every = mem_sample_steps()
        self._ring = _recent.setdefault(
            pid, collections.deque(maxlen=RECENT_STEPS))
        self._open = []             # spans open now, outermost first
        self._mark = None           # the last boundary, ns
        self._in_flight = collections.deque()   # watched outputs
        self._launching = None      # uploads the newest dispatch awaits
        self._device_seen = False   # a dispatch was noted: starved counts
        self._starved = {}          # name -> ns, this step
        self._starved_top = {}      # outermost span -> ns, this step
        self._index = 0             # steps closed so far
        self._outer = None          # the thread's tracker before this one
        self._resolve_handles()
        self._reset_step()

    def _resolve_handles(self):
        """(Re)fetch the registry instruments.  Keyed on the registry
        epoch so a telemetry.reset() mid-epoch (snapshot-then-reset
        scrape loops) re-registers instead of observing into orphaned
        instruments — same contract as the io/kv handle caches."""
        self._handle_key = (telemetry.registry_epoch(),
                            telemetry.enabled())
        series, counters = self.names.series, self.names.counters
        # disabled telemetry hands back no-op instruments; component()
        # then short-circuits entirely unless the profiler is recording
        self._hists = {c: telemetry.histogram(
            "%s.%s_ms" % (series, c),
            help="per-step %s time" % c) for c in self.names.components}
        self._lazy_hists = {}       # phase and starved series, by name
        self._hist_total = telemetry.histogram(
            series + ".total_ms", help="measured step wall time")
        self._hist_starved = telemetry.histogram(
            series + ".starved_ms",
            help="per-step time the device had no step program to run "
                 "(a lower bound, see steps_run_ahead)")
        self._steps = telemetry.counter(
            counters + ".steps", help="steps observed")
        self._ran_ahead_total = telemetry.counter(
            counters + ".steps_run_ahead",
            help="steps dispatched while an earlier one was in flight")
        self._mem_gauge = telemetry.gauge(
            "device.live_bytes", help="live device memory (sampled)")
        self._peak_gauge = telemetry.gauge(
            "device.peak_bytes",
            help="allocator peak bytes in use (sampled; backends with "
                 "memory_stats only)")
        self._telemetry_on = self._hist_total is not telemetry.NOOP

    def _lazy_hist(self, kind, name):
        hist = self._lazy_hists.get((kind, name))
        if hist is None:
            hist = self._lazy_hists[kind, name] = telemetry.histogram(
                "%s.%s.%s_ms" % (self.names.series, kind, name))
        return hist

    def _reset_step(self):
        self._parts = {c: 0 for c in self.names.components}
        self._phases = {}
        self._step_t0 = None
        self._last_end = None
        self._step_span_id = None
        self._step_ann = None
        self._ran_ahead = False

    def _live(self):
        # both sinks off: the whole step costs one flag check per
        # component (the module's zero-cost-when-disabled contract)
        return self._telemetry_on or tracing.is_recording()

    def component(self, name):
        if not self._live():
            return _NOOP_CM
        return _Span(self, "step:" + name, name, False, False)

    def phase(self, name, dispatches=False, drains=False):
        if not self._live():
            return _NOOP_CM
        return _Span(self, name, None, dispatches, drains)

    # -- boundaries ----------------------------------------------------------

    def _boundary(self, now):
        """Charge [last boundary, now] and look at the watched arrays."""
        if self._device_seen:
            if self._launching is not None:
                # dispatched, but its upload was unfinished when last
                # seen: starved for as long as it still is
                if _all_ready(self._launching):
                    self._launching = None
                else:
                    self._charge_starved(now - self._mark)
            elif not self._in_flight:
                self._charge_starved(now - self._mark)
            # (nothing watched: only a ``drains`` phase clears it)
            while self._in_flight and self._in_flight[0] \
                    and _all_ready(self._in_flight[0]):
                self._in_flight.popleft()
        self._mark = now

    def _charge_starved(self, ns):
        if ns <= 0:
            return
        inner = self._open[-1].name if self._open else self.names.glue
        top = self._open[0].name if self._open else self.names.glue
        self._starved[inner] = self._starved.get(inner, 0) + ns
        self._starved_top[top] = self._starved_top.get(top, 0) + ns

    def _enter(self, span):
        now = self._clock()
        if self._step_t0 is None:
            # the step opens at its first span; its id is allocated now
            # so children can link to a parent emitted after them
            self._step_t0 = now
            self._step_span_id = next(tracing._span_ids)
            self._step_ann = tracing.annotation(self.names.step,
                                                step=self._index)
            self._step_ann.__enter__()
        self._boundary(now)
        if not self._open:
            span._parent = self.names.step
            self._outer = getattr(_tls, "tracker", None)
            _tls.tracker = self
        else:
            span._parent = self._open[-1].name
        self._open.append(span)
        span._ann = tracing.annotation(span.name, step=self._index,
                                       parent=span._parent)
        span._ann.__enter__()
        span._t0 = now

    def _exit(self, span, failed):
        now = self._clock()
        self._boundary(now)
        span._ann.__exit__(None, None, None)
        self._open.pop()
        if not self._open:
            _tls.tracker = self._outer
        dur = now - span._t0
        if span._key is not None:
            self._parts[span._key] += dur
        else:
            self._phases[span.name] = self._phases.get(span.name, 0) + dur
        self._last_end = now
        if not failed:
            if span._dispatches:
                self._dispatched(span)
            if span._drains:
                self._in_flight.clear()
                self._launching = None
        if tracing.is_recording():
            args = {"parent_id": self._step_span_id}
            if span._key is None:
                args.update(parent=span._parent, step=self._index)
            tracing.emit_complete(
                span.name, self._wall0_us + span._t0 / 1e3, dur / 1e3,
                category="step", pid=self.pid, args=args)

    def _dispatched(self, span):
        self._device_seen = True
        if self._in_flight:
            self._ran_ahead = True
        elif span._inputs and not _all_ready(span._inputs):
            self._launching = span._inputs
        self._in_flight.append(span._watch)

    # -- closing a step ------------------------------------------------------

    def close(self):
        """End the open step's ``mx:<step>`` annotation (``step_end``
        does; the loop's owner calls it when the loop ends mid-step)."""
        if self._step_ann is not None:
            self._step_ann.__exit__(None, None, None)
            self._step_ann = None

    def cancel_step(self):
        """Drop the open step unrecorded (a decoder iteration that found
        nothing to run); starved time it saw goes to ``names.glue``."""
        self.close()
        if self._starved:
            total = sum(self._starved.values())
            self._starved = {self.names.glue: total}
            self._starved_top = {self.names.glue: total}
        self._reset_step()

    def step_end(self, nbatch):
        """Close out the step.  Returns its record (the one appended to
        ``recent_steps``: per-component and per-phase milliseconds,
        total, starved time by name) so callers — the flight recorder —
        keep it, or None when no component ran."""
        if self._handle_key != (telemetry.registry_epoch(),
                                telemetry.enabled()):
            self._resolve_handles()
        if self._step_t0 is None:
            return None
        self.close()
        ms = _ms
        total_ms = ms(self._last_end - self._step_t0)
        record = {"step": nbatch, "epoch": self.epoch,
                  "end_s": self._last_end / 1e9, "total_ms": total_ms,
                  "components_ms": {c: ms(v)
                                    for c, v in self._parts.items()},
                  "phases_ms": {p: ms(v) for p, v in self._phases.items()},
                  "starved_ms": None, "starved_by_ms": {},
                  "ran_ahead": self._ran_ahead}
        for c, v in record["components_ms"].items():
            self._hists[c].observe(v)
        for p, v in record["phases_ms"].items():
            self._lazy_hist("phase", p).observe(v)
        self._hist_total.observe(total_ms)
        self._steps.inc()
        if self._ran_ahead:
            self._ran_ahead_total.inc()
        if self._device_seen:
            record["starved_by_ms"] = {n: ms(v)
                                       for n, v in self._starved.items()}
            record["starved_ms"] = ms(sum(self._starved.values()))
            self._hist_starved.observe(record["starved_ms"])
            for n, v in record["starved_by_ms"].items():
                self._lazy_hist("starved", n).observe(v)
        self._ring.append(record)
        if tracing.is_recording():
            args = {"span_id": self._step_span_id, "step": nbatch,
                    "epoch": self.epoch,
                    "starved_ms": record["starved_ms"],
                    "starved_by_ms": {n: ms(v) for n, v
                                      in self._starved_top.items()}
                    if self._device_seen else {},
                    "ran_ahead": self._ran_ahead}
            for c, v in record["components_ms"].items():
                args[c + "_ms"] = v
            tracing.emit_complete(
                self.names.step, self._wall0_us + self._step_t0 / 1e3,
                (self._last_end - self._step_t0) / 1e3,
                category="step", pid=self.pid, args=args)
        if self.names.sample_memory and nbatch % self._mem_every == 0 \
                and self._live():
            # jax.live_arrays() is O(live arrays) — never pay it when
            # nobody is listening
            sample_device_memory(self._mem_gauge, self._peak_gauge)
        self._starved = {}
        self._starved_top = {}
        self._index += 1
        self._reset_step()
        return record


# the most recent device-memory sample, host-side: flight-recorder
# step records carry it so post-mortem dumps show the memory trend
# leading into an anomaly (traceview --flight renders the sparkline)
_last_mem_sample = None


def sample_device_memory(gauge=None, peak_gauge=None):
    """Total live device bytes: the backend allocator's view when it
    has one (``Device.memory_stats`` on TPU), else the sum over jax's
    live arrays.  Sets the ``device.live_bytes`` gauge — and, where the
    allocator reports ``peak_bytes_in_use``, the ``device.peak_bytes``
    gauge — drops a counter sample onto the trace timeline, stashes the
    sample for ``last_memory_sample``, and returns the live byte
    count."""
    global _last_mem_sample
    total = 0
    peak = None
    try:
        import jax
        stats_seen = False
        for dev in jax.local_devices():
            stats = getattr(dev, "memory_stats", lambda: None)()
            if stats and "bytes_in_use" in stats:
                total += int(stats["bytes_in_use"])
                stats_seen = True
            if stats and "peak_bytes_in_use" in stats:
                peak = (peak or 0) + int(stats["peak_bytes_in_use"])
        if not stats_seen:
            total = sum(getattr(a, "nbytes", 0) for a in jax.live_arrays())
    except Exception:
        return 0
    if gauge is None:
        gauge = telemetry.gauge("device.live_bytes",
                                help="live device memory (sampled)")
    gauge.set(total)
    tracing.emit_counter("device_live_bytes", total, category="memory")
    if peak is not None:
        if peak_gauge is None:
            peak_gauge = telemetry.gauge(
                "device.peak_bytes",
                help="allocator peak bytes in use (sampled; backends "
                     "with memory_stats only)")
        peak_gauge.set(peak)
        tracing.emit_counter("device_peak_bytes", peak, category="memory")
    _last_mem_sample = {"live_bytes": total, "peak_bytes": peak,
                        "t": time.time()}
    return total


def last_memory_sample():
    """The most recent ``sample_device_memory`` result as
    ``{live_bytes, peak_bytes, t}`` (None before the first sample).
    ``peak_bytes`` is None on backends without allocator stats."""
    return dict(_last_mem_sample) if _last_mem_sample else None


# per-batch handles, memoized against the registry epoch + enabled flag
# so the io hot path skips the registry lock (and telemetry.reset() in
# tests still invalidates the cache)
_io_cache = (None, None)


def note_io_wait(seconds):
    """One next-batch wait observed by a DataIter consumer (pooled
    across iterators — the starvation question is per-process)."""
    global _io_cache
    key = (telemetry.registry_epoch(), telemetry.enabled())
    cached_key, handles = _io_cache
    if cached_key != key:
        handles = (
            telemetry.histogram("io.next_batch_wait_ms",
                                help="time blocked waiting for a batch"),
            telemetry.counter("io.batches",
                              help="batches produced by DataIters"),
            telemetry.counter("io.next_batch_wait_total_ms",
                              help="cumulative next-batch wait"))
        _io_cache = (key, handles)
    hist, n_batches, total = handles
    ms = seconds * 1e3
    hist.observe(ms)
    n_batches.inc()
    total.inc(ms)


# io_pipeline handles, memoized like the io cache above: the pipeline's
# consumer wait is the per-stage starvation signal (queue_wait), decode
# and h2d histograms attribute where batch time goes, and h2d_ahead
# counts uploads issued under the previous step's compute (the overlap
# contract ``tests/test_io_pipeline.py`` asserts on)
_pipe_cache = (None, None)


def _pipeline_handles():
    global _pipe_cache
    key = (telemetry.registry_epoch(), telemetry.enabled())
    cached_key, handles = _pipe_cache
    if cached_key != key:
        handles = {
            "queue_wait": telemetry.histogram(
                "io_pipeline.queue_wait_ms",
                help="consumer time blocked waiting on pipeline "
                     "output (the starvation numerator)"),
            "decode": telemetry.histogram(
                "io_pipeline.decode_ms",
                help="per-batch read+decode+assemble time (worker-side)"),
            "h2d": telemetry.histogram(
                "io_pipeline.h2d_ms",
                help="host time issuing the device_put (transfer is "
                     "async)"),
            "batches": telemetry.counter(
                "io_pipeline.batches", help="batches produced"),
            "records": telemetry.counter(
                "io_pipeline.records", help="records decoded"),
            "h2d_ahead": telemetry.counter(
                "io_pipeline.h2d_ahead_total",
                help="uploads issued ahead of consumption (overlapped "
                     "with compute)"),
        }
        _pipe_cache = (key, handles)
    return handles


# waits taken while ARMING an epoch (adapter priming at reset) happen
# outside the fit loop's steps by design — counting them would inflate
# the starvation ratio on healthy runs, so the adapter suppresses them
# for its (consumer) thread while it primes
_pipe_tls = threading.local()


class suppress_pipeline_wait:
    """Context manager: waits on this thread are not starvation."""

    def __enter__(self):
        self._prev = getattr(_pipe_tls, "suppress", False)
        _pipe_tls.suppress = True
        return self

    def __exit__(self, *exc):
        _pipe_tls.suppress = self._prev
        return False


def note_pipeline_wait(seconds):
    """One consumer wait on the pipeline's reorder buffer (the
    numerator of the pipeline starvation ratio).  Returns False when
    suppressed (arm-time priming) so callers skip the matching span."""
    if getattr(_pipe_tls, "suppress", False):
        return False
    h = _pipeline_handles()
    h["queue_wait"].observe(seconds * 1e3)
    h["batches"].inc()
    return True


def note_pipeline_decode(seconds, records):
    h = _pipeline_handles()
    h["decode"].observe(seconds * 1e3)
    h["records"].inc(records)


def note_pipeline_h2d(seconds):
    _pipeline_handles()["h2d"].observe(seconds * 1e3)


def note_pipeline_h2d_ahead():
    _pipeline_handles()["h2d_ahead"].inc()


# generation counter for the pipeline gauges: the gauges are
# process-wide (like every io_pipeline series), so when several runs
# are live the LAST-ARMED one owns them; a run tearing down must only
# zero them if it is still the owner (disarm_pipeline_gauges), or an
# ending eval run would stomp the live train run's gauges
_pipe_gauge_token = 0


def arm_pipeline_gauges(task_depth_fn, reorder_fill_fn):
    """Wire the live per-stage queue-depth gauges to the current epoch
    run.  Re-armed at every run start so the gauges survive a
    telemetry.reset() between epochs; returns a token for
    `disarm_pipeline_gauges`."""
    global _pipe_gauge_token
    _pipe_gauge_token += 1
    telemetry.gauge(
        "io_pipeline.task_queue_depth",
        help="tasks parked for workers").set_function(task_depth_fn)
    telemetry.gauge(
        "io_pipeline.reorder_fill",
        help="completed batches held for in-order release"
    ).set_function(reorder_fill_fn)
    return _pipe_gauge_token


def disarm_pipeline_gauges(token):
    """Zero the gauges (dropping their closures' references to the
    run's queues) — only if ``token`` still owns them."""
    if token == _pipe_gauge_token:
        arm_pipeline_gauges(lambda: 0, lambda: 0)


# -- gradient-collective (comm) accounting -----------------------------------
#
# Host-driven kvstore collectives (dist push/pull, tpu_ici push_pull):
# the step waits on them, so their wall time is real exposed comm;
# recorded with bytes + latency + a ``comm:<op>`` span.  The fused
# step's all-reduce is XLA's, inside the step program: the device trace
# shows it (docs/distributed.md), nothing on the host does.
_comm_cache = (None, None)


def _comm_handles():
    global _comm_cache
    key = (telemetry.registry_epoch(), telemetry.enabled())
    cached_key, handles = _comm_cache
    if cached_key != key:
        handles = {
            "bytes_total": telemetry.counter(
                "comm.bytes_total",
                help="gradient-collective payload bytes contributed by "
                     "this worker"),
            "exposed_bytes": telemetry.counter(
                "comm.exposed_bytes",
                help="bytes moved by host-driven (exposed) collectives"),
            "exposed_ms": telemetry.histogram(
                "comm.exposed_ms",
                help="wall time the step spent blocked on exposed "
                     "collectives"),
        }
        _comm_cache = (key, handles)
    return handles


def record_comm_exposed(op, nbytes, seconds, store_type):
    """One host-driven (exposed) collective: bytes + blocked wall time
    + a ``comm:<op>`` span on the trace timeline."""
    if not (telemetry.enabled() or tracing.is_recording()):
        return
    h = _comm_handles()
    h["bytes_total"].inc(nbytes)
    h["exposed_bytes"].inc(nbytes)
    h["exposed_ms"].observe(seconds * 1e3)
    if tracing.is_recording():
        t1 = tracing.now_us()
        tracing.emit_complete("comm:" + op, t1 - seconds * 1e6,
                              seconds * 1e6, category="comm",
                              args={"bytes": nbytes, "store": store_type})


# push/pull handles, memoized per op against the registry epoch +
# enabled flag (kvstore traffic is per key-batch per step — same
# registry-lock-avoidance as the io cache above)
_kv_cache = (None, {})


def _kv_handles(op):
    global _kv_cache
    key = (telemetry.registry_epoch(), telemetry.enabled())
    cached_key, by_op = _kv_cache
    if cached_key != key:
        by_op = {}
        _kv_cache = (key, by_op)
    handles = by_op.get(op)
    if handles is None:
        handles = (
            telemetry.counter("kvstore.%s_bytes" % op,
                              help="payload bytes moved by %s" % op),
            telemetry.histogram("kvstore.%s_ms" % op,
                                help="%s wall latency" % op))
        by_op[op] = handles
    return handles


def record_kv(op, payload, seconds, store_type):
    """One kvstore push/pull: payload bytes + wall latency.  Takes the
    raw payload (NDArray / nested lists) and only walks its shapes when
    a sink is actually listening."""
    if not (telemetry.enabled() or tracing.is_recording()):
        return
    nbytes = payload_nbytes(payload)
    ms = seconds * 1e3
    bytes_counter, latency_hist = _kv_handles(op)
    bytes_counter.inc(nbytes)
    latency_hist.observe(ms)
    if tracing.is_recording():
        t1 = tracing.now_us()
        tracing.emit_complete("kvstore_" + op, t1 - seconds * 1e6,
                              seconds * 1e6, category="kvstore",
                              args={"bytes": nbytes,
                                    "store": store_type})


def payload_nbytes(value):
    """Total bytes of an NDArray / nested list-of-NDArrays payload
    (host-side metadata walk; no device sync)."""
    total = 0
    stack = [value]
    while stack:
        v = stack.pop()
        if isinstance(v, (list, tuple)):
            stack.extend(v)
            continue
        shape = getattr(v, "shape", None)
        if shape is None:
            continue
        n = 1
        for d in shape:
            n *= int(d)
        dtype = getattr(v, "dtype", None)
        try:
            itemsize = np.dtype(dtype).itemsize if dtype is not None else 4
        except TypeError:
            itemsize = 4
        total += n * itemsize
    return total


# -- device time by mechanism: from compiled op to ``mx:`` scope -----------------
#
# Every op of a step program is traced under ``jax.named_scope`` tokens
# ``mx:<mechanism>[:<detail>]`` (docs/observability.md §1), and XLA keeps the
# scope path of every instruction it compiles (fusions by their root) in the
# instruction's ``op_name``.  A device trace names its events by those same
# instructions, so the table made here, joined with a trace's seconds per
# event, is device time by mechanism.

_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_OPERAND = re.compile(r"%([\w.\-]+)")
# instructions that run other computations: a trace's event of one spans the
# events of what it calls
_HLO_HOLDS = re.compile(r"\s(?:while|conditional|call)\(")
_SCOPE_TOKEN = re.compile(r"mx:[\w:.\-]+")
_op_scopes = {}     # program label -> its table, kept past the program's life


def scope_of_op_name(op_name):
    """``{"path", "mechanism", "detail", "pass"}`` of one instruction's
    ``op_name`` (``jit(_step)/transpose(jvp(mx:mtp/mx:moe))/mx:moe:gather/
    gather``): ``path`` its ``mx:`` tokens joined by ``/``, ``detail`` the
    LAST of them, ``mechanism`` that token's first two fields (``mx:moe`` of
    ``mx:moe:gather``; both None with no token), ``pass`` ``"recomputed"``
    under ``rematted_computation`` (the forward run again by
    ``jax.checkpoint``), else ``"backward"`` under ``transpose(`` (JAX puts
    it on the ops of a ``custom_vjp``'s backward rule too), else
    ``"forward"``."""
    tokens = _SCOPE_TOKEN.findall(op_name)
    detail = tokens[-1] if tokens else None
    return {"path": "/".join(tokens),
            "mechanism": ":".join(detail.split(":")[:2]) if detail else None,
            "detail": detail,
            "pass": "recomputed" if "rematted_computation" in op_name
            else "backward" if "transpose(" in op_name
            else "forward"}


def scopes_of_hlo(text):
    """{instruction name: ``scope_of_op_name`` of it} for every instruction of
    every computation in compiled-HLO ``text`` (``compile().as_text()``).
    An instruction whose own ``op_name`` names no scope — a relayout copy or
    an async copy XLA put in, the Mosaic call it makes of a grouped product,
    a parameter — is what XLA made FOR a neighbour, and takes the row of the
    nearest instruction of its computation that has a scope: the first one
    that reads its result, through others like it, else the first it reads
    (``"own": False`` marks such a row); with no such neighbour it maps to no
    mechanism.  Instructions of one ``op_name`` share one row.  A ``while``,
    a ``conditional`` or a ``call`` carries ``"holds": True``: a trace's
    event of it spans the events of the computations it runs, whose seconds
    are theirs (``device_seconds_by_scope`` leaves the holder's out)."""
    rows, table, body, holders = {}, {}, [], []

    def nearest(name, edges):
        """The row of the nearest instruction along ``edges`` that names a
        scope itself, reached through those that do not."""
        seen, queue = {name}, collections.deque([name])
        while queue:
            at = queue.popleft()
            if table[at]["mechanism"] is not None \
                    and table[at].get("own", True):
                return table[at]
            for nxt in edges.get(at, ()):
                if nxt not in seen:
                    seen.add(nxt)
                    queue.append(nxt)
        return None

    def close():
        """Resolve the scope-less instructions of the computation read."""
        reads = {n: [o for o in ops if o in table] for n, ops in body}
        read_by = {}
        for n, ops in reads.items():
            for o in ops:
                read_by.setdefault(o, []).append(n)
        for name, _ in body:
            if table[name]["mechanism"] is None:
                found = nearest(name, read_by) or nearest(name, reads)
                if found:
                    table[name] = dict(found, own=False)
        del body[:]

    for line in text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m is None:
            if line.startswith("}"):
                close()
            continue
        found = _HLO_OP_NAME.search(line, m.end())
        op_name = found.group(1) if found else ""
        if op_name not in rows:
            rows[op_name] = scope_of_op_name(op_name)
        table[m.group(1)] = rows[op_name]
        end = found.start() if found else len(line)
        body.append((m.group(1), _HLO_OPERAND.findall(line, m.end(), end)))
        if _HLO_HOLDS.search(line, m.end(), end):
            holders.append(m.group(1))
    close()
    for name in holders:
        table[name] = dict(table[name], holds=True)
    return table


def capture_device_op_scopes(label, table):
    """Keep a program's ``op_scopes()`` under its ``label`` for
    ``device_op_scopes`` (a later capture of the label replaces it): a plain
    dict that outlives the program and the module that held it."""
    if table:
        _op_scopes[label] = table


def device_op_scopes():
    """The union of the captured tables: {instruction name: scope row}."""
    merged = {}
    for table in _op_scopes.values():
        merged.update(table)
    return merged


def device_seconds_by_scope(op_seconds, scopes=None):
    """Device seconds by mechanism: ``op_seconds`` {instruction name:
    seconds} (a trace event's whole name will do: ``%fusion.4 = ...`` and
    ``fusion.4 fusion`` both name ``fusion.4``) summed into rows
    ``{"mechanism", "detail", "pass", "seconds"}`` through ``scopes`` (the
    captured tables where not given).  A partition: the rows sum to the
    input, and what no table names (an op of another program in the window)
    lands in the row whose three fields are None.  Left out of both: the
    events of instructions that hold others (``"holds"``: a ``while`` loop's
    event spans its body's, which are in the input themselves)."""
    table = device_op_scopes() if scopes is None else scopes
    sums = {}
    for name, seconds in op_seconds.items():
        row = table.get(name.lstrip("%").split(" ", 1)[0])
        if row and row.get("holds"):
            continue
        key = (row["mechanism"], row["detail"], row["pass"]) if row \
            else (None, None, None)
        sums[key] = sums.get(key, 0.0) + float(seconds)
    return [{"mechanism": m, "detail": d, "pass": p, "seconds": s}
            for (m, d, p), s in sums.items()]


# -- the fused step's input ----------------------------------------------------

def note_step_input(staged):
    """``module.input.staged``: the step took its batch from the upload
    that ``Module.prepare`` started a step ahead (``fused:stage``);
    ``module.input.loaded``: it placed the batch at dispatch
    (``fused:load``) — the first step of an epoch, a loop that never
    calls ``prepare``, a batch other than the staged one, or one that was
    on the step's devices already."""
    telemetry.counter(
        "module.input.staged" if staged else "module.input.loaded",
        help="fused steps by where their batch's upload began").inc()


# -- sparse experts, recomputation, attention (models/qwen3_next.py, trinity.py)

def note_recompute_blocks(blocks):
    """``module.recompute.blocks``: the mirror stages (blocks evaluated
    under ``jax.checkpoint``) of the step program just dispatched."""
    if blocks:
        telemetry.counter(
            "module.recompute.blocks",
            help="blocks the step programs recompute in their backward "
                 "pass").inc(blocks)


def note_attention_pairs(computed, visible):
    """``module.attn.pairs_computed`` / ``module.attn.pairs_visible``: the
    (query, key) pairs whose score the attention nodes of the step program
    just dispatched compute, masked or not, in their forward and backward
    schedules, and the pairs their masks let through, once each way
    (``_Program.attention_pairs``: static per program)."""
    if visible:
        telemetry.counter(
            "module.attn.pairs_computed",
            help="(query, key) scores the step programs' attention "
                 "schedules compute, forward and backward").inc(computed)
        telemetry.counter(
            "module.attn.pairs_visible",
            help="(query, key) pairs the attention masks let through, "
                 "once each way").inc(visible)


def note_gdn_chunk_steps(steps, in_kernel, local_in_kernel):
    """``module.gdn.chunk_steps`` / ``module.gdn.chunk_steps_in_kernel`` /
    ``module.gdn.local_chunks_in_kernel``: the chunk steps (chunks x batch x
    key heads x passes: forward, forward again where the block is
    recomputed, backward) of the ``gated_delta_rule`` nodes of the step
    program just dispatched, those of them that run inside the Pallas
    kernels ``gdn_scan_fwd`` / ``gdn_scan_bwd`` and not as bodies of a
    ``lax.scan``, and those whose chunk-local part runs inside
    ``gdn_local_fwd`` / ``gdn_local_bwd`` and not as XLA's
    ``_chunk_local`` (``_Program.gdn_chunk_steps``: static per program; 0
    on the fallback)."""
    if steps:
        telemetry.counter(
            "module.gdn.chunk_steps",
            help="chunk steps of the step programs' delta-rule scans, "
                 "forward, recomputed and backward").inc(steps)
        telemetry.counter(
            "module.gdn.chunk_steps_in_kernel",
            help="chunk steps that run inside the Pallas scan "
                 "kernels").inc(in_kernel)
        telemetry.counter(
            "module.gdn.local_chunks_in_kernel",
            help="chunk steps whose chunk-local part runs inside the "
                 "Pallas local kernels").inc(local_in_kernel)


def note_ssm_lowering(in_kernel):
    """``ops.ssm.lowered_kernel`` / ``ops.ssm.lowered_xla``: one lowering of
    Mamba-2's recurrence (``lm_ops.chunked_gated_delta_rule`` without its
    correction, the ``ssd`` op) into a program being traced, through the
    Pallas kernels ``ssd_scan_fwd`` / ``ssd_scan_bwd`` or through the
    ``lax.scan`` fallback; counted as it is traced, so a program that falls
    back shows without a device trace."""
    telemetry.counter(
        "ops.ssm.lowered_kernel" if in_kernel else "ops.ssm.lowered_xla",
        help="lowerings of the state-space recurrence by path").inc()


def note_counter_rows(rows, names):
    """One step's value of the counters a model's output names
    (``__counters__``, read where the loss is read): row ``i`` of ``rows``,
    averaged over what it holds (the batch), is added to counter
    ``names[i]`` — ``module.lm.loss_main`` / ``module.lm.loss_mtp``, the two
    parts of a loss with a second head, summed over steps."""
    rows = np.asarray(rows, np.float64).reshape(len(names), -1)
    for name, row in zip(names, rows):
        telemetry.counter(name).inc(float(row.mean()))


def note_moe_counts(counts, first_expert, experts_held):
    """One step's routing, from the per-expert selection counts the step
    program hands out ([layers, experts] or [experts]; read where the loss
    is read, so no sync is added): ``module.moe.selections_total`` /
    ``_held`` (all top-k choices, and those that fell on the experts held
    here), ``module.moe.expert_load_max`` / ``_mean`` (tokens on the
    busiest held expert and on the average one, summed over layers).  And
    what ``moe_experts`` moved for them: ``module.moe.rows_live`` (the held
    choices), ``module.moe.rows_moved`` (the rounds each layer took times
    the capacity of a round: the rows gathered, multiplied and added a
    pass), ``module.moe.overflow_rounds`` (rounds beyond a layer's first:
    0 while every layer's held choices fit one round)."""
    from ..ops.lm_ops import moe_capacity, moe_rounds
    counts = np.asarray(counts, np.float64).reshape(-1, counts.shape[-1])
    held = counts[:, first_expert:first_expert + experts_held]
    caps = [moe_capacity(total, experts_held, counts.shape[1])
            for total in counts.sum(axis=1)]
    rounds = [moe_rounds(live, cap)
              for live, cap in zip(held.sum(axis=1), caps)]
    for name, value in (("selections_total", counts.sum()),
                        ("selections_held", held.sum()),
                        ("expert_load_max", held.max(axis=1).sum()),
                        ("expert_load_mean", held.mean(axis=1).sum()),
                        ("rows_live", held.sum()),
                        ("rows_moved",
                         sum(r * cap for r, cap in zip(rounds, caps))),
                        ("overflow_rounds",
                         sum(max(r - 1, 0) for r in rounds))):
        telemetry.counter("module.moe." + name).inc(float(value))
