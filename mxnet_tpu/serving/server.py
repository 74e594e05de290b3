"""The serving front-end: futures API + optional stdlib HTTP endpoint.

``Server`` wires the pieces into one in-process service::

    server = serving.Server(max_batch_size=8)
    server.add_model("mlp", symbol, arg_params, input_shapes={"data": (8,)})
    server.warmup()                     # pre-trace every bucket
    out = server.submit("mlp", {"data": x})          # blocking
    fut = server.submit_async("mlp", {"data": x})    # concurrent.futures
    server.close()                      # graceful drain

Lifecycle contract:

- ``warmup()`` runs every registered model through every batch bucket,
  then sweeps again and asserts the second pass added ZERO executor
  retraces — steady-state traffic after a clean warmup never compiles
  (the PR 2 cache makes this checkable, not hoped-for).
- ``submit*`` raises typed rejections synchronously (``ModelNotFound``,
  ``RequestTooLarge``, ``Overloaded``, ``ServerClosed``, ``BadRequest``)
  and delivers queued-stage rejections (``DeadlineExceeded``) through
  the future.  Every rejection increments
  ``serving.rejected_total.<reason>``.
- ``close(drain=True)`` stops admission, lets the dispatch thread finish
  every already-queued request, and joins it — in-flight work completes,
  new work is refused with ``ServerClosed``.

The HTTP endpoint is deliberately minimal (stdlib ``http.server``, JSON
in/out, gated behind ``serve_http=True``): POST
``/v1/models/<name>:predict``, GET ``/healthz`` and ``/metrics``
(Prometheus text from the PR 3 registry).  Production fronting belongs
to a real RPC stack; this one exists so the service is curl-able and the
rejection->status mapping is pinned by tests.
"""
from __future__ import annotations

import json
import os
import threading
import time
from concurrent.futures import Future
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from .. import threads as _threads
from ..base import MXNetError
from ..log import module_logger as _module_logger
from ..observability import memprof as _memprof
from ..observability import reqtrace as _reqtrace
from ..observability import telemetry
from ..observability import timeseries as _timeseries
from . import metrics
from .admission import AdmissionController, Request
from .batcher import DynamicBatcher
from .errors import (BadRequest, RequestTooLarge, ServerClosed,
                     ServingError)
from .registry import ModelRegistry


def verify_warm_start(totals_before, disk_before, traces, context):
    """The warm-boot contract shared by ``Server.warmup`` and
    ``FleetServer.warmup`` (``expect_warm=True``): since
    ``totals_before``/``disk_before`` were captured, the warmup must
    have added ZERO retraces and ZERO builds/backend compiles — every
    program restored from the persistent cache dir.  Raises MXNetError
    naming the counts, else returns the report's ``warm_start``
    section."""
    from .. import program_cache
    totals = _memprof.build_totals()
    built = totals["built"] - totals_before["built"]
    compiles = (totals["backend_compiles"]
                - totals_before["backend_compiles"])
    restored = totals["restored"] - totals_before["restored"]
    if traces or built or compiles:
        raise MXNetError(
            "%s warm-start verification failed: warmup on cache dir %r "
            "added %d retraces and %d backend compiles (%d programs "
            "built) — a warm replica must restore everything from "
            "disk; run prewarm() at deploy time or check "
            "tools/cachectl.py verify"
            % (context, program_cache.cache_dir(), traces, compiles,
               built))
    return {"traces": 0, "backend_compiles": 0,
            "disk_restores": restored,
            "disk_hits": (program_cache.stats()["hits"]
                          - disk_before["hits"])}


class Server:
    """In-process dynamic-batching inference service."""

    def __init__(self, registry=None, max_batch_size=8, batch_window_ms=2.0,
                 queue_depth=None, serve_http=False, http_host="127.0.0.1",
                 http_port=0, auto_start=True):
        self.registry = registry if registry is not None else ModelRegistry()
        self.max_batch_size = int(max_batch_size)
        self.batch_window_ms = float(batch_window_ms)
        self.admission = AdmissionController(queue_depth)
        self.batcher = self._make_batcher()
        # autotune cadence (MXNET_TPU_AUTOTUNE_EVERY_S): the controllers
        # run INSIDE the long-running serving loop, on the dispatch
        # thread, at most once per period — staged bucket sets adopt at
        # the next warmup boundary, never mid-traffic.  Unset env = the
        # hook costs one None check per dispatched batch.
        self.batcher.cadence = _TunerCadence(self)
        metrics.register_queue_gauge(self.admission)
        # health-plane sampler (MXNET_TPU_TS_INTERVAL_S): a serving
        # process is exactly what the time-series ring + burn-rate
        # alerts exist to watch.  Unset env = no-op, nothing spawned.
        _timeseries.ensure_sampler()
        self._closed = False
        self._close_lock = _threads.package_lock("Server._close_lock")
        self._httpd = None
        self._http_thread = None
        if auto_start:
            self.start()
        if serve_http:
            self._start_http(http_host, http_port)

    def _make_batcher(self):
        """The dispatch engine behind this server's admission queue —
        ``FleetServer`` overrides this with the replica-group router."""
        return DynamicBatcher(self.registry, self.admission,
                              max_batch_size=self.max_batch_size,
                              batch_window_ms=self.batch_window_ms)

    # -- model management ----------------------------------------------------

    def add_model(self, name, symbol, arg_params, aux_params=None,
                  input_shapes=None, ctx=None, quantize=None,
                  calibration=None, slo_ms=None):
        """Register a live symbol + params; buckets sized to this
        server's ``max_batch_size``.  ``input_shapes`` maps input name
        -> per-row feature shape (no batch dim): ``{"data": (8,)}``.
        The graph must be row-wise — no op may mix information across
        the batch axis at inference (docs/serving.md, Determinism
        contract) — or padding/co-batching silently corrupts results.
        ``quantize="int8"`` serves the int8 rewrite of the graph
        (per-channel weight scales; ``calibration`` pins activation
        ranges — docs/serving.md §int8).  ``slo_ms`` declares the
        model's p99 latency target (env default
        ``MXNET_TPU_SERVING_SLO_MS``) — the number the SLO harness and
        ``traceview --serving`` attainment table judge against."""
        if not input_shapes:
            raise BadRequest("input_shapes is required: {input_name: "
                             "per-row feature shape}, e.g. {'data': (8,)}")
        return self.registry.register(
            name, symbol, arg_params, aux_params, input_shapes,
            max_batch_size=self.max_batch_size, ctx=ctx,
            quantize=quantize, calibration=calibration, slo_ms=slo_ms)

    def load_model(self, name, prefix, epoch, input_shapes, ctx=None,
                   quantize=None, calibration=None, slo_ms=None):
        """Register from checkpoint artifacts (``save_checkpoint``'s
        prefix-symbol.json + prefix-%04d.params)."""
        return self.registry.load(
            name, prefix, epoch, input_shapes,
            max_batch_size=self.max_batch_size, ctx=ctx,
            quantize=quantize, calibration=calibration, slo_ms=slo_ms)

    # -- lifecycle -----------------------------------------------------------

    def start(self):
        self.batcher.start()

    # a summed warmup footprint within this fraction of device capacity
    # is "thin": one more replica, bucket, or model likely OOMs
    THIN_MEMORY_MARGIN = 0.10

    def warmup(self, verify=True, expect_warm=False):
        """Pre-trace every bucket of every registered model.  With
        ``verify=True`` (default) a second sweep must add zero executor
        retraces, or MXNetError — a failing verify means some dispatch
        path escapes the program cache and steady-state serving would
        recompile under load.  Returns the per-model report.

        ``expect_warm=True`` is the warm-start contract of the
        persistent program cache (mxnet_tpu/program_cache.py): the
        ENTIRE warmup — first sweep included — must add zero executor
        retraces AND zero backend compiles (verified via the memprof
        compile-time listener's build totals), i.e. every program
        restores from the cache dir.  A replica booted onto a populated
        shared volume asserts this instead of hoping; a violation names
        the retrace/compile counts and raises MXNetError.  The report
        gains a ``warm_start`` section with the disk-restore count.
        The counters are deliberately PROCESS-GLOBAL — the guarantee is
        "nothing compiled during boot", not "serving compiled nothing"
        — so assert the warm boot before starting any concurrent
        training/binding work in the same process.

        Under ``MXNET_TPU_MEMPROF=1`` the report gains a ``memory``
        section: per-model per-bucket byte footprints (XLA's
        ``memory_analysis`` of each bucket program), the summed serving
        footprint (per-bucket temp+output, plus each model's widest
        argument block once — bucket predictors share their weights),
        and — where the backend reports ``bytes_limit`` — the headroom
        against device capacity, warning when the margin is under
        ``THIN_MEMORY_MARGIN``."""
        from .. import executor_cache, program_cache
        report = {}
        names = self.registry.names()
        totals_before = _memprof.build_totals()
        disk_before = program_cache.stats()
        # two phases: warm EVERY model, then verify every model — the
        # trace counters are process-global, so verifying model A while
        # model B still has untraced buckets (or live traffic is tracing
        # them) would blame A for B's compilations
        with executor_cache.watch_traces() as first_sweep:
            for name in names:
                model = self.registry.get(name)
                first = model.warmup()
                report[name] = {"buckets": list(model.buckets),
                                "traces_first_pass": sum(first.values())}
                telemetry.counter(
                    "serving.warmup_traces",
                    help="programs traced during warmup").inc(
                    report[name]["traces_first_pass"])
        if expect_warm:
            warm = verify_warm_start(totals_before, disk_before,
                                     first_sweep.total(), "serving")
            if "warm_start" in report:
                _module_logger(__name__).warning(
                    'a served model is named "warm_start": the report\'s '
                    "warm-start section is omitted (rename the model to "
                    "get it)")
            else:
                report["warm_start"] = warm
        if verify:
            for name in names:
                second = self.registry.get(name).warmup()
                report[name]["traces_verify_pass"] = sum(second.values())
                if report[name]["traces_verify_pass"]:
                    raise MXNetError(
                        "serving warmup verification failed for model %r: "
                        "%d retraces on the second sweep (per bucket: %s) "
                        "— steady-state serving would recompile"
                        % (name, report[name]["traces_verify_pass"],
                           second))
        memory = self._warmup_memory_report(names)
        if memory is not None:
            if "memory" in report:
                # a model registered under the literal name "memory":
                # its warmup entry wins the key; the footprint section
                # is dropped rather than silently replacing it
                _module_logger(__name__).warning(
                    'a served model is named "memory": the warmup '
                    "report's footprint section is omitted (rename the "
                    "model to get it)")
            else:
                report["memory"] = memory
        return report

    def prewarm(self):
        """Deploy-time cache population: run every registered model's
        :meth:`ServedModel.prewarm` so the persistent program-cache dir
        holds every bucket executable, and return the per-model report
        plus totals.  The deploy pipeline runs this once (CI, or the
        first replica); every later replica mounts the dir and boots
        through ``warmup(expect_warm=True)`` in seconds — the
        cold-start economics story (docs/serving.md §prewarm)."""
        from .. import program_cache
        names = self.registry.names()
        if not names:
            # the per-model guards (tier off / read-only) live in
            # ServedModel.prewarm; an empty registry would skip them
            # all and ship an empty volume as "success"
            raise MXNetError(
                "Server.prewarm() with no registered models would "
                "persist nothing — add_model()/load_model() first")
        per_model = {name: self.registry.get(name).prewarm()
                     for name in names}
        return {"cache_dir": program_cache.cache_dir(),
                "models": per_model,
                "disk_writes": sum(m["disk_writes"]
                                   for m in per_model.values()),
                "disk_bytes_written": sum(m["disk_bytes_written"]
                                          for m in per_model.values())}

    def _propagate_staged_buckets(self, model):
        """Hook for the autotune cadence: the single-registry server has
        nothing to mirror; ``FleetServer`` copies a staged bucket set
        onto every replica's twin of ``model`` so all replicas adopt the
        same set at the next warmup boundary."""
        return None

    def _warmup_memory_report(self, names):
        """The summed-footprint-vs-capacity section of the warmup
        report (None when no bucket program was measured — memprof off,
        or every program already cached)."""
        per_model = {}
        footprint = 0
        for name in names:
            bm = self.registry.get(name).bucket_memory
            if not bm:
                continue
            per_model[name] = {str(b): dict(v) for b, v in bm.items()}
            # weights are shared across a model's bucket predictors:
            # count the widest argument block once, temps/outputs per
            # bucket (each bucket's program plan is resident)
            footprint += max(v.get("argument_bytes", 0)
                             for v in bm.values())
            footprint += sum(v.get("temp_bytes", 0)
                             + v.get("output_bytes", 0)
                             for v in bm.values())
        if not per_model:
            return None
        limits = [d["bytes_limit"] for d in _memprof.device_memory()
                  if d.get("bytes_limit")]
        memory = {"per_model": per_model,
                  "footprint_bytes": int(footprint),
                  "device_limit_bytes": int(limits[0]) if limits else None,
                  "headroom_frac": None}
        telemetry.gauge(
            "serving.warmup_footprint_bytes",
            help="summed per-bucket program footprint measured at "
                 "warmup").set(footprint)
        if limits:
            headroom = (limits[0] - footprint) / float(limits[0])
            memory["headroom_frac"] = round(headroom, 4)
            if headroom < self.THIN_MEMORY_MARGIN:
                _module_logger(__name__).warning(
                    "serving warmup footprint %d bytes leaves only "
                    "%.1f%% of device capacity (%d bytes) — thin margin "
                    "(< %.0f%%): one more bucket, model, or replica "
                    "likely RESOURCE_EXHAUSTs",
                    footprint, headroom * 100.0, limits[0],
                    self.THIN_MEMORY_MARGIN * 100.0)
                telemetry.counter(
                    "serving.warmup_thin_memory_margin",
                    help="warmups whose footprint left under the thin-"
                         "margin threshold of device capacity").inc()
        return memory

    def close(self, drain=True, timeout=None):
        """Graceful shutdown: stop the HTTP listener, refuse new
        admissions (``ServerClosed``), and — with ``drain=True`` — wait
        for the dispatch thread to complete every queued request.

        ``timeout`` bounds the drain (the preemption contract: a
        SIGTERM'd replica gets a grace period, not forever): requests
        still queued when the deadline expires are rejected with a
        typed ``ServerClosed`` instead of left hanging on futures no
        replica will ever resolve.  The batch already at the predictor
        finishes regardless — only undispatched work is shed."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        if self._httpd is not None:
            self._httpd.shutdown()
            self._http_thread.join(timeout=5)
            self._httpd.server_close()
        self.admission.close()
        if self.batcher.started and drain:
            self.batcher.join(timeout)
            if self.batcher.alive:
                shed = self.admission.drain_remaining()
                for request in shed:
                    self.batcher.reject(request, ServerClosed(
                        "server drain deadline (%.1fs) expired before "
                        "this queued request for model %r was "
                        "dispatched" % (timeout or 0.0, request.model)))
                if shed:
                    _module_logger(__name__).warning(
                        "drain deadline expired: rejected %d queued "
                        "request(s) with ServerClosed", len(shed))

    def install_signal_handlers(self, drain_deadline_s=30.0,
                                signals=None):
        """Wire SIGTERM/SIGINT to a graceful bounded drain: a preempted
        replica finishes its in-flight requests instead of dropping
        them, and anything still queued past ``drain_deadline_s`` is
        rejected with typed ``ServerClosed`` (``close(drain=True,
        timeout=...)``).  The previous handler (if callable) runs after
        the drain so process supervisors keep their exit semantics.
        Returns the list of signals actually hooked (empty off the main
        thread, where Python forbids installing handlers).

        The handler itself only STARTS a drain thread: it runs on the
        interrupted main thread, which may already hold the
        non-reentrant flight-recorder or logging lock — draining (or
        even logging) in signal context would self-deadlock exactly
        the preempted process this exists to wind down gracefully."""
        import signal as _signal
        if signals is None:
            signals = (_signal.SIGTERM, _signal.SIGINT)
        if not hasattr(self, "_prev_signal_handlers"):
            self._prev_signal_handlers = {}

        def _drain(signum):
            _module_logger(__name__).warning(
                "signal %d: draining serving (deadline %.1fs)",
                signum, drain_deadline_s)
            from ..observability import flight_recorder as _flight
            _flight.note_elastic({"kind": "serving_drain",
                                  "signal": int(signum),
                                  "deadline_s": drain_deadline_s})
            self.close(drain=True, timeout=drain_deadline_s)
            prev = self._prev_signal_handlers.get(signum)
            if callable(prev):
                prev(signum, None)

        def _handler(signum, frame):
            _threads.spawn(_drain, "serving", "drain",
                           args=(signum,))

        installed = []
        for sig in signals:
            try:
                self._prev_signal_handlers[sig] = _signal.signal(
                    sig, _handler)
                installed.append(sig)
            except ValueError:
                _module_logger(__name__).warning(
                    "cannot install the serving drain handler for "
                    "signal %s off the main thread", sig)
        return installed

    @property
    def closed(self):
        return self._closed

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- request path --------------------------------------------------------

    def submit_async(self, model, inputs, deadline_ms=None):
        """Queue one request; returns a ``concurrent.futures.Future``
        resolving to the per-output list of host arrays (each sliced to
        this request's rows).  Raises typed rejections synchronously
        when the request can never be served; queued-stage failures
        (deadline expiry, dispatch errors) arrive through the future."""
        # request-trace context minted at ingress (None when
        # MXNET_TPU_REQTRACE=0): every hop from here to the future's
        # resolution appends a typed segment (docs/observability.md
        # §request-tracing).  The HTTP handler funnels through submit,
        # so one mint point covers both front doors.
        ctx = _reqtrace.mint(model)
        try:
            if self._closed:
                raise ServerClosed("server is closed")
            served = self.registry.get(model)
            arrays, n_rows = self._validate(served, inputs,
                                            self.max_batch_size)
            request = Request(model, arrays, n_rows, Future(),
                              deadline_ms=deadline_ms)
            if ctx is not None:
                ctx.rows = n_rows
                ctx.slo_ms = served.slo_ms
                request.ctx = ctx
            self.admission.offer(request)
        except ServingError as exc:
            metrics.record_rejection(exc.reason, model=model)
            # a submit-time typed rejection (Overloaded, ModelNotFound,
            # RequestTooLarge, ...) is tail-captured too: sheds are the
            # journeys the black box exists for
            _reqtrace.finish_rejected(ctx, exc)
            raise
        metrics.record_admitted(request.n_rows, model=model)
        # debug/verification handle: the queued Request (rows, deadline,
        # and — once dispatched — dispatch_bucket, the program shape the
        # response came from; a bitwise replay oracle needs it)
        request.future.request = request
        return request.future

    def submit(self, model, inputs, deadline_ms=None, timeout=None):
        """Blocking ``submit_async``: returns the output list or raises
        the typed rejection."""
        return self.submit_async(model, inputs,
                                 deadline_ms=deadline_ms).result(timeout)

    @staticmethod
    def _validate(served, inputs, server_max):
        """Coerce ``inputs`` to {name: f32 array of (rows,)+feature} and
        return (arrays, rows).  A bare array is accepted for
        single-input models; a per-row array (feature shape exactly)
        gains a rows=1 leading dim.  Rows are capped by BOTH the model's
        bucket table and this server's assembly cap (a shared registry
        can pair a wide model with a narrower server)."""
        names = sorted(served.input_shapes)
        if not isinstance(inputs, dict):
            if len(names) != 1:
                raise BadRequest(
                    "model %r has inputs %s; pass a {name: array} dict"
                    % (served.name, names))
            inputs = {names[0]: inputs}
        unknown = sorted(set(inputs) - set(names))
        missing = sorted(set(names) - set(inputs))
        if unknown or missing:
            raise BadRequest(
                "model %r inputs mismatch: missing %s, unknown %s"
                % (served.name, missing or "none", unknown or "none"))
        arrays, rows = {}, None
        for name in names:
            feature = served.input_shapes[name]
            try:
                arr = np.asarray(inputs[name], dtype=np.float32)
            except (TypeError, ValueError) as exc:
                raise BadRequest("input %r is not numeric: %s"
                                 % (name, exc)) from exc
            if arr.shape == feature:
                arr = arr[None]  # one row, batch dim added
            if arr.shape[1:] != feature or arr.ndim != len(feature) + 1 \
                    or arr.shape[0] == 0:
                raise BadRequest(
                    "input %r expects shape (rows,)+%s, got %s"
                    % (name, feature, arr.shape))
            if rows is None:
                rows = arr.shape[0]
            elif arr.shape[0] != rows:
                raise BadRequest(
                    "inputs disagree on rows: %r has %d, %r has %d"
                    % (names[0], rows, name, arr.shape[0]))
            arrays[name] = arr
        limit = min(served.max_batch_size, server_max)
        if rows > limit:
            raise RequestTooLarge(
                "request of %d rows exceeds max_batch_size %d for model "
                "%r; split it client-side"
                % (rows, limit, served.name))
        return arrays, rows

    # -- HTTP front-end ------------------------------------------------------

    def _start_http(self, host, port):
        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        self._httpd.daemon_threads = True
        self._httpd.owner = self
        self._http_thread = _threads.spawn(
            self._httpd.serve_forever, "serving", "http")

    @property
    def http_address(self):
        """(host, port) of the live HTTP listener, or None."""
        if self._httpd is None:
            return None
        return self._httpd.server_address[:2]


ENV_AUTOTUNE_EVERY_S = "MXNET_TPU_AUTOTUNE_EVERY_S"


class _TunerCadence:
    """Periodic autotune inside the serving loop (the ROADMAP autotune
    remainder: controllers invoked on a schedule in long-running loops,
    not just at operator/bench call sites).

    ``MXNET_TPU_AUTOTUNE_EVERY_S`` arms it; each elapsed period the
    dispatch thread runs :class:`~mxnet_tpu.observability.autotune.
    ServingBucketTuner` over every registered model.  The tuner's own
    mode gate (``MXNET_TPU_AUTOTUNE=recommend|apply|0``) still decides
    whether a decision is report-only or STAGES a bucket set — staged
    adoption happens at the next ``warmup()``/``prewarm()`` boundary,
    so the cadence never retraces in steady state.  Every run rides
    the flight recorder's tuning ring like any other autotune decision
    (``traceview --tuning``).

    The check runs after a dispatched batch completes: an idle server
    tunes nothing (there is no new traffic evidence to act on), and the
    tuner cost (a telemetry snapshot + quantile math) is paid at most
    once per period, never per batch."""

    def __init__(self, server):
        self._server = server
        self._next = None
        self._warned = False
        self._every = self._parse(os.environ.get(ENV_AUTOTUNE_EVERY_S))
        if self._every:
            self._next = time.monotonic() + self._every

    def _parse(self, raw):
        if not raw:
            return None
        try:
            every = float(raw)
        except ValueError:
            every = -1.0
        if every <= 0:
            if not self._warned:
                self._warned = True
                _module_logger(__name__).warning(
                    "malformed %s=%r (need a positive number of "
                    "seconds); serving-loop autotune cadence disabled",
                    ENV_AUTOTUNE_EVERY_S, raw)
            return None
        return every

    @property
    def enabled(self):
        return self._every is not None

    def __call__(self):
        if self._every is None or time.monotonic() < self._next:
            return None
        self._next = time.monotonic() + self._every
        return self.run_once()

    def run_once(self):
        """One tuner pass over every registered model (also the direct
        entry for tests/operators).  Never raises — a tuner bug must
        not take down the dispatch loop it runs on."""
        from ..observability.autotune import ServingBucketTuner
        decisions = []
        try:
            tuner = ServingBucketTuner()
            for name in self._server.registry.names():
                model = self._server.registry.get(name)
                decision = tuner.run(model)
                if decision is not None:
                    decisions.append(decision)
                self._server._propagate_staged_buckets(model)
        except Exception:
            _module_logger(__name__).exception(
                "serving autotune cadence pass failed; serving "
                "continues untuned")
        return decisions


class _Handler(BaseHTTPRequestHandler):
    """Minimal JSON-over-HTTP mapping of the futures API.

    POST /v1/models/<name>:predict   {"inputs": {...}, "deadline_ms": n}
    GET  /healthz                    liveness + registered models
    GET  /metrics                    Prometheus text exposition
    """

    server_version = "mxnet-tpu-serving"
    protocol_version = "HTTP/1.1"

    def log_message(self, format, *args):
        """Silence per-request stderr lines (telemetry is the log)."""

    def _send(self, status, body, content_type="application/json"):
        data = body.encode() if isinstance(body, str) \
            else json.dumps(body).encode()
        self.send_response(status)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def do_GET(self):
        if self.path == "/healthz":
            self._send(200, {"status": "ok",
                             "models": self.server.owner.registry.names()})
        elif self.path == "/metrics":
            self._send(200, telemetry.to_prometheus(),
                       content_type="text/plain; version=0.0.4")
        else:
            self._send(404, {"error": "not_found", "path": self.path})

    def do_POST(self):
        name = self._model_name()
        if name is None:
            self._send(404, {"error": "not_found", "path": self.path})
            return
        try:
            length = int(self.headers.get("Content-Length", "0"))
            try:
                payload = json.loads(self.rfile.read(length) or b"{}")
            except ValueError as exc:
                raise BadRequest("unparsable JSON body: %s" % exc) from exc
            if not isinstance(payload, dict):
                raise BadRequest("body must be a JSON object")
            inputs = payload.get("inputs", payload.get("data"))
            if inputs is None:
                raise BadRequest('body needs "inputs" (dict or array)')
            outs = self.server.owner.submit(
                name, inputs, deadline_ms=payload.get("deadline_ms"))
            self._send(200, {"model": name,
                             "outputs": [o.tolist() for o in outs]})
        except ServingError as exc:
            self._send(exc.http_status,
                       {"error": type(exc).__name__, "reason": exc.reason,
                        "message": str(exc)})
        except Exception as exc:  # handler thread must answer, not die
            self._send(500, {"error": type(exc).__name__,
                             "message": str(exc)})

    def _model_name(self):
        """Model name from ``/v1/models/<name>:predict`` (TF-serving
        spelling) or ``/predict/<name>``."""
        path = self.path.split("?", 1)[0]
        if path.startswith("/v1/models/") and path.endswith(":predict"):
            return path[len("/v1/models/"):-len(":predict")] or None
        if path.startswith("/predict/"):
            return path[len("/predict/"):] or None
        return None
