"""Fleet tier: replica groups behind one admission queue, with a router.

One :class:`~mxnet_tpu.serving.server.Server` is one process, one
replica.  This module grows it to the fleet story (ROADMAP: "replica
groups with a router — weighted least-loaded dispatch across N
single-chip replicas in one process, shared admission queue, per-replica
warmup"):

- :class:`Replica` — one serving replica: its OWN ``ModelRegistry``
  (own bound predictors, so device placement and failure domains are
  per-replica), its own bounded work lane and worker thread, health
  state, and the per-bucket cost table measured at warmup.
- :class:`ReplicaGroup` — N replicas of the same model set.  On a
  multi-chip host each replica binds its models to a distinct device
  (replica *i* on chip *i* by default, or ``ctxs=[mx.tpu(0), ...]``);
  the cpu smoke harness runs N cpu-backend instances, which share the
  process-wide executor cache — replica 2..N's warmups trace nothing,
  and a shared persistent
  program-cache volume (``prewarm``) makes even replica 1's boot a
  deserialization.
- :class:`Router` — the dispatch engine: consumes the SHARED admission
  queue exactly like ``DynamicBatcher`` (same assembly, same deadline
  sweeps, same typed rejections), but instead of running the batch
  inline it routes each assembled group to the least-loaded healthy
  replica's lane.
- :class:`FleetServer` — the ``Server`` subclass wiring it together:
  ``add_model`` registers on every replica, ``warmup`` sweeps every
  replica (and measures the per-bucket cost the router weighs with),
  ``close`` drains lanes with the same bounded-deadline shedding.

Routing weight
--------------
A replica's load score is the sum over its outstanding (queued +
running) work of ``rows x measured per-row cost`` for the work's
bucket, where the per-bucket cost comes from the warmup verify sweep
(every bucket runs once, timed, AFTER its program is traced — so the
cost is execution, not compilation).  Before warmup measures anything
the score degrades to outstanding rows, which still balances.  Ties
break toward fewer outstanding rows, then the lower replica index (a
deterministic total order, so tests can pin routing).

Health
------
A replica whose dispatch RAISES (the model threw — not a typed
per-request rejection) is quarantined: the failed batch's futures get
the error (typed, counted per request), the replica stops receiving
work, and everything still queued in its lane is re-routed to healthy
replicas.  The server survives; only when EVERY replica is quarantined
do requests fail, with typed :class:`~mxnet_tpu.serving.errors.
NoHealthyReplica`.  Quarantine is deliberately one-strike: a replica
that threw once is suspect (wedged device, poisoned weights), and the
fleet has capacity to spare — operators re-add capacity by building a
fresh group, not by un-quarantining in place.

Determinism: every replica binds the same graph at the same bucket
shapes, so all replicas dispatch the SAME cached program — a routed
response is bitwise-identical to a plain ``predict.Predictor`` replay
at its recorded ``dispatch_bucket`` no matter which replica served it
(``tests/test_serving_fleet.py`` pins this).
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque

from .. import context as _context
from .. import threads as _threads
from ..base import MXNetError
from ..log import module_logger as _module_logger
from ..observability import flight_recorder as _flight
from . import metrics
from .batcher import DynamicBatcher, fail_batch, run_group
from .errors import NoHealthyReplica, ServerClosed, ServingError
from .registry import ModelRegistry
from .server import Server, verify_warm_start

ENV_REPLICAS = "MXNET_TPU_SERVING_REPLICAS"


def default_replicas():
    """Fleet width when the constructor doesn't pin one (default 1 —
    a FleetServer with one replica behaves like a plain Server with
    per-replica health)."""
    try:
        n = int(os.environ.get(ENV_REPLICAS, "1"))
    except ValueError:
        _module_logger(__name__).warning(
            "malformed %s=%r; using 1 replica", ENV_REPLICAS,
            os.environ.get(ENV_REPLICAS))
        return 1
    return max(1, n)


class Replica:
    """One serving replica: registry + work lane + worker thread +
    health + measured per-bucket cost."""

    def __init__(self, index, ctx=None):
        self.index = int(index)
        self.ctx = ctx
        self.registry = ModelRegistry()
        # (model_name, batch, rows, est_ms) work items, router-ordered
        self._lane = deque()
        self._cond = _threads.package_condition("Replica._cond")
        self._thread = None
        self._closed = False
        # accounting the router's least-loaded pick reads: rows and
        # estimated ms of everything queued in the lane; the RUNNING
        # item is tracked separately so its contribution can grow with
        # wall clock (a replica stuck in a 30x-slower-than-estimated
        # dispatch must look loaded, or the router would keep feeding
        # it on stale warmup estimates)
        self._outstanding_rows = 0
        self._outstanding_ms = 0.0
        self._running_est_ms = 0.0
        self._running_since = None
        self._running_rows = 0
        self.healthy = True
        self.quarantine_error = None
        self.dispatches = 0
        self.rows_served = 0
        # {(model_name, bucket): measured wall ms} from the warmup
        # verify sweep (post-trace, so execution cost not compile cost)
        self.bucket_cost_ms = {}

    # -- lifecycle -----------------------------------------------------------

    def start(self):
        if self._thread is not None:
            return
        self._thread = _threads.spawn(
            self._worker, "serving", "replica-%d" % self.index)

    @property
    def alive(self):
        return self._thread is not None and self._thread.is_alive()

    def close(self):
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    def join(self, timeout=None):
        if self._thread is not None:
            self._thread.join(timeout)

    # -- load accounting ------------------------------------------------------

    def estimate_ms(self, model_name, bucket, rows):
        """Routing weight of one group: rows x measured per-row cost at
        the bucket it will dispatch in.  Unmeasured (pre-warmup) work
        weighs rows alone — comparable across equally-unmeasured
        replicas, which is all the router needs to balance."""
        cost = self.bucket_cost_ms.get((model_name, bucket))
        if cost is None or bucket <= 0:
            return float(rows)
        return rows * (cost / float(bucket))

    def load_score(self):
        """(outstanding ms, outstanding rows, index): the router picks
        the lexicographic minimum over healthy replicas.  The running
        item counts as ``max(its estimate, its elapsed wall time)`` —
        estimates come from warmup, but a replica that turned slow
        AFTER warmup (contended device, degraded host) shows its real
        backlog through the clock."""
        with self._cond:
            running_ms = 0.0
            if self._running_since is not None:
                elapsed = (time.monotonic() - self._running_since) * 1e3
                running_ms = max(self._running_est_ms, elapsed)
            return (self._outstanding_ms + running_ms,
                    self._outstanding_rows + self._running_rows,
                    self.index)

    def outstanding(self):
        with self._cond:
            return len(self._lane) + (
                1 if self._running_since is not None else 0)

    # -- the lane -------------------------------------------------------------

    def enqueue(self, model_name, batch, rows, est_ms):
        """Router-side: hand one assembled group to this replica."""
        with self._cond:
            if self._closed or not self.healthy:
                # the router re-checks health under its own pick loop;
                # this guards the race where quarantine lands between
                # pick and enqueue
                raise NoHealthyReplica(
                    "replica %d is %s" % (
                        self.index,
                        "closed" if self._closed else "quarantined"))
            self._lane.append((model_name, batch, rows, est_ms,
                               time.monotonic()))
            self._outstanding_rows += rows
            self._outstanding_ms += est_ms
            self._cond.notify()

    def _take(self):
        with self._cond:
            while not self._lane and not self._closed:
                self._cond.wait()
            if not self._lane:
                return None  # closed and drained
            item = self._lane.popleft()
            _, _, rows, est_ms, _ = item
            # the item moves from queued accounting to running
            # accounting (whose score contribution tracks wall clock)
            self._outstanding_rows -= rows
            self._outstanding_ms -= est_ms
            self._running_rows = rows
            self._running_est_ms = est_ms
            self._running_since = time.monotonic()
            return item

    def _done(self):
        with self._cond:
            self._running_since = None
            self._running_rows = 0
            self._running_est_ms = 0.0

    def _worker(self):
        """The replica's dispatch loop: run routed groups until closed
        and drained, or quarantined."""
        while True:
            item = self._take()
            if item is None:
                return
            model_name, batch, rows, _, t_enq = item
            # lane-wait hop: routed-enqueue -> taken by this worker
            t_take = self._running_since or time.monotonic()
            for r in batch:
                if r.ctx is not None:
                    r.ctx.seg("lane", t_enq, t_take, replica=self.index)
            try:
                try:
                    model = self.registry.get(model_name)
                    run_group(model, batch, rows, replica=self.index)
                    self.dispatches += 1
                    self.rows_served += rows
                except Exception as exc:
                    # the failure path itself must not kill the worker
                    # with healthy=True — a dead lane that still
                    # accepts routed work hangs its futures forever
                    if not isinstance(exc, ServingError):
                        # the batch that felled this replica rode a
                        # replica about to be quarantined: pin BEFORE
                        # fail_batch closes the traces, so the black
                        # box names the quarantine, not just the error
                        for r in batch:
                            if r.ctx is not None:
                                r.ctx.pin("quarantined_replica")
                    try:
                        fail_batch(batch, exc, model_name)
                    except Exception:
                        _module_logger(__name__).exception(
                            "replica %d could not deliver a batch "
                            "failure to its futures", self.index)
                    if not isinstance(exc, ServingError):
                        # a typed rejection (RequestTooLarge through a
                        # narrower twin, ...) is the REQUEST's problem;
                        # anything else means this replica's execution
                        # path is suspect — quarantine it
                        try:
                            self._quarantine(exc)
                        except Exception:
                            _module_logger(__name__).exception(
                                "replica %d quarantine bookkeeping "
                                "failed", self.index)
                            with self._cond:
                                self.healthy = False
                                self.quarantine_error = exc
                        return
            finally:
                self._done()

    def _quarantine(self, exc):
        """Mark unhealthy, surface the event, and hand the still-queued
        lane back to the group for re-routing (drained, not dropped)."""
        with self._cond:
            self.healthy = False
            self.quarantine_error = exc
            stranded = list(self._lane)
            self._lane.clear()
            # the stranded items' accounting unwinds here; the running
            # item's unwind happens in the worker's finally
            for _, _, rows, est_ms, _ in stranded:
                self._outstanding_rows -= rows
                self._outstanding_ms -= est_ms
        # a stranded request RODE a quarantined replica even though a
        # healthy one will eventually serve it: pin its trace so the
        # detour is always in the black box (the re-route appends new
        # route/lane segments to the same waterfall)
        for _, stranded_batch, _, _, _ in stranded:
            for r in stranded_batch:
                if r.ctx is not None:
                    r.ctx.pin("quarantined_replica")
        _module_logger(__name__).error(
            "serving replica %d quarantined after dispatch failure "
            "(%s: %s); re-routing %d queued group(s)",
            self.index, type(exc).__name__, exc, len(stranded))
        metrics.record_replica_quarantined(
            self.index, "%s: %s" % (type(exc).__name__, exc))
        _flight.note("serving_replica_quarantined",
                     {"replica": self.index,
                      "error": "%s: %s" % (type(exc).__name__, exc),
                      "stranded_groups": len(stranded)})
        if self._group is not None:
            self._group.redispatch(stranded)

    _group = None  # set by ReplicaGroup

    # -- warmup ---------------------------------------------------------------

    def warmup_models(self):
        """First-pass warmup of every model on this replica.  Returns
        {model: traces}."""
        traced = {}
        for name in self.registry.names():
            traced[name] = sum(self.registry.get(name).warmup().values())
        return traced

    def verify_and_measure(self):
        """Second sweep: every bucket of every model must trace nothing
        (the Server.warmup verification contract) — and since each run
        is now pure execution, time it: the per-bucket cost table the
        router's weighted least-loaded dispatch reads.  Returns
        {model: {bucket: ms}}."""
        import numpy as np
        costs = {}
        for name in self.registry.names():
            model = self.registry.get(name)
            per_bucket = {}
            for b in model.buckets:
                zeros = {k: np.zeros((b,) + v, dtype=np.float32)
                         for k, v in model.input_shapes.items()}
                t0 = time.monotonic()
                model.run_batch(b, zeros)
                ms = (time.monotonic() - t0) * 1e3
                per_bucket[b] = ms
                self.bucket_cost_ms[(name, b)] = ms
            costs[name] = per_bucket
        return costs


class ReplicaGroup:
    """N replicas of one model set, plus the routing/redispatch core."""

    def __init__(self, n_replicas=None, ctxs=None):
        n = default_replicas() if n_replicas is None else int(n_replicas)
        if n < 1:
            raise MXNetError("a replica group needs >= 1 replica")
        if ctxs is not None and len(ctxs) != n:
            raise MXNetError(
                "ctxs must name one context per replica (%d != %d)"
                % (len(ctxs), n))
        # unplaced replicas spread over the chips: replica i on device i
        self.replicas = [
            Replica(i, ctx=ctxs[i] if ctxs else _context.accelerator(i))
            for i in range(n)]
        for r in self.replicas:
            r._group = self

    def __len__(self):
        return len(self.replicas)

    @property
    def primary_registry(self):
        """Replica 0's registry: the validation/metadata view the
        shared admission path reads (all replicas register identical
        models)."""
        return self.replicas[0].registry

    def healthy_replicas(self):
        # a closed replica's worker may already have drained and
        # exited; routing to it would strand the batch on a dead lane
        return [r for r in self.replicas if r.healthy and not r._closed]

    def start(self):
        for r in self.replicas:
            r.start()

    # -- registration ---------------------------------------------------------

    def register(self, name, symbol, arg_params, aux_params, input_shapes,
                 max_batch_size=8, quantize=None, calibration=None,
                 slo_ms=None):
        """Register the model on EVERY replica (each builds its own
        predictors; the process-wide executor cache makes the duplicate
        programs one trace total per bucket)."""
        models = [
            r.registry.register(
                name, symbol, arg_params, aux_params, input_shapes,
                max_batch_size=max_batch_size, ctx=r.ctx,
                quantize=quantize, calibration=calibration, slo_ms=slo_ms)
            for r in self.replicas]
        return models[0]

    def models_named(self, name):
        """The per-replica twins of one registered model."""
        return [r.registry.get(name) for r in self.replicas]

    # -- routing --------------------------------------------------------------

    def _scored_healthy(self):
        """Healthy replicas with their load scores, best first — the
        ONE place the routing order is defined (``pick`` and
        ``dispatch`` both consume it; the trace records the whole
        list).  The lexicographic (outstanding ms, outstanding rows,
        index) score ends in the unique replica index, so the sort
        never compares Replica objects."""
        return sorted((r.load_score(), r)
                      for r in self.healthy_replicas())

    def pick(self):
        """The least-loaded healthy replica (weighted by measured
        per-bucket cost of outstanding work), or None when the whole
        group is quarantined."""
        scored = self._scored_healthy()
        return scored[0][1] if scored else None

    def dispatch(self, model_name, batch, rows, bucket, t_route0=None):
        """Route one assembled group; fails the batch typed when no
        healthy replica exists.  ``t_route0`` overrides the route-hop
        start for redispatches (whose claim timestamp belongs to the
        FIRST attempt's segments)."""
        if t_route0 is None:
            # contiguous with the queue segment: routing starts the
            # moment the dispatch thread claimed the batch
            t_route0 = (batch[0].t_dispatch
                        if batch and batch[0].t_dispatch is not None
                        else time.monotonic())
        while True:
            # the full scored candidate list (pick()'s order) so the
            # trace can record WHO was considered and why the winner won
            scored = self._scored_healthy()
            if not scored:
                fail_batch(batch, NoHealthyReplica(
                    "all %d replica(s) are quarantined; group for model "
                    "%r not dispatched" % (len(self.replicas),
                                           model_name)), model_name)
                return None
            replica = scored[0][1]
            est_ms = replica.estimate_ms(model_name, bucket, rows)
            # the route segment is appended BEFORE enqueue: the instant
            # the batch lands on the lane a fast replica worker may run
            # it to completion and finish() the traces, after which
            # seg() is a no-op — appending afterwards would race the
            # route hop out of the waterfall.  A lost enqueue race
            # (quarantine landed between scoring and enqueue) leaves
            # this attempt's segment in place and the retry appends
            # another — an honest record of both routing attempts.
            t_route1 = time.monotonic()
            traced = [r for r in batch if r.ctx is not None]
            if traced:
                candidates = [{"replica": rep.index,
                               "score_ms": round(score[0], 4),
                               "score_rows": score[1]}
                              for score, rep in scored]
                for req in traced:
                    req.ctx.seg("route", t_route0, t_route1,
                                winner=replica.index,
                                est_ms=round(est_ms, 4),
                                candidates=candidates)
            try:
                replica.enqueue(model_name, batch, rows, est_ms)
            except NoHealthyReplica:
                t_route0 = time.monotonic()
                continue  # lost the race with a quarantine; re-pick
            return replica

    def redispatch(self, stranded):
        """Re-route a quarantined replica's queued lane.  Called from
        the dying replica's worker thread; items land on healthy
        replicas or fail typed."""
        from .registry import bucket_for
        for model_name, batch, rows, _, _ in stranded:
            try:
                model = self.primary_registry.get(model_name)
                bucket = bucket_for(rows, model.buckets)
            except Exception:
                bucket = rows
            self.dispatch(model_name, batch, rows, bucket,
                          t_route0=time.monotonic())

    # -- lifecycle ------------------------------------------------------------

    def close(self, deadline=None):
        """Drain every lane: close the lanes, join workers until
        ``deadline`` (monotonic timestamp, None = wait), then shed
        whatever is still queued with typed ``ServerClosed``.  Returns
        the number of requests shed."""
        for r in self.replicas:
            r.close()
        shed = 0
        for r in self.replicas:
            timeout = None
            if deadline is not None:
                timeout = max(0.0, deadline - time.monotonic())
            r.join(timeout)
            if r.alive:
                with r._cond:
                    stranded = list(r._lane)
                    r._lane.clear()
                for model_name, batch, _, _, _ in stranded:
                    shed += len(batch)
                    fail_batch(batch, ServerClosed(
                        "fleet drain deadline expired before this "
                        "routed group was dispatched on replica %d"
                        % r.index), model_name)
        return shed

    @property
    def any_alive(self):
        return any(r.alive for r in self.replicas)

    def stats(self):
        """Per-replica routing facts for reports/tests."""
        return [{"replica": r.index,
                 "healthy": r.healthy,
                 "dispatches": r.dispatches,
                 "rows": r.rows_served,
                 "outstanding": r.outstanding(),
                 "bucket_cost_ms": {("%s:%d" % k): round(v, 4)
                                    for k, v in r.bucket_cost_ms.items()}}
                for r in self.replicas]


class Router(DynamicBatcher):
    """The fleet dispatch engine: same admission consumption as
    ``DynamicBatcher`` (assembly windows, deadline sweeps, model-split),
    but assembled groups are ROUTED to replica lanes instead of run
    inline on the dispatch thread."""

    def __init__(self, group, admission, max_batch_size=8,
                 batch_window_ms=2.0):
        super().__init__(group.primary_registry, admission,
                         max_batch_size=max_batch_size,
                         batch_window_ms=batch_window_ms)
        self.group = group

    def start(self):
        self.group.start()
        super().start()

    def _run_group(self, model, batch, rows):
        """Override the inline-run step of ``_dispatch``: route.  Same
        invariant as the base class — ANY failure lands on the batch's
        futures, never on the thread (an unrouted batch with pending
        futures would hang its clients forever)."""
        from .registry import bucket_for
        try:
            bucket = bucket_for(rows, model.buckets)
            self.group.dispatch(model.name, batch, rows, bucket)
        except Exception as exc:
            fail_batch(batch, exc, model.name)

    def join(self, timeout=None):
        """Drain: first the router thread (which empties the admission
        queue into the lanes), then every replica lane, all under ONE
        absolute deadline.  ``timeout=0`` means shed immediately (the
        thread.join semantics), not wait-forever."""
        deadline = (time.monotonic() + timeout) \
            if timeout is not None else None
        super().join(timeout)
        self.group.close(deadline)

    @property
    def alive(self):
        return super().alive or self.group.any_alive


class FleetServer(Server):
    """``Server`` over a :class:`ReplicaGroup`: N replicas of every
    registered model behind one admission queue and one futures API.

    ::

        fleet = serving.FleetServer(n_replicas=2, max_batch_size=8)
        fleet.add_model("mlp", sym, args, input_shapes={"data": (8,)},
                        slo_ms=250.0)
        fleet.warmup()            # per-replica sweeps + cost measurement
        out = fleet.submit("mlp", {"data": x})
        fleet.close()

    The submit/rejection/HTTP surface is inherited unchanged — the
    fleet is a dispatch-side upgrade, invisible to clients except for
    the extra capacity and the per-replica telemetry."""

    def __init__(self, n_replicas=None, ctxs=None, max_batch_size=8,
                 batch_window_ms=2.0, queue_depth=None, serve_http=False,
                 http_host="127.0.0.1", http_port=0, auto_start=True):
        # group before super().__init__: _make_batcher needs it
        self.group = ReplicaGroup(n_replicas, ctxs=ctxs)
        # fleet-size gauge for the health plane: the dashboard (and the
        # coming autoscaler) trend shed rate and queue depth AGAINST
        # the replica count that produced them
        metrics.register_replica_gauge(self.group)
        super().__init__(registry=self.group.primary_registry,
                         max_batch_size=max_batch_size,
                         batch_window_ms=batch_window_ms,
                         queue_depth=queue_depth, serve_http=serve_http,
                         http_host=http_host, http_port=http_port,
                         auto_start=auto_start)

    def _make_batcher(self):
        return Router(self.group, self.admission,
                      max_batch_size=self.max_batch_size,
                      batch_window_ms=self.batch_window_ms)

    @property
    def n_replicas(self):
        return len(self.group)

    # -- model management ----------------------------------------------------

    def add_model(self, name, symbol, arg_params, aux_params=None,
                  input_shapes=None, ctx=None, quantize=None,
                  calibration=None, slo_ms=None):
        """Register on EVERY replica.  ``ctx`` is refused — per-replica
        placement belongs to the group's ``ctxs`` (one device per
        replica), not to one model."""
        from .errors import BadRequest
        if ctx is not None:
            raise MXNetError(
                "FleetServer.add_model does not take ctx: replica "
                "placement is the group's ctxs=[...] (one context per "
                "replica)")
        if not input_shapes:
            raise BadRequest("input_shapes is required: {input_name: "
                             "per-row feature shape}, e.g. {'data': (8,)}")
        return self.group.register(
            name, symbol, arg_params, aux_params, input_shapes,
            max_batch_size=self.max_batch_size, quantize=quantize,
            calibration=calibration, slo_ms=slo_ms)

    def load_model(self, name, prefix, epoch, input_shapes, ctx=None,
                   quantize=None, calibration=None, slo_ms=None):
        from ..model import load_checkpoint
        if ctx is not None:
            raise MXNetError(
                "FleetServer.load_model does not take ctx: replica "
                "placement is the group's ctxs=[...]")
        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
        return self.add_model(name, symbol, arg_params, aux_params,
                              input_shapes, quantize=quantize,
                              calibration=calibration, slo_ms=slo_ms)

    def _propagate_staged_buckets(self, model):
        """A bucket set the cadence tuner staged on the primary must
        adopt on EVERY replica at the same warmup boundary, or routing
        would dispatch the same rows into different bucket tables."""
        staged = model.pending_buckets()
        if not staged:
            return None
        for twin in self.group.models_named(model.name)[1:]:
            twin.stage_buckets(staged)
        return staged

    # -- warmup ---------------------------------------------------------------

    def warmup(self, verify=True, expect_warm=False):
        """Per-replica warmup + verification + cost measurement.

        Phase 1 warms every model on every replica (cpu-harness
        replicas share the executor cache, so replicas 2..N trace
        nothing; distinct-device replicas each trace their own
        programs).  Phase 2 re-sweeps every bucket of every replica:
        it must add ZERO retraces (the Server.warmup contract) and,
        being pure execution, each run is timed — producing the
        per-(model, bucket) cost table the router's weighted
        least-loaded dispatch uses.  ``expect_warm=True`` keeps the
        persistent-cache warm-boot contract: the ENTIRE warmup adds
        zero retraces and zero backend compiles."""
        from .. import executor_cache, program_cache
        from ..observability import memprof as _memprof
        report = {}
        totals_before = _memprof.build_totals()
        disk_before = program_cache.stats()
        with executor_cache.watch_traces() as first_sweep:
            for replica in self.group.replicas:
                traced = replica.warmup_models()
                for name, n in traced.items():
                    entry = report.setdefault(
                        name, {"buckets": list(
                            self.registry.get(name).buckets),
                            "traces_first_pass": 0,
                            "per_replica": {}})
                    entry["traces_first_pass"] += n
                    entry["per_replica"][replica.index] = {
                        "traces_first_pass": n}
        if expect_warm:
            warm = verify_warm_start(
                totals_before, disk_before, first_sweep.total(),
                "fleet (%d replicas)" % len(self.group))
            if "warm_start" in report:
                _module_logger(__name__).warning(
                    'a served model is named "warm_start": the report\'s '
                    "warm-start section is omitted (rename the model to "
                    "get it)")
            else:
                report["warm_start"] = warm
        if verify:
            with executor_cache.watch_traces() as second_sweep:
                for replica in self.group.replicas:
                    costs = replica.verify_and_measure()
                    for name, per_bucket in costs.items():
                        report[name]["per_replica"].setdefault(
                            replica.index, {})["bucket_cost_ms"] = {
                            str(b): round(ms, 4)
                            for b, ms in per_bucket.items()}
            if second_sweep.total():
                raise MXNetError(
                    "fleet warmup verification failed: %d retraces on "
                    "the verify sweep across %d replicas — steady-state "
                    "serving would recompile (delta: %s)"
                    % (second_sweep.total(), len(self.group),
                       second_sweep.delta()))
        memory = self._warmup_memory_report(self.registry.names())
        if memory is not None and "memory" not in report:
            report["memory"] = memory
        report["replicas"] = self.group.stats()
        return report

    def prewarm(self):
        """Deploy-time population of the shared program-cache volume.
        One replica's sweep writes every bucket executable (replicas
        bind identical programs — ``self.registry`` IS replica 0's),
        so the plain Server prewarm does the whole job; only the
        replica count is added to the report."""
        report = super().prewarm()
        report["replicas"] = len(self.group)
        return report
