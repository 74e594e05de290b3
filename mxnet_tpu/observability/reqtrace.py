"""End-to-end request tracing: per-request waterfalls for the serving
fleet, with head-sampled storage and a tail-capture black box.

The rest of the observability stack answers process-level questions
(telemetry: "what are the rates", memprof: "where did the memory go",
flight recorder: "what happened around the crash").  This module
answers the question a fleet operator actually asks: *why was THIS
request slow?*  Dapper-style per-request context, specialized to the
serving stack's hops:

- a :class:`RequestContext` is minted at ``Server.submit_async`` (the
  HTTP handler funnels through the same call) and rides the queued
  ``Request`` object through every hop;
- each hop appends one **typed segment** — ``queue`` (admission wait),
  ``route`` (router candidate scoring: which replicas were considered,
  their load scores, who won), ``lane`` (replica work-lane wait),
  ``assemble`` (concat + pad, co-batched neighbours, dispatch bucket),
  ``dispatch`` (executor wall), ``split`` (slice + future resolution),
  ``reject`` (typed rejection), ``decode_step`` (one continuous-batcher
  iteration: slot id, occupancy) — so a completed request owns its full
  waterfall;
- segments are host-side dicts with monotonic-clock offsets from the
  request's origin.  NOTHING here touches a traced program: tracing on
  vs off leaves exec-cache counters and served bytes bitwise identical
  (``tests/test_reqtrace.py`` asserts exactly that).

Storage is two-tier, the production trade-off:

- **head-sampled ring** (always on): ``MXNET_TPU_REQTRACE`` is the
  sampling rate — 1/N of requests, decided at mint time, default 1/64;
  ``0`` disables tracing entirely (no contexts minted).  The ring is
  bounded twice: ``MXNET_TPU_REQTRACE_RING`` entries and
  ``MXNET_TPU_REQTRACE_RING_BYTES`` serialized bytes — the steady-state
  view of normal traffic can never grow without bound.
- **tail capture** (the black box): a request that breached its
  declared ``slo_ms``, was rejected with a typed error, or rode a
  quarantined replica is pinned IN FULL into the ``requests`` ring
  (``MXNET_TPU_REQTRACE_PINNED`` entries) regardless of the sampling
  draw — the journeys that matter are always there.  Every flight-
  recorder dump embeds both rings (``requests`` / ``requests_sampled``
  sections), and ``tools/traceview.py --requests`` renders waterfalls
  plus the p99 attribution table from either a flight dump or a
  standalone :func:`dump`.

Fleet correlation: the first context minted in a process establishes a
**trace root** — written back into ``os.environ`` under
``MXNET_TPU_REQTRACE_CTX`` (``<root>:<epoch0>``) so subprocess workers
(fleet replicas, elastic/chaos children) inherit it automatically.
Every dump carries the root + the wall-clock epoch, which is what lets
``traceview --fleet <dir>`` merge dumps from many processes onto one
shared-epoch timeline.
"""
from __future__ import annotations

import itertools
import json
import os
import threading

from .. import threads as _threads
import time
import uuid
from collections import deque

from ..log import module_logger as _module_logger
from . import telemetry as _telemetry

ENV_RATE = "MXNET_TPU_REQTRACE"
ENV_RING = "MXNET_TPU_REQTRACE_RING"
ENV_RING_BYTES = "MXNET_TPU_REQTRACE_RING_BYTES"
ENV_PINNED = "MXNET_TPU_REQTRACE_PINNED"
ENV_CTX = "MXNET_TPU_REQTRACE_CTX"

DEFAULT_RATE = 64            # head-sample 1 in 64 requests
DEFAULT_RING = 512           # sampled-ring entries
DEFAULT_RING_BYTES = 2 << 20  # sampled-ring serialized-byte cap (2 MiB)
DEFAULT_PINNED = 256         # tail-capture ("requests") ring entries

# per-context segment cap: a runaway stream (thousands of decode
# iterations) must not grow one record without bound; past the cap,
# segments are counted-and-dropped and the record says so
MAX_SEGMENTS = 512

# the canonical hop order --requests renders attribution in (a pinned
# copy lives in tools/traceview.py, which stays import-free)
SEGMENT_ORDER = ("queue", "route", "lane", "assemble", "dispatch",
                 "split", "reject", "decode_step")

_lock = _threads.package_lock("reqtrace._lock")
_seq = itertools.count()
_sampled = None       # deque of records (created lazily; env-sized)
_sampled_bytes = 0
_sampled_dropped = 0  # evicted for the entry/byte caps
_pinned = None        # deque of tail-captured records
_minted = 0
_finished = 0
_root = None          # (root_id, epoch0) once established


def _int_env(name, default, minimum=1):
    raw = os.environ.get(name, "")
    if not raw:
        return default
    try:
        return max(minimum, int(raw))
    except ValueError:
        _module_logger(__name__).warning(
            "ignoring malformed %s=%r (want an integer); using %d",
            name, raw, default)
        return default


def rate():
    """The head-sampling rate: 0 = tracing off, N = sample 1/N
    (default 64).  Read per mint so tests/tools can flip it without a
    process restart."""
    raw = os.environ.get(ENV_RATE, "")
    if not raw:
        return DEFAULT_RATE
    try:
        n = int(raw)
    except ValueError:
        _module_logger(__name__).warning(
            "ignoring malformed %s=%r (want an integer sampling rate); "
            "using %d", ENV_RATE, raw, DEFAULT_RATE)
        return DEFAULT_RATE
    return max(0, n)


def enabled():
    return rate() > 0


def trace_root():
    """(root_id, epoch0) of this process's trace context.  The first
    call either adopts an env-propagated parent context
    (``MXNET_TPU_REQTRACE_CTX``) or establishes a fresh root AND writes
    it back into ``os.environ`` — so any subprocess spawned afterwards
    (a fleet replica, an elastic/chaos worker) inherits the same root
    and its dumps merge onto the parent's ``--fleet`` timeline."""
    global _root
    with _lock:
        if _root is not None:
            return _root
        raw = os.environ.get(ENV_CTX, "")
        if raw:
            parts = raw.split(":", 1)
            try:
                _root = (parts[0], float(parts[1]) if len(parts) > 1
                         else time.time())
                return _root
            except ValueError:
                _module_logger(__name__).warning(
                    "ignoring malformed %s=%r; establishing a fresh "
                    "trace root", ENV_CTX, raw)
        root_id = uuid.uuid4().hex[:8]
        epoch0 = time.time()
        _root = (root_id, epoch0)
        os.environ[ENV_CTX] = "%s:%.6f" % (root_id, epoch0)
        return _root


class RequestContext:
    """One request's trace: identity, monotonic segment clock, and the
    typed segment list every hop appends to.  Host-side only."""

    __slots__ = ("trace_id", "model", "rows", "slo_ms", "kind",
                 "t0_mono", "t0_epoch", "segments", "sampled",
                 "pin_reason", "bucket", "replica", "extra",
                 "_dropped_segments", "_finished")

    def __init__(self, trace_id, model, rows, slo_ms, kind, sampled):
        self.trace_id = trace_id
        self.model = model
        self.rows = rows
        self.slo_ms = slo_ms
        self.kind = kind           # "request" | "stream"
        self.t0_mono = time.monotonic()
        self.t0_epoch = time.time()
        self.segments = []
        self.sampled = sampled
        self.pin_reason = None     # set -> tail-captured regardless
        self.bucket = None
        self.replica = None
        self.extra = None
        self._dropped_segments = 0
        self._finished = False

    def seg(self, name, t0, t1, **attrs):
        """Append one typed segment: ``[t0, t1]`` on THIS process's
        monotonic clock, stored as (offset-from-origin, duration) ms.
        Extra attrs ride along (bucket, replica, candidates, ...)."""
        if self._finished:
            return
        if len(self.segments) >= MAX_SEGMENTS:
            self._dropped_segments += 1
            return
        entry = {"name": name,
                 "t0_ms": round((t0 - self.t0_mono) * 1e3, 4),
                 "dur_ms": round(max(0.0, t1 - t0) * 1e3, 4)}
        if attrs:
            entry.update(attrs)
        self.segments.append(entry)

    def pin(self, reason):
        """Force tail capture for this request (first reason wins) —
        the quarantine path marks stranded/failed requests with
        ``quarantined_replica`` before they re-route or fail."""
        if self.pin_reason is None:
            self.pin_reason = str(reason)


def mint(model, rows=None, slo_ms=None, kind="request"):
    """Mint a context for one incoming request, or return ``None`` when
    tracing is off (``MXNET_TPU_REQTRACE=0``) — every instrumentation
    site guards on None, so the off path adds one env read + one
    comparison per request and allocates nothing."""
    n = rate()
    if n <= 0:
        return None
    global _minted
    root_id, _ = trace_root()
    with _lock:
        seq = next(_seq)
        _minted += 1
    sampled = (seq % n) == 0
    return RequestContext("%s-%06d" % (root_id, seq), model, rows,
                          slo_ms, kind, sampled)


def finish(ctx, status="ok", reason=None, **extra):
    """Close the context: compute the total, decide its fate (tail-pin
    vs sampled ring vs dropped), and store the record.  Idempotent —
    the first finish wins, exactly the futures contract, so a close()
    racing an in-flight dispatch cannot double-record."""
    if ctx is None:
        return None
    with _lock:
        if ctx._finished:
            return None
        ctx._finished = True
    t_done = time.monotonic()
    total_ms = (t_done - ctx.t0_mono) * 1e3
    pin_reason = ctx.pin_reason
    if pin_reason is None and status != "ok":
        pin_reason = "rejected"
    if pin_reason is None and ctx.slo_ms and total_ms > ctx.slo_ms:
        pin_reason = "slo_breach"
    record = {"trace_id": ctx.trace_id, "kind": ctx.kind,
              "model": ctx.model, "rows": ctx.rows,
              "t0": round(ctx.t0_epoch, 6),
              "total_ms": round(total_ms, 4),
              "status": status, "segments": ctx.segments}
    if reason is not None:
        record["reason"] = str(reason)
    if ctx.slo_ms:
        record["slo_ms"] = ctx.slo_ms
    if ctx.bucket is not None:
        record["bucket"] = ctx.bucket
    if ctx.replica is not None:
        record["replica"] = ctx.replica
    if pin_reason is not None:
        record["pinned"] = pin_reason
    if ctx._dropped_segments:
        record["segments_dropped"] = ctx._dropped_segments
    if extra:
        record.update(extra)
    _store(record, pin_reason is not None, ctx.sampled)
    return record


def _rings_locked():
    """Create the rings lazily at their env-configured sizes (call with
    ``_lock`` held)."""
    global _sampled, _pinned
    if _sampled is None:
        _sampled = deque()
        _pinned = deque(maxlen=_int_env(ENV_PINNED, DEFAULT_PINNED))
    return _sampled, _pinned


def _store(record, pinned, sampled):
    global _sampled_bytes, _sampled_dropped, _finished
    if pinned:
        _telemetry.counter(
            "reqtrace.pinned_total",
            help="requests tail-captured into the flight requests "
                 "ring").inc()
    elif sampled:
        _telemetry.counter(
            "reqtrace.sampled_total",
            help="requests stored in the head-sampled ring").inc()
    with _lock:
        _finished += 1
        sring, pring = _rings_locked()
        if pinned:
            pring.append(record)
            return
        if not sampled:
            return
        # byte accounting: the serialized size is what a dump costs —
        # estimated once per stored record (records are a few hundred
        # bytes; this is the slow path of 1/N requests)
        try:
            nbytes = len(json.dumps(record, default=str))
        except Exception:
            nbytes = 512
        record["_bytes"] = nbytes
        sring.append(record)
        _sampled_bytes += nbytes
        max_entries = _int_env(ENV_RING, DEFAULT_RING)
        max_bytes = _int_env(ENV_RING_BYTES, DEFAULT_RING_BYTES)
        while sring and (len(sring) > max_entries
                         or _sampled_bytes > max_bytes):
            dropped = sring.popleft()
            _sampled_bytes -= dropped.get("_bytes", 0)
            _sampled_dropped += 1


def finish_rejected(ctx, exc):
    """Typed-rejection finish (submit-time raises and queued-stage
    rejections both land here): append the ``reject`` segment and
    close the context as rejected — which tail-pins it."""
    if ctx is None:
        return None
    now = time.monotonic()
    reason = getattr(exc, "reason", type(exc).__name__)
    ctx.seg("reject", now, now, reason=reason)
    return finish(ctx, status="rejected", reason=reason)


# -- introspection / dumps ----------------------------------------------------

def _strip(record):
    """A record without the internal byte-accounting field."""
    if "_bytes" not in record:
        return record
    out = dict(record)
    out.pop("_bytes", None)
    return out


def sampled_snapshot():
    """The head-sampled ring, oldest first."""
    with _lock:
        if _sampled is None:
            return []
        return [_strip(r) for r in _sampled]


def pinned_snapshot():
    """The tail-capture (``requests``) ring, oldest first."""
    with _lock:
        if _pinned is None:
            return []
        return [dict(r) for r in _pinned]


def stats():
    with _lock:
        return {"minted": _minted, "finished": _finished,
                "sampled": len(_sampled) if _sampled else 0,
                "sampled_bytes": _sampled_bytes,
                "sampled_dropped": _sampled_dropped,
                "pinned": len(_pinned) if _pinned else 0,
                "rate": rate()}


def fleet_header():
    """The per-process correlation header every dump carries."""
    root_id, epoch0 = trace_root()
    return {"root": root_id, "epoch0": round(epoch0, 6),
            "pid": os.getpid()}


def dump(path):
    """Write a standalone reqtrace dump (both rings + the fleet
    header) — the per-process artifact ``traceview --requests`` and
    ``--fleet`` read when no flight dump exists.  Returns the path."""
    doc = {"kind": "mxnet_tpu_reqtrace", "version": 1,
           "created": time.time(),
           "fleet": fleet_header(),
           "stats": stats(),
           "requests": pinned_snapshot(),
           "requests_sampled": sampled_snapshot()}
    with open(path, "w") as f:
        json.dump(doc, f, default=str)
    return path


def reset():
    """Drop rings, counters, and the process trace root (tests).  Does
    NOT clear ``MXNET_TPU_REQTRACE_CTX`` from the environment — callers
    that need a fresh root pop it explicitly."""
    global _sampled, _pinned, _sampled_bytes, _sampled_dropped
    global _minted, _finished, _root, _seq
    with _lock:
        _sampled = None
        _pinned = None
        _sampled_bytes = 0
        _sampled_dropped = 0
        _minted = 0
        _finished = 0
        _root = None
        _seq = itertools.count()
