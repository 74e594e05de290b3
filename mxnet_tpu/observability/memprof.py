"""Memory & compile observability: per-program HBM attribution, the
compile-time breakdown, and the OOM black box.

HBM exhaustion and surprise recompiles are the two failure modes a
sampled ``device.live_bytes`` gauge cannot explain: the gauge says *how
much* is allocated, never *which program* or *which buffers*.  The
reference framework answered this with its storage profiler
(``src/profiler/storage_profiler`` + the GPU memory profiler hooks);
the TPU-native equivalent implemented here attributes memory to the
unit XLA actually allocates for — the compiled program:

- **Program records** (``program_records()``): one row per real
  (re)compile, in build order.  ``executor_cache.note_trace`` arms a
  record from INSIDE the traced body (so rows correspond 1:1 with the
  real-retrace counters), and a ``jax.monitoring`` duration listener
  fills in the trace / lower / backend-compile wall times — zero extra
  work on the dispatch path, the compiler was already doing all of it.
  The backend-compile time also feeds the ``exec_cache.compile_ms``
  histogram and (when the profiler is recording) ``compile:*`` spans.
- **Per-program memory_analysis** (``MXNET_TPU_MEMPROF=1``): with the
  flag on, the cached programs dispatch through :class:`ProfiledJit`,
  an AOT-managed twin of ``jax.jit`` (explicit trace → lower → compile
  via the SAME underlying jit object, so the jaxpr cache and the
  retrace counters behave identically — ``tests/test_memprof.py``
  asserts bitwise-equal counters on/off).  The compiled executable's
  ``memory_analysis()`` (argument / output / temp / generated-code
  bytes — XLA's own allocation plan) lands on the program record.
  Resolved at program-build time; flipping the flag re-keys nothing
  and retraces nothing.
- **Live-array census** (``live_array_census()``): every live
  ``jax.Array`` grouped by (shape, dtype) with counts and bytes — the
  "what is actually resident" complement to the per-program plan.
- **OOM black box** (``maybe_record_oom``): the fused-step, executor,
  and serving dispatch paths call this on any dispatch failure; a
  RESOURCE_EXHAUSTED error writes ONE flight-recorder dump augmented
  with the full memory report (program table + census + per-device
  ``memory_stats``) before the error propagates — the post-mortem a
  dead overnight run needs.  ``tools/traceview.py --memory`` renders
  the report; ``--flight`` exits 1 on the dump (the OOM is recorded as
  a fired anomaly, rule ``oom``).

Everything here is host-side bookkeeping: no extra device dispatches,
no program changes, and — with the flag off — no dispatch-path changes
at all.
"""
from __future__ import annotations

import json
import os
import threading

from .. import threads as _threads
import time

import numpy as np

from . import telemetry as _telemetry
from . import tracing as _tracing
from ..log import module_logger as _module_logger

_ENV = "MXNET_TPU_MEMPROF"

# program-record ring bound: one row per real compile; 512 programs is
# far past any healthy process (the executor cache LRU caps at 128)
MAX_RECORDS = 512

_lock = _threads.package_lock("memprof._lock")
_records = []          # program records, build order, bounded
_tls = threading.local()
_listener_installed = False
# monotonic totals (never reset by the ring bound): how many program
# records were opened for real builds vs disk restores, and how many
# backend-compile events landed on an armed record.  The persistent
# program cache's warm-start verification (serving warmup,
# elastic resume) asserts the "built"/"backend_compiles" deltas are
# ZERO across a warm window — the listener-verified form of "nothing
# compiled".
_totals = {"built": 0, "restored": 0, "backend_compiles": 0}

# jax.monitoring event names -> record fields (the three phases of one
# program build: python trace, jaxpr->MLIR lowering, XLA backend
# compile).  A missing event (e.g. a persistent-compilation-cache hit)
# just leaves the field at 0.
_EVENT_FIELDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_ms",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_ms",
    "/jax/core/compile/backend_compile_duration": "compile_ms",
}

# CompiledMemoryStats fields captured off memory_analysis(), renamed to
# plain *_bytes keys in the record
_MEM_FIELDS = (
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("temp_size_in_bytes", "temp_bytes"),
    ("alias_size_in_bytes", "alias_bytes"),
    ("generated_code_size_in_bytes", "generated_code_bytes"),
)


def enabled():
    """Per-program ``memory_analysis`` capture is opt-in
    (``MXNET_TPU_MEMPROF=1``, read per program build): it routes cached
    programs through the AOT dispatch twin, which adds a small host-side
    signature lookup per dispatch.  The compile-time records, the
    retrace explainer, and the OOM black box are always on — they cost
    nothing on the dispatch path."""
    return os.environ.get(_ENV, "0") == "1"


# -- compile-event capture ----------------------------------------------------

def _install_listener():
    """Register the jax.monitoring duration listener once per process.
    Registration is lazy (first program build) so importing the package
    never touches jax.monitoring."""
    global _listener_installed
    with _lock:
        if _listener_installed:
            return
        _listener_installed = True
    try:
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_event)
    except Exception:
        _module_logger(__name__).debug(
            "jax.monitoring unavailable; compile-time spans disabled")


def _on_event(name, duration_secs, **_kwargs):
    """jax.monitoring callback: fill the armed program record.  Must
    never raise (it runs inside the compiler)."""
    try:
        field = _EVENT_FIELDS.get(name)
        if field is None:
            return
        rec = getattr(_tls, "armed", None)
        if rec is None:
            return
        rec[field] = rec.get(field, 0.0) + duration_secs * 1e3
        if field == "compile_ms":
            # backend compile is the last phase: close the record
            _tls.armed = None
            with _lock:
                _totals["backend_compiles"] += 1
            _finalize(rec)
    except Exception:
        pass


def _finalize(rec):
    """One program build completed: feed the histogram + trace spans."""
    _telemetry.histogram(
        "exec_cache.compile_ms",
        help="XLA backend-compile wall time per program").observe(
        rec["compile_ms"])
    if _tracing.is_recording():
        now = _tracing.now_us()
        t = now
        # back-dated spans (we have durations, not start timestamps):
        # rendered adjacent so the trace shows the phase breakdown
        for field, name in (("compile_ms", "compile:backend"),
                            ("lower_ms", "compile:lower"),
                            ("trace_ms", "compile:trace")):
            dur_us = rec.get(field, 0.0) * 1e3
            _tracing.emit_complete(
                name, t - dur_us, dur_us, category="compile",
                args={"label": rec.get("label"), "kind": rec.get("kind")})
            t -= dur_us


def note_build(kind, label=None):
    """Open a program record and arm it for the compile events that
    follow on this thread.  Called by ``executor_cache.note_trace`` from
    inside traced bodies — a record therefore corresponds to one real
    retrace, and the build-order list mirrors the retrace counters."""
    _install_listener()
    rec = {"kind": str(kind), "label": label or "?", "t": time.time(),
           "trace_ms": 0.0, "lower_ms": 0.0, "compile_ms": 0.0,
           "memory": None}
    with _lock:
        _records.append(rec)
        while len(_records) > MAX_RECORDS:
            _records.pop(0)
        _totals["built"] += 1
    _tls.armed = rec
    return rec


def note_restore(label, nbytes=0):
    """Open a program record for an executable DESERIALIZED from the
    persistent disk tier (mxnet_tpu/program_cache.py): kind ``disk``, no
    compile phases, and — deliberately — no listener arming, so a later
    real compile on this thread can never be attributed to the restore.
    The ``disk`` kind is what keeps memory/compile attribution honest on
    warm-started replicas, and it is NOT a recompile: no retrace counter
    moves and no ``recompile_cause:*`` fires."""
    _install_listener()
    armed = getattr(_tls, "armed", None)
    if armed is not None and not armed.get("lower_ms") \
            and not armed.get("compile_ms"):
        # safety net: a record armed by a trace that never lowered is
        # waiting for a compile this restore just proved is never
        # coming.  Retract it — otherwise a warm boot reads built != 0
        # and the dangling arm attributes the next UNRELATED compile
        # on this thread here, both of which break the elastic
        # warm-resume proof (build_totals deltas must be zero on a
        # fully disk-restored replacement worker).
        _tls.armed = None
        with _lock:
            if armed in _records:
                _records.remove(armed)
                _totals["built"] -= 1
    rec = {"kind": "disk", "label": label or "?", "t": time.time(),
           "trace_ms": 0.0, "lower_ms": 0.0, "compile_ms": 0.0,
           "memory": None, "restored_bytes": int(nbytes)}
    with _lock:
        _records.append(rec)
        while len(_records) > MAX_RECORDS:
            _records.pop(0)
        _totals["restored"] += 1
    return rec


def build_totals():
    """Monotonic {built, restored, backend_compiles} counters.  Deltas
    over a window prove what happened in it: a warm start from a
    populated program-cache dir must show built == backend_compiles == 0
    while restored covers every program dispatched."""
    with _lock:
        return dict(_totals)


def program_records():
    """Snapshot of the per-program records (build order): kind, label,
    trace/lower/compile ms, and — under ``MXNET_TPU_MEMPROF=1`` — the
    compiled ``memory_analysis`` byte breakdown."""
    with _lock:
        return [dict(r) for r in _records]


def record_count():
    with _lock:
        return len(_records)


def compile_summary():
    """{count, total_ms, max_ms, mean_ms} over the recorded backend
    compiles (records that actually reached the compiler)."""
    with _lock:
        times = [r["compile_ms"] for r in _records if r["compile_ms"] > 0]
    if not times:
        return {"count": 0, "total_ms": 0.0, "max_ms": 0.0, "mean_ms": 0.0}
    total = sum(times)
    return {"count": len(times), "total_ms": round(total, 3),
            "max_ms": round(max(times), 3),
            "mean_ms": round(total / len(times), 3)}


def reset():
    """Drop the program records (tests / between bench passes)."""
    with _lock:
        del _records[:]


# -- the AOT dispatch twin ----------------------------------------------------

def _memory_analysis_dict(compiled):
    """CompiledMemoryStats -> plain dict, or None when the backend does
    not report it."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for src, dst in _MEM_FIELDS:
        v = getattr(ma, src, None)
        if v is not None:
            out[dst] = int(v)
    if not out:
        return None
    out["total_bytes"] = (out.get("argument_bytes", 0)
                          + out.get("output_bytes", 0)
                          + out.get("temp_bytes", 0))
    return out


def dispatch_signature(args, static_argnums=()):
    """(hashable dispatch key, dynamic leaves, dynamic args) for an AOT
    dispatch wrapper: pytree structure, per-leaf shapes/dtypes/weak
    types/committed devices, and static values — the same information
    ``jax.jit``'s own cache keys on.  THE single definition, shared by
    :class:`ProfiledJit` and the persistent program cache's
    ``DiskCachedJit`` so the two tiers can never disagree on what
    counts as the same program.  Raises on an unhashable non-array
    leaf when the key is later hashed — callers treat that as a
    permanent fallback to the plain jit path."""
    import jax
    statics = tuple((i, args[i]) for i in static_argnums)
    dyn = tuple(a for i, a in enumerate(args) if i not in static_argnums)
    leaves, treedef = jax.tree_util.tree_flatten(dyn)
    sig = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            # non-array leaf: hashable value participates directly
            sig.append(("py", type(leaf).__name__, leaf))
            continue
        devices = getattr(leaf, "devices", None)
        sig.append((tuple(int(d) for d in shape), np.dtype(dtype).str,
                    bool(getattr(leaf, "weak_type", False)),
                    frozenset(devices()) if callable(devices) else None))
    return (treedef, tuple(sig), statics), leaves, dyn


def aot_compile(jitted, args, kind, label, capture_memory=None):
    """Explicit ``lower() -> compile()`` on the SAME jit object, with
    the program-record bookkeeping of the AOT dispatch twin: the jaxpr
    cache and the in-body retrace counters behave exactly like the
    plain call path, the armed record captures the compile phases, and
    a jaxpr-cache hit (body did not re-run) still opens a record so the
    table stays complete.  ``capture_memory`` defaults to the memprof
    flag; the persistent program cache compiles through here so its
    write-back always holds a ``jax.stages.Compiled``."""
    _tls.armed = None
    lowered = jitted.lower(*args)
    rec = getattr(_tls, "armed", None)
    if rec is None:
        # jaxpr-cache hit: the body did not re-run, so no in-body
        # note_trace armed a record (the dp fused step always lands
        # here — its shape probe owns the only body run).  Open one
        # NOW, before compile, so the backend-compile phase attributes
        # to this executable instead of vanishing unarmed.
        rec = note_build(kind, label)
    compiled = lowered.compile()
    # a cached/deduplicated compile may fire no closing event: never
    # leave the record armed past this build (a dangling arm would
    # swallow the next unrelated compile on the thread)
    _tls.armed = None
    if enabled() if capture_memory is None else capture_memory:
        rec["memory"] = _memory_analysis_dict(compiled)
    return compiled


class ProfiledJit:
    """AOT-managed twin of a ``jax.jit`` callable.

    Dispatch goes through explicitly compiled executables (``lower()``
    then ``compile()`` on the SAME jit object, so jax's jaxpr-trace
    cache — and therefore the in-body retrace counters — behave exactly
    as the plain call path), which is the only way to reach the
    compiled program's ``memory_analysis()``.  The executable is chosen
    by a host-side signature over the call arguments (pytree structure,
    shapes, dtypes, weak-types, committed devices, static values) —
    the same information ``jax.jit``'s own cache keys on.

    Any argument this signature cannot describe falls the wrapper back
    to the plain jit path permanently (one warning): correctness over
    attribution.
    """

    __slots__ = ("_jitted", "_kind", "_label", "_static", "_compiled",
                 "_lock", "_fallback")

    def __init__(self, jitted, kind, label, static_argnums=()):
        self._jitted = jitted
        self._kind = kind
        self._label = label
        self._static = tuple(static_argnums)
        self._compiled = {}
        self._lock = _threads.package_lock("ProfiledJit._lock")
        self._fallback = False

    def _arg_key(self, args):
        return dispatch_signature(args, self._static)[0]

    def _compile(self, args):
        # ProfiledJit exists only under the flag: always capture
        return aot_compile(self._jitted, args, self._kind, self._label,
                           capture_memory=True)

    def __call__(self, *args):
        if self._fallback:
            return self._jitted(*args)
        try:
            key = self._arg_key(args)
            compiled = self._compiled.get(key)  # raises if unhashable
        except Exception:
            self._fallback = True
            _module_logger(__name__).warning(
                "memprof: could not build a dispatch signature for "
                "program %r; falling back to the plain jit path (no "
                "memory_analysis for this program)", self._label)
            return self._jitted(*args)
        if compiled is None:
            with self._lock:
                compiled = self._compiled.get(key)
                if compiled is None:
                    compiled = self._compile(args)
                    self._compiled[key] = compiled
        dyn = [a for i, a in enumerate(args) if i not in self._static]
        return compiled(*dyn)


def wrap_jit(jitted, kind, label, static_argnums=()):
    """The program's dispatchable: the plain jit object when memprof is
    off (resolved HERE, at build time — flipping the env affects only
    programs built afterwards), the AOT twin when on."""
    if not enabled():
        return jitted
    return ProfiledJit(jitted, kind, label, static_argnums=static_argnums)


# -- live state ---------------------------------------------------------------

def live_array_census(limit=30):
    """Every live ``jax.Array`` grouped by (shape, dtype): the resident-
    buffer view that complements the per-program allocation plan.
    Host-side metadata walk — O(live arrays), no device sync."""
    try:
        import jax
        arrays = jax.live_arrays()
    except Exception:
        return {"groups": [], "group_count": 0, "array_count": 0,
                "total_bytes": 0}
    groups = {}
    count = 0
    total = 0
    for a in arrays:
        try:
            key = (tuple(int(d) for d in a.shape), np.dtype(a.dtype).str)
            nbytes = int(getattr(a, "nbytes", 0))
        except Exception:
            continue
        g = groups.get(key)
        if g is None:
            g = groups[key] = {"shape": list(key[0]), "dtype": key[1],
                               "count": 0, "total_bytes": 0}
        g["count"] += 1
        g["total_bytes"] += nbytes
        count += 1
        total += nbytes
    rows = sorted(groups.values(), key=lambda g: -g["total_bytes"])
    return {"groups": rows[:int(limit)], "group_count": len(rows),
            "array_count": count, "total_bytes": total}


# -- device-resident block pools ----------------------------------------------
# One buffer, many logical owners: a block pool (serving/continuous.py
# KVBlockPool) allocates one device array and hands out PAGES of it, so
# the live-array census sees a single opaque tensor.  Pools register
# here with a page-granular usage callback; the report carries one row
# per pool (reserved bytes, pages used, bytes used) — the per-page
# footprint accounting the census cannot provide.

_pools = {}


def register_pool(name, page_bytes, total_pages, used_fn):
    """Account a device-resident block pool page-by-page.  ``used_fn``
    () -> pages currently held (active + cached); it must not raise and
    should hold no locks the report path could contend on.  Re-registering
    a name replaces the entry (pool rebuilds)."""
    with _lock:
        _pools[str(name)] = {"page_bytes": int(page_bytes),
                             "total_pages": int(total_pages),
                             "used_fn": used_fn}


def unregister_pool(name):
    with _lock:
        _pools.pop(str(name), None)


def pool_records():
    """One row per registered pool: the page-granular footprint."""
    with _lock:
        items = list(_pools.items())
    out = []
    for name, p in items:
        try:
            used = int(p["used_fn"]())
        except Exception:
            used = None
        row = {"name": name, "page_bytes": p["page_bytes"],
               "total_pages": p["total_pages"],
               "bytes_reserved": p["page_bytes"] * p["total_pages"],
               "pages_used": used,
               "bytes_used": None if used is None
               else used * p["page_bytes"]}
        out.append(row)
    return out


def device_memory():
    """Per-device allocator stats where the backend reports them
    (``Device.memory_stats`` — TPU; None fields on CPU)."""
    out = []
    try:
        import jax
        for dev in jax.local_devices():
            stats = getattr(dev, "memory_stats", lambda: None)() or {}
            out.append({"device": str(dev),
                        "bytes_in_use": stats.get("bytes_in_use"),
                        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                        "bytes_limit": stats.get("bytes_limit")})
    except Exception:
        pass
    return out


def report():
    """The full memory report: program table + live-array census +
    per-device allocator stats.  This is the document
    ``tools/traceview.py --memory`` renders and the OOM dump embeds."""
    try:
        # lazy: program_cache imports this module at its top level
        from .. import program_cache as _program_cache
        disk = _program_cache.stats()
    except Exception:
        disk = None
    return {"kind": "mxnet_tpu_memory", "version": 1,
            "created": time.time(), "memprof_enabled": enabled(),
            "programs": program_records(),
            "compile": compile_summary(),
            "disk": disk,
            "census": live_array_census(),
            "pools": pool_records(),
            "device_memory": device_memory()}


def write_report(path):
    """Write ``report()`` as one strict-JSON file and return the path."""
    from .flight_recorder import _json_safe
    with open(path, "w") as f:
        json.dump(_json_safe(report()), f, allow_nan=False)
    return path


# -- the OOM black box --------------------------------------------------------

def is_oom(exc):
    """Is this a device out-of-memory?  XLA surfaces allocator
    exhaustion as a RESOURCE_EXHAUSTED status (``XlaRuntimeError``);
    matching the status token keeps this independent of where jaxlib
    parks the exception class."""
    return isinstance(exc, Exception) and "RESOURCE_EXHAUSTED" in str(exc)


# oom anomalies recorded per process before the noting stops: the
# flight recorder's anomaly list is unbounded (its FIRST entry is the
# diagnosis), so a serving loop that keeps OOMing every batch must not
# grow it without bound — the counter keeps the full tally
MAX_OOM_ANOMALIES = 64


def record_oom(context, exc):
    """Write the OOM post-mortem: an ``oom`` anomaly on the flight
    recorder plus ONE dump (per process) augmented with the full memory
    report.  Returns the dump path (None when a dump already exists —
    repeats stay cheap: the census-walking report is only built for the
    dump that will actually be written, and anomaly noting stops at
    ``MAX_OOM_ANOMALIES``)."""
    from . import flight_recorder as _flight
    recorder = _flight.get_recorder()
    step = recorder.last_step()
    if recorder.anomaly_count("oom") < MAX_OOM_ANOMALIES:
        recorder.note_anomaly({
            "rule": "oom", "step": step if step is not None else -1,
            "context": str(context),
            "message": str(exc)[:2000]})
    _telemetry.counter(
        "memprof.oom_total",
        help="RESOURCE_EXHAUSTED dispatches observed").inc()
    if recorder.has_dumped("oom"):
        return None
    path = recorder.dump_once(reason="oom",
                              sections={"memory": report()})
    if path:
        _module_logger(__name__).error(
            "device OOM in %s: flight dump with memory report written "
            "to %s", context, path)
    return path


def maybe_record_oom(context, exc):
    """Dispatch-failure hook: records the black box when ``exc`` is a
    device OOM, and never raises (it runs on error paths that must
    surface the ORIGINAL exception).  Idempotent per exception object:
    a sync-surfacing OOM passes through both the dispatch guard and the
    fit loop's handler, and one OOM must count once."""
    try:
        if is_oom(exc) and not getattr(exc, "_mxtpu_oom_recorded", False):
            try:
                exc._mxtpu_oom_recorded = True
            except Exception:
                pass  # slotted exception: double-count beats losing the dump
            return record_oom(context, exc)
    except Exception:
        _module_logger(__name__).exception(
            "OOM black-box capture failed (original error propagates)")
    return None
