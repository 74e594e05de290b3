"""Process-wide compiled-program cache for Executors (ref: CachedOp +
the shared memory pools of src/executor/graph_executor.cc).

The reference gets its symbolic-mode speed from reusing compiled graphs:
CachedOp keeps one optimized graph per (graph, shape) signature and
GraphExecutor shares memory pools across rebinds.  Here the equivalent
asset is the *traced, jitted XLA program*: tracing a whole-graph
evaluator is the expensive step (seconds for real models), so every
`Executor.__init__` used to pay it again even when an identical program
already existed — each rebind, `Executor.reshape`, `BucketingModule`
bucket, and `Module._rebind_for_batch` retraced from scratch.

This module keys programs by the full dispatch signature

    (structural graph fingerprint, arg shapes+dtypes, aux shapes+dtypes,
     gradient-taking arg names)

so Executors constructed over the same signature share ONE entry holding:

- the `_Program` (topo order, rng nodes, shape overrides),
- `fwd`:     jitted (args, auxs, keys, train) -> (outputs, new_auxs)
- `fwd_bwd`: jitted (args, auxs, keys, heads) -> (outputs, new_auxs,
  grads) — forward AND backward as one fused `jax.vjp` program, the
  north-star "one XLA program per training step" dispatch.  An empty
  `heads` tuple means ones head-gradients built inside the program (the
  canonical training form — no per-step ones upload).  On TPU the aux
  buffers are donated into the program (`donate_argnums`) so BatchNorm
  moving stats update in place instead of doubling their HBM footprint.

Trace counters increment inside the traced function bodies — a Python
body only runs when jax actually (re)traces — so `stats()` reports real
recompiles, not guesses, and a recompile regression shows up as a
counter jump in the tests.

Config: `MXNET_TPU_EXEC_CACHE=0` disables sharing (each Executor builds
a private program); `MXNET_TPU_EXEC_CACHE_SIZE` caps the LRU (default
128 entries).  Cache events surface as Chrome-trace counter events when
the profiler is running (`profiler.record_counter`).
"""
from __future__ import annotations

import functools
import os
import threading

from . import threads as _threads
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from . import profiler as _profiler
from .log import module_logger as _module_logger
from .observability import health as _health
from .observability import memprof as _memprof
from .observability import telemetry as _telemetry

_lock = _threads.package_lock("executor_cache._lock")
_entries = OrderedDict()  # key -> ProgramEntry, LRU order
_stats = {"hits": 0, "misses": 0, "evictions": 0,
          "traces_fwd": 0, "traces_fwd_bwd": 0, "traces_fused_step": 0}
_recompile_causes = {}  # cause slug -> count (the retrace explainer)


def _enabled():
    return os.environ.get("MXNET_TPU_EXEC_CACHE", "1") != "0"


def _maxsize():
    return int(os.environ.get("MXNET_TPU_EXEC_CACHE_SIZE", "128"))


class ProgramEntry:
    """One cached compiled form of a graph signature.

    `fwd_bwd` may donate its aux inputs (TPU); `fwd_bwd_nd` never does —
    the compatibility backward() path feeds it buffers that stay live.
    When donation is off they are the same jitted callable, so the pair
    costs no extra trace.

    `health` marks entries whose `fwd_bwd` appends the in-program
    numerics summary (observability/health.py) and returns a 4-tuple
    `(outputs, new_aux, grads, health_vec)`; the flag is part of the
    cache key, so enabling the sentinel costs exactly one retrace per
    program and disabling it costs zero.

    `label` names the entry in the memory/compile observability layer
    (observability/memprof.py): program records, `stats()["programs"]`,
    and `traceview --memory` all carry it."""

    __slots__ = ("prog", "fwd", "fwd_bwd", "fwd_bwd_nd", "donates_aux",
                 "n_keys", "health", "health_layout", "label")

    def __init__(self, prog, fwd, fwd_bwd, fwd_bwd_nd, donates_aux, n_keys,
                 health=False, health_layout=None, label=None):
        self.prog = prog
        self.fwd = fwd
        self.fwd_bwd = fwd_bwd
        self.fwd_bwd_nd = fwd_bwd_nd
        self.donates_aux = donates_aux
        self.n_keys = n_keys
        self.health = health
        self.health_layout = health_layout
        self.label = label


def note_trace(kind, label=None, build_record=True):
    """Record one jax trace of kind 'fwd' / 'fwd_bwd' / 'fused_step'.

    Called from INSIDE jitted function bodies: the body only executes
    when jax traces (first call per signature), so this counts real
    retraces.  Also used by module/fused_step.py for its step program.
    A recompile is the single most important instant on a TPU timeline,
    so it also lands as an "i" marker in the trace and increments the
    registry counter (both emits run at trace time, on the host — they
    cannot themselves change the program being traced).  ``label``
    (the entry's label) opens a memprof program record that the
    compile-duration listener fills in — the per-program compile-time
    attribution behind ``stats()["programs"]``.

    ``build_record=False`` counts the retrace WITHOUT opening/arming a
    memprof record: the dp fused step's shape-derivation probe is a
    real (and its only) trace, but no compile follows it directly — a
    record armed there would swallow the next unrelated compile on the
    thread (a sharded device_put's transfer program, say) and put
    phantom builds into the warm-boot totals the elastic resume proof
    reads.  Its real compile attributes via ``memprof.aot_compile``.
    """
    with _lock:
        _stats["traces_" + kind] += 1
        value = _stats["traces_" + kind]
    if build_record:
        _memprof.note_build(kind, label)
    _telemetry.counter("exec_cache.traces_" + kind,
                       help="real jax retraces of the %s program"
                       % kind).inc()
    _profiler.record_counter("exec_cache_traces_" + kind, value)
    _profiler.record_instant("recompile:" + kind, category="exec_cache",
                             args={"total": value})


def _note(event):
    with _lock:
        _stats[event] += 1
        value = _stats[event]
    _telemetry.counter("exec_cache." + event).inc()
    _profiler.record_counter("exec_cache_" + event, value)


def _signature(symbol, arg_dict, aux_dict, grad_names, platform, health):
    # the resolved Pallas-kernel modes key the entry exactly like the
    # health flag: flipping MXNET_TPU_PALLAS_* re-keys the program (one
    # retrace to enable, zero to disable, off-path program untouched) —
    # the op impls resolve the same modes at trace time (docs/kernels.md)
    from .ops import pallas_kernels as _pk
    fp = symbol.structural_hash()
    arg_sig = tuple(sorted(
        (n, tuple(int(d) for d in a.shape), str(np.dtype(a.dtype)))
        for n, a in arg_dict.items()))
    aux_sig = tuple(sorted(
        (n, tuple(int(d) for d in a.shape), str(np.dtype(a.dtype)))
        for n, a in aux_dict.items()))
    return (fp, arg_sig, aux_sig, tuple(grad_names), platform,
            bool(health), _pk.kernel_signature(platform))


# -- retrace explainer --------------------------------------------------------
#
# a cache miss whose symbol already has a cached sibling is the
# interesting kind: the graph did not change, so SOMETHING in the
# dispatch signature did, and "1 unexpected retrace" should come with a
# name.  diff_signatures names the differing component(s); the miss
# path emits a `recompile_cause:<primary>` instant + counter + log line.

# primary-cause priority: the most common/most actionable first
_CAUSE_PRIORITY = ("shapes", "dtypes", "arg_names", "aux_names",
                   "grad_names", "platform", "health", "kernel_flags")


def _diff_shape_sig(prefix, old_sig, new_sig, causes, details):
    """Diff two sorted (name, shape, dtype) tuples; appends causes
    '<prefix>_names' / 'shapes' / 'dtypes' with one-line details."""
    old_d = {n: (s, d) for n, s, d in old_sig}
    new_d = {n: (s, d) for n, s, d in new_sig}
    if set(old_d) != set(new_d):
        causes.append(prefix + "_names")
        added = sorted(set(new_d) - set(old_d))
        removed = sorted(set(old_d) - set(new_d))
        details.append("%s added=%s removed=%s"
                       % (prefix, added or "[]", removed or "[]"))
    shape_diffs = [(n, old_d[n][0], new_d[n][0])
                   for n in sorted(set(old_d) & set(new_d))
                   if old_d[n][0] != new_d[n][0]]
    dtype_diffs = [(n, old_d[n][1], new_d[n][1])
                   for n in sorted(set(old_d) & set(new_d))
                   if old_d[n][1] != new_d[n][1]]
    if shape_diffs:
        causes.append("shapes")
        n, a, b = shape_diffs[0]
        more = "" if len(shape_diffs) == 1 \
            else " (+%d more)" % (len(shape_diffs) - 1)
        details.append("%s %r: %s -> %s%s" % (prefix, n, a, b, more))
    if dtype_diffs:
        causes.append("dtypes")
        n, a, b = dtype_diffs[0]
        more = "" if len(dtype_diffs) == 1 \
            else " (+%d more)" % (len(dtype_diffs) - 1)
        details.append("%s %r: %s -> %s%s" % (prefix, n, a, b, more))


def diff_signatures(old_key, new_key):
    """Explain how two same-symbol cache keys differ.

    Returns ``(primary_cause, all_causes, detail)`` where causes are
    slugs from ``shapes / dtypes / arg_names / aux_names / grad_names /
    platform / health / kernel_flags`` (primary = highest-priority one)
    and ``detail`` is a human one-liner naming the first difference per
    component.  ``(None, [], "")`` when the keys are identical."""
    causes, details = [], []
    _diff_shape_sig("arg", old_key[1], new_key[1], causes, details)
    _diff_shape_sig("aux", old_key[2], new_key[2], causes, details)
    if old_key[3] != new_key[3]:
        causes.append("grad_names")
        details.append("grad names %s -> %s"
                       % (list(old_key[3]), list(new_key[3])))
    if old_key[4] != new_key[4]:
        causes.append("platform")
        details.append("platform %s -> %s" % (old_key[4], new_key[4]))
    if old_key[5] != new_key[5]:
        causes.append("health")
        details.append("health sentinel %s -> %s"
                       % (old_key[5], new_key[5]))
    if old_key[6] != new_key[6]:
        causes.append("kernel_flags")
        details.append("kernel flags %s -> %s"
                       % (old_key[6], new_key[6]))
    if not causes:
        return None, [], ""
    primary = next(c for c in _CAUSE_PRIORITY if c in causes)
    return primary, causes, "; ".join(details)


def _explain_miss(sibling_key, new_key):
    """A miss with a cached same-symbol sibling: name what changed.
    Host-side, on the (rare, compile-bound) miss path only."""
    primary, causes, detail = diff_signatures(sibling_key, new_key)
    if primary is None:
        return
    with _lock:
        _recompile_causes[primary] = _recompile_causes.get(primary, 0) + 1
    _telemetry.counter(
        "exec_cache.recompile_cause." + primary,
        help="same-symbol cache misses explained by this component").inc()
    _profiler.record_instant(
        "recompile_cause:" + primary, category="exec_cache",
        args={"causes": list(causes), "detail": detail})
    _module_logger(__name__).info(
        "executor cache miss on an already-cached symbol: %s changed "
        "(%s) — this dispatch will trace a new program", primary, detail)


def _build_entry(symbol, known_shapes, grad_names, platform, health=False,
                 key=None):
    # lazy imports: executor.py imports this module at its top level,
    # and program_cache imports observability (keep import cost off the
    # common path)
    from . import program_cache as _program_cache
    from .executor import _Program
    from .ops import pallas_kernels as _pk

    prog = _Program(symbol)
    prog.finalize_shapes(known_shapes)
    n_keys = len(prog.rng_nodes)
    arg_names = prog.arg_names
    aux_names = prog.aux_names
    grad_names = list(grad_names)
    # the memprof label: human symbol name + structural fingerprint
    # prefix, stable across rebinds of the same graph
    label = "%s@%s" % (getattr(symbol, "name", None) or "sym",
                       symbol.structural_hash()[:10])

    # persistent disk tier (program_cache.py): the signature key IS the
    # disk key material; `tag` keeps the donating fwd_bwd and its
    # non-donating twin in distinct files (same args, different
    # executables).  Tier off -> wrap_program == memprof.wrap_jit,
    # today's behavior exactly.
    def _wrap(jitted, kind, tag, static_argnums=()):
        return _program_cache.wrap_program(
            jitted, kind, label, key_material=key, platform=platform,
            tag=tag, static_argnums=static_argnums)

    def _for_platform(impl):
        # the ops resolve their kernel flags against the platform this
        # entry is BOUND for, not the process default backend: an
        # mx.cpu() executor on a TPU host traces no Mosaic kernel
        @functools.wraps(impl)
        def scoped(*args):
            with _pk.trace_scope(platform=platform):
                return impl(*args)
        return scoped

    def _fwd_impl(arg_vals, aux_vals, keys, train):
        note_trace("fwd", label)
        arg_map = dict(zip(arg_names, arg_vals))
        aux_map = dict(zip(aux_names, aux_vals))
        outs, new_aux = prog.evaluate(arg_map, aux_map, keys, train)
        return outs, [new_aux[n] for n in aux_names]

    _fwd = _wrap(jax.jit(_for_platform(_fwd_impl), static_argnums=(3,)),
                 "fwd", "fwd", static_argnums=(3,))

    # the sentinel layout is derived from the program's static structure
    # (output count, grad-name order, attention-node names), never from
    # traced values
    health_layout = _health.HealthLayout(
        len(prog.entries), grad_names,
        tap_names=_health.attention_tap_names(prog.order)) \
        if health else None

    def _fwd_bwd_impl(arg_vals, aux_vals, keys, head_grads):
        note_trace("fwd_bwd", label)
        arg_map = dict(zip(arg_names, arg_vals))
        aux_map = dict(zip(aux_names, aux_vals))

        def f(gvals):
            amap = dict(arg_map)
            amap.update(zip(grad_names, gvals))
            outs, new_aux = prog.evaluate(amap, aux_map, keys, True)
            return outs, [new_aux[n] for n in aux_names]

        gvals = [arg_map[n] for n in grad_names]
        if health:
            # attention ops note_tap their max|logit| bound while the
            # forward traces; the frame collects them in topo order —
            # the order the layout's tap slots were named in.  The taps
            # ride out of the vjp as has_aux values (returning the
            # frame's tracers directly would leak them out of the
            # linearization trace)
            def f_tapped(gvals):
                with _health.collect_taps() as frame:
                    result = f(gvals)
                return result, list(frame)

            (outs, new_aux), vjp_fn, taps = jax.vjp(
                f_tapped, gvals, has_aux=True)
        else:
            taps = None
            (outs, new_aux), vjp_fn = jax.vjp(f, gvals)
        heads = list(head_grads) if head_grads \
            else [jnp.ones_like(o) for o in outs]
        zeros_aux = [jnp.zeros_like(a) for a in new_aux]
        (grads,) = vjp_fn((heads, zeros_aux))
        if health:
            # in-program numerics summary: a few extra reductions over
            # values this program already holds; the fused dispatch
            # returns one small vector alongside its usual results
            hvec = _health.pack_summary(health_layout, outs, gvals,
                                        list(grads), taps=taps)
            return outs, new_aux, grads, hvec
        return outs, new_aux, grads

    # donation halves the aux-state footprint, but jax only implements it
    # on accelerator backends — donating on cpu would warn on every
    # compile without freeing anything.  Decided by the BIND context's
    # platform (part of the cache key), not the process default backend:
    # a cpu-context executor on a TPU host must not donate.  Only
    # forward_backward() may use the donating form (it replaces the aux
    # buffers right after); the compatibility backward() path uses the
    # non-donating twin because the buffers it feeds stay live in
    # aux_dict.
    donate = (1,) if platform == "tpu" else ()
    _fwd_bwd_impl = _for_platform(_fwd_bwd_impl)
    _fwd_bwd = _wrap(jax.jit(_fwd_bwd_impl, donate_argnums=donate),
                     "fwd_bwd", "fwd_bwd")
    _fwd_bwd_nd = _wrap(jax.jit(_fwd_bwd_impl), "fwd_bwd", "fwd_bwd_nd") \
        if donate else _fwd_bwd

    return ProgramEntry(prog, _fwd, _fwd_bwd, _fwd_bwd_nd, bool(donate),
                        n_keys, health=bool(health),
                        health_layout=health_layout, label=label)


def get_entry(symbol, arg_dict, aux_dict, grad_names, platform="cpu",
              health=None):
    """The shared ProgramEntry for this bind signature (building and
    inserting it on first sight).  arg_dict/aux_dict map name -> array-
    like with .shape/.dtype; grad_names is the ordered tuple of
    arguments whose gradients the backward program must produce;
    platform is the bind context's device platform (keys the entry and
    gates aux donation); health (default: the MXNET_TPU_HEALTH env)
    appends the in-program numerics summary to fwd_bwd and keys the
    entry — gradient-free signatures never split on it, since only
    fwd_bwd carries the sentinel."""
    if health is None:
        health = _health.enabled()
    health = bool(health) and bool(grad_names)
    known = {n: tuple(int(d) for d in a.shape) for n, a in arg_dict.items()}
    known.update((n, tuple(int(d) for d in a.shape))
                 for n, a in aux_dict.items())
    if not _enabled():
        from . import program_cache as _program_cache
        _note("misses")
        # no in-process sharing, but the DISK tier (when configured)
        # still wants the signature as its key material
        key = _signature(symbol, arg_dict, aux_dict, grad_names,
                         platform, health) \
            if _program_cache.enabled() else None
        return _build_entry(symbol, known, grad_names, platform,
                            health=health, key=key)
    key = _signature(symbol, arg_dict, aux_dict, grad_names, platform,
                     health)
    sibling_key = None
    with _lock:
        entry = _entries.get(key)
        if entry is not None:
            _entries.move_to_end(key)
            _stats["hits"] += 1
            hits = _stats["hits"]
        else:
            hits = None
            # most-recently-used cached signature of the SAME symbol:
            # the retrace explainer's diff baseline
            for k in reversed(_entries):
                if k[0] == key[0]:
                    sibling_key = k
                    break
    if entry is not None:
        _telemetry.counter("exec_cache.hits").inc()
        _profiler.record_counter("exec_cache_hits", hits)
        return entry
    if sibling_key is not None:
        _explain_miss(sibling_key, key)
    _note("misses")
    entry = _build_entry(symbol, known, grad_names, platform,
                         health=health, key=key)
    with _lock:
        # a concurrent bind may have built the same signature; first
        # insertion wins so every caller shares one traced program
        existing = _entries.get(key)
        if existing is not None:
            return existing
        _entries[key] = entry
        evicted = 0
        while len(_entries) > _maxsize():
            _entries.popitem(last=False)
            _stats["evictions"] += 1
            evicted += 1
    if evicted:
        _telemetry.counter("exec_cache.evictions").inc(evicted)
        _profiler.record_instant("exec_cache_eviction",
                                 category="exec_cache",
                                 args={"evicted": evicted})
    return entry


def trace_counts():
    """Snapshot of the real-retrace counters only ({'traces_fwd': ...,
    'traces_fwd_bwd': ..., 'traces_fused_step': ...}).  These increment
    INSIDE traced bodies, so a delta of zero between two points proves
    no program was (re)compiled in between — the serving warmup
    verification contract (mxnet_tpu/serving/, docs/serving.md)."""
    with _lock:
        return {k: _stats[k] for k in _stats if k.startswith("traces_")}


class watch_traces:
    """Context manager over ``trace_counts``: ``delta()``/``total()``
    report the retraces that happened since ``__enter__``.  Usable after
    exit (the end snapshot freezes at ``__exit__``) so callers can
    assert zero-recompile windows::

        with executor_cache.watch_traces() as w:
            serve_requests()
        assert w.total() == 0, w.delta()
    """

    def __enter__(self):
        self._t0 = trace_counts()
        self._t1 = None
        return self

    def __exit__(self, *exc):
        self._t1 = trace_counts()
        return False

    def delta(self):
        end = self._t1 if self._t1 is not None else trace_counts()
        return {k: end[k] - self._t0.get(k, 0) for k in end}

    def total(self):
        return sum(self.delta().values())


def stats():
    """Counter snapshot: hits/misses/evictions, per-kind trace counts,
    live entry count, whether sharing is enabled, the retrace-explainer
    tallies (``recompile_causes``), and the memory/compile observability
    layer's view of the cached programs — ``programs`` (one record per
    real compile: label, kind, trace/lower/compile ms, and under
    ``MXNET_TPU_MEMPROF=1`` the compiled ``memory_analysis`` byte
    breakdown) plus the backend-compile-time summary ``compile_ms``
    (full distribution in the ``exec_cache.compile_ms`` telemetry
    histogram), and the persistent disk tier's counters (``disk``:
    hits/misses/evictions/writes/bytes — program_cache.py, mirrored as
    ``exec_cache.disk.*`` telemetry)."""
    from . import program_cache as _program_cache
    with _lock:
        out = dict(_stats)
        out["entries"] = len(_entries)
        out["recompile_causes"] = dict(_recompile_causes)
    out["enabled"] = _enabled()
    out["programs"] = _memprof.program_records()
    out["compile_ms"] = _memprof.compile_summary()
    out["disk"] = _program_cache.stats()
    return out


def reset_stats():
    """Zero the counters (entries stay cached; the memprof program
    records are owned by observability.memprof and reset there)."""
    with _lock:
        for k in _stats:
            _stats[k] = 0
        _recompile_causes.clear()


def clear():
    """Drop every cached entry (live Executors keep their references;
    only future binds rebuild)."""
    with _lock:
        _entries.clear()
