"""Persistent compiled-program cache: the DISK tier of the executor
program cache (ref: the reference's CachedOp pool solves the in-process
half — SURVEY.md L2/L8; this is the TPU-native extension).

Every process pays the full trace -> lower -> backend-compile pipeline
for every program at startup, and ``exec_cache.compile_ms`` shows
backend compile dominating time-to-first-step.  At fleet scale
(N replicas x M serving buckets x every deploy/preemption) it dominates
time-to-serving outright.  This module serializes the compiled XLA
executable of every cached program to a directory
(``MXNET_TPU_PROGRAM_CACHE_DIR``) via JAX's AOT serialization machinery
(``jax.experimental.serialize_executable``), so a fresh replica restores
its programs from disk in milliseconds instead of recompiling them:

- **Keying.**  A disk entry is addressed by the sha256 of the owning
  in-memory cache key — the executor cache's ``_signature`` tuple
  (structural graph hash + shapes/dtypes + platform + health / kernel /
  comm flags) for entry programs, an equivalent material tuple for the
  fused train step — plus the program kind and a per-call argument
  fingerprint (pytree structure, shapes, dtypes, weak types, devices,
  static values: the same information ``jax.jit``'s own cache keys on).
  The jax/jaxlib/libtpu + mxnet_tpu **version fingerprint** is stored in
  the entry header and VALIDATED at load: a mismatch is never trusted.
- **Restore path.**  On an in-process miss with a disk hit the
  executable is deserialized instead of compiled: zero retrace (the
  traced body never runs) and zero backend compile.  memprof records the
  program with a ``disk`` kind so attribution stays honest, and no
  ``recompile_cause:*`` fires — a restore is not a recompile.
- **Never trust a bad entry.**  Corruption (magic/sha mismatch, torn
  pickle), version skew, and device mismatch all evict the file with a
  warning and fall back to a fresh compile that overwrites it.
- **Concurrent replicas.**  Writes go to a temp file named with pid AND
  a process-local counter, then ``os.replace`` — the same atomic-rename
  contract as ``io_pipeline._build_rec_index`` / io_native ``_run_gxx``
  — so replicas warming one shared cache dir never read a torn
  executable.  ``MXNET_TPU_PROGRAM_CACHE_RO=1`` makes a replica
  read-only (shared immutable volumes: the deploy pipeline owns writes).

Config: ``MXNET_TPU_PROGRAM_CACHE_DIR`` unset = off, today's behavior
(``wrap_program`` degrades to ``memprof.wrap_jit``, bit-identical).
Operators manage a cache volume with ``tools/cachectl.py``
(ls / verify / prune) instead of reading pickle innards.
"""
from __future__ import annotations

import hashlib
import itertools
import json
import os
import pickle
import struct
import threading

from . import threads as _threads
import time

import numpy as np

from . import profiler as _profiler
from .base import __version__ as _mxtpu_version
from .log import module_logger as _module_logger
from .observability import memprof as _memprof
from .observability import telemetry as _telemetry

ENV_DIR = "MXNET_TPU_PROGRAM_CACHE_DIR"
ENV_RO = "MXNET_TPU_PROGRAM_CACHE_RO"
ENV_MAX_MB = "MXNET_TPU_PROGRAM_CACHE_MAX_MB"

# container format: magic + u32be header length + JSON header + pickled
# (payload, in_tree, out_tree).  The header is readable without touching
# the pickle — tools/cachectl.py lists a volume from headers alone.
MAGIC = b"MXTPC1\n"
SUFFIX = ".mxprog"

_lock = _threads.package_lock("program_cache._lock")
_stats = {"hits": 0, "misses": 0, "evictions": 0, "writes": 0,
          "bytes_written": 0, "bytes_read": 0, "pruned": 0,
          "pruned_bytes": 0}
_max_mb_warned = False
# tmp names carry pid AND this counter: two threads of one process
# saving the same entry must not collide on the temp file either
_TMP_COUNTER = itertools.count()


def cache_dir():
    """The configured disk-tier directory, or None (tier off)."""
    d = os.environ.get(ENV_DIR, "").strip()
    return d or None


def enabled():
    return cache_dir() is not None


def read_only():
    """Read-only replicas restore but never write or evict — the mode
    for N replicas sharing one immutable prewarmed volume."""
    return os.environ.get(ENV_RO, "0") == "1"


def max_cache_bytes():
    """``MXNET_TPU_PROGRAM_CACHE_MAX_MB`` as bytes, or None (no cap —
    the default).  With a cap set, every successful ``save`` prunes the
    directory back under budget OLDEST-FIRST (the cachectl prune core,
    protecting the entry just written), so an unattended RW volume —
    CI, a long-lived deploy pipeline — cannot grow without bound;
    ``tools/cachectl.py prune`` stays for manual, classified pruning.
    Malformed or non-positive values warn once and read as uncapped."""
    global _max_mb_warned
    raw = os.environ.get(ENV_MAX_MB, "").strip()
    if not raw:
        return None
    try:
        mb = float(raw)
    except ValueError:
        mb = -1.0
    if mb <= 0:
        if not _max_mb_warned:
            _max_mb_warned = True
            _module_logger(__name__).warning(
                "ignoring %s=%r (want a positive size in MB); cache "
                "uncapped", ENV_MAX_MB, raw)
        return None
    return int(mb * 1024 * 1024)


def _bump(event, n=1):
    with _lock:
        _stats[event] += n
        value = _stats[event]
    _telemetry.counter("exec_cache.disk." + event).inc(n)
    _profiler.record_counter("exec_cache_disk_" + event, value)


def stats():
    """Disk-tier counter snapshot (mirrored under
    ``executor_cache.stats()["disk"]`` and the ``exec_cache.disk.*``
    telemetry series)."""
    with _lock:
        out = dict(_stats)
    out["enabled"] = enabled()
    out["dir"] = cache_dir()
    out["read_only"] = read_only()
    return out


def reset_stats():
    with _lock:
        for k in _stats:
            _stats[k] = 0


# -- fingerprints -------------------------------------------------------------

def _libtpu_version():
    try:
        from importlib import metadata
    except ImportError:  # pragma: no cover - py<3.8
        return ""
    for dist in ("libtpu", "libtpu-nightly"):
        try:
            return metadata.version(dist)
        except Exception:
            continue
    return ""


# jax.config entries that change what the compiler emits (numerics,
# precision, prng layout) without changing the traced graph's avals —
# they must invalidate a disk entry exactly like a toolchain bump
_JAX_CONFIG_KEYS = ("jax_enable_x64", "jax_default_matmul_precision",
                    "jax_default_prng_impl", "jax_threefry_partitionable")


def version_fingerprint():
    """The toolchain AND compile environment baked into a compiled
    executable: a disk entry is only trusted when ALL of it matches
    exactly — an XLA binary is an artifact of its compiler and the
    compiler's configuration (XLA_FLAGS, precision/prng jax.config
    settings), not of the graph alone.  Joins both the entry header
    (validated at load) and the filename (different environments
    COEXIST in one shared volume instead of mutually evicting)."""
    import jax
    import jaxlib
    cfg = {}
    for k in _JAX_CONFIG_KEYS:
        try:
            cfg[k] = repr(getattr(jax.config, k))
        except AttributeError:
            cfg[k] = ""
    return {"jax": str(jax.__version__),
            "jaxlib": str(jaxlib.__version__),
            "libtpu": _libtpu_version(),
            "mxnet_tpu": str(_mxtpu_version),
            "xla_flags": os.environ.get("XLA_FLAGS", ""),
            "jax_config": cfg}


def version_fp():
    """Short stable hash of :func:`version_fingerprint` — the filename
    segment that keeps mixed-toolchain fleets (rolling deploys sharing
    one RW volume) from thrashing each other's entries."""
    return fingerprint(version_fingerprint())[:10]


def _canon(obj):
    """Canonical, process-stable stringification of key material
    (primitives, tuples/lists, dicts, dtypes) — and NOTHING else.  An
    opaque value collapsed to a type name would ALIAS two different
    programs onto one disk entry (wrong-constants restore), so it
    raises TypeError instead; ``wrap_program`` turns that into
    "decline to persist" (the optimizer_fingerprint pattern)."""
    if isinstance(obj, dict):
        items = sorted(obj.items(), key=lambda kv: repr(kv[0]))
        return "{%s}" % ",".join("%s:%s" % (_canon(k), _canon(v))
                                 for k, v in items)
    if isinstance(obj, (list, tuple)):
        return "(%s)" % ",".join(_canon(x) for x in obj)
    if obj is None or isinstance(obj, (bool, int, float, str, bytes)):
        return repr(obj)
    if isinstance(obj, np.dtype):
        return repr(str(obj))
    if isinstance(obj, np.ndarray):
        return "ndarray:%r:%s:%s" % (tuple(obj.shape), obj.dtype.str,
                                     hashlib.sha256(
                                         np.ascontiguousarray(obj)
                                         .tobytes()).hexdigest())
    if isinstance(obj, np.generic):
        return "npscalar:%s:%r" % (obj.dtype.str, obj.item())
    raise TypeError(
        "unrepresentable key-material value of type %s — an opaque "
        "value cannot key a disk entry faithfully" % type(obj).__name__)


def fingerprint(material):
    """sha256 hex over the canonical form of key material.  Raises
    TypeError when the material contains a value ``_canon`` cannot
    represent exactly."""
    return hashlib.sha256(_canon(material).encode()).hexdigest()


# Optimizer attributes the fused_update trace can NEVER bake in: they
# feed the step program through the per-step scalar ARGUMENTS
# (lr/wd/extras via _get_lr/_get_wd/fused_scalars) or belong to the
# non-fused updater path, so their values need not key the disk entry.
_OPT_ARG_FED_ATTRS = frozenset((
    "lr_scheduler", "param_dict", "lr_mult", "wd_mult", "idx2name",
    "sym_info", "_index_update_count", "_all_index_update_counts",
    "num_update", "begin_num_update", "weight_previous",
))


def _opt_value_key(v):
    """Exact canonical form of one optimizer attribute value (the ONE
    ``_canon`` definition of "faithfully representable"), or None when
    it cannot be represented.  Collapsing an unrepresentable value
    (say, a numpy schedule table the fused update indexes) to its type
    name would ALIAS two different traced programs onto one disk entry
    — the caller must decline to cache instead."""
    try:
        return _canon(v)
    except TypeError:
        return None


def optimizer_fingerprint(opt):
    """Key material for an optimizer's fused-update trace, as
    ``(material, unkeyable_attr_names)``.  The trace bakes
    hyperparameters (momentum, betas, clip, rescale_grad, schedule
    tables, ...) in as program constants, so every attribute the trace
    COULD read keys the disk entry exactly — primitives, containers,
    and numpy arrays (content-hashed).  Known arg-fed attributes
    (schedulers, per-index lr/wd maps — they reach the program as
    per-step scalar arguments, never as traced constants) are skipped.
    Anything else that cannot be represented faithfully lands in
    ``unkeyable_attr_names``: the caller must DISABLE disk caching for
    that program rather than risk restoring an executable with the
    wrong baked constants."""
    items = []
    unkeyable = []
    attrs = vars(opt)
    for k in sorted(attrs):
        if k in _OPT_ARG_FED_ATTRS:
            continue
        vk = _opt_value_key(attrs[k])
        if vk is None:
            unkeyable.append(k)
        else:
            items.append((k, vk))
    return ((type(opt).__module__ + "." + type(opt).__qualname__,
             tuple(items)), tuple(unkeyable))


def _device_kind(platform):
    try:
        import jax
        return str(jax.devices(platform)[0].device_kind)
    except Exception:
        return ""


# -- the on-disk store --------------------------------------------------------

class ProgramStore:
    """One cache directory: encode/decode/save/load of entry files.

    ``load`` is the trust boundary: magic, header fingerprint, platform/
    device kind, and payload sha256 are all validated before the pickle
    is touched, and any failure evicts the file with a warning instead
    of trusting it.  ``inspect`` runs the same validation WITHOUT
    evicting (tools/cachectl.py verify)."""

    def __init__(self, root, ro=None):
        self.root = root
        self.ro = read_only() if ro is None else bool(ro)
        self._log = _module_logger(__name__)

    # -- paths ---------------------------------------------------------------

    def path_for(self, entry_fp, tag, arg_fp):
        # the version segment makes cross-toolchain entries DISTINCT
        # files: a rolling deploy's two jax versions coexist in one RW
        # volume (cachectl prune --stale reclaims the losing side); the
        # header fingerprint check below stays as the trust boundary
        # for tampered/colliding files
        return os.path.join(
            self.root, "%s.%s.%s.%s%s" % (entry_fp[:24], tag,
                                          arg_fp[:16], version_fp(),
                                          SUFFIX))

    def entries(self):
        """Sorted entry paths currently in the directory."""
        try:
            names = os.listdir(self.root)
        except OSError:
            return []
        return sorted(os.path.join(self.root, n) for n in names
                      if n.endswith(SUFFIX))

    # -- encode / decode -----------------------------------------------------

    @staticmethod
    def encode(header, blob):
        hjson = json.dumps(header, sort_keys=True).encode()
        return MAGIC + struct.pack(">I", len(hjson)) + hjson + blob

    @staticmethod
    def split(data):
        """(header dict, blob bytes) from raw entry bytes, or
        ``(None, None)`` when the container framing is broken (no pickle
        is touched)."""
        if len(data) < len(MAGIC) + 4 or not data.startswith(MAGIC):
            return None, None
        (hlen,) = struct.unpack_from(">I", data, len(MAGIC))
        start = len(MAGIC) + 4
        if len(data) < start + hlen:
            return None, None
        try:
            header = json.loads(data[start:start + hlen].decode())
        except (ValueError, UnicodeDecodeError):
            return None, None
        if not isinstance(header, dict):
            return None, None
        return header, data[start + hlen:]

    @classmethod
    def read_header(cls, data):
        """Header dict alone from raw entry bytes."""
        return cls.split(data)[0]

    @staticmethod
    def read_header_file(path):
        """``(header dict or None, file bytes)`` reading ONLY the
        bounded header region — cachectl ls over a fleet volume must
        not stream every multi-MB executable across the mount."""
        size = os.path.getsize(path)
        with open(path, "rb") as f:
            prefix = f.read(len(MAGIC) + 4)
            if len(prefix) < len(MAGIC) + 4 \
                    or not prefix.startswith(MAGIC):
                return None, size
            (hlen,) = struct.unpack_from(">I", prefix, len(MAGIC))
            if hlen > (1 << 20):  # a sane header is a few hundred bytes
                return None, size
            hbytes = f.read(hlen)
        if len(hbytes) < hlen:
            return None, size
        try:
            header = json.loads(hbytes.decode())
        except (ValueError, UnicodeDecodeError):
            return None, size
        return (header if isinstance(header, dict) else None), size

    def decode(self, data, expect_dyn=None, expect_identity=None):
        """Validate + deserialize one entry's raw bytes.

        Returns ``(status, header, loaded)`` with status one of ``ok`` /
        ``corrupt`` / ``identity-mismatch`` / ``version-skew`` /
        ``device-mismatch`` / ``stale-args``; ``loaded`` is the callable
        ``jax.stages.Compiled`` only when ok.  ``expect_dyn`` (optional
        flat list of the actual dynamic call arguments) cross-checks the
        restored program's input avals — a wrong-shape restore must fail
        HERE, not at dispatch.  ``expect_identity`` (optional
        ``(entry_fp, kind, arg_fp)``) cross-checks the header against
        the identity the caller ASKED for: a file renamed/copied onto
        another entry's path (same toolchain, compatible avals) must
        never answer for the wrong program."""
        header, blob = self.split(data)
        if header is None:
            return "corrupt", None, None
        if expect_identity is not None:
            e_fp, kind, a_fp = expect_identity
            if header.get("entry_fp") != e_fp \
                    or header.get("kind") != kind \
                    or header.get("arg_fp") != a_fp:
                return "identity-mismatch", header, None
        try:
            if len(blob) != int(header.get("blob_bytes", -1)) or \
                    hashlib.sha256(blob).hexdigest() \
                    != header.get("blob_sha256"):
                return "corrupt", header, None
        except (TypeError, ValueError):
            return "corrupt", header, None
        if header.get("fingerprint") != version_fingerprint():
            return "version-skew", header, None
        platform = header.get("platform") or None
        try:
            import jax
            devices = jax.devices(platform)
        except Exception:
            return "device-mismatch", header, None
        if header.get("device_kind") and \
                str(devices[0].device_kind) != header["device_kind"]:
            return "device-mismatch", header, None
        # restore onto the devices the entry was BUILT for: left to its
        # default, deserialize_and_load spreads the executable over every
        # local device and a one-device program refuses its arguments
        by_id = {d.id: d for d in devices}
        ids = header.get("device_ids") or []
        if not ids or any(i not in by_id for i in ids):
            return "device-mismatch", header, None
        try:
            from jax.experimental import serialize_executable as _se
            payload, in_tree, out_tree = pickle.loads(blob)
            loaded = _se.deserialize_and_load(
                payload, in_tree, out_tree, backend=platform,
                execution_devices=[by_id[i] for i in ids])
        except Exception:
            return "corrupt", header, None
        if expect_dyn is not None:
            import jax
            want = jax.tree_util.tree_leaves(loaded.args_info)
            if len(want) != len(expect_dyn) or any(
                    tuple(w.shape) != tuple(np.shape(a))
                    or np.dtype(w.dtype) != np.dtype(
                        getattr(a, "dtype", np.result_type(a)))
                    for w, a in zip(want, expect_dyn)):
                return "stale-args", header, None
        return "ok", header, loaded

    # -- save / load ---------------------------------------------------------

    def save(self, path, compiled, *, kind, label, entry_fp, arg_fp,
             platform):
        """Serialize + atomically publish one executable.  Returns the
        path, or None when serialization is unsupported, the store is
        read-only, or the filesystem refuses (all warn, none raise: the
        caller holds a perfectly good freshly-compiled program)."""
        if self.ro:
            return None
        try:
            from jax.experimental import serialize_executable as _se
            payload, in_tree, out_tree = _se.serialize(compiled)
            blob = pickle.dumps((payload, in_tree, out_tree),
                                protocol=pickle.HIGHEST_PROTOCOL)
        except Exception as exc:
            self._log.warning(
                "persistent program cache: backend cannot serialize "
                "program %r (%s); entry not written", label, exc)
            return None
        header = {
            "version": 1, "kind": str(kind), "label": str(label),
            "entry_fp": entry_fp, "arg_fp": arg_fp,
            "platform": str(platform or ""),
            "device_kind": _device_kind(platform),
            "n_devices": self._device_count(platform),
            "device_ids": [
                int(d.id) for d in
                compiled.runtime_executable().local_devices()],
            "fingerprint": version_fingerprint(),
            "created": time.time(), "writer_pid": os.getpid(),
            "blob_bytes": len(blob),
            "blob_sha256": hashlib.sha256(blob).hexdigest(),
        }
        data = self.encode(header, blob)
        tmp = "%s.tmp.%d.%d" % (path, os.getpid(), next(_TMP_COUNTER))
        try:
            with open(tmp, "wb") as f:
                f.write(data)
            os.replace(tmp, path)
        except OSError as exc:
            self._log.warning(
                "persistent program cache: could not write %s (%s)",
                path, exc)
            try:
                os.remove(tmp)
            except OSError:
                pass
            return None
        _bump("writes")
        _bump("bytes_written", len(data))
        limit = max_cache_bytes()
        if limit is not None:
            # size-capped auto-prune on write: the freshly published
            # entry is protected; everything else ages out oldest-first
            self.prune(max_bytes=limit, protect=(path,))
        return path

    @staticmethod
    def _device_count(platform):
        try:
            import jax
            return len(jax.devices(platform or None))
        except Exception:
            return 0

    def load(self, path, *, label=None, tag=None, expect_dyn=None,
             expect_identity=None):
        """The restore path: validated deserialize, or None (counted as
        a miss when the file is absent, as an eviction when present but
        untrusted).  A successful restore opens a memprof program record
        with kind ``disk`` and emits a ``disk_restore:*`` instant — a
        restore is attributable, but it is NOT a ``recompile_cause``."""
        try:
            with open(path, "rb") as f:
                data = f.read()
        except FileNotFoundError:
            _bump("misses")
            return None
        except OSError as exc:
            self._log.warning(
                "persistent program cache: could not read %s (%s); "
                "treating as a miss", path, exc)
            _bump("misses")
            return None
        status, header, loaded = self.decode(
            data, expect_dyn=expect_dyn, expect_identity=expect_identity)
        if status != "ok":
            self.evict(path, status, label=label)
            return None
        _bump("hits")
        _bump("bytes_read", len(data))
        rec = _memprof.note_restore(label or header.get("label"),
                                    nbytes=len(data))
        if _memprof.enabled():
            # restored programs attribute memory too: the warm replica's
            # footprint report must not go blind because nothing compiled
            rec["memory"] = _memprof._memory_analysis_dict(loaded)
        _profiler.record_instant(
            "disk_restore:%s" % (tag or header.get("kind", "?")),
            category="exec_cache",
            args={"label": label or header.get("label"),
                  "bytes": len(data)})
        return loaded

    def evict(self, path, reason, label=None, detail=""):
        """Drop an untrusted entry with a warning.  Never trusted, never
        silently kept: the caller recompiles and the fresh save
        overwrites the file (read-only stores skip the unlink but still
        refuse the entry)."""
        _bump("evictions")
        _telemetry.counter(
            "exec_cache.disk.evict_reason." + reason.replace("-", "_"),
            help="disk-tier entries evicted, by reason").inc()
        self._log.warning(
            "persistent program cache: evicting %s entry %s%s%s — "
            "falling back to a fresh compile", reason, path,
            (" for program %r" % label) if label else "",
            (" (%s)" % detail) if detail else "")
        if not self.ro:
            try:
                os.remove(path)
            except OSError:
                pass

    # -- pruning -------------------------------------------------------------

    def prune(self, max_bytes=None, stale=False, drop_corrupt=False,
              dry_run=False, protect=()):
        """The prune core shared by ``tools/cachectl.py prune`` and the
        on-write auto-prune (``MXNET_TPU_PROGRAM_CACHE_MAX_MB``).

        Classification happens first: with ``drop_corrupt`` entries
        whose container framing is unreadable are doomed, with
        ``stale`` entries whose FULL version fingerprint (toolchain +
        compile environment) no longer matches this process's are
        doomed.  Then, with ``max_bytes``, surviving entries are
        dropped OLDEST-FIRST (mtime) until the directory fits the
        budget.  ``protect`` paths are never removed (the auto-prune
        shields the entry it just wrote).  A trusted, in-budget entry
        is never deleted.  Runs regardless of the store's ``ro`` flag —
        pruning is an explicit capacity/admin action, distinct from the
        load path's never-evict-when-ro contract.

        Returns ``[{file, path, reason, bytes, mtime}]`` of the removed
        (or, with ``dry_run``, matched) entries, and mirrors actual
        removals into the ``pruned``/``pruned_bytes`` stats counters.
        """
        protect = {os.path.abspath(p) for p in protect}
        current = version_fingerprint()
        classify = stale or drop_corrupt
        rows = []
        doomed = []
        for path in self.entries():
            row = {"file": os.path.basename(path), "path": path,
                   "protected": os.path.abspath(path) in protect}
            try:
                row["bytes"] = os.path.getsize(path)
                row["mtime"] = os.path.getmtime(path)
            except FileNotFoundError:
                continue  # vanished mid-walk (a concurrent prune/evict)
            except OSError:
                # present but unstat-able (permissions, stale NFS
                # handle): the CLI removes it as untrusted; budget
                # pruning treats it as oldest so it can be reclaimed
                row["bytes"] = 0
                row["mtime"] = 0
                if drop_corrupt and not row["protected"]:
                    row["reason"] = "unreadable"
                    doomed.append(row)
                    continue
            if row["protected"]:
                rows.append(row)
                continue
            if classify:
                # the header is only opened when a classification mode
                # needs it — a budget-only auto-prune on every save must
                # cost one stat per entry, not one read per entry
                try:
                    header, _ = self.read_header_file(path)
                except FileNotFoundError:
                    continue
                except OSError:
                    header = None
                if header is None:
                    if drop_corrupt:
                        row["reason"] = "corrupt"
                        doomed.append(row)
                        continue
                    # still budget-accountable: oldest-first claims it
                elif stale and header.get("fingerprint") != current:
                    row["reason"] = "stale"
                    doomed.append(row)
                    continue
            rows.append(row)
        if max_bytes is not None:
            # protected entries COUNT toward the budget (the directory
            # must fit) but are never the ones removed
            rows.sort(key=lambda r: r.get("mtime", 0))
            total = sum(r.get("bytes", 0) for r in rows)
            for row in list(rows):
                if total <= max_bytes:
                    break
                if row["protected"]:
                    continue
                total -= row.get("bytes", 0)
                row["reason"] = "over-budget"
                doomed.append(row)
        removed = []
        for row in doomed:
            row.pop("protected", None)
            if not dry_run:
                try:
                    os.remove(row["path"])
                except OSError as exc:
                    self._log.warning(
                        "persistent program cache: could not prune %s "
                        "(%s)", row["path"], exc)
                    continue
            removed.append(row)
        if removed and not dry_run:
            _bump("pruned", len(removed))
            _bump("pruned_bytes", sum(r.get("bytes", 0) for r in removed))
            self._log.info(
                "persistent program cache: pruned %d entr%s (%d bytes) "
                "from %s", len(removed),
                "y" if len(removed) == 1 else "ies",
                sum(r.get("bytes", 0) for r in removed), self.root)
        return removed


def get_store(root=None):
    """The store for ``root`` (default: the env dir), creating the
    directory on first use.  None when the tier is off or the directory
    cannot be created."""
    root = root or cache_dir()
    if root is None:
        return None
    try:
        os.makedirs(root, exist_ok=True)
    except OSError as exc:
        _module_logger(__name__).warning(
            "persistent program cache: cannot create %s (%s); disk tier "
            "disabled for this program", root, exc)
        return None
    return ProgramStore(root)


# -- the dispatch wrapper -----------------------------------------------------

class DiskCachedJit:
    """AOT twin of a ``jax.jit`` callable with a persistent executable
    tier (the ``memprof.ProfiledJit`` dispatch discipline, extended one
    level down the storage hierarchy).

    Dispatch resolves a host-side argument fingerprint, then: in-memory
    executable -> disk restore (zero trace, zero compile) -> explicit
    ``lower().compile()`` on the SAME jit object (so the jaxpr cache and
    the in-body retrace counters behave exactly like the plain call
    path) followed by an atomic write-back.  Arguments the fingerprint
    cannot describe fall back to the plain jit path permanently (one
    warning): correctness over persistence."""

    __slots__ = ("_jitted", "_kind", "_tag", "_label", "_static",
                 "_entry_fp", "_platform", "_store", "_compiled", "_lock",
                 "_fallback")

    def __init__(self, jitted, kind, label, store, entry_fp, platform,
                 tag=None, static_argnums=()):
        self._jitted = jitted
        self._kind = kind
        self._tag = tag or kind
        self._label = label
        self._store = store
        self._entry_fp = entry_fp
        self._platform = platform
        self._static = tuple(static_argnums)
        self._compiled = {}
        self._lock = _threads.package_lock("DiskCachedJit._lock")
        self._fallback = False

    def _mem_key(self, args):
        """(cheap hashable dispatch key, dynamic leaves, dynamic args)
        for the per-call in-memory lookup — ``memprof``'s single shared
        signature definition (the two AOT tiers must never disagree on
        what counts as the same program), with NO string/hash building
        on the steady-state path."""
        return _memprof.dispatch_signature(args, self._static)

    @staticmethod
    def _arg_fingerprint(mem_key):
        """Process-stable sha256 of a dispatch key (the disk filename
        component): two replicas dispatching the same program agree on
        it.  Miss-path only — one string build per executable, ever."""
        treedef, sig, statics = mem_key
        parts = [repr(statics), str(treedef)]
        for entry in sig:
            if entry and entry[0] == "py":
                parts.append("py:%s:%r" % (entry[1], entry[2]))
                continue
            shape, dtype, weak, devs = entry
            parts.append("%r:%s:%d:%s"
                         % (shape, dtype, int(weak),
                            ",".join(sorted(str(d) for d in devs))
                            if devs else ""))
        return hashlib.sha256("\x1f".join(parts).encode()).hexdigest()

    def _obtain(self, args, mem_key, leaves):
        arg_fp = self._arg_fingerprint(mem_key)
        path = self._store.path_for(self._entry_fp, self._tag, arg_fp)
        loaded = self._store.load(
            path, label=self._label, tag=self._tag, expect_dyn=leaves,
            expect_identity=(self._entry_fp, self._tag, arg_fp))
        if loaded is not None:
            return loaded
        compiled = _memprof.aot_compile(self._jitted, args, self._kind,
                                        self._label)
        self._store.save(path, compiled, kind=self._tag,
                         label=self._label, entry_fp=self._entry_fp,
                         arg_fp=arg_fp, platform=self._platform)
        return compiled

    def __call__(self, *args):
        if self._fallback:
            return self._jitted(*args)
        try:
            mem_key, leaves, dyn = self._mem_key(args)
            compiled = self._compiled.get(mem_key)  # raises if unhashable
        except Exception:
            self._fallback = True
            _module_logger(__name__).warning(
                "persistent program cache: could not build a dispatch "
                "signature for program %r; falling back to the plain "
                "jit path (no disk tier for this program)", self._label)
            return self._jitted(*args)
        if compiled is None:
            with self._lock:
                compiled = self._compiled.get(mem_key)
                if compiled is None:
                    compiled = self._obtain(args, mem_key, leaves)
                    self._compiled[mem_key] = compiled
        return compiled(*dyn)


def wrap_program(jitted, kind, label, key_material=None, platform=None,
                 tag=None, static_argnums=()):
    """The program's dispatchable.  Disk tier off (or no key material):
    exactly today's behavior — ``memprof.wrap_jit`` (the plain jit
    object, or the memprof AOT twin under ``MXNET_TPU_MEMPROF=1``).
    Disk tier on: a :class:`DiskCachedJit` keyed by
    ``sha256(key_material)``, which also captures ``memory_analysis``
    when memprof is enabled.  Resolved HERE, at program-build time —
    flipping the env affects only programs built afterwards, exactly
    like the memprof flag."""
    store = get_store() if key_material is not None else None
    if store is None:
        return _memprof.wrap_jit(jitted, kind, label,
                                 static_argnums=static_argnums)
    try:
        entry_fp = fingerprint(key_material)
    except TypeError as exc:
        _module_logger(__name__).warning(
            "persistent program cache: program %r not persisted — %s",
            label, exc)
        return _memprof.wrap_jit(jitted, kind, label,
                                 static_argnums=static_argnums)
    return DiskCachedJit(jitted, kind, label, store, entry_fp, platform,
                         tag=tag, static_argnums=static_argnums)
