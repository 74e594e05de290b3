"""What every cell shares: the manifest, the look for a chip, spans on the
profiler's clock, the traced window, the per-layer readers and the result
line.  Whatever belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name ``BENCHMARK.json``
gives it; adding a cell, a configuration or a metric adds files and manifest
entries and edits nothing here."""
from __future__ import annotations

import contextlib
import glob
import importlib
import json
import os
import sys
import time

from . import peaks, trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Refused(Exception):
    """The run cannot be made here (no chip, unknown cell...): exit non-zero
    and print no result."""


# -- manifest -----------------------------------------------------------------

def load_cell(workload, manifest_path=None):
    """The cell's manifest entry with its configuration and traffic files
    read in.  Data files are looked for beside the manifest, under its
    ``paths``."""
    manifest_path = manifest_path or os.path.join(ROOT, "BENCHMARK.json")
    with open(manifest_path) as f:
        manifest = json.load(f)
    base = os.path.dirname(os.path.abspath(manifest_path))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise Refused("no workload %r in %s (have: %s)"
                      % (workload, manifest_path, sorted(cells)))
    cell = dict(cells[workload])
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(base, entry["file"])) as f:
        config = json.load(f)
    traffic = _find(base, manifest["paths"], "traffic", cell["traffic"])
    return {"cell": cell, "config": config, "traffic": traffic,
            "manifest": manifest, "base": base}


def _find(base, paths, kind, name):
    for p in paths:
        path = os.path.join(base, p, kind, name + ".json")
        if os.path.isfile(path):
            with open(path) as f:
                return json.load(f)
    raise Refused("no %s file %s.json under %s" % (kind, name, paths))


def metrics_for(loaded, group):
    """The manifest's metrics of ``group`` that this cell reports."""
    name = loaded["cell"]["name"]
    return [m for m in loaded["manifest"][group]
            if "workloads" not in m or name in m["workloads"]]


# -- the chip -------------------------------------------------------------------

def find_chip(chips, require_chip=True):
    """The devices the cell runs on, as JAX reports them.  Refuses anything
    but a TPU whose kind is in the peak table, with chips enough."""
    import jax
    devices = jax.devices()
    if require_chip:
        if jax.default_backend() != "tpu":
            raise Refused("the default JAX backend is %r, not a TPU"
                          % jax.default_backend())
        try:
            peaks.peak(devices[0].device_kind)
        except peaks.UnknownDevice as e:
            raise Refused(str(e)) from None
    if len(devices) < chips:
        raise Refused("the cell needs %d chip(s), JAX found %d"
                      % (chips, len(devices)))
    return devices[:chips]


def use_compile_cache():
    """JAX's persistent compilation cache at a FIXED path inside the
    checkout (the path is part of the cache's key), unless the environment
    places it; every program is kept, however quick its compile."""
    import jax
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def memory_peak_bytes(devices, cell, compile_again):
    """The peak on the fullest chip: the larger of the allocator's
    ``peak_bytes_in_use`` and what XLA says the program the window drives
    needs at its peak (``memory_analysis``: arguments and temporaries live
    together).  On the v5e runtime the allocator's peak leaves an
    executable's temporaries out (a reference step with 7.9 GB of them left
    it at 1.165 GB, chip run of PR 24), and a training step's activations
    live there.  Reading the analysis means loading the executable a second
    time, so a checkout reads it once per cell and keeps the number beside
    its compile cache."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    path = os.path.join(ROOT, ".bench_cache", "program_peak_bytes.json")
    known = {}
    if os.path.isfile(path):
        with open(path) as f:
            known = json.load(f)
    if cell not in known:
        analysis = compile_again().memory_analysis()
        sizes = {k: int(getattr(analysis, k) or 0) for k in dir(analysis)
                 if k.endswith("_in_bytes")}
        print("memory_analysis of the window's program: %s" % sizes,
              file=sys.stderr)
        known[cell] = sizes.get("peak_memory_in_bytes") or (
            sizes.get("temp_size_in_bytes", 0)
            + sizes.get("argument_size_in_bytes", 0))
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = "%s.%d" % (path, os.getpid())
        with open(tmp, "w") as f:
            json.dump(known, f)
        os.replace(tmp, path)
    return max(peak, known[cell])


# -- spans and the traced window ---------------------------------------------

class Spans:
    """``bench:``-prefixed annotations on the profiler's clock; free when
    tracing is off."""

    def __init__(self, on):
        self.on = bool(on)
        self._window = None

    def __call__(self, name):
        if not self.on:
            return contextlib.nullcontext()
        from jax.profiler import TraceAnnotation
        return TraceAnnotation("bench:" + name)

    def open_window(self):
        if self.on:
            from jax.profiler import TraceAnnotation
            self._window = TraceAnnotation(trace_reduce.WINDOW_SPAN)
            self._window.__enter__()

    def close_window(self):
        if self._window is not None:
            self._window.__exit__(None, None, None)
            self._window = None


class Tracer:
    """The profiler round one traced window.  The Python tracer is off: it
    would record every call of the host loop and slow what it measures."""

    def __init__(self, on):
        self.on = bool(on)
        self.dir = os.path.join(ROOT, ".bench_trace", "run-%d" % os.getpid())
        self.started = False

    def start(self):
        if not self.on or self.started:
            return
        import jax
        import shutil
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.started = True

    def stop_and_reduce(self):
        """Stop the profiler, reduce what it wrote, delete the files."""
        if not self.started:
            return None
        import jax
        import shutil
        jax.profiler.stop_trace()
        self.started = False
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        try:
            if not found:
                return None
            recording = trace_reduce.load_xplane(found[0])
            # diagnosis: keep the recording (tests/data's slice was cut so)
            keep = os.environ.get("BENCH_KEEP_RECORDING")
            if keep:
                with open(keep, "w") as f:
                    json.dump(recording, f)
            return trace_reduce.reduce(recording)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# -- per-layer readers --------------------------------------------------------

def read_per_layer(loaded, obs):
    """{name: {"value", "unit"}} of the cell's per-layer metrics: each
    metric's own file names its reader; a reader that finds nothing to read
    returns None and the metric is left out of the line."""
    out = {}
    for m in metrics_for(loaded, "per_layer"):
        spec = _find(loaded["base"], loaded["manifest"]["paths"], "metrics",
                     m["name"])
        reader = importlib.import_module(spec["reader"])
        value = reader.read(obs, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- the result ---------------------------------------------------------------

def device_block(devices, memory_peak, reduced=None):
    block = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices),
             "memory_peak_bytes": int(memory_peak)}
    if reduced is not None:
        block["busy_s"] = reduced["busy_s"]
        block["window_s"] = reduced["window_s"]
    return block


def checks_ok(checks):
    """``checks``: {name: [value, limit]}; a value above its limit, or not a
    number, fails."""
    return all(v == v and v <= limit for v, limit in checks.values())


def emit(result, checks, notes=()):
    """Notes and the numbers compared on standard error (the comparison
    last), then the one result line as the last line of standard output,
    with the comparison under its last key."""
    for note in notes:
        print(note, file=sys.stderr)
    print("compared (value <= limit): " + ", ".join(
        "%s %.6g <= %.6g" % (k, v, lim) for k, (v, lim) in checks.items()),
        file=sys.stderr, flush=True)
    line = dict(result)
    line["compared"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    print(json.dumps(line), flush=True)


def now():
    return time.perf_counter()
