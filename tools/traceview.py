"""Summarize a Chrome trace-event dump from mxnet_tpu.profiler.

    python tools/traceview.py /tmp/mxnet_tpu_smoke_trace.json [--top N]
    python tools/traceview.py --serving /tmp/trace_or_telemetry.json
    python tools/traceview.py --flight /tmp/flight_dump.json
    python tools/traceview.py --memory /tmp/memory_report_or_flight.json
    python tools/traceview.py --elastic /tmp/flight_dump.json
    python tools/traceview.py --requests /tmp/flight_or_reqtrace.json
    python tools/traceview.py --fleet /tmp/fleet_dump_dir/
    python tools/traceview.py --dash /tmp/mxnet_tpu_ts_<root>/
    python tools/traceview.py --alerts /tmp/flight_dump.json

Three views over one trace:

- **Top spans**: per-(category, name) call counts and total/avg wall
  time — the first place a perf regression shows up.
- **Step breakdown**: the per-step components `BaseModule.fit` emits
  (data_wait / fwd_bwd_dispatch / update / metric / sync) as a table
  with each component's share of measured step time, plus the coverage
  fraction (how much of the step the components explain) and the
  input-starvation ratio (data_wait / step — the "is the step
  input-bound?" answer).
- **Instants**: recompiles and cache evictions, counted by name.

`--serving` switches to the inference-service view (p50/p95/p99 request
latency, queue/dispatch phase breakdown, batch-size distribution,
rejection counts by reason).  It accepts EITHER a Chrome trace holding
`serving:*` spans (exact percentiles over the recorded requests) OR a
telemetry JSON-lines dump from `observability.telemetry.to_json_lines`
(percentiles estimated with the shared log2-interpolation estimator —
a pinned copy of `telemetry.quantile_from_snapshot`, linear inside the
holding bucket and clamped to the recorded min/max; the old
bucket-upper-bound answer overstated p99 by up to 2x at coarse
buckets).

`--requests` renders the end-to-end request traces
(`observability/reqtrace.py`): one waterfall per tail-captured request
(admission wait, router candidate scoring, lane wait, assembly,
dispatch, split — or per-iteration decode segments for streams), plus
the p99 attribution table: per model, each hop's share of tail-request
latency.  Accepts a flight dump (`requests` / `requests_sampled`
sections) or a standalone `reqtrace.dump()` file.  Exits 2 when the
input holds no request records.

`--fleet <dir>` merges every parseable JSON dump in a directory —
flight dumps, reqtrace dumps, from fleet replicas or elastic/chaos
subprocess workers sharing an env-propagated trace root
(`MXNET_TPU_REQTRACE_CTX`) — onto one shared-epoch timeline: per-source
table (pid, trace root, records, wall span), the merged request
timeline, and the fleet-wide attribution table.  Exits 2 when no dump
holds request records.  Both `--requests` and `--fleet` accept
`--since SECONDS` to keep only requests that started within the
trailing window of the (fleet-wide) newest request start.

`--dash <dir>` is the fleet health dashboard: it merges every
`series_*.jsonl` file the timeseries sampler's shipper
(`observability/shipper.py`) wrote into a shared directory — one file
per process, parent and elastic/fleet children alike, all keyed to the
same env-propagated trace root — and renders sparkline rows for the
health-plane signals: fleet request rate and shed rate (per-source
adjacent-sample counter deltas summed into shared time bins, reset
spans skipped via the registry generation token), queue depth and
replica count (gauges, per-source bin means summed), and per-model p99
vs declared SLO (bucket-delta histograms merged across sources before
the quantile — the delta form of the shared estimator).  The alert
timeline (every `alert` line shipped) and the rules still firing
close the report.  Exits 2 when no samples were shipped.

`--alerts` renders the alert-engine firing history
(`observability/alerts.py`): per-rule fired/resolved counts and each
transition with the windows and values that tripped it (burn-rate
windows show burn factor, error ratio, served/shed counts; threshold
windows show the measured value vs the rule).  Accepts a flight dump
(the `alerts` ring every dump carries), a bare JSON list of transition
records, or an `{"alerts": [...]}` document.  Exits 2 when the input
holds no transitions.

`--flight` reads a flight-recorder dump
(`observability/flight_recorder.py`): first-anomaly step, per-rule
anomaly counts, a grad/loss trend table with sparklines over the
recorded step window (plus a device-memory sparkline when the step
records carry the sampled gauges), captured events and log-record
count — and, for OOM dumps, the embedded memory report.  Exits 1
when the dump contains a fired anomaly, 0 otherwise — CI can gate on
"did the black box record a divergence" without parsing JSON.

`--memory` renders a memory report (`observability/memprof.py
write_report`, or a flight dump embedding one): the per-program table
(label, kind, compile ms, argument/output/temp bytes from XLA's
memory_analysis), the live-array census grouped by (shape, dtype), and
per-device allocator stats where the backend reports them.

`--tuning` renders the autotune decision log
(`observability/autotune.py`): per-controller/action counts plus one
block per decision — action, reason, candidates considered, and the
cost paid (retraces spent vs budget).  Accepts a flight dump (the
`tuning` ring every dump carries), a bare JSON list of decision
records, or a `{"decisions": [...]}` document.  Exits 2 when the input
holds no decisions (the autotune layer never ran).

`--elastic` renders the checkpoint/resume lineage
(`mxnet_tpu/elastic/`): every committed snapshot (step, trigger
reason, bytes, wall ms), rejected-at-verify snapshots with their
problems, preemption signals, chaos faults, and resume records with
their warm-restore counters (disk restores / builds / backend
compiles).  Accepts a flight dump (the `elastic` ring every dump
carries), a bare JSON list of records, or an `{"elastic": [...]}`
document.  Exits 2 when the input holds no elastic records.

Understands both the native "X" complete-event encoding and legacy
"B"/"E" pairs (paired LIFO per (cat, name, tid, pid))."""
from __future__ import annotations

import argparse
import json
import math
import re
import sys

# pinned copy of mxnet_tpu/observability/instrument.py:STEP_COMPONENTS —
# this CLI stays import-free so it can summarize a trace anywhere; a
# component added there must be added here or coverage under-reports
STEP_COMPONENTS = ("data_wait", "fwd_bwd_dispatch", "update", "metric",
                   "sync")

# pinned copy of the io_pipeline span names (category "io_pipeline",
# names "pipe:<stage>") — emitted by mxnet_tpu/io_pipeline/{executor,
# pipeline,device}.py; a stage added there must be added here
PIPELINE_STAGES = ("queue_wait", "decode", "h2d")

# pinned copy of observability/telemetry.py:BUCKET_BOUNDS (2**k for k in
# [-10, 20] plus +Inf overflow) — needed to turn a JSON-lines histogram
# snapshot back into quantile estimates without importing the framework
_HIST_K_MIN, _HIST_K_MAX = -10, 20
HIST_BUCKET_BOUNDS = tuple(2.0 ** k
                           for k in range(_HIST_K_MIN, _HIST_K_MAX + 1))

# pinned copies of telemetry.py's strict-JSON export contract: numeric
# fields whose non-finite values ship as string tokens
_JSON_NUMERIC_KEYS = ("value", "sum", "min", "max")
_NONFINITE_TOKENS = {"NaN": float("nan"), "Infinity": float("inf"),
                     "-Infinity": float("-inf")}


def _restore_nonfinite(obj):
    for k in _JSON_NUMERIC_KEYS:
        v = obj.get(k)
        if isinstance(v, str) and v in _NONFINITE_TOKENS:
            obj[k] = _NONFINITE_TOKENS[v]
    return obj


def load_trace(path):
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, list):  # bare event-array form is also legal
        return {"traceEvents": doc}
    return doc


def load_any(path):
    """Load either a Chrome trace document or a telemetry JSON-lines
    dump.  Returns ("trace", doc) or ("telemetry", {name: snap})."""
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except ValueError:
        doc = None
    if isinstance(doc, list):
        return "trace", {"traceEvents": doc}
    if isinstance(doc, dict):
        if "traceEvents" in doc:
            return "trace", doc
        if "name" in doc and "type" in doc:  # one-metric JSON-lines dump
            return "telemetry", {doc["name"]: _restore_nonfinite(doc)}
        return "trace", doc
    metrics = {}
    for line in text.splitlines():
        if not line.strip():
            continue
        obj = _restore_nonfinite(json.loads(line))  # malformed fails loudly
        metrics[obj.pop("name")] = obj
    return "telemetry", metrics


def span_durations(events):
    """[(cat, name, dur_ms)] over every completed span in the trace.

    The legacy B/E pairing mirrors profiler.aggregate_stats (LIFO per
    (cat, name, tid, pid)) — keep the two decoders matched."""
    out = []
    open_ts = {}
    for e in events:
        ph = e.get("ph")
        if ph == "X":
            out.append((e.get("cat", ""), e["name"],
                        e.get("dur", 0.0) / 1e3))
        elif ph == "B":
            key = (e.get("cat"), e["name"], e.get("tid"), e.get("pid"))
            open_ts.setdefault(key, []).append(e["ts"])
        elif ph == "E":
            key = (e.get("cat"), e["name"], e.get("tid"), e.get("pid"))
            if open_ts.get(key):
                out.append((e.get("cat", ""), e["name"],
                            (e["ts"] - open_ts[key].pop()) / 1e3))
    return out


def aggregate(durations):
    """{(cat, name): {count, total_ms, avg_ms, max_ms}}"""
    agg = {}
    for cat, name, ms in durations:
        s = agg.setdefault((cat, name), {"count": 0, "total_ms": 0.0,
                                         "max_ms": 0.0})
        s["count"] += 1
        s["total_ms"] += ms
        s["max_ms"] = max(s["max_ms"], ms)
    for s in agg.values():
        s["avg_ms"] = s["total_ms"] / s["count"]
    return agg


def step_breakdown(events):
    """Per-component totals over the `step` spans fit() emits.

    Returns None when the trace holds no step spans; otherwise a dict
    with per-component stats, total measured step time, coverage
    (sum(components)/sum(steps)) and starvation (data_wait share)."""
    durations = span_durations(events)
    steps = [ms for cat, name, ms in durations
             if cat == "step" and name == "step"]
    if not steps:
        return None
    comp = {c: {"count": 0, "total_ms": 0.0} for c in STEP_COMPONENTS}
    for cat, name, ms in durations:
        if cat == "step" and name.startswith("step:"):
            c = name[len("step:"):]
            if c in comp:
                comp[c]["count"] += 1
                comp[c]["total_ms"] += ms
    step_total = sum(steps)
    covered = sum(s["total_ms"] for s in comp.values())
    # device starvation as the tracker measured it (the step event's
    # args): time with no step program in flight, by the component it
    # fell under ("glue": between components).  None on traces of a
    # loop that notes no dispatch, or from before the tracker kept it
    starved_ms, starved_by, ran_ahead = None, {}, 0
    for e in events:
        args = e.get("args") or {}
        if e.get("ph") != "X" or e.get("cat") != "step" \
                or e.get("name") != "step" \
                or args.get("starved_ms") is None:
            continue
        starved_ms = (starved_ms or 0.0) + _fnum(args["starved_ms"], 0)
        ran_ahead += 1 if args.get("ran_ahead") else 0
        for name, ms in (args.get("starved_by_ms") or {}).items():
            c = name.split(":", 1)[1] if name.startswith("step:") else name
            starved_by[c] = starved_by.get(c, 0.0) + _fnum(ms, 0)
    return {
        "steps": len(steps),
        "step_total_ms": step_total,
        "step_avg_ms": step_total / len(steps),
        "components": comp,
        "coverage": covered / step_total if step_total else 0.0,
        "starvation": (comp["data_wait"]["total_ms"] / step_total
                       if step_total else 0.0),
        "starved_ms": starved_ms,
        "starved_by": starved_by,
        "ran_ahead": ran_ahead,
    }


def pipeline_breakdown(events):
    """Per-stage totals over the ``pipe:*`` spans the io_pipeline
    emits: consumer queue wait vs worker decode vs H2D issue.  Returns
    None when the trace holds no pipeline spans; otherwise per-stage
    {count, total_ms, avg_ms} plus the pipeline starvation ratio
    (queue_wait / step time) when step spans are present too."""
    durations = span_durations(events)
    stages = {s: {"count": 0, "total_ms": 0.0} for s in PIPELINE_STAGES}
    seen = False
    for cat, name, ms in durations:
        if cat == "io_pipeline" and name.startswith("pipe:"):
            stage = name[len("pipe:"):]
            if stage in stages:
                seen = True
                stages[stage]["count"] += 1
                stages[stage]["total_ms"] += ms
    if not seen:
        return None
    for s in stages.values():
        s["avg_ms"] = s["total_ms"] / s["count"] if s["count"] else 0.0
    step_total = sum(ms for cat, name, ms in durations
                     if cat == "step" and name == "step")
    return {
        "stages": stages,
        "step_total_ms": step_total,
        "starvation": (stages["queue_wait"]["total_ms"] / step_total
                       if step_total else None),
    }


def comm_breakdown(events):
    """Gradient-communication view (docs/distributed.md): the
    ``comm:*`` spans, the kvstore collectives the step waits on.
    Returns None when the trace carries none."""
    durations = span_durations(events)
    exposed = {"count": 0, "total_ms": 0.0, "bytes": 0}
    for e in events:
        if e.get("ph") == "X" and e.get("cat") == "comm":
            exposed["count"] += 1
            exposed["total_ms"] += e.get("dur", 0) / 1e3
            exposed["bytes"] += int((e.get("args") or {}).get("bytes", 0))
    if not exposed["count"]:
        return None
    steps = sum(1 for cat, name, ms in durations
                if cat == "step" and name == "step") or None
    return {"exposed": exposed, "steps": steps}


def instants(events):
    """{name: count} over instant ("i") markers — recompiles, evictions."""
    out = {}
    for e in events:
        if e.get("ph") == "i":
            out[e["name"]] = out.get(e["name"], 0) + 1
    return out


# -- flight-recorder view ----------------------------------------------------

_SPARK_BLOCKS = "▁▂▃▄▅▆▇█"


def _fnum(value, default=float("nan")):
    """Float from a flight-dump field: strict-JSON non-finite tokens
    ("NaN"/"Infinity"/"-Infinity") restore to floats."""
    if isinstance(value, str):
        return _NONFINITE_TOKENS.get(value, default)
    try:
        return float(value)
    except (TypeError, ValueError):
        return default


def _isfinite(x):
    try:
        return math.isfinite(x)
    except TypeError:
        return False


def _sparkline(values):
    """One block character per value; non-finite values render '!'.
    Scaled min->max over the finite values."""
    finite = [v for v in values if _isfinite(v)]
    if not finite:
        return "!" * len(values)
    lo, hi = min(finite), max(finite)
    span = (hi - lo) or 1.0
    out = []
    for v in values:
        if not _isfinite(v):
            out.append("!")
            continue
        idx = int((v - lo) / span * (len(_SPARK_BLOCKS) - 1))
        out.append(_SPARK_BLOCKS[idx])
    return "".join(out)


def flight_stats(doc):
    """The machine-readable summary `--flight` renders (and tests
    assert on): first anomaly, per-rule counts, per-step trend series
    (including the sampled device-memory gauges when recorded)."""
    steps = doc.get("steps") or []
    anomalies = doc.get("anomalies") or []
    by_rule = {}
    for a in anomalies:
        by_rule[a.get("rule", "?")] = by_rule.get(a.get("rule", "?"), 0) + 1
    series = []
    for s in steps:
        h = s.get("health") or {}
        mem = s.get("mem") or {}
        series.append({
            "step": s.get("step"),
            "loss": _fnum(h.get("out_mean")),
            "grad_norm": _fnum(h.get("grad_norm")),
            "update_ratio": _fnum(h.get("update_ratio")),
            "finite": _fnum(h.get("all_finite"), 1.0) >= 1.0,
            "mem_bytes": _fnum(mem.get("live_bytes")),
        })
    return {
        "reason": doc.get("reason"),
        "created": doc.get("created_iso") or doc.get("created"),
        "steps": len(steps),
        "capacity": doc.get("capacity"),
        "first_anomaly_step": doc.get("first_anomaly_step"),
        "anomaly_count": len(anomalies),
        "anomalies_by_rule": by_rule,
        "series": series,
        "events": len(doc.get("events") or []),
        "logs": len(doc.get("logs") or []),
    }


def summarize_flight(doc, trend_rows=12):
    """The text report for one flight dump."""
    stats = flight_stats(doc)
    anomalies = doc.get("anomalies") or []
    lines = []
    lines.append("== flight recorder: reason=%s created=%s =="
                 % (stats["reason"], stats["created"]))
    fp = doc.get("fingerprint") or {}
    env = fp.get("env") or {}
    lines.append("pid %s  python %s  jax %s  backend %s"
                 % (fp.get("pid"), fp.get("python"), fp.get("jax"),
                    fp.get("backend")))
    knobs = {k: env[k] for k in sorted(env) if k.startswith("MXNET_TPU_")}
    if knobs:
        lines.append("env: " + "  ".join("%s=%s" % kv
                                         for kv in knobs.items()))
    lines.append("steps recorded: %d (ring capacity %s)"
                 % (stats["steps"], stats["capacity"]))
    lines.append("")
    lines.append("== anomalies ==")
    if not anomalies:
        lines.append("(none recorded)")
    else:
        first = anomalies[0]
        lines.append("FIRST ANOMALY: step %s  rule=%s"
                     % (first.get("step"), first.get("rule")))
        lines.append("  %s" % first.get("message", ""))
        lines.append("%-18s %7s" % ("Rule", "Fired"))
        for rule in sorted(stats["anomalies_by_rule"]):
            lines.append("%-18s %7d"
                         % (rule, stats["anomalies_by_rule"][rule]))
    lines.append("")
    lines.append("== grad / loss trend ==")
    series = stats["series"]
    if not series:
        lines.append("(no per-step health records — was MXNET_TPU_HEALTH"
                     "=1 set?)")
    else:
        lines.append("grad-norm: %s"
                     % _sparkline([r["grad_norm"] for r in series]))
        lines.append("loss:      %s"
                     % _sparkline([r["loss"] for r in series]))
        mem_series = [r["mem_bytes"] for r in series]
        if any(_isfinite(v) for v in mem_series):
            # the sampled device-memory trend leading into the anomaly
            lines.append("mem:       %s  (last %s)"
                         % (_sparkline(mem_series),
                            _fmt_bytes(next(
                                (v for v in reversed(mem_series)
                                 if _isfinite(v)), 0))))
        lines.append("%-8s %12s %12s %12s %7s"
                     % ("Step", "Loss", "GradNorm", "UpdRatio", "Finite"))
        for r in series[-trend_rows:]:
            lines.append("%-8s %12.5g %12.5g %12.5g %7s"
                         % (r["step"], r["loss"], r["grad_norm"],
                            r["update_ratio"],
                            "yes" if r["finite"] else "NO"))
    lines.append("")
    lines.append("events: %d   captured log records: %d"
                 % (stats["events"], stats["logs"]))
    decisions = doc.get("tuning") or []
    if decisions:
        lines.append("autotune decisions: %d (render with --tuning)"
                     % len(decisions))
    elastic = doc.get("elastic") or []
    if elastic:
        estats = elastic_stats(elastic)
        note = "elastic records: %d (render with --elastic)" \
            % len(elastic)
        if estats["last_checkpoint_step"] is not None:
            note += "; last checkpoint: step %s" \
                % estats["last_checkpoint_step"]
        lines.append(note)
    requests_pinned = doc.get("requests") or []
    if requests_pinned:
        lines.append("tail-captured request traces: %d (render with "
                     "--requests)" % len(requests_pinned))
    if doc.get("memory"):
        # an OOM dump embeds the full memory report — render it inline
        lines.append("")
        lines.append(summarize_memory(doc["memory"]))
    return "\n".join(lines)


# -- memory view -------------------------------------------------------------

def _fmt_bytes(n):
    """Human bytes: 4 significant-ish digits, binary units."""
    try:
        n = float(n)
    except (TypeError, ValueError):
        return "?"
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0 or unit == "TiB":
            return ("%d %s" % (n, unit)) if unit == "B" \
                else ("%.2f %s" % (n, unit))
        n /= 1024.0
    return "?"


def summarize_memory(memdoc, top=20):
    """The text report for one memory report document
    (observability/memprof.py `report()` shape)."""
    lines = []
    lines.append("== memory: per-program table (XLA memory_analysis) ==")
    programs = memdoc.get("programs") or []
    with_mem = [p for p in programs if p.get("memory")]
    if not with_mem:
        lines.append("(no per-program memory captured — run with "
                     "MXNET_TPU_MEMPROF=1)")
    else:
        lines.append("%-28s %-11s %10s %10s %10s %10s"
                     % ("Program", "Kind", "Compile", "Args", "Temp",
                        "Total"))
        for p in sorted(with_mem,
                        key=lambda p: -p["memory"].get("total_bytes",
                                                       0))[:top]:
            m = p["memory"]
            lines.append("%-28s %-11s %8.1fms %10s %10s %10s"
                         % (str(p.get("label", "?"))[:28],
                            str(p.get("kind", "?"))[:11],
                            _fnum(p.get("compile_ms"), 0.0),
                            _fmt_bytes(m.get("argument_bytes", 0)),
                            _fmt_bytes(m.get("temp_bytes", 0)),
                            _fmt_bytes(m.get("total_bytes", 0))))
    compiled = [p for p in programs if _fnum(p.get("compile_ms"), 0.0) > 0]
    restored = [p for p in programs if p.get("kind") == "disk"]
    if compiled or restored:
        total_ms = sum(_fnum(p["compile_ms"], 0.0) for p in compiled)
        lines.append("programs recorded: %d   backend compiles: %d   "
                     "compile time: %.1f ms total   disk restores: %d"
                     % (len(programs), len(compiled), total_ms,
                        len(restored)))
    disk = memdoc.get("disk")
    lines.append("")
    lines.append("== memory: persistent program cache (disk tier) ==")
    if not disk or not disk.get("enabled"):
        lines.append("(disabled — set MXNET_TPU_PROGRAM_CACHE_DIR to "
                     "persist compiled executables across processes)")
    else:
        lines.append("dir %s%s" % (disk.get("dir"),
                                   "   [read-only]"
                                   if disk.get("read_only") else ""))
        lines.append("hits %d   misses %d   evictions %d   writes %d   "
                     "written %s   read %s"
                     % (disk.get("hits", 0), disk.get("misses", 0),
                        disk.get("evictions", 0), disk.get("writes", 0),
                        _fmt_bytes(disk.get("bytes_written", 0)),
                        _fmt_bytes(disk.get("bytes_read", 0))))
        if disk.get("pruned"):
            lines.append("auto-pruned %d entries (%s) — "
                         "MXNET_TPU_PROGRAM_CACHE_MAX_MB"
                         % (disk["pruned"],
                            _fmt_bytes(disk.get("pruned_bytes", 0))))
    lines.append("")
    lines.append("== memory: live-array census (by shape/dtype) ==")
    census = memdoc.get("census") or {}
    groups = census.get("groups") or []
    if not groups:
        lines.append("(no live arrays)")
    else:
        lines.append("%-26s %-10s %7s %12s"
                     % ("Shape", "Dtype", "Count", "Bytes"))
        for g in groups[:top]:
            lines.append("%-26s %-10s %7d %12s"
                         % (str(tuple(g.get("shape") or ()))[:26],
                            str(g.get("dtype", "?"))[:10],
                            g.get("count", 0),
                            _fmt_bytes(g.get("total_bytes", 0))))
        lines.append("live arrays: %d in %d groups, %s total"
                     % (census.get("array_count", 0),
                        census.get("group_count", 0),
                        _fmt_bytes(census.get("total_bytes", 0))))
    devices = memdoc.get("device_memory") or []
    reported = [d for d in devices if d.get("bytes_in_use") is not None
                or d.get("bytes_limit") is not None]
    lines.append("")
    lines.append("== memory: device allocator ==")
    if not reported:
        lines.append("(backend reports no memory_stats — census above "
                     "is the live view)")
    else:
        for d in reported:
            lines.append("%-24s in_use %s   peak %s   limit %s"
                         % (str(d.get("device", "?"))[:24],
                            _fmt_bytes(d.get("bytes_in_use")),
                            _fmt_bytes(d.get("peak_bytes_in_use")),
                            _fmt_bytes(d.get("bytes_limit"))))
    return "\n".join(lines)


# -- tuning view -------------------------------------------------------------

def tuning_records(doc):
    """Extract the autotune decision list from any accepted input form:
    a flight dump (its ``tuning`` ring), a ``{"decisions": [...]}``
    document, or a bare JSON list of records."""
    if isinstance(doc, list):
        return doc
    if isinstance(doc, dict):
        if isinstance(doc.get("tuning"), list):
            return doc["tuning"]
        if isinstance(doc.get("decisions"), list):
            return doc["decisions"]
    return []


def tuning_stats(records):
    """The machine-readable summary `--tuning` renders (and tests +
    bench assert on): counts by controller and action, applied
    changes, total retraces spent."""
    by_controller = {}
    by_action = {}
    applied = []
    retraces = 0
    for r in records:
        c = r.get("controller", "?")
        a = r.get("action", "?")
        by_controller[c] = by_controller.get(c, 0) + 1
        by_action[a] = by_action.get(a, 0) + 1
        retraces += int(_fnum((r.get("cost") or {}).get("retraces", 0),
                              0))
        if a == "apply":
            applied.append({"controller": c,
                            "decision": r.get("decision") or {}})
    return {"decisions": len(records), "by_controller": by_controller,
            "by_action": by_action, "applied": applied,
            "retraces_spent": retraces}


def summarize_tuning(records, top=20):
    """The text report for one decision log."""
    stats = tuning_stats(records)
    lines = []
    lines.append("== autotune: decision log ==")
    if not records:
        lines.append("(no decisions recorded — were the controllers "
                     "run?  MXNET_TPU_AUTOTUNE=0 disables them)")
        return "\n".join(lines)
    lines.append("decisions: %d   applied: %d   retraces spent: %d"
                 % (stats["decisions"], len(stats["applied"]),
                    stats["retraces_spent"]))
    lines.append("%-18s %s" % ("Controller", "Decisions"))
    for c in sorted(stats["by_controller"]):
        lines.append("%-18s %9d" % (c, stats["by_controller"][c]))
    lines.append("%-18s %s" % ("Action", "Count"))
    for a in sorted(stats["by_action"]):
        lines.append("%-18s %9d" % (a, stats["by_action"][a]))
    lines.append("")
    for r in records[-top:]:
        lines.append("%-16s %-10s mode=%-9s" % (r.get("controller", "?"),
                                                r.get("action", "?"),
                                                r.get("mode", "?")))
        lines.append("  %s" % r.get("reason", ""))
        for cand in (r.get("candidates") or [])[:6]:
            lines.append("  candidate: %s" % json.dumps(cand,
                                                        sort_keys=True))
        decision = r.get("decision")
        if decision:
            lines.append("  decision:  %s" % json.dumps(decision,
                                                        sort_keys=True))
    return "\n".join(lines)


# -- elastic view ------------------------------------------------------------

def elastic_records(doc):
    """Extract the elastic lineage list from any accepted input form:
    a flight dump (its ``elastic`` ring), an ``{"elastic": [...]}``
    document, or a bare JSON list of records."""
    if isinstance(doc, list):
        return doc
    if isinstance(doc, dict) and isinstance(doc.get("elastic"), list):
        return doc["elastic"]
    return []


def elastic_stats(records):
    """The machine-readable summary `--elastic` renders (and tests +
    bench assert on): per-kind counts, the checkpoint list, the last
    checkpoint step, rejected snapshots, and resume records with their
    warm-restore counters."""
    by_kind = {}
    checkpoints = []
    rejected = []
    resumes = []
    for r in records:
        kind = r.get("kind", "?")
        by_kind[kind] = by_kind.get(kind, 0) + 1
        if kind == "checkpoint":
            checkpoints.append({"step": r.get("step"),
                                "reason": r.get("reason"),
                                "bytes": r.get("bytes"),
                                "wall_ms": r.get("wall_ms"),
                                "path": r.get("path")})
        elif kind == "checkpoint_rejected":
            rejected.append({"step": r.get("step"),
                             "problems": r.get("problems")})
        elif kind == "resume":
            resumes.append({"from_step": r.get("from_step"),
                            "refactorized": r.get("refactorized"),
                            "n_dev_from": r.get("n_dev_from"),
                            "n_dev_to": r.get("n_dev_to"),
                            "warm": r.get("warm") or {}})
    return {"records": len(records), "by_kind": by_kind,
            "checkpoints": checkpoints,
            "last_checkpoint_step": (checkpoints[-1]["step"]
                                     if checkpoints else None),
            "rejected": rejected, "resumes": resumes}


def summarize_elastic(records):
    """The text report for one elastic lineage."""
    stats = elastic_stats(records)
    lines = ["== elastic: checkpoint/resume lineage =="]
    if not records:
        lines.append("(no elastic records — was a Checkpointer "
                     "attached?  see docs/elastic.md)")
        return "\n".join(lines)
    lines.append("records: %d   checkpoints: %d   rejected: %d   "
                 "resumes: %d"
                 % (stats["records"], len(stats["checkpoints"]),
                    len(stats["rejected"]), len(stats["resumes"])))
    lines.append("%-24s %s" % ("Kind", "Count"))
    for kind in sorted(stats["by_kind"]):
        lines.append("%-24s %5d" % (kind, stats["by_kind"][kind]))
    if stats["checkpoints"]:
        lines.append("")
        lines.append("%-10s %-18s %12s %9s" % ("Step", "Trigger",
                                               "Bytes", "Wall ms"))
        for c in stats["checkpoints"]:
            lines.append("%-10s %-18s %12s %9s"
                         % (c["step"], c["reason"],
                            _fmt_bytes(_fnum(c["bytes"], 0)),
                            c["wall_ms"]))
        lines.append("last checkpoint: step %s"
                     % stats["last_checkpoint_step"])
    for r in stats["rejected"]:
        lines.append("REJECTED snapshot step %s: %s"
                     % (r["step"], "; ".join(r["problems"] or [])))
    for r in stats["resumes"]:
        warm = r["warm"]
        lines.append("")
        lines.append("RESUME from step %s  %s"
                     % (r["from_step"],
                        "re-factorized %s -> %s device(s)"
                        % (r["n_dev_from"], r["n_dev_to"])
                        if r.get("refactorized")
                        else "same factorization (%s device(s))"
                        % r["n_dev_to"]))
        lines.append("  warm boot: %s disk restore(s), %s built, %s "
                     "backend compile(s), %s retrace(s)"
                     % (warm.get("restored", 0), warm.get("built", 0),
                        warm.get("backend_compiles", 0),
                        warm.get("traces", 0)))
    return "\n".join(lines)


# -- serving view ------------------------------------------------------------

def _percentile(sorted_vals, q):
    """Exact nearest-rank percentile over a sorted list."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def _snap_bound(snap, key):
    """The recorded min/max of a snapshot as a finite float, or None."""
    v = snap.get(key)
    if isinstance(v, str):
        v = _NONFINITE_TOKENS.get(v)
    return float(v) if isinstance(v, (int, float)) \
        and math.isfinite(v) else None


def _hist_quantile(snap, q):
    """Quantile estimate from a fixed log2-bucket histogram snapshot —
    a pinned copy of ``observability.telemetry.quantile_from_snapshot``
    (this CLI stays import-free): LINEAR interpolation inside the
    bucket holding the q-th observation, clamped to the recorded
    min/max so single-valued histograms and q=0/1 are exact.  The old
    bucket-upper-bound answer overstated p99 by up to 2x at coarse log2
    buckets."""
    count = snap.get("count", 0) or 0
    buckets = snap.get("buckets") or []
    if count <= 0 or not buckets:
        return 0.0
    mn = _snap_bound(snap, "min")
    mx = _snap_bound(snap, "max")
    q = min(1.0, max(0.0, float(q)))
    target = max(1.0, q * count)  # 1-based rank; q=0 -> the first
    cumulative = 0
    est = 0.0
    for i, n in enumerate(buckets):
        if not n:
            continue
        cumulative += n
        if cumulative >= target:
            if i < len(HIST_BUCKET_BOUNDS):
                lo = 0.0 if i == 0 else HIST_BUCKET_BOUNDS[i - 1]
                hi = HIST_BUCKET_BOUNDS[i]
            else:  # overflow: the recorded max is the only upper bound
                lo = HIST_BUCKET_BOUNDS[-1]
                hi = mx if mx is not None else HIST_BUCKET_BOUNDS[-1] * 2
            frac = (target - (cumulative - n)) / n
            est = lo + frac * (hi - lo)
            break
    if mn is not None:
        est = max(est, mn)
    if mx is not None:
        est = min(est, mx)
    return est


def serving_from_trace(events):
    """Serving stats from recorded `serving:*` spans (exact)."""
    requests, queue, dispatch = [], [], []
    batch_rows = {}
    rejects = {}
    replicas = {}
    decode_iters, decode_joins, decode_active = 0, 0, []
    for e in events:
        ph, name = e.get("ph"), e.get("name", "")
        if ph == "X" and e.get("cat") == "serving":
            ms = e.get("dur", 0.0) / 1e3
            args = e.get("args") or {}
            if name == "serving:request":
                requests.append(ms)
            elif name == "serving:queue":
                queue.append(ms)
            elif name == "serving:paged_decode_step":
                decode_iters += 1
                decode_joins += int(args.get("joins") or 0)
                if args.get("active") is not None:
                    decode_active.append(int(args["active"]))
            elif name == "serving:dispatch":
                dispatch.append(ms)
                if args.get("replica") is not None:
                    rep = replicas.setdefault(
                        int(args["replica"]),
                        {"dispatches": 0, "rows": 0, "ms": []})
                    rep["dispatches"] += 1
                    rep["ms"].append(ms)
            elif name == "serving:batch":
                rows = args.get("rows")
                if rows is not None:
                    batch_rows[rows] = batch_rows.get(rows, 0) + 1
                if args.get("replica") is not None and rows is not None:
                    rep = replicas.setdefault(
                        int(args["replica"]),
                        {"dispatches": 0, "rows": 0, "ms": []})
                    rep["rows"] += rows
        elif ph == "i" and name.startswith("serving_reject:"):
            reason = name[len("serving_reject:"):]
            rejects[reason] = rejects.get(reason, 0) + 1
    requests.sort()
    replica_rows = []
    for idx in sorted(replicas):
        rep = replicas[idx]
        ms = sorted(rep["ms"])
        replica_rows.append({
            "replica": idx, "dispatches": rep["dispatches"],
            "rows": rep["rows"],
            "p50": _percentile(ms, 0.50), "p95": _percentile(ms, 0.95),
            "p99": _percentile(ms, 0.99)})
    decode = None
    if decode_iters:
        # pool gauges live in telemetry only; the trace form carries
        # the per-iteration spans
        sorted_active = sorted(decode_active)
        decode = {
            "iterations": decode_iters, "joins": decode_joins,
            "leaves": None,
            "active_p50": _percentile(sorted_active, 0.50),
            "kv_pages_in_use": None, "kv_pages_total": None,
            "kv_pages_high_water": None,
            "prefix_lookups": None, "prefix_hits": None,
            "kv_evictions": None, "kv_cow_clones": None,
            "pages_per_stream_p50": None,
        }
    return {
        "source": "trace (exact)",
        "requests": len(requests),
        "p50": _percentile(requests, 0.50),
        "p95": _percentile(requests, 0.95),
        "p99": _percentile(requests, 0.99),
        "queue_avg": sum(queue) / len(queue) if queue else 0.0,
        "dispatch_avg": sum(dispatch) / len(dispatch) if dispatch else 0.0,
        "batches": sum(batch_rows.values()),
        "batch_rows": batch_rows,
        "rejects": rejects,
        "replicas": replica_rows,
        "decode": decode,
        "slo": [],  # declared targets live in telemetry gauges only
    }


def serving_from_telemetry(metrics):
    """Serving stats from a telemetry JSON-lines dump (quantiles via
    the shared log2-interpolation estimator — see ``_hist_quantile``)."""
    lat = metrics.get("serving.request_latency_ms", {})
    queue = metrics.get("serving.queue_ms", {})
    dispatch = metrics.get("serving.dispatch_ms", {})
    batch = metrics.get("serving.batch_size", {})
    batch_rows = {}
    for i, n in enumerate(batch.get("buckets") or []):
        if not n:
            continue
        bound = (HIST_BUCKET_BOUNDS[i] if i < len(HIST_BUCKET_BOUNDS)
                 else float("inf"))
        batch_rows["<=%g" % bound] = n
    prefix = "serving.rejected_total."
    rejects = {name[len(prefix):]: snap.get("value", 0)
               for name, snap in metrics.items()
               if name.startswith(prefix)}
    def avg(snap):
        return snap.get("sum", 0.0) / snap["count"] if snap.get("count") \
            else 0.0
    # per-replica routing breakdown (serving.replica.<i>.*)
    rep_re = re.compile(r"^serving\.replica\.(\d+)\.(dispatches|rows|"
                        r"dispatch_ms)$")
    replicas = {}
    for name, snap in metrics.items():
        m = rep_re.match(name)
        if not m:
            continue
        rep = replicas.setdefault(int(m.group(1)),
                                  {"dispatches": 0, "rows": 0, "ms": None})
        if m.group(2) == "dispatches":
            rep["dispatches"] = int(snap.get("value", 0))
        elif m.group(2) == "rows":
            rep["rows"] = int(snap.get("value", 0))
        else:
            rep["ms"] = snap
    replica_rows = []
    for idx in sorted(replicas):
        rep = replicas[idx]
        ms = rep["ms"] or {}
        replica_rows.append({
            "replica": idx, "dispatches": rep["dispatches"],
            "rows": rep["rows"],
            "p50": _hist_quantile(ms, 0.50),
            "p95": _hist_quantile(ms, 0.95),
            "p99": _hist_quantile(ms, 0.99)})
    # SLO attainment: declared targets (serving.slo_ms.<model> gauges)
    # vs the per-model latency histogram's p99 estimate
    slo_prefix = "serving.slo_ms."
    slo_rows = []
    for name, snap in sorted(metrics.items()):
        if not name.startswith(slo_prefix):
            continue
        model = name[len(slo_prefix):]
        target = snap.get("value")
        mlat = metrics.get("serving.request_latency_ms." + model, {})
        p99 = _hist_quantile(mlat, 0.99)
        served = mlat.get("count", 0)
        slo_rows.append({
            "model": model, "target_ms": target, "served": served,
            "p50": _hist_quantile(mlat, 0.50),
            "p95": _hist_quantile(mlat, 0.95), "p99": p99,
            "met": bool(served) and target is not None and p99 <= target})
    # continuous-decode / paged-KV page-pool rows (serving.decode.*)
    def _val(name):
        snap = metrics.get(name)
        return snap.get("value") if isinstance(snap, dict) else None

    decode = None
    if any(name.startswith("serving.decode.") for name in metrics):
        decode = {
            "iterations": int(_val("serving.decode.iterations") or 0),
            "joins": int(_val("serving.decode.joins") or 0),
            "leaves": int(_val("serving.decode.leaves") or 0),
            "active_p50": _hist_quantile(
                metrics.get("serving.decode.active_slots", {}), 0.50),
            "kv_pages_in_use": _val("serving.decode.kv_pages_in_use"),
            "kv_pages_total": _val("serving.decode.kv_pages_total"),
            "kv_pages_high_water":
                _val("serving.decode.kv_pages_high_water"),
            "prefix_lookups": _val("serving.decode.prefix_lookups"),
            "prefix_hits": _val("serving.decode.prefix_hits"),
            "kv_evictions": _val("serving.decode.kv_evictions"),
            "kv_cow_clones": _val("serving.decode.kv_cow_clones"),
            "pages_per_stream_p50": _hist_quantile(
                metrics.get("serving.decode.kv_pages_per_stream", {}),
                0.50),
        }
    return {
        "source": "telemetry (interpolated histogram estimates)",
        "requests": lat.get("count", 0),
        "p50": _hist_quantile(lat, 0.50),
        "p95": _hist_quantile(lat, 0.95),
        "p99": _hist_quantile(lat, 0.99),
        "queue_avg": avg(queue),
        "dispatch_avg": avg(dispatch),
        "batches": batch.get("count", 0),
        "batch_rows": batch_rows,
        "rejects": rejects,
        "replicas": replica_rows,
        "decode": decode,
        "slo": slo_rows,
    }


def summarize_serving(kind, payload):
    """The text report for `--serving` over either input form."""
    stats = serving_from_trace(payload.get("traceEvents", [])) \
        if kind == "trace" else serving_from_telemetry(payload)
    lines = []
    lines.append("== serving: request latency (%s) ==" % stats["source"])
    if not stats["requests"]:
        lines.append("(no serving requests recorded — run traffic with "
                     "the profiler on, or pass a telemetry dump)")
    else:
        lines.append("requests: %d" % stats["requests"])
        lines.append("p50: %.3f ms   p95: %.3f ms   p99: %.3f ms"
                     % (stats["p50"], stats["p95"], stats["p99"]))
        lines.append("phase avg: queue %.3f ms   dispatch %.3f ms"
                     % (stats["queue_avg"], stats["dispatch_avg"]))
    lines.append("")
    lines.append("== serving: batch-size distribution ==")
    if not stats["batch_rows"]:
        lines.append("(no batches recorded)")
    else:
        lines.append("%-12s %7s" % ("Rows", "Batches"))
        # keys are ints (trace form) or "<=bound" strings (telemetry form)
        for rows in sorted(stats["batch_rows"],
                           key=lambda r: float(str(r).lstrip("<="))):
            lines.append("%-12s %7d" % (rows, stats["batch_rows"][rows]))
        lines.append("total batches: %d" % stats["batches"])
    lines.append("")
    lines.append("== serving: per-replica routing ==")
    if not stats.get("replicas"):
        lines.append("(single-replica or no replica-tagged dispatches "
                     "recorded)")
    else:
        lines.append("%-8s %10s %10s %10s %10s %10s"
                     % ("Replica", "Dispatches", "Rows", "p50(ms)",
                        "p95(ms)", "p99(ms)"))
        for rep in stats["replicas"]:
            lines.append("%-8d %10d %10d %10.3f %10.3f %10.3f"
                         % (rep["replica"], rep["dispatches"], rep["rows"],
                            rep["p50"], rep["p95"], rep["p99"]))
    lines.append("")
    lines.append("== serving: continuous decode / page pool ==")
    dec = stats.get("decode")
    if not dec:
        lines.append("(no continuous-decode traffic recorded)")
    else:
        def _num(v, fmt="%d"):
            return (fmt % v) if v is not None else "n/a"
        lines.append("iterations: %s   joins: %s   leaves: %s   "
                     "active p50: %.1f"
                     % (_num(dec["iterations"]), _num(dec["joins"]),
                        _num(dec["leaves"]), dec["active_p50"] or 0.0))
        if dec["kv_pages_total"] is not None:
            lines.append("kv pages: %s in use / %s total "
                         "(high-water %s, per-stream p50 %.1f)"
                         % (_num(dec["kv_pages_in_use"]),
                            _num(dec["kv_pages_total"]),
                            _num(dec["kv_pages_high_water"]),
                            dec["pages_per_stream_p50"] or 0.0))
            lookups = dec["prefix_lookups"] or 0
            hits = dec["prefix_hits"] or 0
            lines.append("prefix cache: %d hit page(s) / %d lookup(s)"
                         " (ratio %.2f)   evictions: %s   "
                         "cow clones: %s"
                         % (hits, lookups,
                            (hits / lookups) if lookups else 0.0,
                            _num(dec["kv_evictions"]),
                            _num(dec["kv_cow_clones"])))
        else:
            lines.append("(page-pool gauges live in telemetry — pass a "
                         "telemetry dump for the kv/prefix rows)")
    lines.append("")
    lines.append("== serving: SLO attainment ==")
    if not stats.get("slo"):
        lines.append("(no declared SLOs — declare with add_model("
                     "slo_ms=...) or MXNET_TPU_SERVING_SLO_MS; targets "
                     "live in telemetry gauges, pass a telemetry dump)")
    else:
        lines.append("%-16s %10s %8s %10s %10s %10s %6s"
                     % ("Model", "Target(ms)", "Served", "p50(ms)",
                        "p95(ms)", "p99(ms)", "Met"))
        for row in stats["slo"]:
            lines.append("%-16s %10.1f %8d %10.3f %10.3f %10.3f %6s"
                         % (row["model"], row["target_ms"] or 0.0,
                            row["served"], row["p50"], row["p95"],
                            row["p99"], "yes" if row["met"] else "NO"))
        shed = sum(stats["rejects"].values())
        lines.append("shed: %d request(s)%s" % (shed, (
            " (" + ", ".join("%s=%d" % (r, n) for r, n in
                             sorted(stats["rejects"].items())) + ")")
            if shed else ""))
    lines.append("")
    lines.append("== serving: rejections ==")
    if not stats["rejects"]:
        lines.append("(none)")
    else:
        for reason in sorted(stats["rejects"]):
            lines.append("%-24s %7d" % (reason, stats["rejects"][reason]))
    return "\n".join(lines)


# -- request-trace view (reqtrace) -------------------------------------------

# pinned copy of observability/reqtrace.py:SEGMENT_ORDER — the hop
# order the attribution table renders in
REQUEST_SEGMENTS = ("queue", "route", "lane", "assemble", "dispatch",
                    "split", "reject", "decode_step")


def request_records(doc):
    """(pinned, sampled) request-trace record lists from any accepted
    input form: a flight dump or a standalone ``reqtrace.dump()``
    document (both carry ``requests`` / ``requests_sampled``)."""
    if not isinstance(doc, dict):
        return [], []
    return (list(doc.get("requests") or []),
            list(doc.get("requests_sampled") or []))


def requests_stats(pinned, sampled):
    """The machine-readable summary `--requests` renders (and tests +
    bench assert on): per model, the exact p99 over recorded totals
    and — over the TAIL set (records at/above p99) — each hop's share
    of measured latency.  ``coverage`` is the instrumented fraction
    (sum of segment durations / sum of totals); the remainder is
    inter-hop scheduling gaps, reported as ``other``."""
    records = [r for r in list(pinned) + list(sampled)
               if _fnum(r.get("total_ms"), 0.0) > 0.0]
    by_model = {}
    for r in records:
        by_model.setdefault(str(r.get("model", "?")), []).append(r)
    rows = []
    for model in sorted(by_model):
        recs = by_model[model]
        totals = sorted(_fnum(r.get("total_ms"), 0.0) for r in recs)
        p99 = _percentile(totals, 0.99)
        tail = [r for r in recs
                if _fnum(r.get("total_ms"), 0.0) >= p99] or recs
        tail_total = sum(_fnum(r.get("total_ms"), 0.0) for r in tail)
        seg_ms = {}
        covered = 0.0
        for r in tail:
            for s in r.get("segments") or []:
                d = _fnum(s.get("dur_ms"), 0.0)
                seg_ms[str(s.get("name", "?"))] = \
                    seg_ms.get(str(s.get("name", "?")), 0.0) + d
                covered += d
        shares = {name: (ms / tail_total if tail_total else 0.0)
                  for name, ms in seg_ms.items()}
        rows.append({
            "model": model,
            "requests": len(recs),
            "pinned": sum(1 for r in recs if r.get("pinned")),
            "p50_ms": _percentile(totals, 0.50),
            "p99_ms": p99,
            "tail_requests": len(tail),
            "shares": shares,
            "coverage": covered / tail_total if tail_total else 0.0,
        })
    by_pin = {}
    for r in list(pinned):
        key = str(r.get("pinned", "?"))
        by_pin[key] = by_pin.get(key, 0) + 1
    return {"records": len(records), "pinned": len(list(pinned)),
            "sampled": len(list(sampled)), "by_pin_reason": by_pin,
            "models": rows}


def _waterfall_lines(record, width=30, max_segments=16):
    """The text waterfall for one request record."""
    total = _fnum(record.get("total_ms"), 0.0)
    scale = total if total > 0 else 1.0
    head = "req %s  model=%s rows=%s total=%.3fms status=%s" % (
        record.get("trace_id", "?"), record.get("model", "?"),
        record.get("rows", "?"), total, record.get("status", "?"))
    if record.get("reason"):
        head += " reason=%s" % record["reason"]
    if record.get("pinned"):
        head += "  PINNED=%s" % record["pinned"]
    if record.get("slo_ms"):
        head += "  slo=%gms" % _fnum(record["slo_ms"], 0.0)
    if record.get("replica") is not None:
        head += "  replica=%s" % record["replica"]
    lines = [head]
    segments = record.get("segments") or []
    shown = segments if len(segments) <= max_segments else (
        segments[:max_segments // 2] + [None]
        + segments[-(max_segments - max_segments // 2):])
    for s in shown:
        if s is None:
            lines.append("  ... (%d segment(s) elided)"
                         % (len(segments) - max_segments))
            continue
        t0 = _fnum(s.get("t0_ms"), 0.0)
        dur = _fnum(s.get("dur_ms"), 0.0)
        start = min(width - 1, max(0, int(width * t0 / scale)))
        span = max(1, int(round(width * dur / scale)))
        bar = " " * start + "#" * min(span, width - start)
        note = ""
        name = s.get("name", "?")
        if name == "route":
            cands = s.get("candidates") or []
            note = "-> replica %s of %d candidate(s)" % (
                s.get("winner", "?"), len(cands))
        elif name == "assemble":
            note = "bucket=%s cobatched=%s padded=%s" % (
                s.get("bucket", "?"), s.get("cobatched", "?"),
                s.get("padded_rows", "?"))
        elif name in ("dispatch", "lane") \
                and s.get("replica") is not None:
            note = "replica=%s" % s["replica"]
        elif name == "decode_step":
            note = "slot=%s active=%s" % (s.get("slot", "?"),
                                          s.get("active", "?"))
            if s.get("pages") is not None:
                # paged-KV decode: the stream's table size, its reused
                # prefix pages, and the pool occupancy at dispatch
                note += " pages=%s prefix=%s pool=%s" % (
                    s.get("pages"), s.get("prefix_pages", "?"),
                    s.get("pool_in_use", "?"))
        elif name == "reject":
            note = str(s.get("reason", ""))
        lines.append("  %-11s %9.3f +%9.3fms |%-*s| %s"
                     % (name[:11], t0, dur, width, bar, note))
    if record.get("segments_dropped"):
        lines.append("  (%d segment(s) dropped at the per-request cap)"
                     % record["segments_dropped"])
    return lines


def summarize_requests(doc, top=8):
    """The text report for `--requests` over one dump."""
    pinned, sampled = request_records(doc)
    stats = requests_stats(pinned, sampled)
    lines = []
    fleet = doc.get("fleet") or {}
    lines.append("== requests: end-to-end traces (pinned %d, sampled "
                 "%d)%s ==" % (stats["pinned"], stats["sampled"],
                               ("  root=%s pid=%s"
                                % (fleet.get("root"), fleet.get("pid")))
                               if fleet else ""))
    if not stats["records"]:
        lines.append("(no request traces recorded — is "
                     "MXNET_TPU_REQTRACE=0, or did no traffic run?)")
        return "\n".join(lines)
    if stats["by_pin_reason"]:
        lines.append("tail-captured by reason: " + "  ".join(
            "%s=%d" % kv for kv in sorted(
                stats["by_pin_reason"].items())))
    lines.append("")
    lines.append("== requests: p99 attribution (tail-request hop "
                 "shares) ==")
    seg_cols = [s for s in REQUEST_SEGMENTS
                if any(s in m["shares"] for m in stats["models"])]
    header = "%-14s %8s %9s %9s" % ("Model", "Requests", "p50(ms)",
                                    "p99(ms)")
    for s in seg_cols:
        header += " %9s" % s[:9]
    header += " %9s" % "other"
    lines.append(header)
    for m in stats["models"]:
        row = "%-14s %8d %9.3f %9.3f" % (m["model"][:14],
                                         m["requests"], m["p50_ms"],
                                         m["p99_ms"])
        for s in seg_cols:
            row += " %8.1f%%" % (m["shares"].get(s, 0.0) * 100.0)
        row += " %8.1f%%" % (max(0.0, 1.0 - m["coverage"]) * 100.0)
        lines.append(row)
        lines.append("  (tail set: %d request(s); segments explain "
                     "%.1f%% of tail latency)"
                     % (m["tail_requests"], m["coverage"] * 100.0))
    lines.append("")
    lines.append("== requests: tail-captured waterfalls ==")
    if not pinned:
        lines.append("(none pinned — no SLO breaches, typed "
                     "rejections, or quarantined-replica rides)")
    else:
        for record in pinned[-top:]:
            lines.extend(_waterfall_lines(record))
            lines.append("")
        if len(pinned) > top:
            lines.append("(%d more pinned request(s) in the ring)"
                         % (len(pinned) - top))
    return "\n".join(lines)


# -- fleet view (merged multi-process dumps) ---------------------------------

def fleet_sources(dirpath):
    """Every parseable JSON document in ``dirpath`` as (filename, doc),
    sorted by name.  Non-JSON files (telemetry JSON-lines, traces with
    trailing garbage) are skipped — a fleet dir mixes artifacts."""
    import os as _os
    sources = []
    for fn in sorted(_os.listdir(dirpath)):
        if not fn.endswith(".json"):
            continue
        try:
            with open(_os.path.join(dirpath, fn)) as f:
                doc = json.load(f)
        except Exception:
            continue
        if isinstance(doc, dict):
            sources.append((fn, doc))
    return sources


def _filter_doc_since(doc, cutoff):
    """Shallow-copied dump with request records older than ``cutoff``
    (epoch seconds) dropped."""
    pinned, sampled = request_records(doc)
    out = dict(doc)
    out["requests"] = [r for r in pinned
                       if _fnum(r.get("t0"), 0.0) >= cutoff]
    out["requests_sampled"] = [r for r in sampled
                               if _fnum(r.get("t0"), 0.0) >= cutoff]
    return out


def filter_since(doc, since):
    """Scope one dump's request records to the trailing ``since``
    seconds, measured back from the newest record — the `--since`
    incident window an alert names.  No-op on dumps without
    timestamped records."""
    pinned, sampled = request_records(doc)
    times = [t for t in (_fnum(r.get("t0")) for r in pinned + sampled)
             if _isfinite(t)]
    if not times:
        return doc
    return _filter_doc_since(doc, max(times) - float(since))


def fleet_stats(sources, since=None):
    """The machine-readable `--fleet` summary: per-source facts and
    the merged, epoch-ordered request timeline.  ``since`` scopes every
    source to the trailing window measured back from the newest record
    FLEET-WIDE (one shared cutoff, so the per-source tables stay
    comparable)."""
    if since is not None:
        times = []
        for _, doc in sources:
            pinned, sampled = request_records(doc)
            times += [_fnum(r.get("t0")) for r in pinned + sampled]
        times = [t for t in times if _isfinite(t)]
        if times:
            cutoff = max(times) - float(since)
            sources = [(fn, _filter_doc_since(doc, cutoff))
                       for fn, doc in sources]
    rows, merged = [], []
    for fn, doc in sources:
        pinned, sampled = request_records(doc)
        recs = list(pinned) + list(sampled)
        fleet = doc.get("fleet") or {}
        fp = doc.get("fingerprint") or {}
        times = [_fnum(r.get("t0")) for r in recs]
        times += [_fnum(s.get("t")) for s in (doc.get("steps") or [])]
        times += [_fnum(e.get("t")) for e in (doc.get("elastic") or [])]
        times = [t for t in times if _isfinite(t) and t > 0]
        rows.append({"source": fn,
                     "kind": doc.get("kind", "?"),
                     "pid": fleet.get("pid", fp.get("pid")),
                     "root": fleet.get("root"),
                     "requests": len(recs), "pinned": len(pinned),
                     "steps": len(doc.get("steps") or []),
                     "elastic": len(doc.get("elastic") or []),
                     "t_min": min(times) if times else None,
                     "t_max": max(times) if times else None})
        for r in recs:
            merged.append((fn, r))
    merged.sort(key=lambda fr: _fnum(fr[1].get("t0"), 0.0))
    t_mins = [r["t_min"] for r in rows if r["t_min"] is not None]
    return {"sources": rows, "merged": merged,
            "roots": sorted({r["root"] for r in rows if r["root"]}),
            "epoch0": min(t_mins) if t_mins else None}


def summarize_fleet(stats, top=30):
    """The text report for `--fleet` over one dump directory."""
    lines = []
    lines.append("== fleet: %d dump(s), %d request trace(s), trace "
                 "root(s): %s =="
                 % (len(stats["sources"]), len(stats["merged"]),
                    ", ".join(stats["roots"]) or "(none)"))
    lines.append("%-34s %-8s %-10s %9s %7s %6s %8s"
                 % ("Source", "Pid", "Root", "Requests", "Pinned",
                    "Steps", "Span(s)"))
    epoch0 = stats["epoch0"]
    for r in stats["sources"]:
        span = (r["t_max"] - r["t_min"]) \
            if r["t_min"] is not None and r["t_max"] is not None else None
        lines.append("%-34s %-8s %-10s %9d %7d %6d %8s"
                     % (r["source"][:34], r["pid"] or "?",
                        (r["root"] or "?")[:10], r["requests"],
                        r["pinned"], r["steps"],
                        ("%.2f" % span) if span is not None else "?"))
    lines.append("")
    lines.append("== fleet: merged request timeline (shared epoch) ==")
    if not stats["merged"]:
        lines.append("(no request traces in any dump)")
    else:
        lines.append("%-9s %-24s %-12s %5s %10s %-9s %s"
                     % ("t(+s)", "Source", "Model", "Rows",
                        "Total(ms)", "Status", "Pinned"))
        shown = stats["merged"][-top:]
        if len(stats["merged"]) > top:
            lines.append("... (%d earlier request(s) elided)"
                         % (len(stats["merged"]) - top))
        for fn, r in shown:
            rel = _fnum(r.get("t0"), 0.0) - (epoch0 or 0.0)
            lines.append("%-9.3f %-24s %-12s %5s %10.3f %-9s %s"
                         % (rel, fn[:24], str(r.get("model", "?"))[:12],
                            r.get("rows", "?"),
                            _fnum(r.get("total_ms"), 0.0),
                            str(r.get("status", "?"))[:9],
                            r.get("pinned", "")))
        # fleet-wide attribution over the merged set
        merged_records = [r for _, r in stats["merged"]]
        rstats = requests_stats(
            [r for r in merged_records if r.get("pinned")],
            [r for r in merged_records if not r.get("pinned")])
        lines.append("")
        lines.append("== fleet: merged p99 attribution ==")
        for m in rstats["models"]:
            shares = "  ".join(
                "%s=%.1f%%" % (s, m["shares"][s] * 100.0)
                for s in REQUEST_SEGMENTS if s in m["shares"])
            lines.append("%-14s p99 %.3f ms over %d request(s): %s"
                         % (m["model"][:14], m["p99_ms"],
                            m["requests"], shares))
    return "\n".join(lines)


# -- health-plane dashboard + alert history ----------------------------------

def _hist_delta(snap_a, snap_b):
    """Pinned copy of ``observability.telemetry.delta_snapshot`` (this
    CLI stays import-free): the histogram of only the observations made
    between two snapshots of the same instrument — per-bucket count
    differences, bounds clamped to the newer snapshot's min/max.  A
    generation change (``gen`` token) or any negative difference means
    the registry was reset between the snapshots: the result is the
    newer snapshot alone, flagged ``"reset": True``."""
    if not snap_a:
        out = dict(snap_b)
        out["reset"] = False
        return out
    ba = snap_a.get("buckets") or []
    bb = snap_b.get("buckets") or []
    ca = snap_a.get("count", 0) or 0
    cb = snap_b.get("count", 0) or 0
    reset = snap_a.get("gen") != snap_b.get("gen")
    diff = []
    if not reset:
        if cb < ca or len(ba) != len(bb):
            reset = True
        else:
            diff = [y - x for x, y in zip(ba, bb)]
            if any(d < 0 for d in diff):
                reset = True
    if reset:
        out = dict(snap_b)
        out["reset"] = True
        return out
    count = cb - ca
    return {"count": count,
            "sum": _fnum(snap_b.get("sum"), 0.0)
            - _fnum(snap_a.get("sum"), 0.0),
            "min": snap_b.get("min") if count else None,
            "max": snap_b.get("max") if count else None,
            "buckets": diff, "reset": False}


def _hist_quantile_between(snap_a, snap_b, q):
    """Pinned copy of ``telemetry.quantile_between``: the delta-form
    quantile — only the observations made between the two snapshots."""
    return _hist_quantile(_hist_delta(snap_a, snap_b), q)


def _merge_hist(acc, d):
    """Accumulate delta-histogram snapshots (the dash's per-bin merge
    across sources — same arithmetic as the timeseries window merge)."""
    if acc is None:
        return dict(d, buckets=list(d.get("buckets") or []))
    bd = d.get("buckets") or []
    ba = acc.get("buckets") or []
    if len(bd) > len(ba):
        ba = ba + [0] * (len(bd) - len(ba))
    acc["buckets"] = [x + (bd[i] if i < len(bd) else 0)
                      for i, x in enumerate(ba)]
    acc["count"] = (acc.get("count", 0) or 0) + (d.get("count", 0) or 0)
    acc["sum"] = _fnum(acc.get("sum"), 0.0) + _fnum(d.get("sum"), 0.0)
    for key, pick in (("min", min), ("max", max)):
        vals = [v for v in (acc.get(key), d.get(key)) if v is not None]
        acc[key] = pick(vals) if vals else None
    return acc


def dash_sources(dirpath):
    """Every fleet-shipper series file (``series_*.jsonl``, written by
    ``observability/shipper.py``) in ``dirpath`` as
    ``{"source", "fleet", "samples", "alerts"}`` dicts.  Unparseable
    lines are skipped — a series file may still be mid-write."""
    import os as _os
    sources = []
    for fn in sorted(_os.listdir(dirpath)):
        if not (fn.startswith("series_") and fn.endswith(".jsonl")):
            continue
        fleet, samples, alerts = {}, [], []
        try:
            with open(_os.path.join(dirpath, fn)) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        obj = json.loads(line)
                    except ValueError:
                        continue
                    kind = obj.get("kind")
                    if kind == "header":
                        fleet = obj.get("fleet") or fleet
                    elif kind == "sample":
                        samples.append(obj)
                    elif kind == "alert":
                        alerts.append(obj)
        except OSError:
            continue
        if samples or alerts:
            samples.sort(key=lambda s: _fnum(s.get("rel"), 0.0))
            sources.append({"source": fn, "fleet": fleet,
                            "samples": samples, "alerts": alerts})
    return sources


def dash_stats(sources, bins=48):
    """The machine-readable `--dash` summary: fleet-merged binned
    signal series (request rate, shed rate, queue depth, replicas,
    per-model p99 vs SLO) plus the merged alert timeline.  Counter
    rates are per-source adjacent-sample deltas summed into shared
    time bins (reset spans skipped via the ``gen`` token); histogram
    bins merge bucket deltas across sources before the quantile."""
    all_samples = [s for src in sources for s in src["samples"]]
    out = {"sources": [
        {"source": src["source"],
         "pid": (src["fleet"] or {}).get("pid"),
         "root": (src["fleet"] or {}).get("root"),
         "samples": len(src["samples"]), "alerts": len(src["alerts"])}
        for src in sources]}
    out["roots"] = sorted({r["root"] for r in out["sources"]
                           if r["root"]})
    epochs = [_fnum((src["fleet"] or {}).get("epoch0"))
              for src in sources]
    epochs = [e for e in epochs if _isfinite(e)]
    out["epoch0"] = min(epochs) if epochs else None
    merged_alerts = sorted((a for src in sources for a in src["alerts"]),
                           key=lambda a: _fnum(a.get("t"), 0.0))
    last_state = {}
    for a in merged_alerts:
        last_state[str(a.get("rule", "?"))] = a.get("state")
    out["alerts"] = merged_alerts
    out["firing"] = sorted(r for r, s in last_state.items()
                           if s == "firing")
    if not all_samples:
        out.update({"bins": 0, "bin_s": 0.0, "rel0": 0.0, "rel1": 0.0,
                    "req_rate": [], "req_total": 0.0, "shed_rate": [],
                    "shed_total": 0.0, "queue_depth": [],
                    "replicas": [], "models": []})
        return out
    rels = [_fnum(s.get("rel"), 0.0) for s in all_samples]
    rel0, rel1 = min(rels), max(rels)
    span = max(rel1 - rel0, 1e-9)
    nbins = max(1, min(bins, len(all_samples)))
    width = span / nbins

    def bin_of(rel):
        return min(nbins - 1, max(0, int((rel - rel0) / width)))

    def pairs(src):
        ss = src["samples"]
        return zip(ss, ss[1:])

    def counter_rate(match):
        deltas = [0.0] * nbins
        for src in sources:
            for a, b in pairs(src):
                sa = a.get("series") or {}
                sb = b.get("series") or {}
                mid = (_fnum(a.get("rel"), 0.0)
                       + _fnum(b.get("rel"), 0.0)) / 2.0
                i = bin_of(mid)
                for name, snap in sb.items():
                    if not match(name) \
                            or (snap or {}).get("type") != "counter":
                        continue
                    vb = _fnum(snap.get("value"), 0.0)
                    prev = sa.get(name)
                    if prev is None:
                        deltas[i] += vb
                        continue
                    va = _fnum(prev.get("value"), 0.0)
                    if prev.get("gen") != snap.get("gen") or vb < va:
                        continue  # reset span: no negative rates
                    deltas[i] += vb - va
        return [d / width for d in deltas], sum(deltas)

    def gauge_series(match):
        per = {}
        for si, src in enumerate(sources):
            for s in src["samples"]:
                for name, snap in (s.get("series") or {}).items():
                    if not match(name) \
                            or (snap or {}).get("type") != "gauge":
                        continue
                    i = bin_of(_fnum(s.get("rel"), 0.0))
                    per.setdefault((si, i), []).append(
                        _fnum(snap.get("value"), 0.0))
        series = [0.0] * nbins
        for (si, i), vals in per.items():
            series[i] += sum(vals) / len(vals)
        return series

    out.update({"bins": nbins, "bin_s": width, "rel0": rel0,
                "rel1": rel1})
    out["req_rate"], out["req_total"] = counter_rate(
        lambda n: n == "serving.requests_total")
    out["shed_rate"], out["shed_total"] = counter_rate(
        lambda n: n.startswith("serving.rejected_total."))
    out["queue_depth"] = gauge_series(
        lambda n: n == "serving.queue_depth")
    out["replicas"] = gauge_series(lambda n: n == "serving.replicas")

    lat_prefix = "serving.request_latency_ms."
    models = sorted({name[len(lat_prefix):]
                     for s in all_samples
                     for name in (s.get("series") or {})
                     if name.startswith(lat_prefix)})
    out["models"] = []
    for model in models:
        lname = lat_prefix + model
        per_bin = [None] * nbins
        overall = None
        for src in sources:
            for a, b in pairs(src):
                sb = (b.get("series") or {}).get(lname)
                if not sb:
                    continue
                d = _hist_delta((a.get("series") or {}).get(lname) or {},
                                sb)
                if d.get("reset") or (d.get("count") or 0) <= 0:
                    continue
                mid = (_fnum(a.get("rel"), 0.0)
                       + _fnum(b.get("rel"), 0.0)) / 2.0
                i = bin_of(mid)
                per_bin[i] = _merge_hist(per_bin[i], d)
                overall = _merge_hist(overall, d)
        slo = None
        for s in all_samples:  # newest declared SLO wins
            snap = (s.get("series") or {}).get("serving.slo_ms." + model)
            if snap is not None:
                slo = _fnum(snap.get("value"), 0.0)
        out["models"].append({
            "model": model,
            "p99_ms": [_hist_quantile(m, 0.99) if m else 0.0
                       for m in per_bin],
            "p99_overall": _hist_quantile(overall, 0.99)
            if overall else 0.0,
            "served": (overall or {}).get("count", 0),
            "slo_ms": slo})
    return out


def _alert_detail(rec):
    """The windows/values that tripped (or resolved) one rule, as one
    compact line."""
    parts = []
    windows = rec.get("windows") or {}
    for wname in sorted(windows):
        w = windows[wname] or {}
        if "burn" in w:
            parts.append(
                "%s[%gs] burn=%.2f err=%.1f%% served=%s shed=%s"
                % (wname, _fnum(w.get("window_s"), 0.0),
                   _fnum(w.get("burn"), 0.0),
                   _fnum(w.get("error_ratio"), 0.0) * 100.0,
                   w.get("served", "?"), w.get("rejected", "?")))
        else:
            parts.append("%s[%gs] value=%s"
                         % (wname, _fnum(w.get("window_s"), 0.0),
                            w.get("value")))
    if rec.get("burn_threshold") is not None:
        parts.append("burn_threshold=%g"
                     % _fnum(rec["burn_threshold"], 0.0))
        if rec.get("windows", {}).get("fast", {}).get("slo_ms") \
                is not None:
            parts.append("slo=%gms"
                         % _fnum(rec["windows"]["fast"]["slo_ms"], 0.0))
    elif rec.get("threshold") is not None:
        parts.append("%s %s %s" % (rec.get("field", "value"),
                                   rec.get("op", "?"),
                                   rec.get("threshold")))
    return "  ".join(parts)


def alert_records(doc):
    """Alert transition records from a flight dump (the ``alerts``
    ring), a bare JSON list, or an ``{"alerts": [...]}`` document."""
    if isinstance(doc, list):
        return [r for r in doc if isinstance(r, dict)]
    if isinstance(doc, dict):
        return [r for r in (doc.get("alerts") or [])
                if isinstance(r, dict)]
    return []


def alerts_stats(records):
    """The machine-readable `--alerts` summary: per-rule fire/resolve
    counts and the rules still firing at the end of the record."""
    by_rule = {}
    for r in records:
        st = by_rule.setdefault(str(r.get("rule", "?")),
                                {"fired": 0, "resolved": 0, "last": None})
        if r.get("state") == "firing":
            st["fired"] += 1
        elif r.get("state") == "resolved":
            st["resolved"] += 1
        st["last"] = r.get("state")
    return {"records": len(records), "rules": by_rule,
            "firing": sorted(rule for rule, st in by_rule.items()
                             if st["last"] == "firing")}


def summarize_alerts(records, top=20):
    """The text report for `--alerts`: per-rule counts + the firing
    history with the windows and values that tripped each rule."""
    stats = alerts_stats(records)
    lines = ["== alerts: %d transition(s), firing now: %s =="
             % (stats["records"],
                ", ".join(stats["firing"]) or "(none)")]
    if not records:
        lines.append("(no alert transitions recorded — no rules armed, "
                     "or nothing fired)")
        return "\n".join(lines)
    lines.append("%-28s %6s %9s %-9s"
                 % ("Rule", "Fired", "Resolved", "Last"))
    for rule in sorted(stats["rules"]):
        st = stats["rules"][rule]
        lines.append("%-28s %6d %9d %-9s"
                     % (rule[:28], st["fired"], st["resolved"],
                        st["last"] or "?"))
    lines.append("")
    lines.append("== alerts: firing history (newest last) ==")
    t0 = min(_fnum(r.get("t"), 0.0) for r in records)
    if len(records) > top:
        lines.append("... (%d earlier transition(s) elided)"
                     % (len(records) - top))
    for r in records[-top:]:
        lines.append("%9.3fs %-9s %-28s [%s]"
                     % (_fnum(r.get("t"), 0.0) - t0,
                        str(r.get("state", "?")),
                        str(r.get("rule", "?"))[:28],
                        str(r.get("kind", "?"))))
        detail = _alert_detail(r)
        if detail:
            lines.append("           %s" % detail)
    return "\n".join(lines)


def summarize_dash(stats, top_alerts=10):
    """The text report for `--dash`: the fleet-merged sparkline
    dashboard (req rate, shed rate, p99 vs SLO, queue depth, live
    alerts)."""
    lines = []
    n_samples = sum(r["samples"] for r in stats["sources"])
    lines.append("== fleet dash: %d source(s), %d sample(s) over "
                 "%.1f s, root(s): %s =="
                 % (len(stats["sources"]), n_samples,
                    stats["rel1"] - stats["rel0"] if stats["bins"]
                    else 0.0,
                    ", ".join(stats["roots"]) or "(none)"))
    lines.append("%-30s %-8s %-10s %8s %7s"
                 % ("Source", "Pid", "Root", "Samples", "Alerts"))
    for r in stats["sources"]:
        lines.append("%-30s %-8s %-10s %8d %7d"
                     % (r["source"][:30], r["pid"] or "?",
                        (r["root"] or "?")[:10], r["samples"],
                        r["alerts"]))
    if not stats["bins"]:
        lines.append("(no series samples shipped — is "
                     "MXNET_TPU_TS_INTERVAL_S set?)")
        return "\n".join(lines)
    lines.append("")
    lines.append("== signals (each bin = %.2f s) ==" % stats["bin_s"])
    lines.append("req rate /s   %s  total %d  peak %.1f/s"
                 % (_sparkline(stats["req_rate"]),
                    stats["req_total"],
                    max(stats["req_rate"]) if stats["req_rate"]
                    else 0.0))
    lines.append("shed rate /s  %s  total %d  peak %.1f/s"
                 % (_sparkline(stats["shed_rate"]),
                    stats["shed_total"],
                    max(stats["shed_rate"]) if stats["shed_rate"]
                    else 0.0))
    lines.append("queue depth   %s  last %.1f  max %.1f"
                 % (_sparkline(stats["queue_depth"]),
                    stats["queue_depth"][-1] if stats["queue_depth"]
                    else 0.0,
                    max(stats["queue_depth"]) if stats["queue_depth"]
                    else 0.0))
    if any(stats["replicas"]):
        lines.append("replicas      %s  last %.0f"
                     % (_sparkline(stats["replicas"]),
                        stats["replicas"][-1]))
    lines.append("")
    lines.append("== p99 vs SLO (windowed delta quantiles) ==")
    if not stats["models"]:
        lines.append("(no per-model latency series shipped)")
    for m in stats["models"]:
        verdict = "?"
        if m["slo_ms"]:
            verdict = ("OK (%.0f%% of slo)"
                       if m["p99_overall"] <= m["slo_ms"]
                       else "BREACH (%.0f%% of slo)") \
                % (100.0 * m["p99_overall"] / m["slo_ms"])
        lines.append("%-14s p99(ms) %s  overall %.2f ms  slo %s  %s"
                     % (m["model"][:14], _sparkline(m["p99_ms"]),
                        m["p99_overall"],
                        ("%g ms" % m["slo_ms"]) if m["slo_ms"]
                        else "(undeclared)", verdict))
    lines.append("")
    lines.append("== alerts (%d transition(s), firing now: %s) =="
                 % (len(stats["alerts"]),
                    ", ".join(stats["firing"]) or "(none)"))
    epoch0 = stats["epoch0"] or 0.0
    ats = [_fnum(a.get("t")) for a in stats["alerts"]]
    ats = [t for t in ats if _isfinite(t)]
    # anchor at run start when the clocks agree, else at the first alert
    base = epoch0 if (ats and epoch0 and min(ats) >= epoch0) \
        else (min(ats) if ats else 0.0)
    for a in stats["alerts"][-top_alerts:]:
        lines.append("%9.3fs %-9s %-28s %s"
                     % (_fnum(a.get("t"), 0.0) - base,
                        str(a.get("state", "?")),
                        str(a.get("rule", "?"))[:28],
                        _alert_detail(a)))
    return "\n".join(lines)


def summarize(trace, top=15):
    """The full text report for one loaded trace document."""
    events = trace.get("traceEvents", [])
    lines = []
    agg = aggregate(span_durations(events))

    lines.append("== top spans by total time ==")
    lines.append("%-34s %-12s %7s %12s %12s"
                 % ("Name", "Category", "Calls", "Total(ms)", "Avg(ms)"))
    rows = sorted(agg.items(), key=lambda kv: -kv[1]["total_ms"])[:top]
    for (cat, name), s in rows:
        lines.append("%-34s %-12s %7d %12.3f %12.3f"
                     % (name[:34], cat[:12], s["count"], s["total_ms"],
                        s["avg_ms"]))
    if not rows:
        lines.append("(no spans recorded)")

    bd = step_breakdown(events)
    lines.append("")
    lines.append("== per-step breakdown ==")
    if bd is None:
        lines.append("(no step spans — trace a Module.fit / BaseModule "
                     "training loop to get the breakdown)")
    else:
        lines.append("steps: %d   measured step time: %.3f ms total, "
                     "%.3f ms avg" % (bd["steps"], bd["step_total_ms"],
                                      bd["step_avg_ms"]))
        starved = bd["starved_ms"] is not None
        lines.append("%-18s %7s %12s %12s %8s %12s"
                     % ("Component", "Calls", "Total(ms)", "Avg/step(ms)",
                        "Step%", "Starved(ms)"))
        for c in STEP_COMPONENTS:
            s = bd["components"][c]
            share = (s["total_ms"] / bd["step_total_ms"] * 100.0
                     if bd["step_total_ms"] else 0.0)
            lines.append("%-18s %7d %12.3f %12.3f %7.1f%% %12s"
                         % (c, s["count"], s["total_ms"],
                            s["total_ms"] / bd["steps"], share,
                            "%.3f" % bd["starved_by"].get(c, 0.0)
                            if starved else "-"))
        lines.append("component coverage of step time: %.1f%%"
                     % (bd["coverage"] * 100.0))
        lines.append("input starvation (data_wait / step): %.1f%%"
                     % (bd["starvation"] * 100.0))
        if starved:
            lines.append(
                "device starved (no step program in flight): %.3f ms "
                "total, %.3f ms/step (%.3f ms between components)%s"
                % (bd["starved_ms"], bd["starved_ms"] / bd["steps"],
                   bd["starved_by"].get("glue", 0.0),
                   "; %d steps ran ahead: a lower bound" % bd["ran_ahead"]
                   if bd["ran_ahead"] else ""))

    pb = pipeline_breakdown(events)
    if pb is not None:
        lines.append("")
        lines.append("== io pipeline breakdown ==")
        lines.append("%-18s %7s %12s %12s"
                     % ("Stage", "Calls", "Total(ms)", "Avg(ms)"))
        for stage in PIPELINE_STAGES:
            s = pb["stages"][stage]
            lines.append("%-18s %7d %12.3f %12.3f"
                         % (stage, s["count"], s["total_ms"],
                            s["avg_ms"]))
        if pb["starvation"] is not None:
            lines.append("pipeline starvation (queue_wait / step): "
                         "%.1f%%" % (pb["starvation"] * 100.0))

    cb = comm_breakdown(events)
    if cb is not None:
        lines.append("")
        lines.append("== gradient communication ==")
        ex = cb["exposed"]
        steps = cb["steps"]
        per_step = " (%.3f ms/step)" % (ex["total_ms"] / steps) \
            if steps else ""
        lines.append("exposed:    %d collectives, %.3f ms total%s, %s"
                     % (ex["count"], ex["total_ms"], per_step,
                        _fmt_bytes(ex["bytes"])))

    inst = instants(events)
    if inst:
        lines.append("")
        lines.append("== instant events ==")
        for name in sorted(inst):
            lines.append("%-34s %7d" % (name[:34], inst[name]))
    return "\n".join(lines)


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="Summarize an mxnet_tpu Chrome trace dump")
    parser.add_argument("trace", help="trace JSON written by "
                        "profiler.dump_profile() (or, with --serving, a "
                        "telemetry JSON-lines dump; with --fleet, a "
                        "DIRECTORY of dumps)")
    parser.add_argument("--top", type=int, default=15,
                        help="rows in the top-spans table")
    parser.add_argument("--serving", action="store_true",
                        help="inference-service view: request-latency "
                        "percentiles, batch-size distribution, rejection "
                        "counts")
    parser.add_argument("--flight", action="store_true",
                        help="flight-recorder view: first-anomaly step, "
                        "per-rule counts, grad/loss/memory trend; exits 1 "
                        "when the dump holds a fired anomaly")
    parser.add_argument("--memory", action="store_true",
                        help="memory view: per-program memory_analysis "
                        "table, live-array census, device allocator "
                        "stats (a memprof report JSON, or a flight dump "
                        "embedding one)")
    parser.add_argument("--tuning", action="store_true",
                        help="autotune view: the decision log "
                        "(controllers, actions, candidates, retrace "
                        "cost) from a flight dump or a bare decision-"
                        "log JSON; exits 2 when no decisions are "
                        "recorded")
    parser.add_argument("--requests", action="store_true",
                        help="request-trace view: per-request "
                        "waterfalls + the p99 attribution table "
                        "(queue/route/lane/assemble/dispatch/split "
                        "shares of tail latency, per model) from a "
                        "flight dump or a reqtrace dump; exits 2 when "
                        "no request traces are recorded")
    parser.add_argument("--fleet", action="store_true",
                        help="fleet view: merge every JSON dump in a "
                        "DIRECTORY (fleet replicas, elastic workers "
                        "sharing an env-propagated trace root) onto "
                        "one shared-epoch timeline; exits 2 when no "
                        "dump holds request traces")
    parser.add_argument("--dash", action="store_true",
                        help="fleet health dashboard: merge every "
                        "series_*.jsonl shipped by the timeseries "
                        "sampler in a DIRECTORY into sparkline rows "
                        "(req rate, shed rate, p99 vs SLO, queue "
                        "depth, replicas) plus the live alert state; "
                        "exits 2 when no samples were shipped")
    parser.add_argument("--alerts", action="store_true",
                        help="alert view: the firing/resolve history "
                        "with the windows and values that tripped "
                        "each rule, from a flight dump (the `alerts` "
                        "ring) or a bare record-list JSON; exits 2 "
                        "when no transitions are recorded")
    parser.add_argument("--since", type=float, default=None,
                        metavar="SECONDS",
                        help="with --requests/--fleet: only requests "
                        "that STARTED within the trailing SECONDS of "
                        "the (fleet-wide) newest request start")
    parser.add_argument("--elastic", action="store_true",
                        help="elastic view: the checkpoint/resume "
                        "lineage (snapshots by trigger, rejected-at-"
                        "verify snapshots, preemption signals, resume "
                        "warm-restore counters) from a flight dump or "
                        "a bare record-list JSON; exits 2 when no "
                        "elastic records are recorded")
    args = parser.parse_args(argv)
    if args.dash:
        stats = dash_stats(dash_sources(args.trace))
        print(summarize_dash(stats))
        return 0 if stats["bins"] else 2
    if args.alerts:
        with open(args.trace) as f:
            doc = json.load(f)
        records = alert_records(doc)
        print(summarize_alerts(records))
        return 0 if records else 2
    if args.fleet:
        stats = fleet_stats(fleet_sources(args.trace), since=args.since)
        print(summarize_fleet(stats))
        return 0 if stats["merged"] else 2
    if args.requests:
        with open(args.trace) as f:
            doc = json.load(f)
        if args.since is not None:
            doc = filter_since(doc, args.since)
        print(summarize_requests(doc))
        pinned, sampled = request_records(doc)
        return 0 if (pinned or sampled) else 2
    if args.elastic:
        with open(args.trace) as f:
            doc = json.load(f)
        records = elastic_records(doc)
        print(summarize_elastic(records))
        return 0 if records else 2
    if args.tuning:
        with open(args.trace) as f:
            doc = json.load(f)
        records = tuning_records(doc)
        print(summarize_tuning(records))
        return 0 if records else 2
    if args.flight:
        with open(args.trace) as f:
            doc = json.load(f)
        print(summarize_flight(doc))
        # CI contract: a dump holding a fired anomaly exits non-zero
        return 1 if (doc.get("anomalies") or []) else 0
    if args.memory:
        with open(args.trace) as f:
            doc = json.load(f)
        if doc.get("kind") == "mxnet_tpu_flight" or "steps" in doc:
            memdoc = doc.get("memory")
            if not memdoc:
                print("flight dump %s embeds no memory report (only OOM "
                      "dumps carry one)" % args.trace)
                return 2
            doc = memdoc
        print(summarize_memory(doc))
        return 0
    if args.serving:
        kind, payload = load_any(args.trace)
        print(summarize_serving(kind, payload))
        return 0
    print(summarize(load_trace(args.trace), top=args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
