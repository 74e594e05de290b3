"""Unified runtime telemetry (ref: src/engine/profiler.{h,cc} §5.1 +
the metrics/logging surface of §5.5, grown into a production shape).

Three layers, lowest first:

- ``tracing``   — the structured trace-event sink: nested spans with
  parent/child links over a thread-local span stack, Chrome "X"
  complete-events with real thread ids, instant events (recompiles,
  evictions), counter samples.  ``mxnet_tpu.profiler`` is the
  reference-compatible facade over this buffer.
- ``telemetry`` — the process-wide metrics registry: named Counter /
  Gauge / Histogram (fixed log2 buckets, no numpy in the hot path) with
  ``snapshot()`` plus Prometheus-text and JSON-lines exporters.
  ``MXNET_TPU_TELEMETRY=0`` hands out shared no-op instruments instead.
- ``instrument`` — the hot-path helpers the framework itself uses: the
  per-step breakdown tracker driving ``BaseModule.fit``
  (data_wait / fwd_bwd_dispatch / update / metric / sync), the
  input-starvation accounting behind ``io.DataIter``, kvstore push/pull
  bytes+latency, and the device-memory gauge.
- ``flight_recorder`` — the bounded black box: last-N step records,
  recent ``mxnet_tpu.*`` log lines, anomalies and events, dumped as one
  JSON file on anomaly / unhandled exception / demand.
- ``health`` — the training health sentinel: the in-program numerics
  summary (``MXNET_TPU_HEALTH=1``) and the host-side ``HealthMonitor``
  anomaly rules (docs/observability.md §health).
- ``memprof`` — memory & compile observability: per-program compile
  times (always on, via a jax.monitoring listener), per-program
  ``memory_analysis`` byte attribution (``MXNET_TPU_MEMPROF=1``), the
  live-array census, and the OOM black box
  (docs/observability.md §memory).
- ``reqtrace`` — end-to-end request tracing for the serving fleet:
  a per-request context minted at submit/HTTP ingress, typed segments
  appended at every hop (admission wait, router scoring, lane wait,
  assembly, dispatch, split, decode iterations), head-sampled storage
  plus tail capture of SLO breaches and typed rejections into the
  flight recorder's ``requests`` ring (``traceview --requests`` /
  ``--fleet``; docs/observability.md §request-tracing).
- ``autotune`` — the CONTROL half of the loop: controllers that turn
  the recorded signals above into bounded, auditable configuration
  changes (traffic-shaped serving buckets, io worker counts) behind
  ``MXNET_TPU_AUTOTUNE=recommend|apply|0``, every decision a structured
  record riding the flight recorder (docs/autotune.md).
- ``timeseries`` — the health plane's TREND layer: a bounded ring of
  timestamped registry snapshots (``MXNET_TPU_TS_INTERVAL_S``; sampler
  thread via ``threads.spawn``) with windowed signals — counter rates,
  gauge min/mean/max, histogram delta quantiles
  (docs/observability.md §health-plane).
- ``alerts`` — declarative alert rules over those windows: threshold,
  absence, and multi-window SLO burn-rate rules (auto-discovered per
  served model, extended via ``MXNET_TPU_ALERT_RULES``); every
  firing/resolve a flight-recorder ``alerts`` record +
  ``health.alerts.*`` counters (``traceview --alerts``).
- ``shipper`` — per-process JSON-lines series in a fleet-shared dir
  keyed by the env-propagated reqtrace root, so replicas and elastic
  children merge onto one ``traceview --dash`` timeline.

Every callsite stays OUTSIDE jitted bodies: instrumentation must never
change a traced program (the exec-cache trace counters prove it adds
zero recompiles — ``tests/test_step_phases.py`` asserts exactly that).
"""
from __future__ import annotations

from . import tracing
from . import telemetry
from . import instrument
from . import flight_recorder
from . import health
from . import memprof
from . import reqtrace
from . import autotune
from . import timeseries
from . import alerts
from . import shipper
from .tracing import span, emit_instant
from .telemetry import counter, gauge, histogram, snapshot
from .health import HealthMonitor, TrainingDivergedError

__all__ = ["tracing", "telemetry", "instrument", "flight_recorder",
           "health", "memprof", "reqtrace", "autotune", "timeseries",
           "alerts", "shipper", "span", "emit_instant",
           "counter", "gauge", "histogram", "snapshot", "HealthMonitor",
           "TrainingDivergedError"]
