"""Fleet health plane (observability/{timeseries,alerts,shipper}.py).

Pins its contracts:

- every instrument snapshot carries the registry generation token; a
  `telemetry.reset()` inside a window surfaces as a `resets` marker
  with the straddling span excluded — never a negative rate;
- `quantile_between` is the documented delta form of the shared
  estimator: quantiles over only the observations made between two
  snapshots (empty delta, single-bucket, and overflow edges pinned);
- `TimeSeries.window` derives counter rates, gauge min/mean/max, and
  histogram delta quantiles from the snapshot ring;
- threshold / absence / multi-window burn-rate rules fire and resolve
  with hysteresis, each transition a structured record in the flight
  `alerts` ring plus `health.alerts.*` counters;
- `MXNET_TPU_ALERT_RULES` parses inline JSON, skipping malformed
  specs without discarding the rest;
- the sampler spawns through `threads.spawn` (leak-fixture visible),
  stays off with the env unset, and runs clean under locksan;
- the fleet shipper merges parent + subprocess series files keyed to
  one env-propagated trace root onto a shared epoch, monotonic per
  source — and `traceview --dash` / `--alerts` render the result.
"""
import importlib.util
import json
import os
import subprocess
import sys
import time

import pytest

from mxnet_tpu import threads
from mxnet_tpu.observability import (alerts, flight_recorder, reqtrace,
                                     shipper, telemetry, timeseries)
from mxnet_tpu.observability.telemetry import (
    counter_delta, delta_snapshot, fraction_over, quantile_between)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolate_health_plane(monkeypatch):
    """Fresh registry/ring/engine per test; no ambient sampler env."""
    monkeypatch.setenv("MXNET_TPU_TELEMETRY", "1")
    for var in ("MXNET_TPU_TS_INTERVAL_S", "MXNET_TPU_TS_RING",
                "MXNET_TPU_ALERT_RULES", "MXNET_TPU_REQTRACE_CTX"):
        monkeypatch.delenv(var, raising=False)
    telemetry.reset()
    timeseries.reset()
    alerts.reset()
    flight_recorder.reset()
    reqtrace.reset()
    yield
    timeseries.reset()
    alerts.reset()
    telemetry.reset()


def _load_traceview():
    path = os.path.join(REPO, "tools", "traceview.py")
    spec = importlib.util.spec_from_file_location("_ts_traceview", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# -- generation token + delta derivation ------------------------------------

def test_snapshots_carry_generation_token():
    gen0 = telemetry.registry_epoch()
    c = telemetry.counter("t.hits")
    c.inc(3)
    snap_a = telemetry.snapshot()["t.hits"]
    assert snap_a["gen"] == gen0
    telemetry.reset()
    assert telemetry.registry_epoch() == gen0 + 1
    c2 = telemetry.counter("t.hits")
    c2.inc(1)
    snap_b = telemetry.snapshot()["t.hits"]
    assert snap_b["gen"] == gen0 + 1
    # the delta sees the reset, not a -2 decrease
    delta, reset = counter_delta(snap_a, snap_b)
    assert reset and delta == 1.0


def test_counter_delta_from_zero_is_not_a_reset():
    c = telemetry.counter("t.hits")
    c.inc(4)
    snap = telemetry.snapshot()["t.hits"]
    delta, reset = counter_delta(None, snap)
    assert (delta, reset) == (4.0, False)


def test_quantile_between_edges():
    h = telemetry.histogram("t.lat")
    h.observe(5.0)
    a = telemetry.snapshot()["t.lat"]
    # empty delta: no observations between the snapshots
    assert quantile_between(a, a, 0.99) == 0.0
    # single-bucket delta: the one new observation is every quantile
    h.observe(5.0)
    b = telemetry.snapshot()["t.lat"]
    for q in (0.0, 0.5, 0.99):
        assert quantile_between(a, b, q) == 5.0
    # overflow bucket: interpolation clamps toward the recorded max
    big = 2.0 ** 25
    h.observe(big)
    c = telemetry.snapshot()["t.lat"]
    d = delta_snapshot(b, c)
    assert d["count"] == 1 and not d["reset"]
    assert quantile_between(b, c, 0.99) == big


def test_fraction_over_interpolates():
    h = telemetry.histogram("t.lat")
    for _ in range(10):
        h.observe(4.0)
    snap = telemetry.snapshot()["t.lat"]
    assert fraction_over(snap, 3.0) == 1.0
    assert fraction_over(snap, 4.0) == 0.0
    assert fraction_over(snap, 2.0 ** 30) == 0.0


# -- windowed signals --------------------------------------------------------

def test_window_counter_rate_and_gauge_stats():
    ts = timeseries.TimeSeries(capacity=16)
    c = telemetry.counter("t.req")
    g = telemetry.gauge("t.depth")
    t0 = 1000.0
    for i, (inc, depth) in enumerate([(0, 2.0), (10, 4.0), (10, 6.0)]):
        c.inc(inc)
        g.set(depth)
        ts.sample(now=t0 + i * 1.0)
    w = ts.window("t.req", 10.0, now=t0 + 2.0)
    assert w["kind"] == "counter"
    assert w["delta"] == 20.0 and w["rate_per_s"] == pytest.approx(10.0)
    assert w["resets"] == 0
    wg = ts.window("t.depth", 10.0, now=t0 + 2.0)
    assert (wg["min"], wg["max"], wg["last"]) == (2.0, 6.0, 6.0)
    assert wg["mean"] == pytest.approx(4.0)
    # trailing-window restriction drops the oldest sample
    w1 = ts.window("t.req", 1.5, now=t0 + 2.0)
    assert w1["samples"] == 2 and w1["delta"] == 10.0
    assert ts.window("t.nope", 10.0) is None


def test_window_reset_marker_excludes_straddling_span():
    ts = timeseries.TimeSeries(capacity=16)
    c = telemetry.counter("t.req")
    c.inc(50)
    ts.sample(now=1000.0)
    telemetry.reset()  # counter restarts from zero in a new generation
    c2 = telemetry.counter("t.req")
    c2.inc(5)
    ts.sample(now=1001.0)
    c2.inc(5)
    ts.sample(now=1002.0)
    w = ts.window("t.req", 10.0, now=1002.0)
    assert w["resets"] == 1
    # only the post-reset span counts: 5 over 1 s, never (10-50)/2 s
    assert w["delta"] == 5.0 and w["rate_per_s"] == pytest.approx(5.0)


def test_window_histogram_delta_quantiles():
    ts = timeseries.TimeSeries(capacity=16)
    h = telemetry.histogram("t.lat")
    for _ in range(20):
        h.observe(100.0)
    ts.sample(now=1000.0)
    for _ in range(10):
        h.observe(2.0)
    ts.sample(now=1002.0)
    # the full-history quantile would still sit at 100; the windowed
    # delta sees only the 10 fast observations
    w = ts.window("t.lat", 1.5, now=1002.0)
    assert w is None or w["count"] == 0  # single sample: no pairs
    w = ts.window("t.lat", 10.0, now=1002.0)
    assert w["count"] == 10
    assert w["rate_per_s"] == pytest.approx(5.0)
    assert telemetry.quantile_from_snapshot(w["delta"], 0.99) == 2.0


# -- alert rules -------------------------------------------------------------

def test_threshold_and_absence_rules():
    ts = timeseries.TimeSeries(capacity=16)
    g = telemetry.gauge("t.depth")
    c = telemetry.counter("t.beat")
    g.set(2.0)
    c.inc()
    ts.sample(now=1000.0)
    g.set(20.0)
    ts.sample(now=1001.0)  # heartbeat counter stalls here
    thr = alerts.ThresholdRule("deep", "t.depth", field="max", op=">",
                               value=10.0, window_s=30.0)
    firing, info = thr.evaluate(ts, now=1001.0)
    assert firing and info["windows"]["window"]["value"] == 20.0
    absent = alerts.AbsenceRule("stalled", "t.beat", window_s=30.0)
    firing, _ = absent.evaluate(ts, now=1001.0)
    assert firing  # two samples, zero increments
    c.inc()
    ts.sample(now=1002.0)
    firing, _ = absent.evaluate(ts, now=1002.0)
    assert not firing
    missing = alerts.AbsenceRule("gone", "t.never", window_s=30.0)
    assert missing.evaluate(ts, now=1002.0)[0]


def test_burn_rate_fires_and_resolves_with_hysteresis():
    ts = timeseries.TimeSeries(capacity=64)
    telemetry.gauge("serving.slo_ms.mlp").set(5.0)
    lat = telemetry.histogram("serving.request_latency_ms.mlp")
    rej = telemetry.counter("serving.rejected_total.queue_full")
    engine = alerts.AlertEngine(auto_slo_burn=False, rules=[
        alerts.BurnRateRule("burn.mlp", "mlp", objective=0.95,
                            fast_s=2.0, slow_s=8.0, burn=2.0)])
    now = 1000.0

    def tick(n_ok, n_slow, n_shed):
        nonlocal now
        for _ in range(n_ok):
            lat.observe(1.0)
        for _ in range(n_slow):
            lat.observe(50.0)
        rej.inc(n_shed)
        ts.sample(now=now)
        out = engine.evaluate(ts, now=now)
        now += 0.5
        return out

    for _ in range(4):
        assert tick(10, 0, 0) == []
    trans = []
    for _ in range(6):
        trans += tick(2, 8, 10)
    assert [t["state"] for t in trans] == ["firing"]
    fired = trans[0]
    assert fired["rule"] == "burn.mlp" and fired["kind"] == "burn_rate"
    assert fired["windows"]["fast"]["burn"] >= 2.0
    assert fired["windows"]["slow"]["burn"] >= 2.0
    assert engine.firing() == ["burn.mlp"]
    # hysteresis: resolve needs only the FAST window to cool
    trans = []
    for _ in range(8):
        trans += tick(10, 0, 0)
    assert [t["state"] for t in trans] == ["resolved"]
    assert trans[0]["windows"]["fast"]["burn"] < 2.0
    assert engine.firing() == []
    # surfaced: flight alerts ring + health counters
    assert flight_recorder.get_recorder().alerts_recorded() == 2
    snap = telemetry.snapshot()
    assert snap["health.alerts.fired_total"]["value"] == 1.0
    assert snap["health.alerts.resolved_total"]["value"] == 1.0
    assert snap["health.alerts.firing"]["value"] == 0.0


def test_engine_autodiscovers_slo_models():
    ts = timeseries.TimeSeries(capacity=8)
    telemetry.gauge("serving.slo_ms.mlp").set(100.0)
    ts.sample(now=1000.0)
    engine = alerts.AlertEngine()
    engine.evaluate(ts, now=1000.0)
    names = [r.name for r in engine.all_rules()]
    assert names == ["slo_burn.mlp"]


def test_rules_from_env_inline_json_skips_malformed(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_ALERT_RULES", json.dumps([
        {"kind": "threshold", "signal": "t.depth", "field": "max",
         "op": ">", "value": 12, "window_s": 30},
        {"kind": "nonsense"},
        {"kind": "burn_rate", "model": "mlp", "burn": 3.5},
    ]))
    rules = alerts.rules_from_env()
    assert [r.kind for r in rules] == ["threshold", "burn_rate"]
    assert rules[0].name == "threshold.t.depth"
    assert rules[1].burn == 3.5
    monkeypatch.setenv("MXNET_TPU_ALERT_RULES", "not json")
    assert alerts.rules_from_env() == []


# -- sampler lifecycle -------------------------------------------------------

def test_sampler_off_by_default_and_env_start_stop(monkeypatch):
    assert timeseries.ensure_sampler() is None
    assert timeseries.current_sampler() is None
    assert len(timeseries.get_timeseries()) == 0
    monkeypatch.setenv("MXNET_TPU_TS_INTERVAL_S", "0.02")
    sampler = timeseries.ensure_sampler()
    assert sampler is not None and sampler.alive
    assert timeseries.ensure_sampler() is sampler  # idempotent
    names = [t.name for t in threads.live_package_threads()]
    assert "mxnet_tpu/timeseries/sampler" in names
    deadline = time.monotonic() + 5.0
    while len(timeseries.get_timeseries()) < 3 \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(timeseries.get_timeseries()) >= 3
    timeseries.stop_sampler()
    assert not sampler.alive
    assert timeseries.current_sampler() is None


def test_sampler_malformed_interval_warns_off(monkeypatch, caplog):
    monkeypatch.setenv("MXNET_TPU_TS_INTERVAL_S", "soon")
    with caplog.at_level("WARNING"):
        assert timeseries.ensure_sampler() is None
    assert "MXNET_TPU_TS_INTERVAL_S" in caplog.text


def test_sampler_clean_under_locksan(monkeypatch, tmp_path):
    from mxnet_tpu.analysis import locksan
    monkeypatch.setenv("MXNET_TPU_LOCKSAN", "1")
    monkeypatch.delenv("MXNET_TPU_LOCKSAN_RULES", raising=False)
    locksan.reset()
    try:
        monkeypatch.setenv("MXNET_TPU_TS_INTERVAL_S", "0.02")
        telemetry.gauge("serving.slo_ms.mlp").set(100.0)
        h = telemetry.histogram("serving.request_latency_ms.mlp")
        sampler = timeseries.start_sampler(ship_dir=str(tmp_path))
        deadline = time.monotonic() + 5.0
        while len(timeseries.get_timeseries()) < 4 \
                and time.monotonic() < deadline:
            h.observe(1.0)
            time.sleep(0.01)
        timeseries.stop_sampler()
        assert not sampler.alive
        assert locksan.violations() == []
    finally:
        locksan.reset()


# -- shipper + fleet merge ---------------------------------------------------

_CHILD = r"""
import os, sys, time
sys.path.insert(0, %(repo)r)
os.environ["MXNET_TPU_TELEMETRY"] = "1"
from mxnet_tpu.observability import telemetry, timeseries
c = telemetry.counter("serving.requests_total")
sampler = timeseries.start_sampler(interval=0.02,
                                   ship_dir=%(ship_dir)r)
for _ in range(6):
    c.inc(5)
    time.sleep(0.03)
timeseries.stop_sampler()
"""


def test_fleet_shipper_merges_processes(tmp_path):
    """Two subprocesses + the parent ship to one dir keyed to the
    parent's trace root; the merged dash is monotonic per source and
    skew-reconciled through the shared epoch."""
    ship_dir = str(tmp_path / "series")
    root, epoch0 = reqtrace.trace_root()
    env = dict(os.environ)
    env["MXNET_TPU_REQTRACE_CTX"] = os.environ["MXNET_TPU_REQTRACE_CTX"]
    env.setdefault("JAX_PLATFORMS", "cpu")
    script = _CHILD % {"repo": REPO, "ship_dir": ship_dir}
    procs = [subprocess.Popen([sys.executable, "-c", script], env=env,
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE)
             for _ in range(2)]

    c = telemetry.counter("serving.requests_total")
    sampler = timeseries.start_sampler(interval=0.02, ship_dir=ship_dir)
    for _ in range(6):
        c.inc(5)
        time.sleep(0.03)
    timeseries.stop_sampler()
    assert not sampler.alive
    for p in procs:
        _, err = p.communicate(timeout=120)
        assert p.returncode == 0, err.decode()

    tv = _load_traceview()
    sources = tv.dash_sources(ship_dir)
    assert len(sources) == 3
    pids = set()
    for src in sources:
        # every source keyed to the PARENT's env-propagated root, with
        # the parent's epoch (wall-clock skew reconciled via `rel`)
        assert src["fleet"]["root"] == root
        assert src["fleet"]["epoch0"] == pytest.approx(epoch0, abs=0.01)
        pids.add(src["fleet"]["pid"])
        rels = [s["rel"] for s in src["samples"]]
        assert rels == sorted(rels)  # monotonic per source
        assert len(src["samples"]) >= 3
    assert len(pids) == 3
    stats = tv.dash_stats(sources)
    assert stats["roots"] == [root]
    # 3 processes x 6 ticks x 5 increments, minus each process's
    # pre-first-sample increments (absent-before pairs count from the
    # sample's value, so only sub-interval timing trims the total)
    assert stats["req_total"] >= 45.0
    assert stats["bins"] >= 1 and sum(stats["req_rate"]) > 0


def test_shipper_writes_header_and_filters_prefixes(tmp_path):
    telemetry.counter("serving.requests_total").inc(2)
    telemetry.counter("internal.cache_hits").inc(9)
    ship = shipper.SeriesShipper(dirpath=str(tmp_path))
    ts = timeseries.TimeSeries(capacity=8)
    ship.ship(ts.sample(now=1000.0))
    ship.close()
    files = sorted(os.listdir(str(tmp_path)))
    assert files == ["series_%d.jsonl" % os.getpid()]
    with open(str(tmp_path / files[0])) as f:
        lines = [json.loads(ln) for ln in f]
    assert lines[0]["kind"] == "header"
    assert lines[0]["fleet"]["pid"] == os.getpid()
    series = lines[1]["series"]
    assert "serving.requests_total" in series
    assert "internal.cache_hits" not in series  # not a shipped prefix
    assert lines[1]["rel"] == pytest.approx(
        1000.0 - lines[0]["fleet"]["epoch0"])


def test_default_ship_dir_derives_from_trace_root(monkeypatch):
    root, _ = reqtrace.trace_root()
    d = shipper.default_dir()
    assert d.endswith("mxnet_tpu_ts_" + root)


# -- traceview rendering -----------------------------------------------------

def test_traceview_alerts_from_flight_dump(tmp_path):
    ts = timeseries.TimeSeries(capacity=16)
    g = telemetry.gauge("t.depth")
    engine = alerts.AlertEngine(auto_slo_burn=False, rules=[
        alerts.ThresholdRule("deep", "t.depth", field="max", op=">",
                             value=10.0, window_s=30.0)])
    g.set(2.0)
    ts.sample(now=1000.0)
    engine.evaluate(ts, now=1000.0)
    g.set(20.0)
    ts.sample(now=1001.0)
    engine.evaluate(ts, now=1001.0)
    g.set(1.0)
    ts.sample(now=1040.0)  # the spike ages out of the window
    engine.evaluate(ts, now=1040.0)
    dump = str(tmp_path / "flight.json")
    flight_recorder.get_recorder().dump(dump)
    tv = _load_traceview()
    with open(dump) as f:
        records = tv.alert_records(json.load(f))
    stats = tv.alerts_stats(records)
    assert stats["rules"]["deep"] == {"fired": 1, "resolved": 1,
                                      "last": "resolved"}
    assert tv.main(["--alerts", dump]) == 0
    assert tv.main(["--alerts", str(tmp_path / "flight.json")]) == 0
    empty = str(tmp_path / "empty.json")
    with open(empty, "w") as f:
        json.dump({"alerts": []}, f)
    assert tv.main(["--alerts", empty]) == 2


def test_traceview_requests_since_filter(tmp_path):
    def req(t0):
        return {"t0": t0, "model": "mlp", "request_id": "r%g" % t0,
                "total_ms": 1.0,
                "segments": [{"name": "dispatch", "t0_ms": 0.0,
                              "dur_ms": 1.0}]}
    doc = {"requests": [req(10.0), req(99.0)],
           "requests_sampled": [req(5.0)]}
    tv = _load_traceview()
    kept = tv.filter_since(doc, 10.0)
    assert [r["t0"] for r in kept["requests"]] == [99.0]
    assert kept["requests_sampled"] == []
    # --since filtering everything out exits 2 like an empty dump
    p = str(tmp_path / "reqs.json")
    with open(p, "w") as f:
        json.dump(doc, f)
    assert tv.main(["--requests", p, "--since", "10"]) == 0
    with open(p, "w") as f:
        json.dump({"requests": [req(10.0)]}, f)
    assert tv.main(["--requests", p, "--since", "0"]) == 0
