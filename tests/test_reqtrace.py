"""End-to-end request tracing (observability/reqtrace.py).

Pins its contracts:

- a served request owns a CONTIGUOUS typed waterfall (queue ->
  assemble -> dispatch -> split on the single-process path; + route and
  lane hops on the fleet path, with the router's candidate scoring
  recorded);
- tail capture is exhaustive: SLO breaches, typed rejections (submit-
  time AND queued-stage), and quarantined-replica rides are pinned
  into the flight recorder's ``requests`` ring regardless of the
  head-sampling draw;
- the sampled ring honors BOTH its entry cap and its byte cap;
- ``MXNET_TPU_REQTRACE=0`` disables everything: a 2-replica fleet run
  is bitwise-identical (responses AND exec-cache trace counters) to an
  instrumented one — the PR 3 on/off contract extended to the fleet
  path;
- rejected-while-queued requests record their accrued wait into
  ``serving.queue_ms`` (the shed-bias fix);
- continuous-decode streams carry per-iteration segments;
- dumps round-trip through ``traceview --requests`` / ``--fleet``.
"""
import importlib.util
import json
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import executor_cache, serving
from mxnet_tpu.observability import flight_recorder, reqtrace, telemetry

rng = np.random.RandomState(5)

FEAT = 6


@pytest.fixture(autouse=True)
def _isolate_reqtrace_env(monkeypatch):
    """Fresh tracer per test: no ambient rate/ring/root leaks between
    tests (or from an operator shell)."""
    for var in ("MXNET_TPU_SERVING_DEFAULT_DEADLINE_MS",
                "MXNET_TPU_SERVING_QUEUE_DEPTH",
                "MXNET_TPU_SERVING_REPLICAS",
                "MXNET_TPU_SERVING_SLO_MS",
                "MXNET_TPU_AUTOTUNE_EVERY_S",
                "MXNET_TPU_REQTRACE",
                "MXNET_TPU_REQTRACE_RING",
                "MXNET_TPU_REQTRACE_RING_BYTES",
                "MXNET_TPU_REQTRACE_PINNED",
                "MXNET_TPU_REQTRACE_CTX"):
        monkeypatch.delenv(var, raising=False)
    reqtrace.reset()
    yield
    reqtrace.reset()


def _mlp_parts(nh=8, classes=3, seed=11):
    r = np.random.RandomState(seed)
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=nh,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc2")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    arg_shapes, _, _ = sym.infer_shape(data=(1, FEAT))
    args = {n: mx.nd.array(r.normal(0, 0.1, s).astype(np.float32))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}
    return sym, args


def _load_traceview():
    tv_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "traceview.py")
    spec = importlib.util.spec_from_file_location("_reqtrace_traceview",
                                                  tv_path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# -- context/core ----------------------------------------------------------

def test_mint_off_returns_none(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_REQTRACE", "0")
    assert reqtrace.mint("m") is None
    assert not reqtrace.enabled()
    # finish/finish_rejected are None-safe (the guard every call site
    # relies on)
    assert reqtrace.finish(None) is None
    assert reqtrace.finish_rejected(None, ValueError("x")) is None


def test_head_sampling_rate(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_REQTRACE", "4")
    ctxs = [reqtrace.mint("m") for _ in range(8)]
    assert sum(1 for c in ctxs if c.sampled) == 2  # seq 0 and 4
    # every context exists (tail capture needs the journey even for
    # unsampled requests); only the draw differs
    assert all(c is not None for c in ctxs)


def test_malformed_rate_falls_back(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_REQTRACE", "banana")
    assert reqtrace.rate() == reqtrace.DEFAULT_RATE


def test_finish_is_idempotent(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_REQTRACE", "1")
    ctx = reqtrace.mint("m", rows=1)
    assert reqtrace.finish(ctx, status="ok") is not None
    assert reqtrace.finish(ctx, status="ok") is None
    assert reqtrace.stats()["finished"] == 1


def test_slo_breach_pins(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_REQTRACE", "1000000")  # never sampled..
    ctx = reqtrace.mint("m", rows=1, slo_ms=0.0001)
    ctx2 = reqtrace.mint("m", rows=1, slo_ms=1e9)
    time.sleep(0.002)
    rec = reqtrace.finish(ctx, status="ok")
    rec2 = reqtrace.finish(ctx2, status="ok")
    assert rec["pinned"] == "slo_breach"       # ..but breaches pin
    assert "pinned" not in rec2
    pinned = reqtrace.pinned_snapshot()
    assert [r["trace_id"] for r in pinned] == [ctx.trace_id]


def test_explicit_pin_wins(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_REQTRACE", "1")
    ctx = reqtrace.mint("m")
    ctx.pin("quarantined_replica")
    ctx.pin("something_else")  # first reason sticks
    rec = reqtrace.finish(ctx, status="ok")
    assert rec["pinned"] == "quarantined_replica"


def test_segment_cap_counts_drops(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_REQTRACE", "1")
    ctx = reqtrace.mint("m")
    now = time.monotonic()
    for i in range(reqtrace.MAX_SEGMENTS + 7):
        ctx.seg("decode_step", now, now, iteration=i)
    rec = reqtrace.finish(ctx, status="ok")
    assert len(rec["segments"]) == reqtrace.MAX_SEGMENTS
    assert rec["segments_dropped"] == 7


def test_sampled_ring_honors_entry_and_byte_caps(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_REQTRACE", "1")
    monkeypatch.setenv("MXNET_TPU_REQTRACE_RING", "5")
    for _ in range(12):
        reqtrace.finish(reqtrace.mint("m", rows=1), status="ok")
    stats = reqtrace.stats()
    assert stats["sampled"] == 5
    assert stats["sampled_dropped"] == 7
    # byte cap binds tighter than the entry cap
    reqtrace.reset()
    monkeypatch.setenv("MXNET_TPU_REQTRACE_RING", "1000")
    one = len(json.dumps(reqtrace.finish(reqtrace.mint("m", rows=1),
                                         status="ok")))
    reqtrace.reset()
    monkeypatch.setenv("MXNET_TPU_REQTRACE_RING_BYTES", str(3 * one))
    for _ in range(10):
        reqtrace.finish(reqtrace.mint("m", rows=1), status="ok")
    stats = reqtrace.stats()
    assert stats["sampled_bytes"] <= 3 * one
    assert stats["sampled"] < 10


def test_pinned_ring_bounded(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_REQTRACE", "1")
    monkeypatch.setenv("MXNET_TPU_REQTRACE_PINNED", "4")
    for i in range(9):
        ctx = reqtrace.mint("m", rows=1)
        reqtrace.finish_rejected(ctx, serving.Overloaded("full"))
    pinned = reqtrace.pinned_snapshot()
    assert len(pinned) == 4  # oldest evicted, newest kept
    assert all(r["reason"] == "overloaded" for r in pinned)


def test_trace_root_propagates_via_env(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_REQTRACE", "1")
    root, epoch0 = reqtrace.trace_root()
    # written back for subprocess inheritance
    raw = os.environ["MXNET_TPU_REQTRACE_CTX"]
    assert raw.startswith(root + ":")
    # a "child" (fresh tracer state, same env) adopts the SAME root
    reqtrace.reset()
    root2, epoch2 = reqtrace.trace_root()
    assert (root2, round(epoch2, 3)) == (root, round(epoch0, 3))


# -- serving integration ----------------------------------------------------

def test_served_request_waterfall_and_sampling(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_REQTRACE", "1")
    sym, args = _mlp_parts()
    srv = serving.Server(max_batch_size=4, batch_window_ms=0.5)
    try:
        srv.add_model("mlp", sym, args, input_shapes={"data": (FEAT,)},
                      slo_ms=60000.0)
        srv.warmup()
        out = srv.submit("mlp",
                         {"data": rng.rand(2, FEAT).astype(np.float32)})
        assert out[0].shape[0] == 2
    finally:
        srv.close()
    recs = reqtrace.sampled_snapshot()
    assert len(recs) == 1
    rec = recs[0]
    assert rec["status"] == "ok" and rec["model"] == "mlp"
    assert rec["rows"] == 2 and rec["bucket"] == 2
    assert rec["slo_ms"] == 60000.0
    names = [s["name"] for s in rec["segments"]]
    assert names == ["queue", "assemble", "dispatch", "split"]
    # contiguous, ordered offsets; durations sum close to the total
    offs = [s["t0_ms"] for s in rec["segments"]]
    assert offs == sorted(offs)
    covered = sum(s["dur_ms"] for s in rec["segments"])
    assert covered <= rec["total_ms"]
    assert covered >= 0.5 * rec["total_ms"]
    asm = rec["segments"][1]
    assert asm["bucket"] == 2 and asm["cobatched"] == 1 \
        and asm["padded_rows"] == 0


def test_fleet_waterfall_has_route_and_lane(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_REQTRACE", "1")
    sym, args = _mlp_parts()
    fleet = serving.FleetServer(n_replicas=2, max_batch_size=4,
                                batch_window_ms=0.5)
    try:
        fleet.add_model("mlp", sym, args,
                        input_shapes={"data": (FEAT,)})
        fleet.warmup()
        srv_out = fleet.submit(
            "mlp", {"data": rng.rand(1, FEAT).astype(np.float32)})
        assert srv_out
    finally:
        fleet.close()
    rec = reqtrace.sampled_snapshot()[0]
    names = [s["name"] for s in rec["segments"]]
    assert names == ["queue", "route", "lane", "assemble", "dispatch",
                     "split"]
    route = rec["segments"][1]
    assert route["winner"] in (0, 1)
    assert len(route["candidates"]) == 2  # both replicas scored
    assert {c["replica"] for c in route["candidates"]} == {0, 1}
    lane = rec["segments"][2]
    assert lane["replica"] == route["winner"]
    assert rec["replica"] == route["winner"]


def test_submit_time_rejection_pins(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_REQTRACE", "1000000")
    sym, args = _mlp_parts()
    srv = serving.Server(max_batch_size=4)
    try:
        srv.add_model("mlp", sym, args, input_shapes={"data": (FEAT,)})
        srv.warmup()
        with pytest.raises(serving.RequestTooLarge):
            srv.submit("mlp",
                       {"data": rng.rand(64, FEAT).astype(np.float32)})
        with pytest.raises(serving.ModelNotFound):
            srv.submit("nope", {"data": rng.rand(1, FEAT)})
    finally:
        srv.close()
    pinned = reqtrace.pinned_snapshot()
    assert [r["reason"] for r in pinned] == ["request_too_large",
                                             "model_not_found"]
    assert all(r["status"] == "rejected" and r["pinned"] == "rejected"
               and r["segments"][-1]["name"] == "reject"
               for r in pinned)


def test_queued_deadline_rejection_pins_and_feeds_queue_ms(monkeypatch):
    """The satellite fix: a DeadlineExceeded shed records its accrued
    wait into serving.queue_ms (only-served-requests bias), and its
    trace pins with the queue segment."""
    monkeypatch.setenv("MXNET_TPU_REQTRACE", "1000000")
    telemetry.reset()
    sym, args = _mlp_parts()
    srv = serving.Server(max_batch_size=4, batch_window_ms=1.0,
                         auto_start=False)
    try:
        srv.add_model("mlp", sym, args, input_shapes={"data": (FEAT,)})
        srv.warmup()
        # batcher NOT started: the request expires while queued
        fut = srv.submit_async(
            "mlp", {"data": rng.rand(1, FEAT).astype(np.float32)},
            deadline_ms=15.0)
        time.sleep(0.05)
        srv.start()
        with pytest.raises(serving.DeadlineExceeded):
            fut.result(timeout=10)
    finally:
        srv.close()
    pinned = reqtrace.pinned_snapshot()
    assert len(pinned) == 1
    rec = pinned[0]
    assert rec["reason"] == "deadline_exceeded"
    names = [s["name"] for s in rec["segments"]]
    assert names == ["queue", "reject"]
    assert rec["segments"][0]["dur_ms"] >= 15.0
    snap = telemetry.snapshot().get("serving.queue_ms", {})
    assert snap.get("count", 0) == 1  # the SHED request fed it
    assert snap.get("min", 0) >= 15.0


def test_quarantined_replica_ride_pins(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_REQTRACE", "1000000")
    sym, args = _mlp_parts()
    fleet = serving.FleetServer(n_replicas=2, max_batch_size=4,
                                batch_window_ms=0.5)
    try:
        fleet.add_model("mlp", sym, args,
                        input_shapes={"data": (FEAT,)})
        fleet.warmup()
        # poison replica 0's model twin so its next dispatch throws
        bad = fleet.group.replicas[0].registry.get("mlp")
        orig = bad.run_batch

        def _boom(bucket, inputs):
            raise RuntimeError("injected replica failure")

        bad.run_batch = _boom
        failures, served = 0, 0
        for _ in range(8):
            try:
                fleet.submit("mlp",
                             {"data": rng.rand(1, FEAT)
                              .astype(np.float32)}, timeout=30)
                served += 1
            except Exception:
                failures += 1
        bad.run_batch = orig
        assert failures >= 1 and served >= 1
        assert not fleet.group.replicas[0].healthy
    finally:
        fleet.close()
    pinned = reqtrace.pinned_snapshot()
    rides = [r for r in pinned
             if r.get("pinned") == "quarantined_replica"]
    assert rides, pinned
    # the felled batch's requests carry the quarantine pin on top of
    # their typed dispatch error
    assert any(r["status"] == "rejected" for r in rides)


def test_continuous_stream_segments(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_REQTRACE", "1")
    data = mx.sym.Variable("data")
    state = mx.sym.Variable("state")
    nxt = data + state
    sym = mx.sym.Group([2.0 * nxt, nxt])
    cb = serving.ContinuousBatcher(
        sym, {}, input_shapes={"data": (3,)},
        state_shapes={"state": (3,)}, state_pairs=[("state", 1)],
        slot_count=4, name="toy_decode")
    cb.warmup()
    s = cb.submit({"data": rng.rand(5, 3).astype(np.float32)})
    cb.drain()
    s.wait(timeout=10)
    recs = reqtrace.sampled_snapshot()
    assert len(recs) == 1
    rec = recs[0]
    assert rec["kind"] == "stream" and rec["model"] == "toy_decode"
    assert rec["status"] == "ok" and rec["steps"] == 5
    names = [s_["name"] for s_ in rec["segments"]]
    assert names[0] == "queue"
    decode = [s_ for s_ in rec["segments"] if s_["name"] == "decode_step"]
    assert len(decode) == 5
    assert decode[0]["slot"] == rec["segments"][0]["slot"]
    assert all(d["active"] >= 1 for d in decode)
    cb.close()


def test_closed_stream_pins(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_REQTRACE", "1000000")
    data = mx.sym.Variable("data")
    state = mx.sym.Variable("state")
    nxt = data + state
    sym = mx.sym.Group([2.0 * nxt, nxt])
    cb = serving.ContinuousBatcher(
        sym, {}, input_shapes={"data": (3,)},
        state_shapes={"state": (3,)}, state_pairs=[("state", 1)],
        slot_count=2)
    cb.warmup()
    cb.submit({"data": rng.rand(4, 3).astype(np.float32)})
    cb.step()
    cb.close()  # one step decoded, three to go -> stream fails typed
    pinned = reqtrace.pinned_snapshot()
    assert len(pinned) == 1 and pinned[0]["status"] == "rejected"
    # a submit refused on the closed batcher is a typed rejection too:
    # its context closes (tail-captured), never leaks unfinished
    with pytest.raises(mx.MXNetError):
        cb.submit({"data": rng.rand(2, 3).astype(np.float32)})
    stats = reqtrace.stats()
    assert stats["minted"] == stats["finished"] == 2
    assert len(reqtrace.pinned_snapshot()) == 2


# -- the on/off fleet contract (satellite regression) -----------------------

def _fleet_traffic_run(n=24):
    """One deterministic 2-replica fleet pass; returns (responses,
    trace-counter delta)."""
    sym, args = _mlp_parts(seed=23)
    r = np.random.RandomState(42)
    payloads = [r.rand(1 + (i % 4), FEAT).astype(np.float32)
                for i in range(n)]
    fleet = serving.FleetServer(n_replicas=2, max_batch_size=8,
                                batch_window_ms=0.5)
    try:
        fleet.add_model("mlp", sym, args,
                        input_shapes={"data": (FEAT,)})
        fleet.warmup()
        with executor_cache.watch_traces() as watch:
            futs = [fleet.submit_async("mlp", {"data": p})
                    for p in payloads]
            outs = [f.result(timeout=60) for f in futs]
        return [o[0].tobytes() for o in outs], watch.total()
    finally:
        fleet.close()


def test_fleet_bitwise_identical_with_tracing_off_vs_on(monkeypatch):
    """The PR 3 on/off contract extended to the fleet path:
    MXNET_TPU_TELEMETRY=0 + reqtrace off serves bitwise-identical
    responses with identical exec-cache trace counters vs fully
    instrumented."""
    monkeypatch.setenv("MXNET_TPU_TELEMETRY", "0")
    monkeypatch.setenv("MXNET_TPU_REQTRACE", "0")
    telemetry.reset()
    off_bytes, off_traces = _fleet_traffic_run()
    assert reqtrace.stats()["minted"] == 0  # truly off

    monkeypatch.setenv("MXNET_TPU_TELEMETRY", "1")
    monkeypatch.setenv("MXNET_TPU_REQTRACE", "1")
    telemetry.reset()
    on_bytes, on_traces = _fleet_traffic_run()
    assert reqtrace.stats()["minted"] > 0

    assert off_traces == on_traces == 0  # warm fleet: no retraces at all
    assert off_bytes == on_bytes  # bitwise, response for response


# -- dumps + traceview ------------------------------------------------------

def test_flight_dump_embeds_requests_rings(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_TPU_REQTRACE", "1")
    reqtrace.finish(reqtrace.mint("m", rows=1), status="ok")
    reqtrace.finish_rejected(reqtrace.mint("m", rows=1),
                             serving.Overloaded("full"))
    path = flight_recorder.dump(path=str(tmp_path / "fl.json"),
                                reason="test")
    with open(path) as f:
        doc = json.load(f)
    assert len(doc["requests"]) == 1
    assert doc["requests"][0]["reason"] == "overloaded"
    assert len(doc["requests_sampled"]) == 1
    assert doc["fleet"]["root"] == reqtrace.fleet_header()["root"]
    # no internal byte-accounting field leaks into the dump
    assert "_bytes" not in doc["requests_sampled"][0]


def test_traceview_requests_and_fleet_views(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_TPU_REQTRACE", "1")
    sym, args = _mlp_parts()
    srv = serving.Server(max_batch_size=4, batch_window_ms=0.5)
    try:
        srv.add_model("mlp", sym, args, input_shapes={"data": (FEAT,)},
                      slo_ms=0.001)  # everything breaches -> pins
        srv.warmup()
        for _ in range(4):
            srv.submit("mlp",
                       {"data": rng.rand(1, FEAT).astype(np.float32)})
    finally:
        srv.close()
    fdir = tmp_path / "fleet"
    fdir.mkdir()
    reqtrace.dump(str(fdir / "worker.json"))
    flight_recorder.dump(path=str(fdir / "flight.json"), reason="test")
    (fdir / "not_json.json").write_text("{not json")  # skipped, not fatal

    tv = _load_traceview()
    with open(str(fdir / "flight.json")) as f:
        doc = json.load(f)
    pinned, sampled = tv.request_records(doc)
    assert len(pinned) == 4
    stats = tv.requests_stats(pinned, sampled)
    assert stats["by_pin_reason"] == {"slo_breach": 4}
    row = stats["models"][0]
    assert row["model"] == "mlp" and row["coverage"] > 0.5
    assert abs(sum(row["shares"].values()) - row["coverage"]) < 1e-9
    rendered = tv.summarize_requests(doc)
    assert "p99 attribution" in rendered and "PINNED=slo_breach" \
        in rendered
    assert tv.main(["--requests", str(fdir / "flight.json")]) == 0

    fstats = tv.fleet_stats(tv.fleet_sources(str(fdir)))
    assert len(fstats["sources"]) == 2  # the corrupt file was skipped
    assert len(fstats["roots"]) == 1
    assert tv.main(["--fleet", str(fdir)]) == 0

    # empty inputs exit 2 (the no-records contract)
    empty = tmp_path / "empty.json"
    empty.write_text("{}")
    assert tv.main(["--requests", str(empty)]) == 2
    edir = tmp_path / "edir"
    edir.mkdir()
    assert tv.main(["--fleet", str(edir)]) == 2


def test_traceview_interpolated_quantiles(monkeypatch):
    """The satellite: --serving quantiles interpolate inside the log2
    bucket (clamped to min/max) instead of reporting the bucket upper
    bound, matching telemetry.quantile_from_snapshot."""
    from mxnet_tpu.observability.telemetry import (Histogram,
                                                   quantile_from_snapshot)
    tv = _load_traceview()
    h = Histogram("t")
    for v in (100.0,) * 50:  # single-valued: every quantile exact
        h.observe(v)
    snap = h._snapshot()
    assert tv._hist_quantile(snap, 0.99) == 100.0  # old answer: 128.0
    assert tv._hist_quantile(snap, 0.5) == 100.0
    h2 = Histogram("t2")
    for v in range(1, 101):
        h2.observe(float(v))
    snap2 = h2._snapshot()
    for q in (0.5, 0.95, 0.99):
        assert tv._hist_quantile(snap2, q) == pytest.approx(
            quantile_from_snapshot(snap2, q))
        # strictly inside the holding bucket, not its upper bound
    assert tv._hist_quantile(snap2, 0.99) < 128.0
