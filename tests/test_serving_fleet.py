"""mxnet_tpu.serving fleet tier — replica groups, router, continuous
batching, SLO plumbing.

Pins its contracts:

- weighted least-loaded routing actually shifts load away from a slow
  replica (injected latency skew);
- every routed response is bitwise-equal to a plain serverless
  ``Predictor`` replay at its recorded dispatch bucket, REGARDLESS of
  which replica served it;
- a replica that throws is quarantined and drained — its queued work
  re-routes, the server survives, and only a fully-quarantined group
  fails requests (typed ``NoHealthyReplica``);
- the continuous batcher decodes streams that join/leave mid-flight
  with ZERO retraces, each stream bitwise-equal to decoding it alone;
- overload shedding is typed ``Overloaded``;
- the serving-loop autotune cadence (``MXNET_TPU_AUTOTUNE_EVERY_S``)
  runs the ServingBucketTuner and stages bucket sets onto EVERY
  replica for the next warmup boundary.
"""
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import executor_cache, serving
from mxnet_tpu.observability import telemetry
from mxnet_tpu.predict import Predictor
from mxnet_tpu.rnn import rnn_cell

rng = np.random.RandomState(7)

FEAT = 6


@pytest.fixture(autouse=True)
def _isolate_serving_env(monkeypatch):
    """Deadlines/queue depth/cadence are constructed explicitly per
    test; ambient operator defaults would change behavior."""
    monkeypatch.delenv("MXNET_TPU_SERVING_DEFAULT_DEADLINE_MS",
                       raising=False)
    monkeypatch.delenv("MXNET_TPU_SERVING_QUEUE_DEPTH", raising=False)
    monkeypatch.delenv("MXNET_TPU_SERVING_REPLICAS", raising=False)
    monkeypatch.delenv("MXNET_TPU_SERVING_SLOT_COUNT", raising=False)
    monkeypatch.delenv("MXNET_TPU_SERVING_SLO_MS", raising=False)
    monkeypatch.delenv("MXNET_TPU_AUTOTUNE_EVERY_S", raising=False)
    monkeypatch.delenv("MXNET_TPU_AUTOTUNE", raising=False)


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


def _fleet(n_replicas=2, max_batch_size=8, **kw):
    fleet = serving.FleetServer(n_replicas=n_replicas,
                                max_batch_size=max_batch_size,
                                batch_window_ms=1.0, **kw)
    sym, args = _mlp_parts()
    fleet.add_model("mlp", sym, args, input_shapes={"data": (FEAT,)})
    return fleet, sym, args


# -- routing ---------------------------------------------------------------

def test_fleet_warmup_verifies_and_measures_costs():
    fleet, _, _ = _fleet()
    try:
        report = fleet.warmup()
        assert len(report["replicas"]) == 2
        for rep in fleet.group.replicas:
            for b in fleet.registry.get("mlp").buckets:
                assert rep.bucket_cost_ms[("mlp", b)] > 0.0
        # per-replica report carries the cost table
        for idx in (0, 1):
            costs = report["mlp"]["per_replica"][idx]["bucket_cost_ms"]
            assert set(costs) == {"1", "2", "4", "8"}
    finally:
        fleet.close(drain=True, timeout=30)


def test_fleet_responses_bitwise_equal_serverless_replay():
    """The ISSUE acceptance oracle: whichever replica served it, a
    routed response == a plain Predictor replay at the recorded
    dispatch bucket."""
    fleet, sym, args = _fleet()
    try:
        fleet.warmup()
        payloads = [rng.rand(1 + i % 3, FEAT).astype(np.float32)
                    for i in range(24)]
        with executor_cache.watch_traces() as w:
            futs = [fleet.submit_async("mlp", {"data": p})
                    for p in payloads]
            results = [f.result(timeout=30) for f in futs]
        assert w.total() == 0, w.delta()
        blob = {"arg:%s" % k: v for k, v in args.items()}
        oracles = {}
        for p, f, outs in zip(payloads, futs, results):
            b = f.request.dispatch_bucket
            assert b is not None
            oracle = oracles.get(b)
            if oracle is None:
                oracle = oracles[b] = Predictor(sym.tojson(), blob,
                                                {"data": (b, FEAT)})
            solo = np.zeros((b, FEAT), np.float32)
            solo[:p.shape[0]] = p
            oracle.forward(data=solo)
            want = oracle.get_output(0).asnumpy()[:p.shape[0]]
            assert np.array_equal(outs[0], want)
    finally:
        fleet.close(drain=True, timeout=30)


def test_least_loaded_routing_shifts_load_off_slow_replica():
    """Injected latency skew: replica 0 serves each batch 30 ms slower;
    the outstanding-cost router must route most groups to replica 1."""
    fleet, _, _ = _fleet()
    try:
        fleet.warmup()
        slow_model = fleet.group.replicas[0].registry.get("mlp")
        orig = slow_model.run_batch

        def sluggish(bucket, inputs):
            time.sleep(0.03)
            return orig(bucket, inputs)

        slow_model.run_batch = sluggish
        # full-bucket payloads (one group per request, so routing
        # decisions are per request), PACED a few ms apart: load
        # balancing is feedback — the router can only see a slow
        # replica's backlog once the clock has run, so an instantaneous
        # burst would be routed on estimates alone
        futs = []
        for _ in range(12):
            futs.append(fleet.submit_async(
                "mlp", {"data": rng.rand(8, FEAT).astype(np.float32)}))
            time.sleep(0.005)
        for f in futs:
            f.result(timeout=30)
        r0, r1 = fleet.group.replicas
        assert r1.dispatches > r0.dispatches, (
            "slow replica 0 got %d of %d dispatches"
            % (r0.dispatches, r0.dispatches + r1.dispatches))
        assert r0.dispatches + r1.dispatches == 12
    finally:
        fleet.close(drain=True, timeout=30)


def test_replica_quarantine_drains_not_the_server():
    """A throwing replica is quarantined; its queued work re-routes;
    later traffic is served by the survivors."""
    telemetry.reset()
    fleet, _, _ = _fleet()
    try:
        fleet.warmup()
        bad_model = fleet.group.replicas[0].registry.get("mlp")

        def explode(bucket, inputs):
            raise RuntimeError("induced replica failure")

        bad_model.run_batch = explode
        payloads = [rng.rand(8, FEAT).astype(np.float32)
                    for _ in range(10)]
        futs = [fleet.submit_async("mlp", {"data": p}) for p in payloads]
        failed = served = 0
        for f in futs:
            try:
                f.result(timeout=30)
                served += 1
            except RuntimeError:
                failed += 1
        assert failed >= 1 and served >= 1
        assert failed + served == 10
        r0, r1 = fleet.group.replicas
        assert not r0.healthy and r0.quarantine_error is not None
        assert r1.healthy
        # the server survives: fresh traffic lands on the survivor
        out = fleet.submit("mlp", {"data": payloads[0]}, timeout=30)
        assert out[0].shape == (8, 3)
        snap = telemetry.snapshot()
        assert snap.get("serving.replica_quarantined",
                        {}).get("value", 0) >= 1
    finally:
        fleet.close(drain=True, timeout=30)


def test_fully_quarantined_group_rejects_typed():
    fleet = serving.FleetServer(n_replicas=1, max_batch_size=4,
                                batch_window_ms=1.0)
    sym, args = _mlp_parts()
    fleet.add_model("mlp", sym, args, input_shapes={"data": (FEAT,)})
    try:
        fleet.warmup()
        model = fleet.group.replicas[0].registry.get("mlp")
        model.run_batch = lambda bucket, inputs: (_ for _ in ()).throw(
            RuntimeError("dead replica"))
        doomed = fleet.submit_async(
            "mlp", {"data": rng.rand(2, FEAT).astype(np.float32)})
        with pytest.raises(RuntimeError):
            doomed.result(timeout=30)
        assert not fleet.group.replicas[0].healthy
        # every later request fails TYPED — the group has nowhere to run
        after = fleet.submit_async(
            "mlp", {"data": rng.rand(2, FEAT).astype(np.float32)})
        with pytest.raises(serving.NoHealthyReplica):
            after.result(timeout=30)
    finally:
        fleet.close(drain=True, timeout=30)


def test_overload_shedding_is_typed_overloaded():
    """The SLO harness's shedding contract in miniature: a full
    admission queue rejects with typed Overloaded at submit time."""
    telemetry.reset()
    fleet, _, _ = _fleet(queue_depth=2, auto_start=False)
    try:
        queued = [fleet.submit_async(
            "mlp", {"data": rng.rand(1, FEAT).astype(np.float32)})
            for _ in range(2)]
        with pytest.raises(serving.Overloaded):
            fleet.submit_async(
                "mlp", {"data": rng.rand(1, FEAT).astype(np.float32)})
        snap = telemetry.snapshot()
        assert snap.get("serving.rejected_total.overloaded",
                        {}).get("value", 0) >= 1
        fleet.start()
        for f in queued:
            f.result(timeout=30)
    finally:
        fleet.close(drain=True, timeout=30)


def test_fleet_add_model_refuses_ctx():
    fleet = serving.FleetServer(n_replicas=2)
    sym, args = _mlp_parts()
    try:
        with pytest.raises(mx.base.MXNetError):
            fleet.add_model("mlp", sym, args,
                            input_shapes={"data": (FEAT,)}, ctx=mx.cpu())
    finally:
        fleet.close(drain=True, timeout=5)


def test_default_replicas_env(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_SERVING_REPLICAS", "3")
    assert serving.default_replicas() == 3
    monkeypatch.setenv("MXNET_TPU_SERVING_REPLICAS", "bogus")
    assert serving.default_replicas() == 1
    monkeypatch.setenv("MXNET_TPU_SERVING_SLOT_COUNT", "5")
    assert serving.default_slot_count() == 5


# -- SLO declaration -------------------------------------------------------

def test_declared_slo_lands_in_gauge_and_traceview_table():
    telemetry.reset()
    fleet = serving.FleetServer(n_replicas=2, max_batch_size=4,
                                batch_window_ms=1.0)
    sym, args = _mlp_parts()
    fleet.add_model("slomodel", sym, args,
                    input_shapes={"data": (FEAT,)}, slo_ms=123.0)
    try:
        fleet.warmup()
        for _ in range(4):
            fleet.submit("slomodel",
                         {"data": rng.rand(2, FEAT).astype(np.float32)},
                         timeout=30)
        snap = telemetry.snapshot()
        assert snap["serving.slo_ms.slomodel"]["value"] == 123.0
        assert snap["serving.request_latency_ms.slomodel"]["count"] == 4
        # the traceview attainment table reads exactly this snapshot
        import importlib.util
        import os
        tv_path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "traceview.py")
        spec = importlib.util.spec_from_file_location("_tv_fleet", tv_path)
        tv = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tv)
        stats = tv.serving_from_telemetry(snap)
        assert len(stats["replicas"]) >= 1
        slo_rows = {r["model"]: r for r in stats["slo"]}
        assert slo_rows["slomodel"]["target_ms"] == 123.0
        assert slo_rows["slomodel"]["served"] == 4
        rendered = tv.summarize_serving("telemetry", snap)
        assert "SLO attainment" in rendered
        assert "per-replica routing" in rendered
    finally:
        fleet.close(drain=True, timeout=30)


def test_slo_env_default(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_SERVING_SLO_MS", "77.5")
    sym, args = _mlp_parts()
    model = serving.ServedModel("envslo", sym,
                                {k: v for k, v in args.items()}, None,
                                {"data": (FEAT,)}, max_batch_size=2)
    assert model.slo_ms == 77.5


# -- autotune cadence ------------------------------------------------------

def test_autotune_cadence_runs_tuner_and_stages_on_all_replicas(
        monkeypatch):
    """MXNET_TPU_AUTOTUNE_EVERY_S inside the serving loop: the tuner
    runs on the dispatch thread, its decision lands in the autotune
    log, and (apply mode) the staged set propagates to every replica
    for adoption at the next warmup boundary."""
    from mxnet_tpu.observability import autotune
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE_EVERY_S", "0.01")
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE", "apply")
    telemetry.reset()
    autotune.clear_decisions()
    fleet, _, _ = _fleet()
    try:
        assert fleet.batcher.cadence.enabled
        fleet.warmup()
        # 5-row traffic: quantiles pin 5 exactly (single-valued
        # histogram), so the tuner proposes [5, 8] vs the power-of-two
        # [1, 2, 4, 8] — strictly less padding, must stage
        for i in range(20):
            fleet.submit("mlp",
                         {"data": rng.rand(5, FEAT).astype(np.float32)},
                         timeout=30)
            if i % 5 == 4:
                time.sleep(0.02)  # let a cadence period elapse
        deadline = time.monotonic() + 5
        staged = None
        while time.monotonic() < deadline:
            staged = fleet.registry.get("mlp").pending_buckets()
            if staged:
                break
            fleet.submit("mlp",
                         {"data": rng.rand(5, FEAT).astype(np.float32)},
                         timeout=30)
            time.sleep(0.02)
        assert staged, "cadence never staged a bucket set"
        assert staged[-1] == 8 and 5 in staged
        decisions = [d for d in autotune.decision_log()
                     if d["controller"] == "serving_buckets"]
        assert decisions, "no serving_buckets decision recorded"
        # apply-mode staging propagated to EVERY replica's twin
        for twin in fleet.group.models_named("mlp"):
            assert twin.pending_buckets() == staged \
                or twin.buckets == staged
        # adoption at the warmup boundary, on every replica, no retrace
        # in steady state afterwards
        fleet.warmup()
        for twin in fleet.group.models_named("mlp"):
            assert twin.buckets == staged
            assert twin.pending_buckets() is None
        with executor_cache.watch_traces() as w:
            fleet.submit("mlp",
                         {"data": rng.rand(5, FEAT).astype(np.float32)},
                         timeout=30)
        assert w.total() == 0, w.delta()
    finally:
        fleet.close(drain=True, timeout=30)


def test_autotune_cadence_disabled_by_default():
    fleet, _, _ = _fleet(auto_start=False)
    try:
        assert not fleet.batcher.cadence.enabled
        assert fleet.batcher.cadence() is None
    finally:
        fleet.close(drain=False)


# -- continuous batching ---------------------------------------------------

H = 5
LSTM_FEAT = 4
VOCAB = 3


def _lstm_step_parts(seed=23):
    r = np.random.RandomState(seed)
    data = mx.sym.Variable("data")
    h = mx.sym.Variable("state_h")
    c = mx.sym.Variable("state_c")
    cell = rnn_cell.LSTMCell(H, prefix="lstm_")
    out, (nh, nc) = cell(data, [h, c])
    logits = mx.sym.FullyConnected(out, num_hidden=VOCAB, name="proj")
    from mxnet_tpu import symbol as symmod
    step = symmod.Group([logits, nh, nc])
    arg_shapes, _, _ = step.infer_shape(
        data=(1, LSTM_FEAT), state_h=(1, H), state_c=(1, H))
    params = {n: r.normal(0, 0.3, s).astype(np.float32)
              for n, s in zip(step.list_arguments(), arg_shapes)
              if n not in ("data", "state_h", "state_c")}
    return step, params


def _decode_batcher(step, params, slots):
    return serving.ContinuousBatcher(
        step, params, input_shapes={"data": (LSTM_FEAT,)},
        state_shapes={"state_h": (H,), "state_c": (H,)},
        state_pairs=[("state_h", 1), ("state_c", 2)], slot_count=slots)


def _decode_solo(step, params, seq, slots):
    solo = _decode_batcher(step, params, slots)
    solo.warmup()
    stream = solo.submit({"data": seq})
    solo.drain(max_iterations=200)
    return stream.outputs()[0]


def test_continuous_join_leave_zero_retrace_bitwise_parity():
    """THE continuous-batching acceptance criterion: streams join and
    leave mid-flight with zero retraces, and each stream's decoded
    outputs are bitwise-equal to running it alone through the same
    slot program."""
    step, params = _lstm_step_parts()
    cb = _decode_batcher(step, params, slots=4)
    wu = cb.warmup()
    assert wu["slot_count"] == 4
    r = np.random.RandomState(5)
    seqs = [r.rand(T, LSTM_FEAT).astype(np.float32)
            for T in (6, 3, 8, 4, 2, 5)]
    streams = []
    with executor_cache.watch_traces() as w:
        for s in seqs[:3]:          # 3 join at the start
            streams.append(cb.submit({"data": s}))
        cb.step()
        cb.step()
        for s in seqs[3:]:          # 3 join MID-FLIGHT
            streams.append(cb.submit({"data": s}))
        cb.drain(max_iterations=200)
    assert w.total() == 0, (
        "join/leave retraced: %s" % (w.delta(),))
    assert all(s.done for s in streams)
    assert [s.steps_decoded for s in streams] == [6, 3, 8, 4, 2, 5]
    for seq, stream in zip(seqs, streams):
        want = _decode_solo(step, params, seq, slots=4)
        got = stream.outputs()[0]
        assert got.shape == want.shape
        assert np.array_equal(got, want), (
            "stream decoded differently alongside neighbours "
            "(max diff %g)" % np.abs(got - want).max())


def test_continuous_more_streams_than_slots_queue_and_finish():
    step, params = _lstm_step_parts()
    cb = _decode_batcher(step, params, slots=2)
    cb.warmup()
    r = np.random.RandomState(9)
    seqs = [r.rand(T, LSTM_FEAT).astype(np.float32)
            for T in (4, 2, 3, 5, 1)]
    streams = [cb.submit({"data": s}) for s in seqs]
    assert cb.pending() == 5
    iterations = cb.drain(max_iterations=200)
    assert iterations >= 5  # five streams through two slots
    for seq, stream in zip(seqs, streams):
        assert np.array_equal(stream.outputs()[0],
                              _decode_solo(step, params, seq, slots=2))


def test_continuous_eos_fn_leaves_early():
    step, params = _lstm_step_parts()
    cb = _decode_batcher(step, params, slots=2)
    cb.warmup()
    r = np.random.RandomState(13)
    seq = r.rand(10, LSTM_FEAT).astype(np.float32)
    fired = []

    def eos_after_three(rows):
        fired.append(1)
        return len(fired) >= 3

    stream = cb.submit({"data": seq}, eos_fn=eos_after_three)
    cb.drain(max_iterations=50)
    assert stream.done and stream.steps_decoded == 3


def test_continuous_nonfinite_carry_cannot_poison_next_occupant():
    """The slot reset is a row SELECT, not a multiply: a departed
    stream that left Inf/NaN in its slot's carried state must not leak
    into the next occupant (0 * Inf would be NaN)."""
    step, params = _lstm_step_parts()
    cb = _decode_batcher(step, params, slots=2)
    cb.warmup()
    r = np.random.RandomState(29)
    first = cb.submit({"data": r.rand(2, LSTM_FEAT).astype(np.float32)})
    cb.drain(max_iterations=20)
    assert first.done
    # simulate a stream that overflowed before leaving: poison the
    # carried device state of every (now-free) slot
    poison = np.full((2, H), np.inf, np.float32)
    for name in ("state_h", "state_c"):
        cb._carry[name] = mx.nd.array(poison)
    seq = r.rand(4, LSTM_FEAT).astype(np.float32)
    stream = cb.submit({"data": seq})
    cb.drain(max_iterations=20)
    got = stream.outputs()[0]
    assert np.all(np.isfinite(got))
    assert np.array_equal(got, _decode_solo(step, params, seq, slots=2))


def test_continuous_raising_eos_fn_fails_only_its_stream():
    """A bad user callback ends ITS stream with the error; co-batched
    neighbours keep decoding bitwise-correctly (the callback runs
    outside the scheduler lock, after collection bookkeeping)."""
    step, params = _lstm_step_parts()
    cb = _decode_batcher(step, params, slots=2)
    cb.warmup()
    r = np.random.RandomState(21)
    good_seq = r.rand(5, LSTM_FEAT).astype(np.float32)

    def bad_eos(rows):
        raise ValueError("user callback bug")

    bad = cb.submit({"data": r.rand(6, LSTM_FEAT).astype(np.float32)},
                    eos_fn=bad_eos)
    good = cb.submit({"data": good_seq})
    cb.drain(max_iterations=50)
    assert bad.done and good.done
    with pytest.raises(ValueError):
        bad.outputs()
    assert np.array_equal(good.outputs()[0],
                          _decode_solo(step, params, good_seq, slots=2))


def test_continuous_occupancy_metrics_and_close():
    telemetry.reset()
    step, params = _lstm_step_parts()
    cb = _decode_batcher(step, params, slots=2)
    cb.warmup()
    r = np.random.RandomState(17)
    s1 = cb.submit({"data": r.rand(6, LSTM_FEAT).astype(np.float32)})
    cb.step()
    snap = telemetry.snapshot()
    assert snap["serving.decode.iterations"]["value"] >= 1
    assert snap["serving.decode.joins"]["value"] >= 1
    cb.close()
    assert s1.done
    with pytest.raises(mx.base.MXNetError):
        s1.outputs()
    with pytest.raises(mx.base.MXNetError):
        cb.submit({"data": r.rand(2, LSTM_FEAT).astype(np.float32)})


def test_continuous_validates_shapes_and_states():
    step, params = _lstm_step_parts()
    with pytest.raises(mx.base.MXNetError):
        serving.ContinuousBatcher(
            step, params, input_shapes={"data": (LSTM_FEAT,)},
            state_shapes={"state_h": (H,), "state_c": (H,)},
            state_pairs=[("bogus", 1)], slot_count=2)
    cb = _decode_batcher(step, params, slots=2)
    with pytest.raises(mx.base.MXNetError):
        cb.submit({"data": np.zeros((3, LSTM_FEAT + 1), np.float32)})
    with pytest.raises(mx.base.MXNetError):
        cb.submit({"wrong": np.zeros((3, LSTM_FEAT), np.float32)})


# -- drain shedding --------------------------------------------------------

def test_fleet_drain_deadline_sheds_typed_server_closed():
    """Routed-but-undispatched work sheds typed at the drain deadline
    (the replica-lane analog of the Server drain contract)."""
    fleet, _, _ = _fleet()
    try:
        fleet.warmup()
        slow = fleet.group.replicas[0].registry.get("mlp")
        orig = slow.run_batch

        def crawling(bucket, inputs):
            time.sleep(0.5)
            return orig(bucket, inputs)

        slow.run_batch = crawling
        slow2 = fleet.group.replicas[1].registry.get("mlp")
        slow2.run_batch = crawling
        futs = [fleet.submit_async(
            "mlp", {"data": rng.rand(8, FEAT).astype(np.float32)})
            for _ in range(8)]
    finally:
        fleet.close(drain=True, timeout=1.0)
    outcomes = {"served": 0, "shed": 0}
    for f in futs:
        try:
            f.result(timeout=10)
            outcomes["served"] += 1
        except serving.ServerClosed:
            outcomes["shed"] += 1
    assert outcomes["served"] + outcomes["shed"] == 8
    assert outcomes["shed"] >= 1, outcomes
