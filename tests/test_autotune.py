"""observability.autotune — telemetry-driven auto-tuning controllers.

Pins the safety rails of docs/autotune.md: the shared log2-bucket
quantile estimator at its bucket edges, the mode gate
(``MXNET_TPU_AUTOTUNE=recommend|apply|0``), the serving tuner's
footprint-vs-capacity validation (over-capacity -> rejected, never
staged) and warmup-boundary adoption (zero steady-state retraces), the
io tuner's starvation band, the ``=0`` kill switch (zero new telemetry
series, bitwise-identical training), and the decision log riding the
flight recorder into ``traceview --tuning``.
"""
import importlib.util
import json
import math
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import executor_cache, serving
from mxnet_tpu.observability import autotune, flight_recorder, telemetry

rng = np.random.RandomState(7)

FEAT = 6


@pytest.fixture(autouse=True)
def _isolate(monkeypatch):
    """Each test owns the autotune mode and the knobs the controllers
    may set; the decision log and metrics registry start empty."""
    for var in ("MXNET_TPU_AUTOTUNE", "MXNET_TPU_IO_WORKERS"):
        monkeypatch.delenv(var, raising=False)
    autotune.clear_decisions()
    telemetry.reset()
    flight_recorder.reset()
    yield
    flight_recorder.reset()


def _load_traceview():
    tv_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "traceview.py")
    spec = importlib.util.spec_from_file_location("_autotune_traceview",
                                                  tv_path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# -- the shared quantile estimator -----------------------------------------

def test_quantile_empty_histogram_is_zero():
    assert telemetry.Histogram("q_empty").quantile(0.5) == 0.0
    assert telemetry.quantile_from_snapshot({}, 0.5) == 0.0


def test_quantile_single_value_at_bucket_edge_is_exact():
    # 8.0 is an exact power of two — the edge of its (4, 8] bucket.
    # Interpolation alone would answer inside (4, 8); the min/max clamp
    # makes every quantile exact for a single-valued histogram.
    h = telemetry.Histogram("q_edge")
    for _ in range(10):
        h.observe(8.0)
    for q in (0.0, 0.25, 0.5, 0.99, 1.0):
        assert h.quantile(q) == 8.0


def test_quantile_q0_q1_are_min_max():
    h = telemetry.Histogram("q_minmax")
    for v in (1.0, 3.0, 5.0, 11.0):
        h.observe(v)
    assert h.quantile(0.0) == 1.0
    assert h.quantile(1.0) == 11.0


def test_quantile_interpolates_within_bucket():
    # 100 observations all in (4, 8]: the q-th estimate moves linearly
    # across the bucket instead of snapping to the upper bound
    h = telemetry.Histogram("q_interp")
    for _ in range(100):
        h.observe(5.0)
    est = h.quantile(0.5)
    assert 4.0 < est <= 8.0
    snap = h._snapshot()
    raw = 4.0 + 0.5 * (8.0 - 4.0)
    # clamped to the observed max... which is 5.0 here
    assert telemetry.quantile_from_snapshot(dict(snap, min=None, max=None),
                                            0.5) == pytest.approx(raw)
    assert est == 5.0  # the clamp at work


def test_quantile_mixed_buckets_ranks_correctly():
    h = telemetry.Histogram("q_mixed")
    for v in [2.0] * 20 + [5.0] * 70 + [16.0] * 10:
        h.observe(v)
    # rank 50 of 100 falls 30/70 into the (4, 8] bucket
    assert h.quantile(0.5) == pytest.approx(4.0 + (30.0 / 70.0) * 4.0)
    assert h.quantile(0.1) == 2.0
    assert h.quantile(1.0) == 16.0


def test_quantile_overflow_bucket_clamps_to_max():
    h = telemetry.Histogram("q_over")
    big = float(2 ** 22)  # beyond the last fixed bound (2**20)
    for _ in range(4):
        h.observe(big)
    assert h.quantile(0.5) == big
    assert h.quantile(1.0) == big


# -- mode gate -------------------------------------------------------------

def test_mode_default_is_recommend():
    assert autotune.mode() == "recommend"


@pytest.mark.parametrize("raw,expect", [
    ("recommend", "recommend"), ("apply", "apply"), ("0", "off"),
    ("off", "off"), ("none", "off"), ("bogus", "recommend")])
def test_mode_env_values(monkeypatch, raw, expect):
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE", raw)
    assert autotune.mode() == expect


def test_kill_switch_beats_constructor_mode(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE", "0")
    tuner = autotune.IoWorkerTuner(mode="apply")
    assert tuner.mode == "off"
    assert tuner.run() is None
    assert autotune.decision_log() == []


def test_constructor_mode_overrides_env(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE", "recommend")
    assert autotune.IoWorkerTuner(mode="apply").mode == "apply"
    with pytest.raises(ValueError):
        autotune.IoWorkerTuner(mode="bogus")


# -- ServingBucketTuner ----------------------------------------------------

class _StubModel:
    name = "stub"

    def __init__(self, buckets=(1, 2, 4, 8, 16), max_batch_size=16,
                 bucket_memory=None):
        self.buckets = list(buckets)
        self.max_batch_size = max_batch_size
        self.bucket_memory = dict(bucket_memory or {})
        self.staged = None

    def stage_buckets(self, buckets):
        self.staged = list(buckets)
        return list(buckets)


def _rows_hist(values, name="serving.request_rows"):
    h = telemetry.histogram(name)
    for v in values:
        h.observe(v)
    return h._snapshot()


def test_serving_tuner_skips_on_insufficient_traffic():
    hist = _rows_hist([5, 5, 5])
    rec = autotune.ServingBucketTuner(mode="apply").run(
        _StubModel(), rows_hist=hist)
    assert rec["action"] == "skip"
    assert "insufficient" in rec["reason"]


def test_serving_tuner_shapes_and_stages_in_apply_mode():
    model = _StubModel()
    hist = _rows_hist([5] * 50 + [3] * 20 + [16] * 5)
    rec = autotune.ServingBucketTuner(mode="apply").run(model,
                                                        rows_hist=hist)
    assert rec["action"] == "apply"
    proposed = rec["decision"]["buckets"]
    assert model.staged == proposed
    assert proposed[-1] == model.max_batch_size
    assert proposed != model.buckets
    # the estimate must predict less padding than the power-of-two set
    est_cur = rec["decision"]["est_padded_rows_per_request_current"]
    est_new = rec["candidates"][0]["est_padded_rows_per_request"]
    assert est_new < est_cur


def test_serving_tuner_recommend_does_not_stage():
    model = _StubModel()
    hist = _rows_hist([5] * 50 + [3] * 20)
    rec = autotune.ServingBucketTuner(mode="recommend").run(
        model, rows_hist=hist)
    assert rec["action"] == "recommend"
    assert model.staged is None


def test_serving_tuner_rejects_footprint_over_capacity():
    model = _StubModel(bucket_memory={
        16: {"argument_bytes": 1024, "output_bytes": 4096,
             "temp_bytes": 4096, "total_bytes": 9216}})
    hist = _rows_hist([5] * 60 + [16] * 6)
    rec = autotune.ServingBucketTuner(mode="apply").run(
        model, rows_hist=hist, bytes_limit=4000)
    assert rec["action"] == "reject"
    assert model.staged is None
    assert rec["decision"]["staged"] is False
    assert rec["inputs"]["bytes_limit"] == 4000
    assert rec["candidates"][0]["estimated_footprint_bytes"] > 4000


def test_serving_tuner_never_stages_a_set_that_does_not_beat_incumbent():
    # a hand-tuned incumbent already matching the traffic: the shaped
    # candidate estimates no less padding, so the tuner holds instead
    # of churning the bucket set (a change the evidence cannot justify
    # is not made)
    model = _StubModel(buckets=(3, 5, 16), max_batch_size=16)
    hist = _rows_hist([3] * 40 + [5] * 40)
    rec = autotune.ServingBucketTuner(mode="apply").run(model,
                                                        rows_hist=hist)
    assert rec["action"] == "hold"
    assert model.staged is None
    assert "would not beat" in rec["reason"]


def test_serving_tuner_prefers_per_model_histogram():
    # a shared server mixes traffic shapes: the tuner must read the
    # model's own serving.request_rows.<model> series, not the
    # process-wide one another model dominates
    for _ in range(40):
        telemetry.histogram("serving.request_rows").observe(16)
        telemetry.histogram("serving.request_rows.a").observe(5)
    model = _StubModel()
    model.name = "a"
    rec = autotune.ServingBucketTuner(mode="recommend").run(model)
    assert rec["inputs"]["rows_max"] == 5
    assert 5 in rec["decision"]["buckets"]


def test_serving_tuner_holds_when_shape_matches():
    # uniform traffic already on a bucket edge: the quantiles land on
    # the existing set and the tuner keeps the incumbent
    model = _StubModel(buckets=(8, 16), max_batch_size=16)
    hist = _rows_hist([8] * 60)
    rec = autotune.ServingBucketTuner(mode="apply").run(model,
                                                        rows_hist=hist)
    assert rec["action"] == "hold"
    assert model.staged is None


# -- staged buckets on a REAL ServedModel ----------------------------------

def _mlp_parts(nh=8, classes=3):
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=nh,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc2")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    arg_shapes, _, _ = sym.infer_shape(data=(1, FEAT))
    args = {n: mx.nd.array(rng.normal(0, 0.1, s).astype(np.float32))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}
    return sym, args


def test_stage_buckets_normalizes_and_tops_with_max():
    sym, args = _mlp_parts()
    model = serving.ServedModel("m", sym, args, {}, {"data": (FEAT,)},
                                max_batch_size=8)
    assert model.stage_buckets([3.0, 3, 99, 0]) == [1, 3, 8]
    assert model.pending_buckets() == [1, 3, 8]
    with pytest.raises(ValueError):
        model.stage_buckets([])
    # buckets only swap at the warmup boundary
    assert model.buckets == [1, 2, 4, 8]


def test_staged_buckets_adopt_at_warmup_with_zero_steady_retraces():
    server = serving.Server(max_batch_size=8, batch_window_ms=0.0)
    try:
        sym, args = _mlp_parts()
        model = server.add_model("mlp", sym, args,
                                 input_shapes={"data": (FEAT,)})
        server.warmup()
        model.stage_buckets([3, 8])
        report = server.warmup()  # adopts, traces, verifies
        assert model.buckets == [3, 8]
        assert report["mlp"]["buckets"] == [3, 8]
        assert model.pending_buckets() is None
        with executor_cache.watch_traces() as w:
            fut = server.submit_async(
                "mlp", {"data": np.zeros((3, FEAT), np.float32)})
            outs = fut.result(60)
        assert w.total() == 0
        assert fut.request.dispatch_bucket == 3
        assert outs[0].shape[0] == 3
    finally:
        server.close()


def test_request_rows_recorded_at_admission():
    server = serving.Server(max_batch_size=8, batch_window_ms=0.0)
    try:
        sym, args = _mlp_parts()
        server.add_model("mlp", sym, args, input_shapes={"data": (FEAT,)})
        server.warmup()
        for n in (1, 3, 3, 5):
            server.submit("mlp", {"data": np.zeros((n, FEAT),
                                                   np.float32)})
        snap = telemetry.snapshot().get("serving.request_rows")
        assert snap is not None and snap["count"] == 4
        assert snap["min"] == 1 and snap["max"] == 5
        assert snap["sum"] == 12
        per_model = telemetry.snapshot().get("serving.request_rows.mlp")
        assert per_model is not None and per_model["count"] == 4
    finally:
        server.close()


# -- IoWorkerTuner ---------------------------------------------------------

def _io_snapshot(wait_ms, step_ms, steps=10,
                 source="io_pipeline.queue_wait_ms"):
    return {source: {"count": steps, "sum": wait_ms},
            "module.step.total_ms": {"count": steps, "sum": step_ms}}


def test_io_tuner_starved_recommends_more_workers():
    rec = autotune.IoWorkerTuner(mode="recommend").run(
        snapshot=_io_snapshot(200.0, 1000.0), current_workers=2, cores=8)
    assert rec["action"] == "recommend"
    assert rec["decision"]["workers"] == 4
    assert rec["inputs"]["starvation_ratio"] == pytest.approx(0.2)


def test_io_tuner_idle_releases_a_worker():
    rec = autotune.IoWorkerTuner(mode="recommend").run(
        snapshot=_io_snapshot(1.0, 1000.0), current_workers=4, cores=8)
    assert rec["decision"]["workers"] == 3


def test_io_tuner_in_band_holds():
    rec = autotune.IoWorkerTuner(mode="recommend").run(
        snapshot=_io_snapshot(20.0, 1000.0), current_workers=2, cores=8)
    assert rec["action"] == "hold"
    assert rec["decision"]["workers"] == 2


def test_io_tuner_capped_at_core_count():
    rec = autotune.IoWorkerTuner(mode="recommend").run(
        snapshot=_io_snapshot(500.0, 1000.0), current_workers=2, cores=2)
    assert rec["action"] == "hold"
    assert "core count" in rec["reason"]


def test_io_tuner_apply_sets_env(monkeypatch):
    rec = autotune.IoWorkerTuner(mode="apply").run(
        snapshot=_io_snapshot(200.0, 1000.0), current_workers=2, cores=8)
    assert rec["action"] == "apply"
    assert os.environ["MXNET_TPU_IO_WORKERS"] == "4"


def test_io_tuner_skips_without_telemetry():
    rec = autotune.IoWorkerTuner(mode="apply").run(snapshot={},
                                                   current_workers=2,
                                                   cores=8)
    assert rec["action"] == "skip"


def test_io_tuner_falls_back_to_fit_loop_data_wait():
    rec = autotune.IoWorkerTuner(mode="recommend").run(
        snapshot=_io_snapshot(200.0, 1000.0,
                              source="module.step.data_wait_ms"),
        current_workers=1, cores=4)
    assert rec["inputs"]["signal"] == "module.step.data_wait_ms"
    assert rec["decision"]["workers"] == 2


# -- the =0 kill switch ----------------------------------------------------

def _tiny_fit(seed=0):
    rs = np.random.RandomState(seed)
    X = rs.randn(64, FEAT).astype(np.float32)
    y = (rs.rand(64) * 3).astype(np.float32)
    sym, _ = _mlp_parts()
    mx.random.seed(0)
    it = mx.io.NDArrayIter(X, y, batch_size=16, shuffle=False)
    mod = mx.mod.Module(sym)
    mod.fit(it, num_epoch=2,
            optimizer_params={"learning_rate": 0.05},
            initializer=mx.initializer.Xavier())
    return {n: mod._exec_group.execs[0].arg_dict[n].asnumpy()
            for n in mod._exec_group.param_names}


def test_disabled_autotune_is_inert_and_bitwise(monkeypatch):
    """MXNET_TPU_AUTOTUNE=0: controllers return None without reading a
    signal, creating a telemetry series, or touching a knob — and a
    training run with the tuners invoked is bitwise-identical to one
    without them."""
    baseline = _tiny_fit()
    telemetry.reset()
    autotune.clear_decisions()
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE", "0")

    params = _tiny_fit()
    assert autotune.ServingBucketTuner().run(_StubModel()) is None
    assert autotune.IoWorkerTuner().run() is None
    for k in baseline:
        assert np.array_equal(baseline[k], params[k]), k
    assert autotune.decision_log() == []
    assert not [name for name in telemetry.snapshot()
                if name.startswith("autotune.")]
    assert "MXNET_TPU_IO_WORKERS" not in os.environ


# -- decision log: flight recorder + traceview -----------------------------

def test_decisions_ride_the_flight_dump_and_traceview(tmp_path):
    autotune.IoWorkerTuner(mode="recommend").run(
        snapshot=_io_snapshot(200.0, 1000.0), current_workers=2, cores=8)
    autotune.ServingBucketTuner(mode="recommend").run(
        _StubModel(), rows_hist=_rows_hist([5] * 50 + [3] * 20))
    path = str(tmp_path / "flight.json")
    assert flight_recorder.dump(path=path, reason="test") == path
    doc = json.load(open(path))
    controllers = [r["controller"] for r in doc["tuning"]]
    assert controllers == ["io_workers", "serving_buckets"]
    # strict JSON all the way down (the flight contract)
    for rec in doc["tuning"]:
        json.dumps(rec, allow_nan=False)

    tv = _load_traceview()
    stats = tv.tuning_stats(tv.tuning_records(doc))
    assert stats["decisions"] == 2
    assert stats["by_controller"] == {"io_workers": 1,
                                      "serving_buckets": 1}
    text = tv.summarize_tuning(doc["tuning"])
    assert "serving_buckets" in text and "io_workers" in text
    assert tv.main(["--tuning", path]) == 0
    # a dump with no decisions exits 2 (the "autotune never ran" signal)
    empty = str(tmp_path / "empty.json")
    json.dump({"tuning": []}, open(empty, "w"))
    assert tv.main(["--tuning", empty]) == 2


def test_decision_counters_registered():
    autotune.IoWorkerTuner(mode="recommend").run(
        snapshot=_io_snapshot(200.0, 1000.0), current_workers=2, cores=8)
    snap = telemetry.snapshot()
    assert snap["autotune.decisions.io_workers.recommend"]["value"] == 1
