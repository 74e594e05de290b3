"""mxnet_tpu.serving — dynamic-batching inference service.

Covers the serving contracts that are easy to get subtly wrong: bucket
selection and padding correctness (partial final bucket, multi-request
assembly), typed rejections (oversized request, deadline expiry while
queued, overload backpressure, unknown model, malformed payload),
warmup's zero-recompile verification, graceful drain completing
in-flight work, and the dispatch thread surviving model failures.
"""
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import executor_cache, serving
from mxnet_tpu.base import MXNetError
from mxnet_tpu.observability import telemetry
from mxnet_tpu.predict import Predictor

rng = np.random.RandomState(11)

FEAT = 6


@pytest.fixture(autouse=True)
def _isolate_serving_env(monkeypatch):
    """Deadlines and queue depth are constructed explicitly per test; an
    ambient operator default would expire/reject ordinary requests."""
    monkeypatch.delenv("MXNET_TPU_SERVING_DEFAULT_DEADLINE_MS",
                       raising=False)
    monkeypatch.delenv("MXNET_TPU_SERVING_QUEUE_DEPTH", raising=False)


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


def _server(max_batch_size=4, **kw):
    server = serving.Server(max_batch_size=max_batch_size, **kw)
    sym, args = _mlp_parts()
    server.add_model("mlp", sym, args, input_shapes={"data": (FEAT,)})
    return server, sym, args


# -- bucket arithmetic -----------------------------------------------------

def test_bucket_sizes_powers_of_two_plus_max():
    assert serving.bucket_sizes(1) == [1]
    assert serving.bucket_sizes(8) == [1, 2, 4, 8]
    assert serving.bucket_sizes(6) == [1, 2, 4, 6]
    with pytest.raises(ValueError):
        serving.bucket_sizes(0)


def test_bucket_for_picks_smallest_fit():
    buckets = serving.bucket_sizes(8)
    assert serving.bucket_for(1, buckets) == 1
    assert serving.bucket_for(3, buckets) == 4
    assert serving.bucket_for(8, buckets) == 8
    with pytest.raises(serving.RequestTooLarge):
        serving.bucket_for(9, buckets)


# -- typed submit-time rejections ------------------------------------------

def test_request_larger_than_max_batch_size_is_typed():
    server, _, _ = _server(max_batch_size=4)
    try:
        with pytest.raises(serving.RequestTooLarge):
            server.submit("mlp", {"data": np.zeros((5, FEAT), np.float32)})
    finally:
        server.close()


def test_unknown_model_and_bad_payload_are_typed():
    server, _, _ = _server()
    try:
        with pytest.raises(serving.ModelNotFound):
            server.submit("nope", {"data": np.zeros((1, FEAT), np.float32)})
        with pytest.raises(serving.BadRequest):
            server.submit("mlp", {"data": np.zeros((1, FEAT + 1),
                                                   np.float32)})
        with pytest.raises(serving.BadRequest):
            server.submit("mlp", {"wrong_name": np.zeros((1, FEAT),
                                                         np.float32)})
        with pytest.raises(serving.BadRequest):
            server.submit("mlp", {"data": np.zeros((0, FEAT), np.float32)})
    finally:
        server.close()


def test_submit_after_close_is_server_closed():
    server, _, _ = _server()
    server.close()
    with pytest.raises(serving.ServerClosed):
        server.submit("mlp", {"data": np.zeros((1, FEAT), np.float32)})


# -- padding / splitting correctness ---------------------------------------

def test_partial_final_bucket_pads_correctly():
    """3 rows into a max-4 service: dispatched in the 4-bucket, padding
    row invisible — response bitwise-equal to a plain Predictor run of
    the same padded batch, and row count exactly the request's."""
    server, sym, args = _server(max_batch_size=4)
    try:
        server.warmup()
        x = rng.rand(3, FEAT).astype(np.float32)
        fut = server.submit_async("mlp", {"data": x})
        outs = fut.result(timeout=60)
        assert fut.request.dispatch_bucket == 4
        assert outs[0].shape[0] == 3
        blob = {"arg:%s" % k: v for k, v in args.items()}
        oracle = Predictor(sym.tojson(), blob, {"data": (4, FEAT)})
        solo = np.zeros((4, FEAT), np.float32)
        solo[:3] = x
        oracle.forward(data=solo)
        want = oracle.get_output(0).asnumpy()[:3]
        assert np.array_equal(outs[0], want)
    finally:
        server.close()


def test_multi_request_batch_routes_rows_back():
    """Requests co-batched into one dispatch each get exactly their own
    rows back (distinct inputs -> distinct outputs, order preserved)."""
    server, sym, args = _server(max_batch_size=8, batch_window_ms=50.0,
                                auto_start=False)
    try:
        server.warmup()
        xs = [rng.rand(n, FEAT).astype(np.float32) for n in (1, 2, 1)]
        futs = [server.submit_async("mlp", {"data": x}) for x in xs]
        server.start()
        outs = [f.result(timeout=60) for f in futs]
        # all three rode one bucket-4 dispatch (queued before start)
        assert {f.request.dispatch_bucket for f in futs} == {4}
        blob = {"arg:%s" % k: v for k, v in args.items()}
        oracle = Predictor(sym.tojson(), blob, {"data": (4, FEAT)})
        for x, out in zip(xs, outs):
            solo = np.zeros((4, FEAT), np.float32)
            solo[:x.shape[0]] = x
            oracle.forward(data=solo)
            want = oracle.get_output(0).asnumpy()[:x.shape[0]]
            assert np.array_equal(out[0], want)
    finally:
        server.close()


def test_single_row_gains_batch_dim():
    server, _, _ = _server()
    try:
        out = server.submit("mlp", {"data": np.zeros(FEAT, np.float32)},
                            timeout=60)
        assert out[0].shape[0] == 1
    finally:
        server.close()


# -- warmup ----------------------------------------------------------------

def test_warmup_traces_each_bucket_once_then_none():
    executor_cache.clear()
    executor_cache.reset_stats()
    server, _, _ = _server(max_batch_size=4)
    try:
        report = server.warmup()  # verify pass asserts zero retraces
        assert report["mlp"]["buckets"] == [1, 2, 4]
        assert report["mlp"]["traces_verify_pass"] == 0
        with executor_cache.watch_traces() as w:
            for n in (1, 2, 3, 4, 2):
                server.submit("mlp", {"data": rng.rand(n, FEAT)
                                      .astype(np.float32)}, timeout=60)
        assert w.total() == 0, w.delta()
    finally:
        server.close()


# -- deadlines / overload / drain ------------------------------------------

def test_deadline_expiry_while_queued():
    """A request whose deadline passes while the batcher is stopped is
    rejected with DeadlineExceeded once dispatch resumes — it never
    occupies a batch slot — and the live request still completes."""
    telemetry.reset()
    server, _, _ = _server(auto_start=False)
    try:
        server.warmup()
        doomed = server.submit_async(
            "mlp", {"data": rng.rand(1, FEAT).astype(np.float32)},
            deadline_ms=10)
        alive = server.submit_async(
            "mlp", {"data": rng.rand(1, FEAT).astype(np.float32)})
        time.sleep(0.05)
        server.start()
        with pytest.raises(serving.DeadlineExceeded):
            doomed.result(timeout=60)
        assert doomed.request.dispatch_bucket is None  # never dispatched
        assert len(alive.result(timeout=60)) >= 1
        snap = telemetry.snapshot()
        key = "serving.rejected_total.deadline_exceeded"
        assert snap[key]["value"] == 1
    finally:
        server.close()


def test_overload_rejects_at_queue_depth():
    telemetry.reset()
    server, _, _ = _server(queue_depth=2, auto_start=False)
    try:
        x = rng.rand(1, FEAT).astype(np.float32)
        queued = [server.submit_async("mlp", {"data": x})
                  for _ in range(2)]
        with pytest.raises(serving.Overloaded):
            server.submit_async("mlp", {"data": x})
        snap = telemetry.snapshot()
        assert snap["serving.rejected_total.overloaded"]["value"] == 1
        server.start()
        for f in queued:
            f.result(timeout=60)  # the queued work is unharmed
    finally:
        server.close()


def test_drain_on_shutdown_completes_inflight():
    """close(drain=True) finishes every already-queued request before
    the dispatch thread exits; late submits get ServerClosed."""
    server, _, _ = _server(auto_start=False)
    server.warmup()
    xs = [rng.rand(1 + i % 2, FEAT).astype(np.float32) for i in range(6)]
    futs = [server.submit_async("mlp", {"data": x}) for x in xs]
    server.start()
    server.close(drain=True, timeout=120)
    assert not server.batcher.alive
    for x, f in zip(xs, futs):
        assert f.result(timeout=0)[0].shape[0] == x.shape[0]
    with pytest.raises(serving.ServerClosed):
        server.submit("mlp", {"data": xs[0]})


def test_shared_registry_narrower_server_rejects_not_wedges():
    """A server narrower than a shared model must reject what it cannot
    assemble (min of the two caps) instead of admitting a request its
    dispatch loop can never claim — and must keep serving fitting work."""
    server, _, _ = _server(max_batch_size=8)
    narrow = serving.Server(registry=server.registry, max_batch_size=4)
    try:
        with pytest.raises(serving.RequestTooLarge):
            narrow.submit("mlp", {"data": np.zeros((5, FEAT), np.float32)})
        out = narrow.submit("mlp", {"data": np.zeros((2, FEAT),
                                                     np.float32)},
                            timeout=60)
        assert out[0].shape[0] == 2
    finally:
        narrow.close()
        server.close()


def test_admission_oversized_head_claimed_solo_not_spun():
    """Defense in depth under the same skew: if an oversized request
    does reach the queue, assembly claims it solo (typed failure lands
    on ITS future downstream) rather than busy-spinning forever."""
    from concurrent.futures import Future
    adm = serving.AdmissionController(queue_depth=8)
    r = serving.Request("m", {}, 6, Future())
    adm.offer(r)
    batch = adm.take_batch(4, 1.0, lambda req, exc: None)
    assert batch == [r]
    adm.close()


def test_queue_depth_gauge_aggregates_live_servers():
    """Two servers must both contribute to serving.queue_depth (the
    second registration adds, not replaces)."""
    telemetry.reset()
    s1, _, _ = _server(auto_start=False)
    s2 = serving.Server(registry=s1.registry, max_batch_size=4,
                        auto_start=False)
    try:
        x = rng.rand(1, FEAT).astype(np.float32)
        f1 = s1.submit_async("mlp", {"data": x})
        f2 = s2.submit_async("mlp", {"data": x})
        assert telemetry.snapshot()["serving.queue_depth"]["value"] == 2
        s1.start()
        s2.start()
        f1.result(timeout=60)
        f2.result(timeout=60)
    finally:
        s1.close()
        s2.close()


# -- dispatch-thread survival ----------------------------------------------

def test_model_failure_lands_on_futures_not_thread():
    """A model raising mid-dispatch fails that batch's futures and the
    thread keeps serving the next request."""
    server, _, _ = _server()
    try:
        server.warmup()
        model = server.registry.get("mlp")
        real = model.run_batch
        calls = {"n": 0}

        def boom(bucket, inputs):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("injected dispatch failure")
            return real(bucket, inputs)

        model.run_batch = boom
        x = rng.rand(1, FEAT).astype(np.float32)
        with pytest.raises(RuntimeError, match="injected"):
            server.submit("mlp", {"data": x}, timeout=60)
        assert server.batcher.alive
        assert server.submit("mlp", {"data": x}, timeout=60)[0].shape == \
            (1, 3)
    finally:
        server.close()


# -- HTTP front-end --------------------------------------------------------

def test_http_endpoint_predict_health_metrics_and_statuses():
    import json
    from urllib import request as urlreq
    from urllib.error import HTTPError

    server, _, _ = _server(serve_http=True)
    try:
        server.warmup()
        host, port = server.http_address
        base = "http://%s:%d" % (host, port)

        with urlreq.urlopen(base + "/healthz", timeout=30) as r:
            health = json.loads(r.read())
        assert health["models"] == ["mlp"]

        body = json.dumps({"inputs": {"data": [[0.5] * FEAT]}}).encode()
        req = urlreq.Request(base + "/v1/models/mlp:predict", data=body,
                             headers={"Content-Type": "application/json"})
        with urlreq.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        assert len(out["outputs"][0]) == 1  # one row back

        with urlreq.urlopen(base + "/metrics", timeout=30) as r:
            prom = r.read().decode()
        assert "serving_requests_total" in prom.replace(".", "_") or \
            "serving" in prom

        with pytest.raises(HTTPError) as err:
            urlreq.urlopen(urlreq.Request(
                base + "/v1/models/ghost:predict", data=body), timeout=30)
        assert err.value.code == 404  # ModelNotFound -> 404

        with pytest.raises(HTTPError) as err:
            urlreq.urlopen(urlreq.Request(
                base + "/v1/models/mlp:predict", data=b"not json"),
                timeout=30)
        assert err.value.code == 400  # BadRequest -> 400
    finally:
        server.close()


def test_warmup_verify_raises_on_retrace():
    """A model whose dispatch escapes the program cache fails warmup
    verification with MXNetError instead of silently recompiling in
    steady state."""
    server, _, _ = _server(max_batch_size=2)
    try:
        model = server.registry.get("mlp")
        real = model.run_batch

        def cache_buster(bucket, inputs):
            model._by_bucket.pop(bucket, None)  # fresh executor each call
            executor_cache.clear()
            return real(bucket, inputs)

        model.run_batch = cache_buster
        with pytest.raises(MXNetError, match="warmup verification"):
            server.warmup()
    finally:
        server.close()


def test_drain_deadline_rejects_undispatched_with_server_closed(
        monkeypatch):
    """close(drain=True, timeout=...) past the deadline sheds the
    still-queued requests with typed ServerClosed instead of leaving
    their futures hanging on a replica that is going away (the
    preemption grace-period contract); the batch already at the
    predictor still completes."""
    server, _, _ = _server(max_batch_size=1, auto_start=False,
                           batch_window_ms=0.0)
    try:
        server.warmup()
        model = server.registry.get("mlp")
        real = model.run_batch

        def slow(bucket, padded):
            time.sleep(1.0)
            return real(bucket, padded)

        monkeypatch.setattr(model, "run_batch", slow)
        xs = [rng.rand(1, FEAT).astype(np.float32) for _ in range(5)]
        futs = [server.submit_async("mlp", {"data": x}) for x in xs]
        server.start()
        time.sleep(0.1)  # let the dispatch thread claim the first batch
        server.close(drain=True, timeout=0.2)
        completed, rejected = 0, 0
        for f in futs:
            try:
                out = f.result(timeout=30)
                assert out[0].shape[0] == 1
                completed += 1
            except serving.ServerClosed:
                rejected += 1
        assert completed >= 1, "the in-flight batch must finish"
        assert rejected >= 1, "queued work past the deadline must be " \
                              "shed with a typed rejection"
        assert completed + rejected == len(futs)
    finally:
        server.close()


def test_sigterm_drains_serving_with_deadline(monkeypatch):
    """install_signal_handlers wires SIGTERM to close(drain=True,
    timeout=deadline): in-flight work completes, the deadline sheds the
    rest, and new submits get ServerClosed."""
    import os as _os
    import signal as _signal

    server, _, _ = _server(max_batch_size=1, auto_start=False,
                           batch_window_ms=0.0)
    prev = _signal.getsignal(_signal.SIGTERM)
    try:
        server.warmup()
        installed = server.install_signal_handlers(drain_deadline_s=0.2)
        assert _signal.SIGTERM in installed
        model = server.registry.get("mlp")
        real = model.run_batch

        def slow(bucket, padded):
            time.sleep(0.6)
            return real(bucket, padded)

        monkeypatch.setattr(model, "run_batch", slow)
        xs = [rng.rand(1, FEAT).astype(np.float32) for _ in range(4)]
        futs = [server.submit_async("mlp", {"data": x}) for x in xs]
        server.start()
        time.sleep(0.1)
        _os.kill(_os.getpid(), _signal.SIGTERM)
        # the handler only starts the drain thread (lock-safety in
        # signal context); wait for it to mark the server closed
        deadline = time.monotonic() + 5.0
        while not server.closed and time.monotonic() < deadline:
            time.sleep(0.01)
        assert server.closed
        outcomes = {"completed": 0, "rejected": 0}
        for f in futs:
            try:
                f.result(timeout=30)
                outcomes["completed"] += 1
            except serving.ServerClosed:
                outcomes["rejected"] += 1
        assert outcomes["completed"] >= 1
        assert outcomes["rejected"] >= 1
        with pytest.raises(serving.ServerClosed):
            server.submit("mlp", {"data": xs[0]})
    finally:
        _signal.signal(_signal.SIGTERM, prev)
        server.close()
