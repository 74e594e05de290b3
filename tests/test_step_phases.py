"""The fit loop's and the decoder step's phases (PR 25): where the host
work happens, on the profiler's clock, and how long the device had nothing
to run under each — ``observability.instrument.StepTracker``."""
from __future__ import annotations

import glob
import importlib.util
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import executor_cache, profiler
from mxnet_tpu.gluon.model_zoo import transformer_lm
from mxnet_tpu.observability import flight_recorder, instrument
from mxnet_tpu.observability import telemetry, tracing
from mxnet_tpu.serving import KVBlockPool, PagedTransformerDecoder

MS = 1_000_000      # the fake clock counts nanoseconds


@pytest.fixture(autouse=True)
def _clean_slate():
    telemetry.reset()
    tracing.set_recording(False)
    tracing.clear_events()
    instrument._recent.clear()
    yield
    telemetry.reset()
    tracing.set_recording(False)
    tracing.clear_events()
    instrument._recent.clear()


class Clock:
    def __init__(self):
        self.t = 0

    def __call__(self):
        return self.t

    def tick(self, ms):
        self.t += int(ms * MS)


class Buffer:
    """Stands in for a device array: only ``is_ready`` is asked."""

    def __init__(self, ready=False):
        self.ready = ready

    def is_ready(self):
        return self.ready


def _spend(tracker, clock, name, ms, component=False):
    with (tracker.component(name) if component else tracker.phase(name)):
        clock.tick(ms)


def _dispatch(tracker, clock, out, ms=1.0, inputs=()):
    with tracker.phase("fused:dispatch", dispatches=True) as ph:
        clock.tick(ms)
        ph.watch([out], inputs)


# -- the tracker on a fake clock ---------------------------------------------

def test_starved_opens_at_ready_closes_at_dispatch_and_splits_over_phases():
    clock = Clock()
    tr = instrument.StepTracker(clock_ns=clock)
    out = Buffer()
    with tr.component("fwd_bwd_dispatch"):
        _spend(tr, clock, "fused:load", 5)      # before any dispatch: the
        _dispatch(tr, clock, out)               # tracker knows no device yet
    _spend(tr, clock, "data_wait", 50, component=True)      # device busy
    with tr.component("metric"):
        clock.tick(100)
        out.ready = True            # the step finishes under the fetch ...
        clock.tick(3)
    clock.tick(2)                   # ... and is first SEEN ready here: glue
    with tr.component("sync"):
        _spend(tr, clock, "sync:callbacks", 4)
    first = tr.step_end(0)
    assert first["starved_ms"] == pytest.approx(2 + 4)
    assert first["starved_by_ms"] == {"step:glue": 2.0, "sync:callbacks": 4.0}

    clock.tick(1)                                           # glue
    out2 = Buffer()
    with tr.component("fwd_bwd_dispatch"):
        clock.tick(0.5)             # the component's own time
        _spend(tr, clock, "fused:refresh", 2)
        _spend(tr, clock, "fused:load", 15)
        _spend(tr, clock, "fused:scalars", 3)
        _dispatch(tr, clock, out2, ms=6)
        _spend(tr, clock, "fused:scatter", 7)   # in flight: not starved
    _spend(tr, clock, "update", 9, component=True)
    second = tr.step_end(1)
    assert second["starved_by_ms"] == {
        "step:glue": 1.0, "step:fwd_bwd_dispatch": 0.5, "fused:refresh": 2.0,
        "fused:load": 15.0, "fused:scalars": 3.0, "fused:dispatch": 6.0}
    assert second["starved_ms"] == pytest.approx(27.5)
    assert second["ran_ahead"] is False
    assert second["phases_ms"]["fused:scatter"] == 7.0
    assert second["components_ms"]["fwd_bwd_dispatch"] == pytest.approx(33.5)
    snap = telemetry.snapshot()
    assert snap["module.step.starved_ms"]["count"] == 2
    assert snap["module.step.starved_ms"]["sum"] == pytest.approx(33.5)
    assert snap["module.step.starved.fused:load_ms"]["sum"] == 15.0
    assert snap["module.step.phase.fused:load_ms"]["sum"] == 20.0
    assert snap["module.steps_run_ahead"]["value"] == 0.0


def test_a_step_dispatched_with_one_in_flight_runs_ahead_and_adds_nothing():
    clock = Clock()
    tr = instrument.StepTracker(clock_ns=clock)
    a, b = Buffer(), Buffer()
    with tr.component("fwd_bwd_dispatch"):
        _dispatch(tr, clock, a)
    tr.step_end(0)
    clock.tick(3)
    with tr.component("fwd_bwd_dispatch"):
        _spend(tr, clock, "fused:load", 10)
        _dispatch(tr, clock, b)     # a is still running
    a.ready = True                  # the older one drains: b is in flight
    _spend(tr, clock, "data_wait", 20, component=True)
    rec = tr.step_end(1)
    assert rec["ran_ahead"] is True
    assert rec["starved_ms"] == 0.0 and rec["starved_by_ms"] == {}
    assert telemetry.snapshot()["module.steps_run_ahead"]["value"] == 1.0
    with tr.component("metric"):
        b.ready = True              # finishes under the fetch: seen ready
        clock.tick(5)               # at its end
    _spend(tr, clock, "sync", 2, component=True)
    assert tr.step_end(2)["starved_by_ms"] == {"step:sync": 2.0}


def test_a_dispatch_that_waits_for_its_upload_is_starved_until_it_lands():
    clock = Clock()
    tr = instrument.StepTracker(clock_ns=clock)
    out, upload = Buffer(), Buffer()
    with tr.component("fwd_bwd_dispatch"):
        _dispatch(tr, clock, Buffer(ready=True))
        _spend(tr, clock, "fused:scatter", 1)   # sees the first one ready
    tr.step_end(0)
    with tr.component("fwd_bwd_dispatch"):
        _dispatch(tr, clock, out, ms=2, inputs=[upload])
        _spend(tr, clock, "fused:scatter", 4)   # upload still under way
    with tr.component("data_wait"):
        clock.tick(10)
        upload.ready = True     # lands somewhere in here: a lower bound
        clock.tick(30)          # leaves the whole span out
    _spend(tr, clock, "sync", 5, component=True)
    rec = tr.step_end(1)
    assert rec["starved_by_ms"] == {"fused:dispatch": 2.0,
                                    "fused:scatter": 4.0}
    assert rec["starved_ms"] == pytest.approx(6.0)


def test_decode_names_drain_at_the_fetch_and_cancel_goes_to_the_glue():
    clock = Clock()
    tr = instrument.StepTracker(pid="serving", names=instrument.DECODE,
                                clock_ns=clock)
    for it in range(2):
        _spend(tr, clock, "decode:admit", 1)
        _spend(tr, clock, "decode:tables", 2)
        with tr.phase("decode:dispatch", dispatches=True):
            clock.tick(3)
        with tr.phase("decode:fetch", drains=True):
            clock.tick(40)
        _spend(tr, clock, "decode:commit", 4)
        rec = tr.step_end(it)
        clock.tick(5)           # the caller's loop
    assert rec["starved_by_ms"] == {
        "decode:between_calls": 5.0, "decode:admit": 1.0,
        "decode:tables": 2.0, "decode:dispatch": 3.0, "decode:commit": 4.0}
    # an iteration that finds nothing to run is dropped, its time kept
    _spend(tr, clock, "decode:admit", 1)
    tr.cancel_step()
    clock.tick(2)
    _spend(tr, clock, "decode:admit", 1)
    with tr.phase("decode:dispatch", dispatches=True):
        clock.tick(3)
    rec = tr.step_end(2)
    assert rec["starved_by_ms"] == {"decode:between_calls": 5.0 + 1.0 + 2.0,
                                    "decode:admit": 1.0,
                                    "decode:dispatch": 3.0}
    assert [r["step"] for r in instrument.recent_steps("serving")] \
        == [0, 1, 2]
    snap = telemetry.snapshot()
    assert snap["serving.decode.steps"]["value"] == 3.0
    assert snap["serving.decode.phase.decode:fetch_ms"]["sum"] == 80.0
    assert snap["serving.decode.starved.decode:between_calls_ms"]["sum"] \
        == pytest.approx(13.0)
    assert instrument.recent_steps("train") == []


def test_both_sinks_off_hands_back_the_shared_noop(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_TELEMETRY", "0")
    tr = instrument.StepTracker()
    assert tr.component("data_wait") is instrument._NOOP_CM
    assert tr.phase("fused:load", dispatches=True) is instrument._NOOP_CM
    assert instrument.phase("fused:load") is instrument._NOOP_CM
    with tr.phase("fused:dispatch", dispatches=True) as ph:
        ph.watch([Buffer()])
    assert tr.step_end(0) is None and instrument.recent_steps() == []
    # the profiler alone brings them to life
    tracing.set_recording(True)
    assert tr.component("data_wait") is not instrument._NOOP_CM


def test_the_ring_is_bounded_and_a_phase_finds_the_open_tracker(monkeypatch):
    monkeypatch.setattr(instrument, "RECENT_STEPS", 4)
    assert instrument.phase("fused:load") is instrument._NOOP_CM
    tr = instrument.StepTracker(pid="ringtest")
    for k in range(6):
        with tr.component("fwd_bwd_dispatch"):
            # what FusedTrainStep.run does: no tracker in hand
            with instrument.phase("fused:load"):
                pass
        tr.step_end(k)
    assert instrument.phase("fused:load") is instrument._NOOP_CM
    ring = instrument.recent_steps("ringtest")
    assert [r["step"] for r in ring] == [2, 3, 4, 5]
    assert all("fused:load" in r["phases_ms"] and r["starved_ms"] is None
               for r in ring)


# -- through Module.fit, on the profiler's clock ----------------------------

def _mlp():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8,
                                name="ph_fc1")
    net = mx.sym.Activation(net, act_type="relu", name="ph_relu1")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="ph_fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _fit(**kw):
    rng = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rng.rand(24, 8).astype(np.float32),
                           rng.randint(0, 4, (24,)).astype(np.float32),
                           batch_size=8)
    mx.random.seed(3)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.1}, **kw)
    assert mod._fused_step is not None and mod._fused_step.ran
    return mod


def _host_events(trace_dir, prefix="mx:"):
    """[(name, start, end, stats)] of the host planes, by start."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((e.name, e.start_ns, e.start_ns
                                + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _jax_trace(trace_dir):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def _inside(events, child, parent):
    """Every ``child`` event lies within some ``parent`` event."""
    kids = [e for e in events if e[0] == child]
    folks = [e for e in events if e[0] == parent]
    return bool(kids) and all(
        any(p[1] <= k[1] and k[2] <= p[2] for p in folks) for k in kids)


def test_fit_phases_lie_in_the_device_trace_nested_and_add_no_retrace(
        tmp_path, monkeypatch):
    import jax
    executor_cache.clear()
    before = executor_cache.trace_counts()
    _jax_trace(tmp_path)
    try:
        _fit()
    finally:
        jax.profiler.stop_trace()
    traced = {k: v - before.get(k, 0)
              for k, v in executor_cache.trace_counts().items()}
    events = _host_events(tmp_path)
    names = {e[0] for e in events}
    assert {"mx:step", "mx:step:data_wait", "mx:step:fwd_bwd_dispatch",
            "mx:step:update", "mx:step:metric", "mx:step:sync",
            "mx:fused_train_step", "mx:fused:refresh", "mx:fused:load",
            "mx:fused:scalars", "mx:fused:dispatch", "mx:fused:scatter",
            "mx:metric:fetch", "mx:sync:prepare",
            "mx:sync:callbacks"} <= names
    assert sum(1 for e in events if e[0] == "mx:step") == 3
    for child in ("mx:fused:load", "mx:fused:dispatch", "mx:fused:scatter"):
        assert _inside(events, child, "mx:step:fwd_bwd_dispatch"), child
    assert _inside(events, "mx:sync:callbacks", "mx:step:sync")
    assert _inside(events, "mx:metric:fetch", "mx:step:metric")
    assert _inside(events, "mx:step:metric", "mx:step")
    # one step number on every span of a step, and the span that caused it
    loads = [e for e in events if e[0] == "mx:fused:load"]
    assert [e[3]["step"] for e in loads] == [0, 1, 2]
    assert all(e[3]["parent"] == "fused_train_step" for e in loads)

    ring = instrument.recent_steps()
    assert [r["step"] for r in ring] == [0, 1, 2]
    for r in ring:
        fused = sum(v for p, v in r["phases_ms"].items()
                    if p.startswith("fused:"))
        assert fused <= r["phases_ms"]["fused_train_step"] * 1.001 + 0.01
        assert r["phases_ms"]["fused_train_step"] \
            <= r["components_ms"]["fwd_bwd_dispatch"] * 1.001 + 0.01
        sync = sum(v for p, v in r["phases_ms"].items()
                   if p.startswith("sync:"))
        assert sync <= r["components_ms"]["sync"] * 1.001 + 0.01
        assert r["starved_ms"] is not None and r["ran_ahead"] is False
        assert r["end_s"] > 0
    snap = telemetry.snapshot()
    assert snap["module.step.phase.fused:dispatch_ms"]["count"] == 3
    assert snap["module.step.starved_ms"]["count"] == 3

    # the same fit with telemetry off traces the same programs
    monkeypatch.setenv("MXNET_TPU_TELEMETRY", "0")
    telemetry.reset()
    executor_cache.clear()
    before = executor_cache.trace_counts()
    _fit()
    untracked = {k: v - before.get(k, 0)
                 for k, v in executor_cache.trace_counts().items()}
    assert traced == untracked and sum(traced.values()) > 0
    assert len(instrument.recent_steps()) == 3      # nothing was added


def test_the_flight_recorder_keeps_the_steps_own_record(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_HEALTH", "1")
    flight_recorder.reset()
    try:
        _fit()
        steps = list(flight_recorder.get_recorder()._steps)
    finally:
        flight_recorder.reset()
    assert len(steps) == 3
    ring = instrument.recent_steps()
    for entry, rec in zip(steps, ring):
        assert entry["timings"] == rec
        assert "sync:health" in entry["timings"]["phases_ms"]


def test_a_live_profiler_span_is_an_annotation_too(tmp_path):
    import jax
    _jax_trace(tmp_path)
    try:
        with tracing.span("stopped"):       # mx.profiler not recording
            pass
        tracing.set_recording(True)
        with profiler.record_span("outer"):
            with tracing.span("inner"):
                pass
    finally:
        tracing.set_recording(False)
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    assert [e[0] for e in events] == ["mx:outer", "mx:inner"]
    assert _inside(events, "mx:inner", "mx:outer")


# -- through the paged decoder ----------------------------------------------

VOCAB, EMBED, HEADS, LAYERS, SEQ = 64, 32, 2, 1, 64


@pytest.fixture(scope="module")
def lm_params():
    lm = transformer_lm(VOCAB, embed_dim=EMBED, num_heads=HEADS,
                        num_layers=LAYERS, seq_len=SEQ)
    lm.initialize()
    _ = lm(mx.nd.array(np.zeros((1, SEQ), np.float32)))
    return lm.decode_param_arrays(), lm.config


def test_decoder_iterations_carry_their_phases(lm_params, tmp_path):
    import jax
    params, config = lm_params
    pool = KVBlockPool(LAYERS, HEADS, EMBED // HEADS, num_pages=24,
                       page_size=8, name="phases.kv")
    dec = PagedTransformerDecoder(params, config, slot_count=3, pool=pool,
                                  name="phases")
    try:
        dec.warmup()
        assert dec.step() == 0          # nothing to run: no record
        assert instrument.recent_steps("serving") == []
        stream = dec.submit([1, 2, 3, 4], max_new_tokens=3)
        tracing.set_recording(True)
        _jax_trace(tmp_path)
        try:
            with executor_cache.watch_traces() as w:
                while not stream.done:
                    dec.step()
        finally:
            jax.profiler.stop_trace()
            tracing.set_recording(False)
        assert w.total() == 0
        assert len(stream.outputs()[0]) == 3
    finally:
        dec.close()
    iters = dec.iterations
    ring = instrument.recent_steps("serving")
    assert [r["step"] for r in ring] == list(range(iters))
    phases = {"decode:admit", "decode:tables", "decode:dispatch",
              "decode:fetch", "decode:commit"}
    for r in ring:
        assert set(r["phases_ms"]) == phases and not r["components_ms"]
        assert sum(r["phases_ms"].values()) <= r["total_ms"] * 1.001 + 0.01
        # the fetch is a wait for the device, never starved time
        assert "decode:fetch" not in r["starved_by_ms"]
        assert r["starved_ms"] is not None
    assert "decode:between_calls" in ring[-1]["starved_by_ms"]

    events = _host_events(tmp_path)
    per_iter = [e for e in events if e[0] == "mx:decode:iter"]
    assert [e[3]["step"] for e in per_iter] == list(range(iters))
    for name in phases:
        assert _inside(events, "mx:" + name, "mx:decode:iter"), name
        assert sum(1 for e in events if e[0] == "mx:" + name) == iters
    # the profiler's own span keeps its name and extent, on both clocks
    assert sum(1 for e in events
               if e[0] == "mx:serving:paged_decode_step") == iters
    assert _inside(events, "mx:decode:fetch", "mx:serving:paged_decode_step")
    chrome = [e for e in tracing.snapshot_events() if e.get("ph") == "X"]
    assert sum(1 for e in chrome
               if e["name"] == "serving:paged_decode_step") == iters
    assert {e["name"] for e in chrome if e["cat"] == "step"} \
        == phases | {"decode:iter"}
    snap = telemetry.snapshot()
    assert snap["serving.decode.phase.decode:tables_ms"]["count"] == iters
    assert snap["serving.decode.starved_ms"]["count"] == iters
    assert snap["serving.decode.steps_run_ahead"]["value"] == 0.0


# -- tools/traceview.py -------------------------------------------------------

def test_traceview_breakdown_gains_the_starved_column(tmp_path, capsys):
    fname = str(tmp_path / "fit_trace.json")
    profiler.profiler_set_config(mode="symbolic", filename=fname)
    profiler.profiler_set_state("run")
    _fit()
    profiler.profiler_set_state("stop")
    tv_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "traceview.py")
    spec = importlib.util.spec_from_file_location("_tv_phases", tv_path)
    tv = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tv)
    events = tv.load_trace(fname)["traceEvents"]
    steps = [e for e in events if e["ph"] == "X" and e["name"] == "step"]
    assert len(steps) == 3 and all("starved_ms" in e["args"] for e in steps)
    bd = tv.step_breakdown(events)
    assert bd["starved_ms"] == pytest.approx(
        sum(e["args"]["starved_ms"] for e in steps))
    assert set(bd["starved_by"]) <= set(tv.STEP_COMPONENTS) | {"glue"}
    assert sum(bd["starved_by"].values()) == pytest.approx(
        bd["starved_ms"], abs=1e-2)
    assert bd["ran_ahead"] == 0
    assert tv.main([fname]) == 0
    out = capsys.readouterr().out
    assert "Starved(ms)" in out and "device starved" in out
    # the phases are in the Chrome buffer under the fused step's old name
    fused = [e for e in events if e["name"] == "fused_train_step"]
    assert len(fused) == 3
    loads = [e for e in events if e["name"] == "fused:load"]
    assert [e["args"]["parent"] for e in loads] == ["fused_train_step"] * 3
