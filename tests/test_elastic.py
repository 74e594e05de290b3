"""Elastic training: the legacy epoch-granular restart surface plus the
step-granular preemption-safe subsystem (``mxnet_tpu/elastic/``):
atomic sha256-manifested snapshots, corrupt-fallback, SIGTERM drain,
chaos fault plans, bitwise resume, and optimizer-state round trips
across a mesh re-factorization (SURVEY.md §5.3 / ps-lite tracker
parity; docs/elastic.md)."""
import json
import os
import pickle
import signal

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import elastic
from mxnet_tpu.elastic import Checkpointer, PreemptedError, chaos
from mxnet_tpu.elastic.checkpoint import (PARAMS_FILE, Snapshot,
                                          SnapshotError)


def _net():
    # explicit names: a restarted process resets auto-name counters, but
    # within one test process a second _net() would continue counting and
    # the checkpoint's param names would not match
    net = mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=8, name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="act1")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _data():
    rng = np.random.RandomState(0)
    X = rng.rand(64, 6).astype(np.float32)
    y = (X.sum(axis=1) > 3).astype(np.float32)
    return mx.io.NDArrayIter(X, y, batch_size=16)


def test_latest_checkpoint_discovery(tmp_path):
    prefix = os.path.join(str(tmp_path), "m")
    assert elastic.latest_checkpoint(prefix) is None
    assert elastic.resume_epoch(prefix) == 0
    net = _net()
    for ep in (1, 2, 7):
        mx.model.save_checkpoint(prefix, ep, net,
                                 {"w": mx.nd.ones((2,))}, {})
    ep, path = elastic.latest_checkpoint(prefix)
    assert ep == 7 and path.endswith("m-0007.params")


def test_fit_elastic_resumes_after_crash(tmp_path):
    prefix = os.path.join(str(tmp_path), "job")
    it = _data()

    class Boom(RuntimeError):
        pass

    # first run: crash after epoch 2's checkpoint is written
    def bomb(iter_no, sym, arg, aux):
        if iter_no + 1 == 2:
            raise Boom()

    mod = mx.mod.Module(_net(), context=mx.cpu())
    with pytest.raises(Boom):
        elastic.fit_elastic(mod, it, prefix, num_epoch=4,
                            epoch_end_callback=[bomb])
    assert elastic.resume_epoch(prefix) == 2

    # "restarted process": fresh module, same command — resumes at epoch 2
    it.reset()
    mod2 = mx.mod.Module(_net(), context=mx.cpu())
    elastic.fit_elastic(mod2, it, prefix, num_epoch=4)
    assert elastic.resume_epoch(prefix) == 4

    # resumed params come from the checkpoint (training continued, so the
    # final checkpoint differs from epoch 2's)
    _, args2, _ = mx.model.load_checkpoint(prefix, 2)
    _, args4, _ = mx.model.load_checkpoint(prefix, 4)
    diff = sum(float(np.abs(args2[k].asnumpy()
                            - args4[k].asnumpy()).sum()) for k in args2)
    assert diff > 0

    # already complete: no-op
    it.reset()
    mod3 = mx.mod.Module(_net(), context=mx.cpu())
    elastic.fit_elastic(mod3, it, prefix, num_epoch=4)
    assert elastic.resume_epoch(prefix) == 4


def test_dead_nodes_api():
    assert elastic.dead_nodes() == []
    kv = mx.kv.create("local")
    # parity alias present on the kvstore too, if exposed
    assert not getattr(kv, "get_dead_nodes", lambda *_: [])(60)


def test_fit_elastic_restores_optimizer_states(tmp_path):
    """Momentum survives the restart: .states files are written per epoch
    and loaded on resume."""
    prefix = os.path.join(str(tmp_path), "mom")
    it = _data()
    mod = mx.mod.Module(_net(), context=mx.cpu())

    class Boom(RuntimeError):
        pass

    def bomb(iter_no, *a):
        if iter_no + 1 == 2:
            raise Boom()

    with pytest.raises(Boom):
        elastic.fit_elastic(mod, it, prefix, num_epoch=3,
                            optimizer="sgd",
                            optimizer_params={"learning_rate": 0.1,
                                              "momentum": 0.9},
                            epoch_end_callback=[bomb])
    assert os.path.exists(prefix + "-0002.states")

    it.reset()
    mod2 = mx.mod.Module(_net(), context=mx.cpu())
    elastic.fit_elastic(mod2, it, prefix, num_epoch=3,
                        optimizer="sgd",
                        optimizer_params={"learning_rate": 0.1,
                                          "momentum": 0.9})
    # resumed module restored non-trivial momentum before continuing
    import pickle
    raw = open(prefix + "-0002.states", "rb").read()
    assert raw  # states were persisted for the resume point
    assert os.path.exists(prefix + "-0003.states")


# -- step-granular preemption-safe subsystem ---------------------------------

def _fit_kwargs():
    return dict(optimizer_params={"learning_rate": 0.1,
                                  "momentum": 0.9})


def _params_of(mod):
    return {n: mod._exec_group.execs[0].arg_dict[n].asnumpy()
            for n in mod._exec_group.param_names}


def _run(tmp_path, num_epoch=4, ckpt=None, seed=0, net_fn=None,
         chaos_plan=None):
    """One fit over the 64x6 smoke task; returns (module, params)."""
    mx.random.seed(seed)
    it = _data()
    mod = mx.mod.Module((net_fn or _net)(), context=mx.cpu())
    if ckpt is not None:
        ckpt.attach(mod)
    if chaos_plan is not None:
        chaos.ChaosMonkey(chaos_plan).arm(ckpt)
    mod.fit(it, num_epoch=num_epoch, **_fit_kwargs())
    return mod, _params_of(mod)


def test_checkpointer_schedule_retention_and_manifest(tmp_path):
    d = str(tmp_path / "ck")
    ckpt = Checkpointer(directory=d, every_steps=3, keep=2)
    _run(tmp_path, num_epoch=3, ckpt=ckpt)  # 12 steps -> snaps 3,6,9,12
    snaps = ckpt.snapshots()
    # retention: only the newest `keep` survive
    assert [s.step for _, s in snaps] == [9, 12]
    snap = ckpt.latest()
    assert snap.step == 12 and snap.reason == "schedule"
    assert snap.verify() == []
    m = snap.manifest
    assert m["data_position"]["consumed_batches"] == 4  # epoch boundary
    assert m["data_shapes"][0]["name"] == "data"
    assert m["files"][PARAMS_FILE]["bytes"] > 0
    # params artifact round-trips through the manifest contract
    args, auxs = snap.load_params()
    assert sorted(args) == ["fc1_bias", "fc1_weight", "fc2_bias",
                            "fc2_weight"]


def test_corrupt_snapshot_skipped_at_verify(tmp_path):
    d = str(tmp_path / "ck")
    ckpt = Checkpointer(directory=d, every_steps=4, keep=5)
    _run(tmp_path, num_epoch=3, ckpt=ckpt)  # snaps 4, 8, 12
    newest = ckpt.snapshots()[-1][0]
    chaos.corrupt_snapshot(newest)
    snap = Snapshot.open(newest)
    assert any("sha256" in p for p in snap.verify())
    picked = ckpt.latest()
    assert picked.step == 8  # fell back past the corrupt newest
    # a snapshot directory with no manifest is invisible to latest()
    import shutil
    os.remove(os.path.join(ckpt.snapshots()[0][0], "manifest.json"))
    assert ckpt.latest().step == 8


def test_resume_fit_bitwise_after_chaos_kill(tmp_path):
    d = str(tmp_path / "ck")
    _, p_straight = _run(tmp_path, num_epoch=4)

    ckpt = Checkpointer(directory=d, every_steps=3, keep=3)
    plan = chaos.FaultPlan([{"kind": "kill_at_step", "step": 10,
                             "mode": "raise"}])
    with pytest.raises(chaos.WorkerKilled):
        _run(tmp_path, num_epoch=4, ckpt=ckpt, chaos_plan=plan)
    # snapshots 3,6,9 on disk; corrupt the newest -> resume from 6
    chaos.corrupt_snapshot(ckpt.snapshots()[-1][0])

    mx.random.seed(0)
    it = _data()
    mod = mx.mod.Module(_net(), context=mx.cpu())
    report = elastic.resume_fit(mod, it, num_epoch=4, directory=d,
                                **_fit_kwargs())
    assert report.step == 6
    assert report.begin_epoch == 1 and report.skip_batches == 2
    assert not report.refactorized
    p_resumed = _params_of(mod)
    for k in p_straight:
        assert np.array_equal(p_straight[k], p_resumed[k]), k


def test_resume_without_snapshot_raises(tmp_path):
    mod = mx.mod.Module(_net(), context=mx.cpu())
    with pytest.raises(SnapshotError):
        elastic.resume(mod, directory=str(tmp_path / "empty"))


def test_write_retry_backoff_survives_transient_failures(tmp_path):
    d = str(tmp_path / "ck")
    ckpt = Checkpointer(directory=d, every_steps=0, keep=3)
    failures = {"left": 2, "seen": 0}

    def flaky(path):
        failures["seen"] += 1
        if failures["left"] > 0:
            failures["left"] -= 1
            raise OSError("transient volume hiccup")

    ckpt.pre_write_hooks.append(flaky)
    mx.random.seed(0)
    it = _data()
    mod = mx.mod.Module(_net(), context=mx.cpu())
    mod.fit(it, num_epoch=1, **_fit_kwargs())
    ckpt.step = 4
    path = ckpt.save(mod, epoch=0, batch=3, reason="manual")
    assert failures["seen"] >= 3  # 2 failures + the success
    assert Snapshot.open(path).verify() == []


def test_write_stall_fault_and_exhausted_retries(tmp_path):
    d = str(tmp_path / "ck")
    ckpt = Checkpointer(directory=d, every_steps=0)
    plan = chaos.FaultPlan([{"kind": "write_stall", "seconds": 0.01,
                             "count": 1}])
    monkey = chaos.ChaosMonkey(plan).arm(ckpt)
    mx.random.seed(0)
    it = _data()
    mod = mx.mod.Module(_net(), context=mx.cpu())
    mod.fit(it, num_epoch=1, **_fit_kwargs())
    ckpt.save(mod, reason="manual")
    assert monkey.fired and monkey.fired[0]["kind"] == "write_stall"

    # permanent failure: retries exhaust into SnapshotError, and no
    # committed snapshot appears
    before = len(ckpt.snapshots())
    ckpt.pre_write_hooks.append(
        lambda path: (_ for _ in ()).throw(OSError("dead volume")))
    with pytest.raises(SnapshotError):
        ckpt.save(mod, reason="manual")
    assert len(ckpt.snapshots()) == before


def test_preemption_sigterm_snapshots_and_raises(tmp_path):
    d = str(tmp_path / "ck")
    ckpt = Checkpointer(directory=d, every_steps=0, keep=3,
                        drain_deadline_s=30.0)
    installed = ckpt.install_signal_handlers()
    try:
        # SIGINT is hooked too (the docs' SIGTERM/SIGINT promise)
        assert installed == [signal.SIGTERM, signal.SIGINT]
        mx.random.seed(0)
        it = _data()
        mod = mx.mod.Module(_net(), context=mx.cpu())
        ckpt.attach(mod)

        def send_sigterm(param):
            if param.nbatch == 1:
                os.kill(os.getpid(), signal.SIGTERM)

        with pytest.raises(PreemptedError) as err:
            mod.fit(it, num_epoch=4, batch_end_callback=[send_sigterm],
                    **_fit_kwargs())
        assert err.value.snapshot_path is not None
        snap = ckpt.latest()
        assert snap.reason == "preempt"
        # the in-flight step drained: the snapshot is a step boundary
        assert snap.step == err.value.step
    finally:
        ckpt.remove_signal_handlers()


def test_preemption_past_drain_deadline_skips_snapshot(tmp_path):
    d = str(tmp_path / "ck")
    ckpt = Checkpointer(directory=d, every_steps=0,
                        drain_deadline_s=0.0)
    mx.random.seed(0)
    it = _data()
    mod = mx.mod.Module(_net(), context=mx.cpu())
    ckpt.attach(mod)
    ckpt.preempt()
    with pytest.raises(PreemptedError) as err:
        mod.fit(it, num_epoch=1, **_fit_kwargs())
    assert err.value.snapshot_path is None
    assert ckpt.snapshots() == []


def test_anomaly_checkpoint_after_flight_dump(tmp_path, monkeypatch):
    """Dump-then-checkpoint ordering: the health monitor's flight dump
    exists BEFORE the anomaly snapshot commits (black box first)."""
    from mxnet_tpu.observability import flight_recorder, health

    monkeypatch.setenv("MXNET_TPU_HEALTH", "1")
    monkeypatch.setenv("MXNET_TPU_HEALTH_RULES",
                       "grad_spike=dump,nonfinite=warn")
    monkeypatch.setenv("MXNET_TPU_FLIGHT_PATH",
                       str(tmp_path / "flight.json"))
    flight_recorder.reset()
    d = str(tmp_path / "ck")
    ckpt = Checkpointer(directory=d, every_steps=0, keep=3)
    order = []
    real_save = ckpt.save

    def spy_save(module, **kw):
        if kw.get("reason", "").startswith("anomaly"):
            order.append(("snapshot_commit",
                          os.path.exists(str(tmp_path / "flight.json"))))
        return real_save(module, **kw)

    ckpt.save = spy_save
    mx.random.seed(0)
    it = _data()
    mod = mx.mod.Module(_net(), context=mx.cpu())
    ckpt.attach(mod)
    # fake a spike via the monitor directly once fit created it
    mod.fit(it, num_epoch=1, **_fit_kwargs())
    mon = mod._health_mon
    base = {"grad_norm": 1.0, "param_norm": 1.0, "out_mean": 0.5,
            "all_finite": 1.0, "update_ratio": 0.1}
    for step in range(8):
        mon.observe(step, dict(base))
    mon.observe(99, dict(base, grad_norm=1e6))  # spike -> dump action
    # the callback marked the snapshot pending; the next fit step
    # boundary commits it
    it.reset()
    mod.fit(it, num_epoch=1, **_fit_kwargs())
    assert order and order[0] == ("snapshot_commit", True)
    snap = ckpt.latest()
    assert snap.reason == "anomaly:grad_spike"
    flight_recorder.reset()


def test_flight_elastic_ring_and_traceview(tmp_path):
    from mxnet_tpu.observability import flight_recorder

    flight_recorder.reset()
    d = str(tmp_path / "ck")
    ckpt = Checkpointer(directory=d, every_steps=2, keep=3)
    _run(tmp_path, num_epoch=1, ckpt=ckpt)
    rec = flight_recorder.get_recorder()
    assert rec.elastic_recorded() >= 2
    assert rec.last_checkpoint_step() == 4
    path = rec.dump(path=str(tmp_path / "dump.json"), reason="test")
    with open(path) as f:
        doc = json.load(f)
    tv = _load_traceview()
    stats = tv.elastic_stats(tv.elastic_records(doc))
    assert stats["last_checkpoint_step"] == 4
    assert stats["by_kind"]["checkpoint"] == 2
    rendered = tv.summarize_elastic(tv.elastic_records(doc))
    assert "last checkpoint: step 4" in rendered
    assert "last checkpoint: step 4" in tv.summarize_flight(doc)
    flight_recorder.reset()


def _load_traceview():
    import importlib.util
    tv_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "traceview.py")
    spec = importlib.util.spec_from_file_location("_elastic_traceview",
                                                  tv_path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_fault_plan_validation_and_dryrun():
    plan = chaos.FaultPlan.from_json(json.dumps(
        [{"kind": "kill_at_step", "step": 5},
         {"kind": "corrupt_checkpoint", "at_step": 4},
         {"kind": "write_stall", "seconds": 0.5}]))
    text = plan.dryrun()
    assert "kill worker at step 5" in text
    assert plan.faults[0]["mode"] == "exit"
    assert plan.faults[0]["exit_code"] == chaos.DEFAULT_KILL_EXIT
    with pytest.raises(mx.base.MXNetError):
        chaos.FaultPlan([{"kind": "meteor_strike"}])
    with pytest.raises(mx.base.MXNetError):
        chaos.FaultPlan([{"kind": "kill_at_step"}])  # missing step
    with pytest.raises(mx.base.MXNetError):
        chaos.FaultPlan.from_json("{not json")
    assert chaos.FaultPlan.from_env() is None


def test_chaos_corrupt_checkpoint_hook(tmp_path):
    d = str(tmp_path / "ck")
    ckpt = Checkpointer(directory=d, every_steps=2, keep=10)
    plan = chaos.FaultPlan([{"kind": "corrupt_checkpoint",
                             "at_step": 4}])
    _run(tmp_path, num_epoch=2, ckpt=ckpt, chaos_plan=plan)
    # snap 4 was corrupted right after commit; 2 and later ones intact
    snaps = {s.step: s for _, s in ckpt.snapshots()}
    assert snaps[4].verify() != []
    assert snaps[2].verify() == []
    assert ckpt.latest().step == 8


# -- optimizer-state round trip across a mesh re-factorization ---------------

def _dp_mlp():
    h = mx.sym.Activation(mx.sym.FullyConnected(
        mx.sym.var("data"), num_hidden=32, name="fc1"), act_type="relu")
    return mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        h, num_hidden=4, name="fc2"), name="softmax")


def _dp_fit(n_dev, epochs=2):
    rng = np.random.RandomState(0)
    X = rng.randn(256, 16).astype(np.float32)
    y = (np.arange(256) % 4).astype(np.float32)
    mx.random.seed(0)
    it = mx.io.NDArrayIter(X, y, batch_size=64, shuffle=False)
    mod = mx.mod.Module(_dp_mlp(), context=[mx.cpu(i)
                                            for i in range(n_dev)])
    mod.fit(it, num_epoch=epochs, kvstore="tpu_ici", **_fit_kwargs())
    return mod


def _assert_states_bitwise(mod_a, mod_b):
    """Momentum (and the f32 master, where a parameter has one) of
    every parameter, bitwise."""
    sa = mod_a._fused_step.export_states()
    sb = mod_b._fused_step.export_states()
    assert sorted(sa) == sorted(sb) == sorted(
        mod_a._fused_step.param_names)
    for name in sa:
        assert np.array_equal(np.asarray(sa[name]["state"]),
                              np.asarray(sb[name]["state"])), name
        assert ("master" in sa[name]) == ("master" in sb[name])
        if "master" in sa[name]:
            assert np.array_equal(sa[name]["master"], sb[name]["master"])


def test_optimizer_roundtrip_dp8_to_dp8_bitwise(tmp_path):
    mod8 = _dp_fit(8)
    path = str(tmp_path / "opt.states")
    mod8.save_optimizer_states(path)
    raw = pickle.load(open(path, "rb"))
    assert raw["format"] == "fused_v2"
    assert sorted(raw["states"]) == sorted(mod8._fused_step.param_names)

    mod8b = _dp_fit(8, epochs=1)
    mod8b.load_optimizer_states(path)
    _assert_states_bitwise(mod8, mod8b)


def test_optimizer_roundtrip_dp8_to_dp4_bitwise(tmp_path):
    """The state is replicated over the dp mesh, so a narrower mesh
    takes it as it is."""
    mod8 = _dp_fit(8)
    path = str(tmp_path / "opt.states")
    mod8.save_optimizer_states(path)

    mod4 = _dp_fit(4, epochs=1)
    mod4.load_optimizer_states(path)
    _assert_states_bitwise(mod8, mod4)
    for st in mod4._fused_step.states:
        assert len(st.devices()) == 4


# -- review-hardening regressions --------------------------------------------

def test_double_preemption_positions_stay_absolute(tmp_path):
    """A snapshot written DURING the resumed partial epoch must record
    the absolute data position (fit's nbatch restarts at 0 after the
    fast-forward): kill -> resume -> kill again -> resume again still
    replays the uninterrupted run bitwise."""
    d = str(tmp_path / "ck")
    _, p_straight = _run(tmp_path, num_epoch=4)

    ckpt = Checkpointer(directory=d, every_steps=3, keep=3)
    plan = chaos.FaultPlan([{"kind": "kill_at_step", "step": 10,
                             "mode": "raise"}])
    with pytest.raises(chaos.WorkerKilled):
        _run(tmp_path, num_epoch=4, ckpt=ckpt, chaos_plan=plan)
    assert [s.step for _, s in ckpt.snapshots()] == [3, 6, 9]

    # first resume: from 9 = epoch 2, skip 1; second kill at step 14
    mx.random.seed(0)
    it = _data()
    mod = mx.mod.Module(_net(), context=mx.cpu())
    ck2 = Checkpointer(directory=d, every_steps=3, keep=3)
    plan2 = chaos.FaultPlan([{"kind": "kill_at_step", "step": 14,
                              "mode": "raise"}])
    chaos.ChaosMonkey(plan2).arm(ck2)
    with pytest.raises(chaos.WorkerKilled):
        elastic.resume_fit(mod, it, num_epoch=4, checkpointer=ck2,
                           **_fit_kwargs())
    # snap-12 was written in the resumed partial epoch (raw nbatch 2,
    # absolute batch 3): the offset must be re-added
    snap12 = {s.step: s for _, s in ck2.snapshots()}[12]
    assert snap12.data_position["consumed_batches"] == 4, \
        snap12.data_position

    # second resume: must not replay any epoch-2 batch
    mx.random.seed(0)
    it2 = _data()
    mod2 = mx.mod.Module(_net(), context=mx.cpu())
    report = elastic.resume_fit(mod2, it2, num_epoch=4, directory=d,
                                **_fit_kwargs())
    assert report.step == 12 and report.skip_batches == 4
    p_resumed = _params_of(mod2)
    for k in p_straight:
        assert np.array_equal(p_straight[k], p_resumed[k]), k


def test_schedule_save_failure_does_not_kill_training(tmp_path):
    """A checkpoint-volume outage outlasting the write retries costs
    the snapshot, not the healthy run (the schedule trigger degrades
    like the anomaly/preempt triggers)."""
    d = str(tmp_path / "ck")
    ckpt = Checkpointer(directory=d, every_steps=2, keep=3)
    ckpt.pre_write_hooks.append(
        lambda path: (_ for _ in ()).throw(OSError("volume gone")))
    mod, _ = _run(tmp_path, num_epoch=1, ckpt=ckpt)  # must complete
    assert ckpt.snapshots() == []
    assert ckpt.step == 4  # training ran to the end regardless


def test_diverged_snapshot_records_position(tmp_path, monkeypatch):
    """The raise-action divergence snapshot carries the diverged
    step's (epoch, batch) — its update is in the saved params, so a
    resume continues the data stream at the next batch."""
    from mxnet_tpu.observability import flight_recorder, health

    monkeypatch.setenv("MXNET_TPU_HEALTH", "1")
    monkeypatch.setenv("MXNET_TPU_FLIGHT_PATH",
                       str(tmp_path / "flight.json"))
    flight_recorder.reset()
    d = str(tmp_path / "ck")
    ckpt = Checkpointer(directory=d, every_steps=0, keep=3)
    rng = np.random.RandomState(0)
    X = rng.rand(64, 6).astype(np.float32)
    X[32:48] = np.nan  # batch 2 of a 16-row iterator goes non-finite
    y = (np.nansum(X, axis=1) > 3).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mx.random.seed(0)
    mod = mx.mod.Module(_net(), context=mx.cpu())
    ckpt.attach(mod)
    with pytest.raises(health.TrainingDivergedError):
        mod.fit(it, num_epoch=1, **_fit_kwargs())
    snap = ckpt.latest()
    assert snap.reason == "diverged"
    assert snap.epoch == 0
    assert snap.data_position["consumed_batches"] == 3  # batch 2 done
    # the diverged step's update is in the params: the step counter
    # counts it (steps 1,2 via on_step + the diverged step 3)
    assert snap.step == 3
    flight_recorder.reset()
