"""The fused data-parallel step (module/fused_step.py): ONE program over
a ``dp`` mesh whose gradient all-reduce XLA derives from the shardings.

- it trains what the one-device fused step trains, on the same global
  batches, for the kinds of program whose math depends on the WHOLE
  batch: BatchNorm (auxiliary state from global-batch statistics),
  Dropout (an in-graph rng mask of the global shape) and a
  batch-normalised loss head — and in the bf16 / f32-master arithmetic
  the ResNet-50 dp cell runs;
- its compiled program holds an ``all-reduce``;
- optimizer-state files and elastic snapshots written by a build that
  still had the in-program 2-bit path (a ``__comm_residuals__`` entry, a
  ``comm_signature`` manifest field) load.
"""
import functools
import hashlib
import json
import os
import pickle
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import elastic
from mxnet_tpu.elastic import Checkpointer

BATCH = 32          # global: splits over 2, 4 and 8 devices
STEPS = 4
CLASSES = 4


def _mlp():
    h = mx.sym.Activation(mx.sym.FullyConnected(
        mx.sym.var("data"), num_hidden=16, name="fc1"), act_type="relu")
    return mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        h, num_hidden=CLASSES, name="fc2"), name="softmax")


def _conv_bn(dtype="float32"):
    """``dtype`` as models/resnet.py takes it: cast in at the data, out
    before the loss, so the parameters bind in ``dtype``."""
    h = mx.sym.var("data")
    if dtype != "float32":
        h = mx.sym.Cast(h, dtype=dtype)
    h = mx.sym.Convolution(h, num_filter=8, kernel=(3, 3), pad=(1, 1),
                           no_bias=True, name="conv1")
    h = mx.sym.BatchNorm(h, fix_gamma=False, momentum=0.5, name="bn1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.Pooling(h, kernel=(2, 2), stride=(2, 2), pool_type="avg")
    h = mx.sym.FullyConnected(mx.sym.Flatten(h), num_hidden=CLASSES,
                              name="fc")
    if dtype != "float32":
        h = mx.sym.Cast(h, dtype="float32")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _dropout():
    h = mx.sym.Activation(mx.sym.FullyConnected(
        mx.sym.var("data"), num_hidden=32, name="fc1"), act_type="relu")
    h = mx.sym.Dropout(h, p=0.5, name="drop")
    return mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        h, num_hidden=CLASSES, name="fc2"), name="softmax")


def _batch_normalized_loss():
    h = mx.sym.Activation(mx.sym.FullyConnected(
        mx.sym.var("data"), num_hidden=16, name="fc1"), act_type="relu")
    return mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        h, num_hidden=CLASSES, name="fc2"), normalization="batch",
        name="softmax")


# program -> (symbol builder, one sample's shape, learning rate)
PROGRAMS = {
    "mlp": (_mlp, (12,), 0.1),
    "conv_bn": (_conv_bn, (3, 8, 8), 0.1),
    "dropout": (_dropout, (12,), 0.1),
    # the head divides the gradient by the batch: a larger rate moves
    # the parameters as far as the others move
    "batch_loss": (_batch_normalized_loss, (12,), 1.0),
}


def _batches(sample_shape):
    rng = np.random.RandomState(3)
    X = rng.randn(BATCH * STEPS, *sample_shape).astype(np.float32)
    y = (np.arange(BATCH * STEPS) % CLASSES).astype(np.float32)
    return X, y


def _train(program, n_dev, dtype="float32"):
    """``STEPS`` steps of ``Module.fit`` over ``n_dev`` devices; returns
    (module, parameters, auxiliary states) as numpy."""
    build, sample_shape, lr = PROGRAMS[program]
    X, y = _batches(sample_shape)
    it = mx.io.NDArrayIter(X, y, batch_size=BATCH, shuffle=False)
    net = build() if dtype == "float32" else build(dtype)
    ctx = [mx.cpu(i) for i in range(n_dev)] if n_dev > 1 else mx.cpu(0)
    kvstore = "tpu_ici" if n_dev > 1 else "local"
    # a decay large enough to show on the wrong parameter: weights decay,
    # biases do not, whatever index the optimizer knows them by
    opt = {"learning_rate": lr, "momentum": 0.9, "wd": 1e-2,
           "multi_precision": dtype != "float32"}
    mod = mx.mod.Module(net, context=ctx)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mx.random.seed(0)
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(kvstore=kvstore, optimizer_params=opt)
    assert mod._fused_step is not None and mod._fused_step.n_dev == n_dev
    # the dp step's constructor draws the keys of its shape probe: seed
    # after it, so both runs draw the same key at the same step
    mx.random.seed(11)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # "already bound/initialized"
        mod.fit(it, num_epoch=1, kvstore=kvstore, optimizer_params=opt)
    assert mod._fused_step is not None and mod._fused_step.ran
    args, auxs = mod.get_params()
    return (mod, {k: v.asnumpy().astype(np.float32) for k, v in args.items()},
            {k: v.asnumpy().astype(np.float32) for k, v in auxs.items()})


@functools.lru_cache(maxsize=None)
def _one_device(program, dtype="float32"):
    return _train(program, 1, dtype)[1:]


@functools.lru_cache(maxsize=None)
def _initial(program):
    build, sample_shape, _ = PROGRAMS[program]
    mod = mx.mod.Module(build(), context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (BATCH,) + sample_shape)],
             label_shapes=[("softmax_label", (BATCH,))])
    mx.random.seed(0)
    mod.init_params(initializer=mx.initializer.Xavier())
    return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}


def _assert_close(got, want, rtol, atol):
    assert sorted(got) == sorted(want)
    for name in want:
        np.testing.assert_allclose(got[name], want[name], rtol=rtol,
                                   atol=atol, err_msg=name)


@pytest.mark.parametrize("n_dev", [2, 4, 8])
@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_dp_step_matches_one_device(program, n_dev):
    want_args, want_auxs = _one_device(program)
    mod, args, auxs = _train(program, n_dev)
    _assert_close(args, want_args, rtol=2e-4, atol=2e-5)
    _assert_close(auxs, want_auxs, rtol=2e-4, atol=2e-5)
    if program == "conv_bn":
        assert sorted(auxs) == ["bn1_moving_mean", "bn1_moving_var"]
        # the moving statistics moved, on every device alike
        assert np.abs(auxs["bn1_moving_mean"]).max() > 1e-3
        for exe in mod._exec_group.execs[1:]:
            np.testing.assert_array_equal(
                exe.aux_dict["bn1_moving_var"].asnumpy(),
                mod._exec_group.execs[0].aux_dict["bn1_moving_var"]
                .asnumpy())
    # it trained: no parameter is where the initializer left it
    start = _initial(program)
    assert any(np.abs(args[k] - start[k]).max() > 1e-3 for k in args)


def test_dp_step_matches_one_device_bf16_masters():
    """BatchNorm net, bfloat16 storage with f32 masters, 4 devices: the
    ResNet-50 dp cell's arithmetic."""
    want_args, want_auxs = _one_device("conv_bn", "bfloat16")
    mod, args, auxs = _train("conv_bn", 4, "bfloat16")
    fused = mod._fused_step
    assert any(fused.mixed)
    assert {str(np.dtype(m.dtype)) for m, mixed in
            zip(fused._masters, fused.mixed) if mixed} == {"float32"}
    # half-width arithmetic summed in another order: bf16's 8 bits
    _assert_close(args, want_args, rtol=4e-2, atol=4e-3)
    _assert_close(auxs, want_auxs, rtol=4e-2, atol=4e-3)


@pytest.mark.parametrize("n_dev", [2, 8])
def test_dp_step_reduces_by_xla_all_reduce(n_dev):
    from mxnet_tpu.module.fused_step import collective_counts
    mod = _train("mlp", n_dev)[0]
    counts = collective_counts(mod._fused_step.compiled_hlo())
    assert counts["all-reduce"] >= 1, counts
    # nothing gathers the gradients: they are reduced where they are
    assert counts["all-gather"] == 0, counts


# -- what an older build wrote still loads ------------------------------------

def _old_residual_entry(n_dev):
    return {"signature": (1048, "2bit", 0.05),
            "buckets": [np.ones((n_dev, 7), np.float32)]}


def test_states_file_with_comm_residuals_loads(tmp_path):
    """A ``fused_v2`` file carrying ``__comm_residuals__`` restores
    momentum and masters and skips the key."""
    mod = _train("conv_bn", 4, "bfloat16")[0]
    states = mod._fused_step.export_states()
    assert any("master" in e for e in states.values())
    states["__comm_residuals__"] = _old_residual_entry(4)
    path = str(tmp_path / "old.states")
    with open(path, "wb") as f:
        pickle.dump({"format": "fused_v2", "states": states}, f)

    fresh = _train("conv_bn", 4, "bfloat16")[0]
    # move the fresh module off the saved state first
    for j, m in enumerate(fresh._fused_step._masters):
        fresh._fused_step._masters[j] = m * 0
    fresh._fused_step.states = [
        None if st is None else st * 0 for st in fresh._fused_step.states]
    fresh.load_optimizer_states(path)
    got = fresh._fused_step.export_states()
    assert "__comm_residuals__" not in got
    assert sorted(got) == sorted(fresh._fused_step.param_names)
    for name, entry in got.items():
        np.testing.assert_array_equal(np.asarray(entry["state"]),
                                      np.asarray(states[name]["state"]))
        if "master" in states[name]:
            np.testing.assert_array_equal(entry["master"],
                                          states[name]["master"])


def test_manifest_with_comm_signature_resumes(tmp_path):
    """An elastic snapshot whose manifest has the old ``comm_signature``
    field (and whose states file has the old residual entry) resumes."""
    build, sample_shape, lr = PROGRAMS["mlp"]
    X, y = _batches(sample_shape)
    opt = {"learning_rate": lr, "momentum": 0.9}
    d = str(tmp_path / "ck")
    ckpt = Checkpointer(directory=d, every_steps=2, keep=4)
    mod = mx.mod.Module(build(), context=[mx.cpu(i) for i in range(4)])
    ckpt.attach(mod)
    mx.random.seed(0)
    mod.fit(mx.io.NDArrayIter(X, y, batch_size=BATCH, shuffle=False),
            num_epoch=1, kvstore="tpu_ici", optimizer_params=opt,
            initializer=mx.initializer.Xavier())
    snap = ckpt.latest()
    assert snap.step == STEPS
    want = mod._fused_step.export_states()

    # rewrite it as the older build left it
    spath = snap.artifact("optimizer.states")
    payload = pickle.load(open(spath, "rb"))
    payload["states"]["__comm_residuals__"] = _old_residual_entry(4)
    with open(spath, "wb") as f:
        pickle.dump(payload, f)
    mpath = os.path.join(snap.directory, "manifest.json")
    manifest = json.load(open(mpath))
    assert "comm_signature" not in manifest
    manifest["comm_signature"] = [1048, "2bit", 0.05]
    raw = open(spath, "rb").read()
    manifest["files"]["optimizer.states"] = {
        "bytes": len(raw), "sha256": hashlib.sha256(raw).hexdigest()}
    with open(mpath, "w") as f:
        json.dump(manifest, f)

    fresh = mx.mod.Module(build(), context=[mx.cpu(i) for i in range(4)])
    report = elastic.resume(fresh, directory=d, kvstore="tpu_ici",
                            optimizer_params=opt)
    assert report.step == STEPS and not report.refactorized
    assert report.snapshot.verify() == []
    got = fresh._fused_step.export_states()
    assert sorted(got) == sorted(want)
    for name in want:
        np.testing.assert_array_equal(np.asarray(got[name]["state"]),
                                      np.asarray(want[name]["state"]))
    for k, v in mod.get_params()[0].items():
        np.testing.assert_array_equal(fresh.get_params()[0][k].asnumpy(),
                                      v.asnumpy())
