"""Module tests (ref: tests/python/unittest/test_module.py, 811 LoC)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import assert_almost_equal

rng = np.random.RandomState(11)


def _softmax_mlp(nh=32, classes=4, name="softmax"):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=nh, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc2")
    return mx.sym.SoftmaxOutput(net, name=name)


def _separable(n=512, d=16, classes=4):
    W = rng.randn(d, classes)
    X = rng.randn(n, d).astype(np.float32)
    y = np.argmax(X @ W, axis=1).astype(np.float32)
    return X, y


def test_module_fit_learns():
    # the initialiser and the shuffle draw from the process-wide generators,
    # whose state is whatever the worker's earlier tests left: under xdist
    # one run in a few ended at 0.898 of the 0.9 asked for
    mx.random.seed(11)
    np.random.seed(11)
    X, y = _separable()
    it = mx.io.NDArrayIter(X, y, batch_size=32, shuffle=True)
    mod = mx.mod.Module(_softmax_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=15, optimizer="sgd",
            initializer=mx.initializer.Xavier(),
            optimizer_params={"learning_rate": 0.2, "momentum": 0.9})
    train_acc = mod.score(mx.io.NDArrayIter(X, y, batch_size=32), "acc")[0][1]
    assert train_acc > 0.9, train_acc


def test_module_multi_device():
    X, y = _separable()
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod = mx.mod.Module(_softmax_mlp(), context=[mx.cpu(0), mx.cpu(1)])
    mod.fit(it, num_epoch=6, kvstore="device",
            optimizer_params={"learning_rate": 0.5})
    acc = mod.score(mx.io.NDArrayIter(X, y, batch_size=32), "acc")[0][1]
    assert acc > 0.7, acc


def test_module_predict_and_outputs():
    X, y = _separable(n=64)
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(_softmax_mlp(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    preds = mod.predict(it)
    assert preds.shape == (64, 4)
    probs = preds.asnumpy()
    assert_almost_equal(probs.sum(axis=1), np.ones(64), rtol=1e-4, atol=1e-4)


def test_module_checkpoint_roundtrip(tmp_path):
    X, y = _separable(n=128)
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod = mx.mod.Module(_softmax_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=2, optimizer_params={"learning_rate": 0.1})
    acc1 = mod.score(it, "acc")[0][1]
    prefix = str(tmp_path / "model")
    mod.save_checkpoint(prefix, 2)
    mod2 = mx.mod.Module.load(prefix, 2)
    mod2.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
              for_training=False)
    acc2 = mod2.score(it, "acc")[0][1]
    assert abs(acc1 - acc2) < 1e-9


def test_module_get_set_params():
    X, y = _separable(n=64)
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(_softmax_mlp(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(initializer=mx.initializer.Xavier())
    args, auxs = mod.get_params()
    assert set(args.keys()) == {"fc1_weight", "fc1_bias", "fc2_weight",
                                "fc2_bias"}
    mod2 = mx.mod.Module(_softmax_mlp(), context=mx.cpu())
    mod2.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod2.set_params(args, auxs)
    a2, _ = mod2.get_params()
    for k in args:
        assert_almost_equal(args[k].asnumpy(), a2[k].asnumpy())


def test_module_input_grads():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 6))],
             label_shapes=[("softmax_label", (4,))],
             for_training=True, inputs_need_grad=True)
    mod.init_params()
    batch = mx.io.DataBatch(data=[mx.nd.array(rng.rand(4, 6))],
                            label=[mx.nd.array(np.array([0, 1, 2, 0]))])
    mod.forward_backward(batch)
    (igrad,) = mod.get_input_grads()
    assert igrad.shape == (4, 6)
    assert np.abs(igrad.asnumpy()).sum() > 0


def test_bucketing_module():
    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
        net = mx.sym.SoftmaxOutput(net, name="softmax")
        return net, ("data",), ("softmax_label",)

    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=10,
                                 context=mx.cpu())
    X, y = _separable(n=64, d=10)
    batch10 = mx.io.DataBatch(
        data=[mx.nd.array(X[:16])], label=[mx.nd.array(y[:16])],
        bucket_key=10,
        provide_data=[mx.io.DataDesc("data", (16, 10))],
        provide_label=[mx.io.DataDesc("softmax_label", (16,))])
    mod.bind(data_shapes=batch10.provide_data,
             label_shapes=batch10.provide_label)
    mod.init_params()
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1})
    mod.forward_backward(batch10)
    mod.update()
    assert mod.get_outputs()[0].shape == (16, 4)
    # same-key second batch reuses the bucket executor
    mod.forward(batch10, is_train=False)
    assert mod.get_outputs()[0].shape == (16, 4)


def test_module_reshape():
    X, y = _separable(n=96, d=8)
    mod = mx.mod.Module(_softmax_mlp(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (32, 8))],
             label_shapes=[("softmax_label", (32,))])
    mod.init_params()
    mod.init_optimizer()
    b1 = mx.io.DataBatch(data=[mx.nd.array(X[:32])],
                         label=[mx.nd.array(y[:32])])
    mod.forward_backward(b1)
    mod.update()
    # smaller final batch triggers reshape
    b2 = mx.io.DataBatch(data=[mx.nd.array(X[:16])],
                         label=[mx.nd.array(y[:16])],
                         provide_data=[mx.io.DataDesc("data", (16, 8))],
                         provide_label=[mx.io.DataDesc("softmax_label", (16,))])
    mod.forward(b2, is_train=False)
    assert mod.get_outputs()[0].shape == (16, 4)


def test_module_bn_aux_state_sync():
    data = mx.sym.Variable("data")
    net = mx.sym.BatchNorm(mx.sym.FullyConnected(data, num_hidden=4, name="fc"),
                           name="bn")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    X, y = _separable(n=64, d=6)
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.1})
    _, auxs = mod.get_params()
    assert set(auxs.keys()) == {"bn_moving_mean", "bn_moving_var"}
    assert np.abs(auxs["bn_moving_mean"].asnumpy()).sum() > 0


def test_kvstore_optimizer_states_roundtrip(tmp_path):
    """update_on_kvstore mode: save/load must restore the kvstore updater's
    str-keyed state dict (regression: a heuristic misread it as a fused
    momentum file and skipped the restore)."""
    import os
    net = _softmax_mlp()
    X = np.random.RandomState(0).rand(32, 10).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 2, 32).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=8)
    contexts = [mx.cpu(0), mx.cpu(1)]
    mod = mx.mod.Module(net, context=contexts)
    mod.fit(it, num_epoch=1, kvstore="device",
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    assert mod._update_on_kvstore
    fname = os.path.join(str(tmp_path), "opt.states")
    mod.save_optimizer_states(fname)
    states_before = mod._kvstore._updater.get_states()
    mod.load_optimizer_states(fname)
    assert mod._kvstore._updater.get_states() == states_before
    # and the restored state is non-trivial (momentum exists after a step)
    import pickle
    assert pickle.loads(states_before)


def test_sequential_module_chain():
    """SequentialModule threads outputs into the next stage's data and
    routes labels to take_labels stages."""
    import numpy as np
    net1 = mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=16,
                                 name="fc1")
    net1 = mx.sym.Activation(net1, act_type="relu")
    net2 = mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=4,
                                 name="fc2")
    net2 = mx.sym.SoftmaxOutput(net2, name="softmax")
    seq = mx.mod.SequentialModule()
    seq.add(mx.mod.Module(net1, label_names=None)) \
       .add(mx.mod.Module(net2), take_labels=True, auto_wiring=True)

    rng = np.random.RandomState(0)
    W = rng.randn(8, 4).astype("f")
    X = rng.randn(128, 8).astype("f")
    Y = (X @ W).argmax(1).astype("f")
    it = mx.io.NDArrayIter(X, Y, batch_size=32, shuffle=True,
                           label_name="softmax_label")
    seq.fit(it, num_epoch=6, initializer=mx.initializer.Xavier(),
            optimizer_params={"learning_rate": 0.5})
    acc = dict(seq.score(it, "acc"))["accuracy"]
    assert acc > 0.8, acc
    args, _ = seq.get_params()
    assert "fc1_weight" in args and "fc2_weight" in args


def test_sequential_module_duplicate_names_raise():
    net = mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=4,
                                name="fc")
    seq = mx.mod.SequentialModule()
    seq.add(mx.mod.Module(net, label_names=None)) \
       .add(mx.mod.Module(net, label_names=None), auto_wiring=True)
    seq.bind(data_shapes=[("data", (2, 8))])
    with pytest.raises(AssertionError):
        seq.init_params(mx.initializer.Xavier())


def test_python_loss_module():
    """PythonLossModule supplies a custom gradient as the chain tail."""
    import numpy as np
    net = mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=2,
                                name="fc")
    head = mx.mod.PythonLossModule(
        grad_func=lambda scores, labels:
            scores.asnumpy() - labels.asnumpy())
    seq = mx.mod.SequentialModule()
    seq.add(mx.mod.Module(net, label_names=None)) \
       .add(head, take_labels=True, auto_wiring=True)
    rng = np.random.RandomState(1)
    X = rng.randn(64, 3).astype("f")
    T = X @ rng.randn(3, 2).astype("f")
    it = mx.io.NDArrayIter(X, T, batch_size=16,
                           label_name="softmax_label")
    seq.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    seq.init_params(mx.initializer.Xavier())
    seq.init_optimizer(optimizer_params={"learning_rate": 0.05})
    losses = []
    for _ in range(8):
        it.reset()
        total = 0.0
        for batch in it:
            seq.forward(batch, is_train=True)
            out = seq.get_outputs()[0].asnumpy()
            total += float(((out - batch.label[0].asnumpy()) ** 2).mean())
            seq.backward()
            seq.update()
        losses.append(total)
    assert losses[-1] < losses[0] * 0.5, losses


def test_sequential_module_input_grads():
    """bind(inputs_need_grad=True) must flow through to get_input_grads
    (review regression: the flags were dropped in bind)."""
    import numpy as np
    net = mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=4,
                                name="fcg")
    seq = mx.mod.SequentialModule()
    seq.add(mx.mod.Module(net, label_names=None))
    seq.bind(data_shapes=[("data", (2, 3))], inputs_need_grad=True)
    assert seq.inputs_need_grad and seq.for_training
    seq.init_params(mx.initializer.Xavier())
    batch = mx.io.DataBatch(data=[mx.nd.array(np.ones((2, 3), "f"))])
    seq.forward(batch, is_train=True)
    seq.backward([mx.nd.array(np.ones((2, 4), "f"))])
    g = seq.get_input_grads()[0].asnumpy()
    assert g.shape == (2, 3) and np.abs(g).sum() > 0
