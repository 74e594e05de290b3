"""PipelineModule: pipeline parallelism driven through the Module API
(round-4 verdict item 8 — pp was previously reachable only via the
parallel/ library).  The oracle is an UNPIPELINED ordinary Module built
from the same per-stage parameters: after K fused steps on a pp=2 mesh,
parameters must match the sequential module's to float tolerance."""
import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.parallel import create_mesh
from mxnet_tpu.parallel.mesh import MeshSpec

D, CLASSES, BATCH, STAGES = 8, 4, 16, 2
LR, MOM = 0.2, 0.9


def _mesh(**sizes):
    spec = MeshSpec(**sizes)
    return create_mesh(spec, devices=jax.devices("cpu")[:spec.n_devices])


def _apply_body(x, prefix):
    h = mx.sym.FullyConnected(x, num_hidden=D, name=prefix + "ffn1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=D, name=prefix + "ffn2")
    return x + h


def _head(x):
    out = mx.sym.FullyConnected(x, num_hidden=CLASSES, name="out")
    return mx.sym.SoftmaxOutput(out, name="softmax")


def _problem(rng, n=BATCH):
    X = rng.standard_normal((n, D)).astype(np.float32)
    W = rng.standard_normal((D, CLASSES)).astype(np.float32)
    y = (X @ W).argmax(1).astype(np.float32)
    return X, y


def _pipeline_module(mesh, n_micro=None):
    body = _apply_body(mx.sym.var("x"), "")
    head = _head(mx.sym.var("x"))
    return mx.mod.PipelineModule(body, n_stages=STAGES, head=head,
                                 mesh=mesh, n_micro=n_micro)


def test_pp_training_matches_sequential_module():
    rng = np.random.RandomState(0)
    X, y = _problem(rng)
    mesh = _mesh(dp=2, pp=2)

    pm = _pipeline_module(mesh)
    pm.bind(data_shapes=[("data", (BATCH, D))],
            label_shapes=[("softmax_label", (BATCH,))])
    pm.init_params(mx.initializer.Xavier())
    pm.init_optimizer(optimizer="sgd",
                      optimizer_params={"learning_rate": LR,
                                        "momentum": MOM})
    start_params, _ = pm.get_params()

    # sequential oracle: the SAME graph flattened, seeded with the SAME
    # per-stage parameters, trained by the ordinary single-device Module
    net = mx.sym.var("data")
    for s in range(STAGES):
        net = _apply_body(net, "stage%d_" % s)
    net = _head(net)
    ref = mx.mod.Module(net, context=mx.cpu())
    ref.bind(data_shapes=[("data", (BATCH, D))],
             label_shapes=[("softmax_label", (BATCH,))])
    ref.init_params(initializer=None, arg_params=start_params,
                    aux_params={}, allow_missing=False)
    ref.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": LR,
                                         "momentum": MOM,
                                         "rescale_grad": 1.0 / BATCH})

    from mxnet_tpu.io import DataBatch
    batch = DataBatch([mx.nd.array(X)], [mx.nd.array(y)])
    losses = []
    for step in range(6):
        pm.forward_backward(batch)
        pm.update()
        losses.append(pm.loss)
        ref.forward_backward(batch)
        ref.update()

    pp_params, _ = pm.get_params()
    ref_params, _ = ref.get_params()
    assert set(pp_params) == set(ref_params)
    for n in sorted(ref_params):
        np.testing.assert_allclose(
            pp_params[n].asnumpy(), ref_params[n].asnumpy(),
            rtol=2e-4, atol=2e-5, err_msg=n)
    # and training actually trained
    assert losses[-1] < losses[0], losses


def test_pp_forward_matches_and_scores():
    # Xavier draws from numpy's global generator: unseeded, one start in
    # ten leaves the accuracy at chance after 120 steps
    mx.random.seed(1)
    rng = np.random.RandomState(1)
    X, y = _problem(rng)
    mesh = _mesh(dp=2, pp=2)
    pm = _pipeline_module(mesh)
    pm.bind(data_shapes=[("data", (BATCH, D))],
            label_shapes=[("softmax_label", (BATCH,))])
    pm.init_params(mx.initializer.Xavier())
    pm.init_optimizer(optimizer="sgd",
                      optimizer_params={"learning_rate": 0.2,
                                        "momentum": 0.9})
    from mxnet_tpu.io import DataBatch
    batch = DataBatch([mx.nd.array(X)], [mx.nd.array(y)])
    for _ in range(120):
        pm.forward_backward(batch)
    pm.forward(batch)
    metric = mx.metric.Accuracy()
    pm.update_metric(metric, [mx.nd.array(y)])
    acc = dict([metric.get()] if not isinstance(metric.get()[0], list)
               else zip(*metric.get()))["accuracy"]
    assert acc > 0.9, acc


def test_pp_requires_stateless_stages():
    x = mx.sym.var("x")
    bn = mx.sym.BatchNorm(mx.sym.FullyConnected(x, num_hidden=D,
                                                name="f"), name="bn")
    with pytest.raises(mx.base.MXNetError):
        mx.mod.PipelineModule(bn + x, n_stages=2,
                              head=_head(mx.sym.var("x")),
                              mesh=_mesh(pp=2))


def test_virtual_stages_more_stages_than_pp():
    """n_stages=4 on pp=2: two virtual stages per chip."""
    rng = np.random.RandomState(2)
    X, y = _problem(rng)
    mesh = _mesh(dp=2, pp=2)
    body = _apply_body(mx.sym.var("x"), "")
    pm = mx.mod.PipelineModule(body, n_stages=4, head=_head(mx.sym.var("x")),
                               mesh=mesh)
    pm.bind(data_shapes=[("data", (BATCH, D))],
            label_shapes=[("softmax_label", (BATCH,))])
    pm.init_params(mx.initializer.Xavier())
    pm.init_optimizer(optimizer="sgd",
                      optimizer_params={"learning_rate": 0.2})
    from mxnet_tpu.io import DataBatch
    batch = DataBatch([mx.nd.array(X)], [mx.nd.array(y)])
    first = None
    for _ in range(5):
        pm.forward_backward(batch)
        first = pm.loss if first is None else first
    assert np.isfinite(pm.loss) and pm.loss < first
    args, _ = pm.get_params()
    assert "stage3_ffn1_weight" in args


def test_force_rebind_preserves_params_resets_compiled():
    """Rebind at a new batch size: compiled step (with its baked-in
    rescale_grad and microbatch split) must be dropped, trained params
    carried across, eval possible without a new optimizer."""
    rng = np.random.RandomState(4)
    X, y = _problem(rng)
    mesh = _mesh(dp=2, pp=2)
    pm = _pipeline_module(mesh)
    pm.bind(data_shapes=[("data", (BATCH, D))],
            label_shapes=[("softmax_label", (BATCH,))])
    pm.init_params(mx.initializer.Xavier())
    pm.init_optimizer(optimizer="sgd",
                      optimizer_params={"learning_rate": 0.2,
                                        "momentum": 0.9})
    from mxnet_tpu.io import DataBatch
    batch = DataBatch([mx.nd.array(X)], [mx.nd.array(y)])
    for _ in range(80):
        pm.forward_backward(batch)
    # the carried-params check below is only meaningful if training
    # actually converged (lr 0.2: the 0.5/0.9 setting is chaotically
    # sensitive to float reduction order and diverges on some runs)
    tr_acc = (pm.get_outputs()[0].asnumpy().argmax(1) == y).mean()
    assert tr_acc > 0.9, tr_acc
    w_before = pm.get_params()[0]["stage0_ffn1_weight"].asnumpy()

    half = BATCH // 2
    pm.bind(data_shapes=[("data", (half, D))],
            label_shapes=[("softmax_label", (half,))], force_rebind=True)
    assert pm._step is None and pm._fwd is None
    assert not pm.optimizer_initialized and pm.params_initialized
    np.testing.assert_allclose(
        pm.get_params()[0]["stage0_ffn1_weight"].asnumpy(), w_before)
    # eval at the new batch size, no optimizer needed
    b2 = DataBatch([mx.nd.array(X[:half])], [mx.nd.array(y[:half])])
    pm.forward(b2)
    metric = mx.metric.Accuracy()
    pm.update_metric(metric, [mx.nd.array(y[:half])])
    assert metric.get()[1] > 0.9, metric.get()


def test_init_params_missing_name_raises():
    pm = _pipeline_module(_mesh(dp=2, pp=2))
    pm.bind(data_shapes=[("data", (BATCH, D))],
            label_shapes=[("softmax_label", (BATCH,))])
    with pytest.raises(mx.base.MXNetError):
        pm.init_params(initializer=None,
                       arg_params={"stage0_ffn1_weight":
                                   mx.nd.zeros((D, D))})


def test_labelless_forward_and_odd_batch_divisor():
    """predict-style forward with no labels; and a batch (6) that
    divides dp but not the naive 2*dp microbatch count."""
    rng = np.random.RandomState(6)
    mesh = _mesh(dp=2, pp=2)
    pm = _pipeline_module(mesh)
    pm.bind(data_shapes=[("data", (6, D))],
            label_shapes=[("softmax_label", (6,))])
    assert pm._n_micro in (1, 2, 3, 6) and 6 % pm._n_micro == 0
    pm.init_params(mx.initializer.Xavier())
    from mxnet_tpu.io import DataBatch
    X = rng.standard_normal((6, D)).astype(np.float32)
    pm.forward(DataBatch([mx.nd.array(X)], None))
    out = pm.get_outputs()[0].asnumpy()
    assert out.shape == (6, CLASSES)
    assert np.allclose(out.sum(1), 1.0, atol=1e-4)


def test_labelless_bind_predict_flow():
    """bind WITHOUT label_shapes (the predict workflow): the head's
    label shape is inferred from the graph and zero-filled at feed."""
    rng = np.random.RandomState(8)
    mesh = _mesh(dp=2, pp=2)
    pm = _pipeline_module(mesh)
    pm.bind(data_shapes=[("data", (8, D))], for_training=False)
    pm.init_params(mx.initializer.Xavier())
    from mxnet_tpu.io import DataBatch
    X = rng.standard_normal((8, D)).astype(np.float32)
    pm.forward(DataBatch([mx.nd.array(X)], None))
    out = pm.get_outputs()[0].asnumpy()
    assert out.shape == (8, CLASSES)
    assert np.allclose(out.sum(1), 1.0, atol=1e-4)
