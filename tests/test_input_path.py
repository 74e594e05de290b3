"""The batch's way to the step's devices (PR 29): ``NDArrayIter`` hands out
row views of a host-resident source, and ``Module.prepare`` starts the
upcoming batch's upload one step ahead (``FusedTrainStep.stage``)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import io as mx_io
from mxnet_tpu.observability import telemetry

N, BATCH, WIDTH = 40, 16, 16      # a row is 64 bytes: every batch is aligned


def _source(seed=0):
    rng = np.random.RandomState(seed)
    return (rng.rand(N, WIDTH).astype(np.float32),
            np.arange(N, dtype=np.float32))


def _expected(n, batch, handle, epochs):
    """Per epoch, the (row indices, pad) of every batch the reference's
    cursor arithmetic gives (python/mxnet/io.py NDArrayIter)."""
    if handle == "discard":
        n -= n % batch
    out, cursor = [], -batch
    for _ in range(epochs):
        batches = []
        while True:
            cursor += batch
            if cursor >= n:
                break
            over = max(0, cursor + batch - n)
            batches.append(([(cursor + i) % n for i in range(batch)],
                            over if handle == "pad" else 0))
        out.append(batches)
        if handle == "roll_over" and cursor > n:
            cursor = -batch + (cursor % n) % batch
        else:
            cursor = -batch
    return out


@pytest.mark.parametrize("shuffle", [False, True], ids=["inorder", "shuffle"])
@pytest.mark.parametrize("handle", ["pad", "discard", "roll_over"])
@pytest.mark.parametrize("kind", ["numpy", "ndarray", "off_host"])
def test_ndarray_iter_batches_are_row_views(kind, handle, shuffle,
                                            monkeypatch):
    """Same rows, same order, same pad as the reference's cursor gives; a
    batch that does not wrap aliases the source where the source is in host
    memory, and is a device slice where it is not."""
    X, y = _source()
    if kind == "off_host":      # no accelerator here: say no source is host
        monkeypatch.setattr(mx_io, "host_view", lambda arr: None)
    data, label = (X, y) if kind == "numpy" \
        else (mx.nd.array(X), mx.nd.array(y))
    np.random.seed(7)
    it = mx.io.NDArrayIter(data, label, batch_size=BATCH, shuffle=shuffle,
                           last_batch_handle=handle)
    order = np.arange(N)
    if shuffle:
        np.random.seed(7)
        np.random.shuffle(order)
    elif kind == "ndarray":
        assert it.data[0][1] is data    # held, not copied
    src_x = it.data[0][1].asnumpy()
    src_y = it.label[0][1].asnumpy()
    for want in _expected(N, BATCH, handle, epochs=3):
        got = list(it)
        assert len(got) == len(want)
        for batch, (rows, pad) in zip(got, want):
            bx, by = batch.data[0].asnumpy(), batch.label[0].asnumpy()
            np.testing.assert_array_equal(bx, X[order[rows]])
            np.testing.assert_array_equal(by, y[order[rows]])
            assert batch.pad == pad
            assert batch.data[0].context == it.data[0][1].context
            whole = rows[-1] > rows[0]
            views = whole and kind != "off_host"
            assert np.shares_memory(bx, src_x) == views
            # the CPU backend copies a buffer that is not 64-byte aligned:
            # the 4-byte labels from row 8 on, after a roll-over
            assert np.shares_memory(by, src_y) == (
                views and rows[0] * by.itemsize % 64 == 0)
        it.reset()


def test_a_write_to_a_view_leaves_the_source():
    """``NDArray.__setitem__`` rebinds its handle: the batch changes, the
    source it aliased does not."""
    X, y = _source()
    it = mx.io.NDArrayIter(X, y, batch_size=BATCH)
    batch = it.next()
    assert np.shares_memory(batch.data[0].asnumpy(), it.data[0][1].asnumpy())
    batch.data[0][:] = 0
    batch.label[0][2] = -1
    assert not batch.data[0].asnumpy().any()
    np.testing.assert_array_equal(it.data[0][1].asnumpy(), X)
    np.testing.assert_array_equal(it.label[0][1].asnumpy(), y)


def test_ndarray_iter_h5py_reads_the_window(tmp_path):
    h5py = pytest.importorskip("h5py")
    X, y = _source()
    with h5py.File(str(tmp_path / "d.h5"), "w") as f:
        f.create_dataset("x", data=X)
        f.create_dataset("y", data=y)
    with h5py.File(str(tmp_path / "d.h5"), "r") as f:
        it = mx.io.NDArrayIter(f["x"], f["y"], batch_size=BATCH)
        (want,) = _expected(N, BATCH, "pad", epochs=1)
        for batch, (rows, pad) in zip(it, want):
            np.testing.assert_array_equal(batch.data[0].asnumpy(), X[rows])
            np.testing.assert_array_equal(batch.label[0].asnumpy(), y[rows])
            assert batch.pad == pad


# -- Module.prepare stages the upcoming batch ---------------------------------

def _mlp():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=32,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _task(n=128, d=16):
    rng = np.random.RandomState(3)
    X = rng.rand(n, d).astype(np.float32)
    return X, np.argmax(X @ rng.rand(d, 4), axis=1).astype(np.float32)


CONTEXTS = {"one_device": lambda: [mx.cpu(1)],
            "dp4": lambda: [mx.cpu(i) for i in range(4)]}


def _counts():
    snap = telemetry.snapshot()
    return tuple(int(snap.get("module.input." + k, {}).get("value", 0))
                 for k in ("staged", "loaded"))


def _fit(contexts, staging, steps=4):
    X, y = _task(n=32 * steps)
    mx.random.seed(11)
    mod = mx.mod.Module(_mlp(), context=contexts)
    if not staging:
        mod.prepare = lambda data_batch: None
    telemetry.reset()
    mod.fit(mx.io.NDArrayIter(X, y, batch_size=32), num_epoch=1,
            kvstore="tpu_ici" if len(contexts) > 1 else "local",
            optimizer="sgd", initializer=mx.initializer.Xavier(),
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    assert mod._fused_step is not None and mod._fused_step.ran
    args, _ = mod.get_params()
    return {k: v.asnumpy() for k, v in args.items()}, _counts()


@pytest.mark.parametrize("where", sorted(CONTEXTS))
def test_fit_stages_every_batch_but_the_first_and_trains_the_same(where):
    contexts = CONTEXTS[where]()
    steps = 4
    staged, counts = _fit(contexts, staging=True, steps=steps)
    assert counts == (steps - 1, 1)
    plain, counts = _fit(contexts, staging=False, steps=steps)
    assert counts == (0, steps)
    assert sorted(staged) == sorted(plain)
    for name in staged:
        np.testing.assert_array_equal(staged[name], plain[name])


def _bound(contexts):
    mx.random.seed(11)
    mod = mx.mod.Module(_mlp(), context=contexts)
    mod.bind(data_shapes=[("data", (32, 16))],
             label_shapes=[("softmax_label", (32,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(
        kvstore="tpu_ici" if len(contexts) > 1 else "local",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    assert mod._fused_step is not None
    return mod


def _batches(count):
    X, y = _task(n=32 * count)
    return list(mx.io.NDArrayIter(X, y, batch_size=32))


@pytest.mark.parametrize("where", sorted(CONTEXTS))
def test_another_batch_than_the_staged_one_loads_at_dispatch(where):
    contexts = CONTEXTS[where]()
    first, second = _batches(2)
    mod = _bound(contexts)
    telemetry.reset()
    mod.prepare(first)
    assert set(mod._fused_step._staged) == {"data", "softmax_label"}
    mod.forward_backward(second)
    mod.update()
    assert mod._fused_step._staged == {}        # the stale stage is gone
    assert _counts() == (0, 1)
    mod.prepare(first)
    mod.forward_backward(first)                 # the staged one: taken
    mod.update()
    assert _counts() == (1, 1)

    plain = _bound(contexts)                    # the loop that never prepares
    for batch in (second, first):
        plain.forward_backward(batch)
        plain.update()
    for name, value in mod.get_params()[0].items():
        np.testing.assert_array_equal(
            value.asnumpy(), plain.get_params()[0][name].asnumpy())


def test_a_batch_on_the_steps_device_is_neither_staged_nor_copied():
    (batch,) = _batches(1)
    mod = _bound([mx.cpu(1)])
    there = mx.io.DataBatch(
        data=[a.as_in_context(mx.cpu(1)) for a in batch.data],
        label=[a.as_in_context(mx.cpu(1)) for a in batch.label])
    telemetry.reset()
    mod.prepare(there)
    assert mod._fused_step._staged == {}
    mod.forward_backward(there)
    mod.update()
    exe = mod._exec_group.execs[0]
    assert exe.arg_dict["data"]._h.array is there.data[0]._h.array
    assert exe.arg_dict["softmax_label"]._h.array is there.label[0]._h.array
    assert _counts() == (0, 1)


def test_a_batch_split_over_the_mesh_is_neither_staged_nor_copied():
    import jax
    (batch,) = _batches(1)
    mod = _bound(CONTEXTS["dp4"]())
    fused = mod._fused_step
    split = mx.io.DataBatch(
        data=[mx.nd.NDArray(jax.device_put(a.asnumpy(), fused._sh_dp))
              for a in batch.data],
        label=[mx.nd.NDArray(jax.device_put(a.asnumpy(), fused._sh_dp))
               for a in batch.label])
    mod.prepare(split)
    assert fused._staged == {}
    inputs = fused._inputs(split)
    assert inputs["data"] is split.data[0]._h.array
    assert inputs["softmax_label"] is split.label[0]._h.array


def test_a_batch_of_other_shapes_is_not_staged():
    X, y = _task(n=16)
    mod = _bound([mx.cpu(1)])
    mod.prepare(mx.io.DataBatch(data=[mx.nd.array(X)],
                                label=[mx.nd.array(y)]))
    assert mod._fused_step._staged == {}


def test_bucketing_module_prepare_switches_buckets_then_stages():
    def sym_gen(width):
        net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                                    name="fc")
        return mx.sym.SoftmaxOutput(net, name="softmax"), ("data",), \
            ("softmax_label",)

    def batch_of(width, rows):
        X, y = _task(n=rows, d=width)
        return mx.io.DataBatch(
            data=[mx.nd.array(X)], label=[mx.nd.array(y)], bucket_key=width,
            provide_data=[mx.io.DataDesc("data", (rows, width))],
            provide_label=[mx.io.DataDesc("softmax_label", (rows,))])

    class Buckets(mx.io.DataIter):
        def __init__(self):
            super().__init__(8)
            self.batches = [batch_of(10, 8), batch_of(6, 8), batch_of(10, 8)]
            self.provide_data = self.batches[0].provide_data
            self.provide_label = self.batches[0].provide_label
            self.at = 0

        def reset(self):
            self.at = 0

        def next(self):
            if self.at == len(self.batches):
                raise StopIteration
            self.at += 1
            return self.batches[self.at - 1]

    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=10,
                                 context=mx.cpu(1))
    seen = []
    plain_prepare = mod.prepare

    def prepare(data_batch):
        before = mod._active_key
        plain_prepare(data_batch)
        seen.append((before, data_batch.bucket_key, mod._active_key,
                     data_batch.bucket_key in mod._buckets))
    mod.prepare = prepare
    telemetry.reset()
    mod.fit(Buckets(), num_epoch=1,
            optimizer_params={"learning_rate": 0.1})
    # the upcoming bucket is bound ahead, the step in flight keeps its own
    assert seen == [(10, 6, 10, True), (6, 10, 6, True)]
    # the default bucket's module owns the fused step: its second batch
    # was staged through the delegation
    assert mod._buckets[10]._fused_step is not None
    assert _counts() == (1, 1)
