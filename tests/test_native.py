"""Native (C++) runtime tests: recordio fast path + dependency engine
(parity model: tests/cpp/engine/threaded_engine_test.cc and the recordio
tests in the reference, driven from Python here)."""
import os
import tempfile
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import io_native, recordio

pytestmark = pytest.mark.skipif(io_native.get_lib() is None,
                                reason="native toolchain unavailable")


def test_native_recordio_roundtrip():
    tmp = tempfile.mkdtemp()
    p = os.path.join(tmp, "n.rec")
    w = io_native.NativeRecordWriter(p)
    offs = [w.write(b"payload-%03d" % i) for i in range(50)]
    w.close()
    r = io_native.NativeRecordReader(p, prefetch=False)
    recs = list(r)
    assert len(recs) == 50
    assert recs[7] == b"payload-007"
    r2 = io_native.NativeRecordReader(p, prefetch=True)
    assert list(r2) == recs
    r3 = io_native.NativeRecordReader(p, prefetch=False)
    r3.seek(offs[30])
    assert r3.read() == b"payload-030"


def test_native_python_interop():
    """Files written natively read back through the Python framing and
    vice versa (same dmlc wire format)."""
    tmp = tempfile.mkdtemp()
    p1 = os.path.join(tmp, "a.rec")
    w = io_native.NativeRecordWriter(p1)
    w.write(b"hello")
    w.write(b"worlds!")
    w.close()
    # raw python parse
    import struct
    with open(p1, "rb") as f:
        magic, ln = struct.unpack("<II", f.read(8))
        assert magic == 0xced7230a and ln == 5
        assert f.read(5) == b"hello"

    rio = recordio.MXRecordIO(p1, "r")
    assert rio.read() == b"hello"
    assert rio.read() == b"worlds!"
    assert rio.read() is None
    rio.close()


def test_indexed_recordio_native_backend():
    tmp = tempfile.mkdtemp()
    rec = os.path.join(tmp, "i.rec")
    idx = os.path.join(tmp, "i.idx")
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    for i in range(10):
        w.write_idx(i, b"rec-%d" % i)
    w.close()
    r = recordio.MXIndexedRecordIO(idx, rec, "r")
    assert r.read_idx(6) == b"rec-6"
    assert r.read_idx(1) == b"rec-1"
    r.close()


def test_engine_write_read_ordering():
    eng = io_native.NativeEngine(4)
    v = eng.new_var()
    order = []

    def op(i, delay=0.0):
        def f():
            time.sleep(delay)
            order.append(i)
        return f

    eng.push(op(0, 0.03), mutable_vars=[v])
    eng.push(op(1), const_vars=[v])
    eng.push(op(2), const_vars=[v])
    eng.push(op(3), mutable_vars=[v])
    eng.wait_for_var(v)
    assert order[0] == 0  # writer runs first
    assert order[-1] == 3  # second writer waits for all readers
    assert set(order) == {0, 1, 2, 3}
    eng.close()


def test_engine_concurrent_stress():
    """Many threads pushing ops on shared vars; per-var counters must add up
    (the reference's engine concurrency test pattern)."""
    eng = io_native.NativeEngine(4)
    n_vars = 8
    vs = [eng.new_var() for _ in range(n_vars)]
    counters = [0] * n_vars
    n_per_thread = 30

    def pusher(tid):
        rng = np.random.RandomState(tid)
        for _ in range(n_per_thread):
            i = int(rng.randint(n_vars))

            def inc(i=i):
                counters[i] += 1  # safe: writes to var i are serialized

            eng.push(inc, mutable_vars=[vs[i]])

    threads = [threading.Thread(target=pusher, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    eng.wait_for_all()
    assert sum(counters) == 4 * n_per_thread
    eng.close()


def test_native_corruption_raises():
    """Corruption must raise, not masquerade as EOF (silent data loss)."""
    from mxnet_tpu.base import MXNetError
    tmp = tempfile.mkdtemp()
    p = os.path.join(tmp, "c.rec")
    w = io_native.NativeRecordWriter(p)
    w.write(b"good-record")
    w.write(b"second")
    w.close()
    data = bytearray(open(p, "rb").read())
    data[20] ^= 0xFF  # flip a bit in the second record's magic
    open(p, "wb").write(bytes(data))
    r = io_native.NativeRecordReader(p, prefetch=False)
    assert r.read() == b"good-record"
    with pytest.raises(MXNetError):
        r.read()
    with pytest.raises(FileNotFoundError):
        io_native.NativeRecordReader("/nonexistent/x.rec")


def test_c_predict_abi_roundtrip(tmp_path):
    """Full C-ABI inference path (ref: src/c_api/c_predict_api.cc /
    include/mxnet/c_predict_api.h): train a tiny net, save a checkpoint,
    then run prediction purely through the C functions and compare with the
    Python Predictor."""
    import ctypes
    import os
    from mxnet_tpu.io_native import get_cpredict_lib

    lib = get_cpredict_lib()
    if lib is None:
        pytest.skip("C predict library unavailable (no toolchain)")

    # build + save a small model
    net = mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=3,
                                name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    w = rng.rand(3, 4).astype(np.float32)
    b = rng.rand(3).astype(np.float32)
    params = {"arg:fc_weight": mx.nd.array(w), "arg:fc_bias": mx.nd.array(b)}
    pfile = os.path.join(str(tmp_path), "net-0000.params")
    mx.nd.save(pfile, params)
    sym_json = net.tojson().encode()
    with open(pfile, "rb") as f:
        blob = f.read()

    # C-ABI create
    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint32 * 2)(0, 2)
    shape = (ctypes.c_uint32 * 2)(2, 4)
    handle = ctypes.c_void_p()
    rc = lib.MXPredCreate(sym_json, blob, len(blob), 1, 0, 1, keys,
                          indptr, shape, ctypes.byref(handle))
    assert rc == 0, lib.MXGetLastError().decode()

    x = rng.rand(2, 4).astype(np.float32)
    rc = lib.MXPredSetInput(handle, b"data",
                            x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                            x.size)
    assert rc == 0, lib.MXGetLastError().decode()
    assert lib.MXPredForward(handle) == 0

    sdata = ctypes.POINTER(ctypes.c_uint32)()
    ndim = ctypes.c_uint32()
    assert lib.MXPredGetOutputShape(handle, 0, ctypes.byref(sdata),
                                    ctypes.byref(ndim)) == 0
    oshape = tuple(sdata[i] for i in range(ndim.value))
    assert oshape == (2, 3)

    out = np.zeros(oshape, np.float32)
    rc = lib.MXPredGetOutput(
        handle, 0, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out.size)
    assert rc == 0, lib.MXGetLastError().decode()
    assert lib.MXPredFree(handle) == 0

    # reference: python-side Predictor on the same artifacts
    from mxnet_tpu.predict import Predictor
    pred = Predictor(net.tojson(), pfile, {"data": (2, 4)})
    pred.forward(data=x)
    ref = pred.get_output(0).asnumpy()
    assert np.allclose(out, ref, atol=1e-5)
    # softmax rows sum to one => a real forward ran through the C path
    assert np.allclose(out.sum(axis=1), 1.0, atol=1e-5)


def test_c_predict_abi_error_reporting(tmp_path):
    import ctypes
    from mxnet_tpu.io_native import get_cpredict_lib

    lib = get_cpredict_lib()
    if lib is None:
        pytest.skip("C predict library unavailable (no toolchain)")
    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint32 * 2)(0, 2)
    shape = (ctypes.c_uint32 * 2)(2, 4)
    handle = ctypes.c_void_p()
    rc = lib.MXPredCreate(b"{not json", b"xx", 2, 1, 0, 1, keys, indptr,
                          shape, ctypes.byref(handle))
    assert rc == -1
    assert lib.MXGetLastError()  # non-empty message


def test_c_predict_abi_reshape(tmp_path):
    """MXPredReshape returns a NEW independent handle (reference contract:
    old handle keeps its shapes, both handles freed separately)."""
    import ctypes
    import os
    from mxnet_tpu.io_native import get_cpredict_lib

    lib = get_cpredict_lib()
    if lib is None:
        pytest.skip("C predict library unavailable (no toolchain)")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.var("data"), num_hidden=3, name="fc"), name="softmax")
    rng = np.random.RandomState(0)
    params = {"arg:fc_weight": mx.nd.array(rng.rand(3, 4).astype(np.float32)),
              "arg:fc_bias": mx.nd.array(rng.rand(3).astype(np.float32))}
    pfile = os.path.join(str(tmp_path), "net-0000.params")
    mx.nd.save(pfile, params)
    blob = open(pfile, "rb").read()

    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint32 * 2)(0, 2)
    shape = (ctypes.c_uint32 * 2)(2, 4)
    h = ctypes.c_void_p()
    assert lib.MXPredCreate(net.tojson().encode(), blob, len(blob), 1, 0, 1,
                            keys, indptr, shape, ctypes.byref(h)) == 0

    shape2 = (ctypes.c_uint32 * 2)(5, 4)
    h2 = ctypes.c_void_p()
    assert lib.MXPredReshape(h, 1, keys, indptr, shape2,
                             ctypes.byref(h2)) == 0, lib.MXGetLastError()
    assert h2.value != h.value

    def run(handle, batch):
        x = rng.rand(batch, 4).astype(np.float32)
        assert lib.MXPredSetInput(
            handle, b"data",
            x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), x.size) == 0
        assert lib.MXPredForward(handle) == 0
        sdata = ctypes.POINTER(ctypes.c_uint32)()
        ndim = ctypes.c_uint32()
        assert lib.MXPredGetOutputShape(handle, 0, ctypes.byref(sdata),
                                        ctypes.byref(ndim)) == 0
        return tuple(sdata[i] for i in range(ndim.value))

    assert run(h2, 5) == (5, 3)
    assert run(h, 2) == (2, 3)   # old handle still bound to old shapes
    assert lib.MXPredFree(h) == 0
    assert lib.MXPredFree(h2) == 0


def _build_embed_binary(tmp_path, src_rel, libname, lib_path, out_name):
    """Compile an example that embeds CPython and links one of the ABI
    .so's; returns (exe_path, env) or pytest.skip()s when link flags are
    underivable.  Shared by the predict and train external-binary tests."""
    import subprocess
    import sysconfig
    import site

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    exe = os.path.join(str(tmp_path), out_name)
    libdir = os.path.dirname(lib_path)
    libdir_py = sysconfig.get_config_var("LIBDIR") or ""
    ldver = sysconfig.get_config_var("LDVERSION") or \
        sysconfig.get_config_var("VERSION")
    if not ldver:
        pytest.skip("cannot determine libpython link name")
    ldflags = ["-L" + libdir_py, "-lpython" + ldver] + \
        (sysconfig.get_config_var("LIBS") or "").split() + \
        (sysconfig.get_config_var("SYSLIBS") or "").split()
    cmd = ["g++", "-std=c++17", os.path.join(repo, src_rel),
           "-I" + os.path.join(repo, "include"),
           "-I" + sysconfig.get_paths()["include"],
           "-L" + libdir, "-l" + libname,
           "-Wl,-rpath," + libdir, "-o", exe] + ldflags
    build = subprocess.run(cmd, capture_output=True, text=True)
    assert build.returncode == 0, build.stderr
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [repo] + site.getsitepackages() + [site.getusersitepackages()]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    # the spawned binary must not contend with the parent pytest process
    # for the chip: a chip belongs to one process at a time
    env["JAX_PLATFORMS"] = "cpu"
    return exe, env


def test_cpp_frontend_compiles_and_runs(tmp_path):
    """Compile + run the header-only C++ frontend (predictor.hpp) as a real
    external binary against a saved checkpoint (parity: cpp-package)."""
    import subprocess
    from mxnet_tpu.io_native import get_cpredict_lib, _CPREDICT_PATH

    if get_cpredict_lib() is None:
        pytest.skip("C predict library unavailable")

    # checkpoint artifacts
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.var("data"), num_hidden=4, name="fc"), name="softmax")
    rng = np.random.RandomState(0)
    sym_path = os.path.join(str(tmp_path), "m-symbol.json")
    net.save(sym_path)
    pfile = os.path.join(str(tmp_path), "m-0000.params")
    mx.nd.save(pfile, {
        "arg:fc_weight": mx.nd.array(rng.rand(4, 6).astype(np.float32)),
        "arg:fc_bias": mx.nd.array(rng.rand(4).astype(np.float32))})

    exe, env = _build_embed_binary(
        tmp_path, os.path.join("examples", "predict-c", "predict_demo.cc"),
        "mxnet_tpu_cpredict", _CPREDICT_PATH, "demo")
    run = subprocess.run([exe, sym_path, pfile, "2", "6"],
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert run.returncode == 0, run.stdout + run.stderr
    assert "output shape: 2 4" in run.stdout, run.stdout
    assert "argmax=" in run.stdout


def test_engine_tsan_stress(tmp_path):
    """ThreadSanitizer stress of the native dependency engine (SURVEY.md
    §5.2: the reference relied on design review alone; fresh C++ here gets
    real TSAN coverage).  Any data race fails the run."""
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    exe = os.path.join(str(tmp_path), "engine_stress")
    build = subprocess.run(
        ["g++", "-std=c++17", "-fsanitize=thread", "-O1", "-g", "-pthread",
         os.path.join(repo, "src", "engine.cc"),
         os.path.join(repo, "tests", "cpp", "engine_stress.cc"),
         "-o", exe],
        capture_output=True, text=True)
    if build.returncode != 0:
        err = build.stderr.lower()
        if "tsan" in err or "sanitize" in err or "not supported" in err:
            pytest.skip("TSAN unavailable on this toolchain: %s"
                        % build.stderr[:200])
        assert build.returncode == 0, build.stderr
    env = dict(os.environ)
    env["TSAN_OPTIONS"] = "halt_on_error=1 exitcode=66"
    run = subprocess.run([exe], capture_output=True, text=True, timeout=300,
                         env=env)
    assert run.returncode == 0, \
        "TSAN reported races or ordering broke:\n" + run.stdout + run.stderr
    assert "ENGINE_TSAN_STRESS_OK" in run.stdout


def test_c_predict_output_shape_before_forward(tmp_path):
    """MXPredGetOutputShape must be valid right after MXPredCreate — C
    consumers size their output buffers before calling Forward (ref ABI
    contract: the reference computes out_shapes at create time)."""
    import ctypes
    import os
    from mxnet_tpu.io_native import get_cpredict_lib

    lib = get_cpredict_lib()
    if lib is None:
        pytest.skip("C predict library unavailable (no toolchain)")

    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=3, name="fc"),
        name="softmax")
    rng = np.random.RandomState(1)
    params = {"arg:fc_weight": mx.nd.array(rng.rand(3, 4).astype(np.float32)),
              "arg:fc_bias": mx.nd.array(rng.rand(3).astype(np.float32))}
    pfile = os.path.join(str(tmp_path), "m-0000.params")
    mx.nd.save(pfile, params)
    with open(pfile, "rb") as f:
        blob = f.read()

    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint32 * 2)(0, 2)
    shape = (ctypes.c_uint32 * 2)(5, 4)
    handle = ctypes.c_void_p()
    rc = lib.MXPredCreate(net.tojson().encode(), blob, len(blob), 1, 0, 1,
                          keys, indptr, shape, ctypes.byref(handle))
    assert rc == 0, lib.MXGetLastError().decode()
    # shape query BEFORE any forward
    sdata = ctypes.POINTER(ctypes.c_uint32)()
    ndim = ctypes.c_uint32()
    rc = lib.MXPredGetOutputShape(handle, 0, ctypes.byref(sdata),
                                  ctypes.byref(ndim))
    assert rc == 0, lib.MXGetLastError().decode()
    assert ndim.value == 2 and sdata[0] == 5 and sdata[1] == 3
    lib.MXPredFree(handle)

    # python-side too
    from mxnet_tpu.predict import Predictor
    p = Predictor(net.tojson(), {"arg:" + k[4:]: v for k, v in params.items()},
                  {"data": (7, 4)})
    assert p.get_output_shape(0) == (7, 3)


def test_c_predict_null_handle_is_error_not_crash():
    """NULL handles return -1 with MXGetLastError set (ADVICE: used to
    segfault)."""
    import ctypes
    from mxnet_tpu.io_native import get_cpredict_lib

    lib = get_cpredict_lib()
    if lib is None:
        pytest.skip("C predict library unavailable (no toolchain)")
    assert lib.MXPredForward(None) == -1
    assert b"null" in lib.MXGetLastError()
    assert lib.MXPredSetInput(None, b"data", None, 0) == -1
    sdata = ctypes.POINTER(ctypes.c_uint32)()
    ndim = ctypes.c_uint32()
    assert lib.MXPredGetOutputShape(None, 0, ctypes.byref(sdata),
                                    ctypes.byref(ndim)) == -1
    assert lib.MXPredGetOutput(None, 0, None, 4) == -1
    assert lib.MXPredFree(None) == 0  # free(NULL) no-op
    out = ctypes.c_void_p()
    assert lib.MXPredCreate(None, None, 0, 1, 0, 0, None, None, None,
                            ctypes.byref(out)) == -1


def test_c_train_abi_trains(tmp_path):
    """Training through the C ABI (parity: the reference C API training
    surface cpp-package consumes — executor.h Forward/Backward + updates):
    build the trainer from symbol JSON, run SGD steps on a learnable task,
    assert accuracy, checkpoint, and reload via the predict path."""
    import ctypes
    import os
    from mxnet_tpu.io_native import get_ctrain_lib

    lib = get_ctrain_lib()
    if lib is None:
        pytest.skip("C train library unavailable (no toolchain)")

    h1 = mx.sym.Activation(mx.sym.FullyConnected(
        mx.sym.var("data"), num_hidden=16, name="fc1"), act_type="relu")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        h1, num_hidden=3, name="fc2"), name="softmax")
    rng = np.random.RandomState(0)
    W = rng.randn(8, 3)
    X = rng.randn(256, 8).astype(np.float32)
    y = np.argmax(X @ W, axis=1).astype(np.float32)

    keys = (ctypes.c_char_p * 2)(b"data", b"softmax_label")
    indptr = (ctypes.c_uint32 * 3)(0, 2, 3)
    shapes = (ctypes.c_uint32 * 3)(64, 8, 64)
    okeys = (ctypes.c_char_p * 1)(b"learning_rate")
    ovals = (ctypes.c_float * 1)(0.3)
    handle = ctypes.c_void_p()
    rc = lib.MXTrainCreate(net.tojson().encode(), 1, 0, 2, keys, indptr,
                           shapes, b"sgd", 1, okeys, ovals,
                           ctypes.byref(handle))
    assert rc == 0, lib.MXTrainGetLastError().decode()

    def put(name, arr):
        flat = np.ascontiguousarray(arr, np.float32)
        rc = lib.MXTrainSetInput(
            handle, name,
            flat.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), flat.size)
        assert rc == 0, lib.MXTrainGetLastError().decode()

    for epoch in range(25):
        for i in range(0, 256, 64):
            put(b"data", X[i:i + 64])
            put(b"softmax_label", y[i:i + 64])
            assert lib.MXTrainStep(handle) == 0, \
                lib.MXTrainGetLastError().decode()

    correct = 0
    out = np.zeros((64, 3), np.float32)
    for i in range(0, 256, 64):
        put(b"data", X[i:i + 64])
        put(b"softmax_label", y[i:i + 64])
        assert lib.MXTrainForward(handle) == 0
        sdata = ctypes.POINTER(ctypes.c_uint32)()
        ndim = ctypes.c_uint32()
        assert lib.MXTrainGetOutputShape(handle, 0, ctypes.byref(sdata),
                                         ctypes.byref(ndim)) == 0
        assert ndim.value == 2 and sdata[0] == 64 and sdata[1] == 3
        assert lib.MXTrainGetOutput(
            handle, 0, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            out.size) == 0
        correct += int((np.argmax(out, 1) == y[i:i + 64]).sum())
    acc = correct / 256.0
    assert acc > 0.97, "C-ABI training accuracy %.3f" % acc

    prefix = os.path.join(str(tmp_path), "cmlp")
    assert lib.MXTrainSaveCheckpoint(handle, prefix.encode(), 7) == 0
    assert lib.MXTrainFree(handle) == 0
    # checkpoint is the standard two-artifact format: predict path loads it
    from mxnet_tpu.predict import load_checkpoint_predictor
    p = load_checkpoint_predictor(prefix, 7, {"data": (4, 8)})
    p.forward(data=mx.nd.array(X[:4]))
    probs = p.get_output(0).asnumpy()
    assert (np.argmax(probs, 1) == y[:4]).mean() >= 0.75

    # error paths: null handle, bad input name
    assert lib.MXTrainStep(None) == -1
    assert b"null" in lib.MXTrainGetLastError()


def test_cpp_training_example_compiles_and_trains(tmp_path):
    """Compile examples/train-c/mlp_train.cc as an external binary and let
    it train its MLP through the .so to >97%% accuracy (the port of
    cpp-package/example/mlp.cpp)."""
    import subprocess
    from mxnet_tpu.io_native import get_ctrain_lib, _CTRAIN_PATH

    if get_ctrain_lib() is None:
        pytest.skip("C train library unavailable")

    h1 = mx.sym.Activation(mx.sym.FullyConnected(
        mx.sym.var("data"), num_hidden=64, name="fc1"), act_type="relu")
    h2 = mx.sym.Activation(mx.sym.FullyConnected(
        h1, num_hidden=32, name="fc2"), act_type="relu")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        h2, num_hidden=10, name="fc3"), name="softmax")
    sym_path = os.path.join(str(tmp_path), "mlp-symbol.json")
    net.save(sym_path)

    exe, env = _build_embed_binary(
        tmp_path, os.path.join("examples", "train-c", "mlp_train.cc"),
        "mxnet_tpu_ctrain", _CTRAIN_PATH, "mlp_train")
    ckpt = os.path.join(str(tmp_path), "mlp")
    run = subprocess.run([exe, sym_path, ckpt], capture_output=True,
                         text=True, timeout=600, env=env)
    assert run.returncode == 0, run.stdout + run.stderr
    assert "TRAINED-OK" in run.stdout, run.stdout
    assert os.path.exists(ckpt + "-symbol.json")
    assert os.path.exists(ckpt + "-0011.params")


def _write_tiny_rec(path, n=8, rng=None):
    import cv2
    from mxnet_tpu import recordio
    rng = rng or np.random.RandomState(0)
    w = recordio.MXRecordIO(path, "w")
    for i in range(n):
        ok, buf = cv2.imencode(
            ".jpg", (rng.rand(36, 36, 3) * 255).astype(np.uint8))
        w.write(recordio.pack(recordio.IRHeader(0, float(i % 3), i, 0),
                              buf.tobytes()))
    w.close()


def test_engine_pipeline_iter_equivalence_and_training(tmp_path):
    """The engine-scheduled input pipeline yields the same stream as the
    plain iterator and feeds a real training run (the engine made
    load-bearing: prefetch/decode/upload as engine ops with var deps)."""
    from mxnet_tpu.io_native import get_lib

    if get_lib() is None:
        pytest.skip("native engine unavailable")
    rec = os.path.join(str(tmp_path), "d.rec")
    _write_tiny_rec(rec, n=8)

    def batches(it):
        it.reset()
        out = []
        for b in it:
            out.append((b.label[0].asnumpy().tolist(),
                        float(b.data[0].asnumpy().sum())))
        return out

    plain = mx.io.ImageRecordIter(path_imgrec=rec, data_shape=(3, 32, 32),
                                  batch_size=4)
    piped = mx.io.ImageRecordIter(path_imgrec=rec, data_shape=(3, 32, 32),
                                  batch_size=4, preprocess_threads=2)
    assert type(piped).__name__ == "EnginePipelineIter"
    ref, got = batches(plain), batches(piped)
    assert [l for l, _ in ref] == [l for l, _ in got]
    for (_, a), (_, b) in zip(ref, got):
        # pip-cv2 and the native kernel's system OpenCV may bundle
        # different libjpeg builds: +-1 LSB per pixel on a small fraction
        assert abs(a - b) <= 4 * 32 * 32 * 3 * 0.02 + 1e-3, (a, b)
    # multiple epochs through the engine pipeline are identical
    assert batches(piped) == batches(piped)

    # device-upload lane places batches on the requested context
    dev_piped = mx.io.ImageRecordIter(path_imgrec=rec,
                                      data_shape=(3, 32, 32), batch_size=4,
                                      preprocess_threads=2, ctx=mx.cpu(0))
    dev_piped.reset()
    b = dev_piped.next()
    assert list(b.data[0]._h.array.devices())[0] == mx.cpu(0).jax_device()

    # a Module trains from the engine pipeline
    sym = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Flatten(mx.sym.var("data")), num_hidden=3), name="softmax")
    mod = mx.mod.Module(sym, context=mx.cpu())
    piped.reset()
    mod.fit(piped, num_epoch=2, optimizer_params={"learning_rate": 0.1})


def test_engine_ops_appear_in_profiler_trace(tmp_path):
    """Done-criterion for the load-bearing engine: engine spans show up in
    a profiler trace of an ImageRecordIter training run."""
    import json
    from mxnet_tpu import profiler
    from mxnet_tpu.io_native import get_lib

    if get_lib() is None:
        pytest.skip("native engine unavailable")
    rec = os.path.join(str(tmp_path), "d.rec")
    _write_tiny_rec(rec, n=8)
    it = mx.io.ImageRecordIter(path_imgrec=rec, data_shape=(3, 32, 32),
                               batch_size=4, preprocess_threads=2,
                               ctx=mx.cpu(0))
    sym = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Flatten(mx.sym.var("data")), num_hidden=3), name="softmax")
    mod = mx.mod.Module(sym, context=mx.cpu())

    fname = os.path.join(str(tmp_path), "profile.json")
    profiler.profiler_set_config(mode="all", filename=fname)
    profiler.profiler_set_state("run")
    mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.1})
    profiler.profiler_set_state("stop")

    with open(fname) as f:
        trace = json.load(f)
    # spans are "X" complete-events (nested-span encoding); legacy "B"
    # begin-events also accepted for old dumps
    events = [e for e in trace["traceEvents"] if e.get("ph") in ("B", "X")]
    names = {e["name"] for e in events}
    cats = {e.get("cat") for e in events}
    assert "engine_decode_augment" in names, names
    assert "engine_device_upload" in names, names
    assert "engine" in cats


def test_cpp_lenet_trains_through_header_frontend(tmp_path):
    """Compile examples/train-c/lenet_train.cc — a CONV net driven through
    the RAII mxnet_tpu::Trainer header class (trainer.hpp, the analog of
    cpp-package/include/mxnet-cpp/executor.h + example/lenet.cpp) — and
    let it train past the convergence bar as an external binary.

    De-flaked (PR 14): the subprocess pins its initializer draws via
    MXNET_TPU_SEED (a C host cannot call mx.random.seed before
    TrainSession's init), the binary's bar is 0.93 (it trains to ~0.99;
    a bar within noise of the optimum flaked once under full-suite
    load), and the timeout budgets for a contended 2-core CI box."""
    import subprocess
    from mxnet_tpu.io_native import get_ctrain_lib, _CTRAIN_PATH

    if get_ctrain_lib() is None:
        pytest.skip("C train library unavailable")

    d = mx.sym.var("data")
    c1 = mx.sym.Activation(mx.sym.Convolution(
        d, kernel=(3, 3), num_filter=8, pad=(1, 1), name="c1"),
        act_type="relu")
    p1 = mx.sym.Pooling(c1, kernel=(2, 2), stride=(2, 2), pool_type="max")
    c2 = mx.sym.Activation(mx.sym.Convolution(
        p1, kernel=(3, 3), num_filter=16, pad=(1, 1), name="c2"),
        act_type="relu")
    p2 = mx.sym.Pooling(c2, kernel=(2, 2), stride=(2, 2), pool_type="max")
    f1 = mx.sym.Activation(mx.sym.FullyConnected(
        p2, num_hidden=64, name="f1"), act_type="relu")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        f1, num_hidden=10, name="f2"), name="softmax")
    sym_path = os.path.join(str(tmp_path), "lenet-symbol.json")
    net.save(sym_path)

    exe, env = _build_embed_binary(
        tmp_path, os.path.join("examples", "train-c", "lenet_train.cc"),
        "mxnet_tpu_ctrain", _CTRAIN_PATH, "lenet_train")
    ckpt = os.path.join(str(tmp_path), "lenet")
    env = dict(env)
    env["MXNET_TPU_SEED"] = "20260731"
    run = subprocess.run([exe, sym_path, ckpt], capture_output=True,
                         text=True, timeout=900, env=env)
    assert run.returncode == 0, run.stdout + run.stderr
    assert "TRAINED-OK" in run.stdout, run.stdout
    assert os.path.exists(ckpt + "-symbol.json")
    assert os.path.exists(ckpt + "-%04d.params" % 10)
