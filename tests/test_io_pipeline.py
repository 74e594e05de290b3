"""io_pipeline: the high-throughput native input pipeline (ISSUE 6).

Covers the subsystem contracts — batch-sequence determinism across
worker counts and pool modes, the reorder-buffer bound, exact shard
coverage, clean mid-epoch shutdown, starvation telemetry — plus the
satellite hardening: PrefetchingIter's explicit lifecycle, the forced
pure-Python RecordIO fallback (``MXNET_TPU_IO_NATIVE=0``), and the
atomic-rename rebuild race in the lazy native build.
"""
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import io_pipeline as iop
from mxnet_tpu import recordio
from mxnet_tpu.base import MXNetError
from mxnet_tpu.io_pipeline.executor import PipelineClosed, ReorderBuffer

N_REC, FEAT = 37, 12


class NoisyDecoder:
    """Payload decode + a per-record random draw: exercises the
    determinism of the seeded augmentation stream, not just the record
    order.  Module-level (picklable) for the process-pool tests."""

    def __init__(self, shape):
        self._inner = iop.NDArrayRecordDecoder(shape)

    def __call__(self, raw, rng):
        data, label = self._inner(raw, rng)
        return data + rng.uniform(0.0, 1.0, data.shape).astype(
            np.float32), label


@pytest.fixture(scope="module")
def rec_file(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("iop") / "t.rec")
    rng = np.random.RandomState(0)
    writer = recordio.MXIndexedRecordIO(path + ".idx", path, "w")
    for i in range(N_REC):
        arr = rng.rand(FEAT).astype(np.float32)
        writer.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i % 5), i, 0), arr.tobytes()))
    writer.close()
    return path


def _source(rec_file):
    return iop.RecordFileSource(rec_file, rec_file + ".idx")


def _sequence(pipe, epoch=0):
    return [(b.data.tobytes(), b.label.tobytes(), b.pad)
            for b in pipe.host_batches(epoch)]


def _no_pipeline_threads():
    return not [t for t in threading.enumerate()
                if t.name.startswith("io_pipeline")]


# -- determinism -------------------------------------------------------------

def test_determinism_across_worker_counts(rec_file):
    """Same seed -> bitwise-identical batch sequence (data, labels,
    pad) at 1, 2 and 3 workers, shuffling AND drawing per-record
    augmentation randomness."""
    src = _source(rec_file)
    dec = NoisyDecoder((FEAT,))
    seqs = [_sequence(iop.Pipeline(src, dec, batch_size=8, shuffle=True,
                                   seed=11, num_workers=w))
            for w in (1, 2, 3)]
    assert seqs[0] == seqs[1] == seqs[2]
    assert len(seqs[0]) == 5 and seqs[0][-1][2] == 3  # 37 -> pad 3


def test_determinism_across_depth_and_double_buffer(rec_file):
    src = _source(rec_file)
    dec = NoisyDecoder((FEAT,))
    base = _sequence(iop.Pipeline(src, dec, batch_size=8, shuffle=True,
                                  seed=11, num_workers=2,
                                  prefetch_depth=1))
    deep = _sequence(iop.Pipeline(src, dec, batch_size=8, shuffle=True,
                                  seed=11, num_workers=2,
                                  prefetch_depth=6))
    assert base == deep
    # the adapter view (device NDArrays) matches too, double-buffer
    # on and off
    for db in (True, False):
        pipe = iop.Pipeline(src, dec, batch_size=8, shuffle=True,
                            seed=11, num_workers=2, ctx=mx.cpu(),
                            double_buffer=db)
        with pipe.as_dataiter() as it:
            got = [(b.data[0].asnumpy().tobytes(),
                    b.label[0].asnumpy().tobytes(), b.pad) for b in it]
        assert got == base


def _worker_jax_platforms():
    import os

    import jax
    return os.environ.get("JAX_PLATFORMS"), jax.config.jax_platforms


def test_process_mode_matches_thread_mode(rec_file, monkeypatch):
    """The spawn-process pool yields the same bitwise sequence (worker
    identity never enters the stream), and the worker-measured decode
    telemetry reaches the parent registry."""
    from mxnet_tpu.observability import telemetry
    # what a chip host looks like to a child: no platform pinned in the
    # inherited env (this process's own backend is long since chosen)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    src = _source(rec_file)
    dec = NoisyDecoder((FEAT,))
    thread_seq = _sequence(iop.Pipeline(src, dec, batch_size=8,
                                        shuffle=True, seed=3,
                                        num_workers=2))
    telemetry.reset()
    with iop.Pipeline(src, dec, batch_size=8, shuffle=True, seed=3,
                      num_workers=2, mode="process") as pipe:
        proc_seq = _sequence(pipe)
        snap = telemetry.snapshot()
        # one process per chip: a spawn worker is pinned to the host cpu
        # before it runs anything
        pinned = pipe._proc_exec._pool.submit(_worker_jax_platforms).result(
            timeout=60)
    assert pinned == ("cpu", "cpu")
    assert proc_seq == thread_seq
    # decode runs in other processes; its wall time rides back on the
    # batches so the parent's decode_ms/records series still fill
    assert snap["io_pipeline.decode_ms"]["count"] >= 5
    assert snap["io_pipeline.records"]["value"] >= N_REC


def test_epochs_distinct_but_reproducible(rec_file):
    src = _source(rec_file)
    dec = NoisyDecoder((FEAT,))

    def run(epochs):
        pipe = iop.Pipeline(src, dec, batch_size=8, shuffle=True,
                            seed=5, num_workers=2)
        return [_sequence(pipe, e) for e in epochs]

    (e0, e1), (f0, f1) = run((0, 1)), run((0, 1))
    assert e0 == f0 and e1 == f1  # reproducible per epoch
    assert e0 != e1               # epochs draw distinct orders/augs


# -- reorder buffer ----------------------------------------------------------

def test_reorder_buffer_releases_in_order_and_bounds_fill():
    rb = ReorderBuffer(capacity=3)
    done = []

    def put(seq):
        rb.put(seq, "item%d" % seq)
        done.append(seq)

    threads = [threading.Thread(target=put, args=(s,), daemon=True)
               for s in (2, 0, 1, 4, 3, 5)]
    for t in threads:
        t.start()
    out = [rb.get() for _ in range(6)]
    for t in threads:
        t.join(timeout=5)
    assert out == ["item%d" % i for i in range(6)]
    assert rb.max_fill <= 3


def test_reorder_buffer_put_blocks_past_capacity():
    rb = ReorderBuffer(capacity=2)
    rb.put(0, "a")
    rb.put(1, "b")
    blocked = threading.Event()
    passed = threading.Event()

    def far_ahead():
        blocked.set()
        rb.put(2, "c")  # seq 2 >= next(0) + capacity(2): must block
        passed.set()

    t = threading.Thread(target=far_ahead, daemon=True)
    t.start()
    blocked.wait(5)
    time.sleep(0.05)
    assert not passed.is_set(), "put past the bound did not block"
    assert rb.get() == "a"  # window advances -> the put completes
    passed.wait(5)
    assert passed.is_set()
    t.join(timeout=5)


def test_reorder_buffer_close_unblocks_everyone():
    rb = ReorderBuffer(capacity=1)
    woken = []

    def blocked_get():
        try:
            rb.get()
        except PipelineClosed:
            woken.append("get")

    def blocked_put():
        try:
            rb.put(5, "far")  # way past the window: blocks
        except PipelineClosed:
            woken.append("put")

    threads = [threading.Thread(target=blocked_get, daemon=True),
               threading.Thread(target=blocked_put, daemon=True)]
    for t in threads:
        t.start()
    time.sleep(0.05)
    rb.close()
    for t in threads:
        t.join(timeout=5)
    assert sorted(woken) == ["get", "put"]


def test_reorder_buffer_close_drops_buffered_items():
    """Close DROPS completed-but-unreleased items: they can hold device
    buffers, and a closed run must not pin them."""
    rb = ReorderBuffer(capacity=2)
    rb.put(0, "ready")
    rb.close()
    assert rb.fill() == 0
    with pytest.raises(PipelineClosed):
        rb.get()


# -- sharding / epoch plan ---------------------------------------------------

@pytest.mark.parametrize("n,k", [(10, 3), (37, 4), (8, 8), (5, 1),
                                 (100, 7)])
def test_shard_assignment_exact_cover(n, k):
    """Every record lands in exactly one shard — including the tail the
    reference's truncating num_parts split would drop."""
    parts = [iop.shard_records(n, k, i) for i in range(k)]
    allp = np.concatenate(parts)
    assert sorted(allp.tolist()) == list(range(n))
    assert max(len(p) for p in parts) - min(len(p) for p in parts) <= 1


@pytest.mark.parametrize("shuffle", [False, True])
def test_epoch_plan_covers_every_record_once(shuffle):
    plan = iop.epoch_plan(N_REC, 8, seed=9, epoch=2, shuffle=shuffle)
    non_pad = []
    for task in plan:
        rows = list(task.indices)
        if task.pad:
            rows = rows[:len(rows) - task.pad]
        non_pad.extend(rows)
    assert sorted(non_pad) == list(range(N_REC))
    # pad rows wrap to the epoch's first records
    tail = plan[-1]
    assert tail.pad == 3
    assert list(tail.indices[-tail.pad:]) == \
        list(iop.epoch_order(N_REC, 9, 2, shuffle)[:tail.pad])


def test_epoch_plan_discard_drops_tail():
    plan = iop.epoch_plan(N_REC, 8, seed=9, epoch=0, shuffle=False,
                          last_batch_handle="discard")
    assert len(plan) == N_REC // 8
    assert all(t.pad == 0 for t in plan)


def test_record_file_source_num_parts(rec_file):
    srcs = [iop.RecordFileSource(rec_file, rec_file + ".idx",
                                 num_parts=3, part_index=i)
            for i in range(3)]
    keys = sorted(k for s in srcs for k in s.keys)
    assert keys == list(range(N_REC))


# -- lifecycle ---------------------------------------------------------------

def test_clean_shutdown_mid_epoch(rec_file):
    src = _source(rec_file)
    dec = NoisyDecoder((FEAT,))
    pipe = iop.Pipeline(src, dec, batch_size=4, shuffle=True, seed=1,
                        num_workers=3, ctx=mx.cpu())
    it = pipe.as_dataiter()
    next(it)
    next(it)
    it.close()
    assert _no_pipeline_threads()
    it.close()  # idempotent
    with pytest.raises(MXNetError):
        it.next()
    with pytest.raises(MXNetError):
        it.reset()


def test_reset_mid_epoch_restarts_cleanly(rec_file):
    src = _source(rec_file)
    dec = NoisyDecoder((FEAT,))
    pipe = iop.Pipeline(src, dec, batch_size=8, shuffle=True, seed=1,
                        num_workers=2, ctx=mx.cpu())
    with pipe.as_dataiter() as it:
        next(it)
        it.reset()  # abandon epoch 0 mid-flight
        assert it.epoch == 1
        n = sum(1 for _ in it)
        assert n == 5
    assert _no_pipeline_threads()


def test_decode_error_aborts_epoch_cleanly(rec_file):
    src = _source(rec_file)

    class Exploding:
        def __init__(self):
            self._inner = iop.NDArrayRecordDecoder((FEAT,))

        def __call__(self, raw, rng):
            header, _ = recordio.unpack(raw)
            if header.id == 3:
                raise ValueError("boom on record 3")
            return self._inner(raw, rng)

    pipe = iop.Pipeline(src, Exploding(), batch_size=8, shuffle=False,
                        seed=0, num_workers=2)
    with pytest.raises(ValueError, match="boom"):
        for _ in pipe.host_batches(0):
            pass
    assert _no_pipeline_threads()


def test_fit_owns_and_closes_pipeline_adapter(rec_file):
    """fit() accepts the raw Pipeline, adapts it, trains, and tears the
    workers down on the way out — and with shuffle off the result is
    BITWISE what the same data through NDArrayIter produces, with
    identical exec-cache trace counters (the pipeline is invisible to
    the compiler)."""
    from mxnet_tpu import executor_cache
    from mxnet_tpu.io import NDArrayIter

    src = _source(rec_file)
    reader = src.open_reader()
    feats = np.stack([
        iop.NDArrayRecordDecoder((FEAT,))(reader.read(i), None)[0]
        for i in range(32)])
    labels = np.asarray([float(i % 5) for i in range(32)], np.float32)
    reader.close()

    class First32(iop.RecordFileSource):
        def __init__(self):
            super().__init__(rec_file, rec_file + ".idx")
            self.keys = self.keys[:32]

    def net():
        fc1 = mx.sym.FullyConnected(mx.sym.Variable("data"),
                                    num_hidden=16, name="fc1")
        act = mx.sym.Activation(fc1, act_type="relu", name="relu1")
        fc2 = mx.sym.FullyConnected(act, num_hidden=5, name="fc2")
        return mx.sym.SoftmaxOutput(fc2, name="softmax")

    def fit(data):
        executor_cache.clear()
        executor_cache.reset_stats()
        mx.random.seed(0)
        mod = mx.mod.Module(net(), context=mx.cpu())
        mod.fit(data, num_epoch=2,
                optimizer_params={"learning_rate": 0.1})
        return ({k: v.asnumpy().copy()
                 for k, v in mod.get_params()[0].items()},
                executor_cache.trace_counts())

    params_nd, counts_nd = fit(NDArrayIter(feats, labels, batch_size=8))
    params_pipe, counts_pipe = fit(iop.Pipeline(
        First32(), iop.NDArrayRecordDecoder((FEAT,)), batch_size=8,
        shuffle=False, num_workers=2, ctx=mx.cpu()))
    assert counts_pipe == counts_nd
    assert set(params_pipe) == set(params_nd)
    for k in params_nd:
        np.testing.assert_array_equal(params_pipe[k], params_nd[k])
    assert _no_pipeline_threads()


# -- telemetry ---------------------------------------------------------------

def test_starvation_telemetry_emitted(rec_file):
    from mxnet_tpu.observability import telemetry
    src = _source(rec_file)
    dec = NoisyDecoder((FEAT,))
    telemetry.reset()
    pipe = iop.Pipeline(src, dec, batch_size=8, shuffle=True, seed=2,
                        num_workers=2, ctx=mx.cpu())
    with pipe.as_dataiter() as it:
        for _ in it:
            pass
        snap = telemetry.snapshot()
    # 5 batches - the 2 arm-time primed pulls (suppressed: pipeline
    # spin-up is not starvation) = 3 counted consumer waits
    assert snap["io_pipeline.queue_wait_ms"]["count"] >= 3
    assert snap["io_pipeline.decode_ms"]["count"] >= 5
    assert snap["io_pipeline.records"]["value"] >= N_REC
    assert snap["io_pipeline.h2d_ms"]["count"] >= 5
    # 5 batches - the 2 the adapter primed at arm = 3 ahead pulls
    assert snap["io_pipeline.h2d_ahead_total"]["value"] >= 3
    # per-stage queue-depth gauges are registered and readable
    assert "io_pipeline.task_queue_depth" in snap
    assert "io_pipeline.reorder_fill" in snap
    # the adapter is a real DataIter: the process-wide starvation
    # histogram saw its batches too
    assert snap["io.next_batch_wait_ms"]["count"] >= 5


# -- satellite: PrefetchingIter lifecycle ------------------------------------

def test_prefetching_iter_explicit_close():
    from mxnet_tpu.io import NDArrayIter, PrefetchingIter
    rng = np.random.RandomState(0)
    base = NDArrayIter(rng.rand(24, 4).astype(np.float32),
                       rng.randint(0, 3, (24,)).astype(np.float32),
                       batch_size=8)
    with PrefetchingIter(base) as pf:
        assert sum(1 for _ in pf) == 3
    for t in getattr(pf, "prefetch_threads", []):
        assert not t.is_alive()
    pf.close()  # idempotent
    with pytest.raises(MXNetError):
        pf.next()
    with pytest.raises(MXNetError):
        pf.reset()


# -- satellite: io_native fallback hardening ---------------------------------

@pytest.fixture
def no_native(monkeypatch):
    """Force every native fast path onto its pure-Python fallback."""
    monkeypatch.setenv("MXNET_TPU_IO_NATIVE", "0")
    yield


def test_forced_fallback_pure_python_recordio(no_native, tmp_path):
    from mxnet_tpu import io_native
    assert io_native.get_lib() is None
    assert io_native.get_imgdec_lib() is None
    path = str(tmp_path / "fb.rec")
    writer = recordio.MXIndexedRecordIO(path + ".idx", path, "w")
    assert writer._native is None and writer.handle is not None
    for i in range(7):
        writer.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i), i, 0), b"payload-%d" % i))
    writer.close()
    reader = recordio.MXIndexedRecordIO(path + ".idx", path, "r")
    assert reader._native is None and reader.handle is not None
    header, s = recordio.unpack(reader.read_idx(4))
    assert s == b"payload-4" and header.label == 4.0
    reader.close()


def test_forced_fallback_pipeline_end_to_end(no_native, rec_file):
    """The whole pipeline runs on the pure-Python reader and produces
    the SAME bytes the native path produces (framing parity)."""
    src = _source(rec_file)
    dec = NoisyDecoder((FEAT,))
    fallback_seq = _sequence(iop.Pipeline(src, dec, batch_size=8,
                                          shuffle=True, seed=11,
                                          num_workers=2))
    os.environ.pop("MXNET_TPU_IO_NATIVE", None)
    native_seq = _sequence(iop.Pipeline(src, dec, batch_size=8,
                                        shuffle=True, seed=11,
                                        num_workers=2))
    assert fallback_seq == native_seq


def test_rebuild_rename_race_leaves_intact_library(tmp_path):
    """Regression: concurrent lazy rebuilds of the same .so (xdist
    workers, or two in-process threads hitting different lazy builders)
    must each complete an atomic rename — the final file is exactly ONE
    build's output, never an interleaving, and no temp files leak."""
    from mxnet_tpu.io_native import _run_gxx
    out = str(tmp_path / "lib.so")
    payloads = []
    for i in range(6):
        p = str(tmp_path / ("payload%d" % i))
        with open(p, "wb") as f:
            f.write(bytes([i]) * (200_000 + i))
        payloads.append(p)

    errors = []

    def build(i):
        try:
            # "cp src OUT" stands in for g++ -o OUT: _run_gxx must
            # redirect OUT to a private temp and atomically rename
            _run_gxx(["cp", payloads[i], out], out)
        except Exception as exc:
            errors.append(exc)

    threads = [threading.Thread(target=build, args=(i,))
               for i in range(len(payloads))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors
    with open(out, "rb") as f:
        data = f.read()
    expected = [bytes([i]) * (200_000 + i) for i in range(len(payloads))]
    assert data in expected, "output is an interleaving of builds"
    leftovers = [p for p in os.listdir(str(tmp_path)) if ".build." in p]
    assert not leftovers, leftovers
