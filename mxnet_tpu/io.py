"""Data iterators (ref: python/mxnet/io.py 951 LoC + src/io/ C++ iterators).

DataIter/DataBatch/DataDesc keep the reference API; NDArrayIter, CSVIter and
MNISTIter are implemented natively in Python/numpy feeding device arrays
(the C++ recordio image pipeline lives in mxnet_tpu/io_native + recordio.py).
"""
from __future__ import annotations

import gzip
import os
import struct
import threading
import time
import queue as _queue
from collections import namedtuple

import jax
import numpy as np

from . import threads as _threads
from .base import MXNetError
from .ndarray import NDArray, array
from .ndarray.ndarray import host_view
from .context import cpu
from .observability.instrument import note_io_wait


class DataDesc(namedtuple("DataDesc", ["name", "shape"])):
    def __new__(cls, name, shape, dtype=np.float32, layout="NCHW"):
        ret = super().__new__(cls, name, shape)
        ret.dtype = dtype
        ret.layout = layout
        return ret

    def __repr__(self):
        return "DataDesc[%s,%s,%s,%s]" % (self.name, self.shape, self.dtype,
                                          self.layout)

    @staticmethod
    def get_batch_axis(layout):
        if layout is None:
            return 0
        return layout.find("N")

    @staticmethod
    def get_list(shapes, types):
        if types is not None:
            type_dict = dict(types)
            return [DataDesc(x[0], x[1], type_dict[x[0]]) for x in shapes]
        return [DataDesc(x[0], x[1]) for x in shapes]


class DataBatch:
    def __init__(self, data, label=None, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        if data is not None:
            assert isinstance(data, (list, tuple)), "Data must be list of NDArrays"
        if label is not None:
            assert isinstance(label, (list, tuple)), "Label must be list of NDArrays"
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label

    def __str__(self):
        data_shapes = [d.shape for d in self.data]
        label_shapes = [l.shape for l in self.label] if self.label else None
        return "{}: data shapes: {} label shapes: {}".format(
            self.__class__.__name__, data_shapes, label_shapes)


class DataIter:
    """Base data iterator (ref: io.py:177)."""

    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self):
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=self.getindex())
        raise StopIteration

    def __next__(self):
        # every for-loop/`next()` consumer funnels through here: time the
        # wait so the telemetry registry can answer "is the step
        # input-bound?" (io.next_batch_wait_ms histogram + the
        # starvation ratio tools/traceview.py derives from step spans)
        t0 = time.perf_counter()
        batch = self.next()
        note_io_wait(time.perf_counter() - t0)
        return batch

    def iter_next(self):
        pass

    def getdata(self):
        pass

    def getlabel(self):
        pass

    def getindex(self):
        return None

    def getpad(self):
        pass


class ResizeIter(DataIter):
    """Resize the epoch length of an iterator (ref: io.py:279)."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__()
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None
        self.provide_data = data_iter.provide_data
        self.provide_label = data_iter.provide_label
        self.batch_size = data_iter.batch_size
        if hasattr(data_iter, "default_bucket_key"):
            self.default_bucket_key = data_iter.default_bucket_key

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def iter_next(self):
        if self.cur == self.size:
            return False
        try:
            self.current_batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = self.data_iter.next()
        self.cur += 1
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


class PrefetchingIter(DataIter):
    """Threaded prefetcher over one or more iterators (ref: io.py:344; the
    C++ analog is dmlc::ThreadedIter in iter_prefetcher.h).

    Lifecycle is explicit: call :meth:`close` (or use the iterator as a
    context manager) to stop and join the worker threads; ``__del__``
    remains as a gc-time fallback only.  The historical ``__del__``-only
    teardown let workers outlive the iterator and join() during
    interpreter shutdown — a deadlock when a worker sat blocked inside a
    base iterator's ``next()``.  (`mxnet_tpu.io_pipeline` is the
    multi-worker successor; this class keeps the reference surface.)"""

    def __init__(self, iters, rename_data=None, rename_label=None):
        super().__init__()
        if not isinstance(iters, list):
            iters = [iters]
        self.n_iter = len(iters)
        assert self.n_iter > 0
        self.iters = iters
        self.rename_data = rename_data
        self.rename_label = rename_label
        self.batch_size = self.provide_data[0][1][0]
        self.data_ready = [threading.Event() for _ in range(self.n_iter)]
        self.data_taken = [threading.Event() for _ in range(self.n_iter)]
        for e in self.data_taken:
            e.set()
        self.started = True
        self._closed = False
        self.current_batch = [None for _ in range(self.n_iter)]
        self.next_batch = [None for _ in range(self.n_iter)]

        def prefetch_func(self, i):
            while True:
                self.data_taken[i].wait()
                if not self.started:
                    break
                try:
                    self.next_batch[i] = self.iters[i].next()
                except StopIteration:
                    self.next_batch[i] = None
                self.data_taken[i].clear()
                self.data_ready[i].set()

        self.prefetch_threads = [
            _threads.spawn(prefetch_func, "io", "prefetch-%d" % i,
                           args=(self, i), start=False)
            for i in range(self.n_iter)]
        for thread in self.prefetch_threads:
            thread.start()

    def close(self):
        """Stop and join the prefetch threads (idempotent).  The
        iterator is unusable afterwards; a worker stuck in a base
        iterator's ``next()`` is abandoned (daemon) after a bounded
        join instead of deadlocking the caller."""
        if self._closed:
            return
        self._closed = True
        self.started = False
        for e in self.data_taken:
            e.set()
        for thread in self.prefetch_threads:
            thread.join(timeout=5.0)
        leaked = [t for t in self.prefetch_threads if t.is_alive()]
        if leaked:
            import warnings
            warnings.warn(
                "PrefetchingIter: %d worker(s) blocked in the base "
                "iterator were abandoned at close" % len(leaked))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):
        # gc-time fallback for callers that never close(); during
        # interpreter finalization the daemon threads die with the
        # process, so the bounded join in close() cannot hang exit
        try:
            self.close()
        except Exception:
            pass

    @property
    def provide_data(self):
        if self.rename_data is None:
            return sum([i.provide_data for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     if isinstance(x, DataDesc) else DataDesc(*x)
                     for x in i.provide_data]
                    for r, i in zip(self.rename_data, self.iters)], [])

    @property
    def provide_label(self):
        if self.rename_label is None:
            return sum([i.provide_label for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     if isinstance(x, DataDesc) else DataDesc(*x)
                     for x in i.provide_label]
                    for r, i in zip(self.rename_label, self.iters)], [])

    def reset(self):
        if self._closed:
            raise MXNetError("PrefetchingIter is closed")
        for e in self.data_ready:
            e.wait()
        for i in self.iters:
            i.reset()
        for e in self.data_ready:
            e.clear()
        for e in self.data_taken:
            e.set()

    def iter_next(self):
        if self._closed:
            raise MXNetError("PrefetchingIter is closed")
        for e in self.data_ready:
            e.wait()
        if self.next_batch[0] is None:
            for i in self.next_batch:
                assert i is None, "Number of entry mismatches between iterators"
            return False
        for batch in self.next_batch:
            assert batch.pad == self.next_batch[0].pad, \
                "Number of entry mismatches between iterators"
        self.current_batch = DataBatch(
            sum([batch.data for batch in self.next_batch], []),
            sum([batch.label for batch in self.next_batch], []),
            self.next_batch[0].pad, self.next_batch[0].index,
            provide_data=self.provide_data, provide_label=self.provide_label)
        for e in self.data_ready:
            e.clear()
        for e in self.data_taken:
            e.set()
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


def _is_h5_dataset(obj):
    """h5py.Dataset without importing h5py eagerly (it is optional —
    reference io.py:541 accepts h5py input when the library exists)."""
    mod = type(obj).__module__
    return mod.startswith("h5py") and type(obj).__name__ == "Dataset"


def _init_data(data, allow_empty, default_name):
    assert (data is not None) or allow_empty
    if data is None:
        data = []
    if isinstance(data, (np.ndarray, NDArray)) or _is_h5_dataset(data):
        data = [data]
    if isinstance(data, list):
        if not allow_empty:
            assert len(data) > 0
        if len(data) == 1:
            data = {default_name: data[0]}
        else:
            data = {"_%d_%s" % (i, default_name): d for i, d in enumerate(data)}
    if not isinstance(data, dict):
        raise TypeError("Input must be NDArray, numpy.ndarray, a list of them "
                        "or dict with them as values")
    out = {}
    for k, v in data.items():
        if _is_h5_dataset(v):
            pass  # stays lazy: batches slice the dataset out-of-core
        elif not isinstance(v, NDArray):
            try:
                v = array(v)
            except Exception:
                raise TypeError("Invalid type '%s' for %s" % (type(v), k))
        out[k] = v
    return list(sorted(out.items()))


class NDArrayIter(DataIter):
    """Iterate over NDArray/numpy data (ref: io.py:541).

    A batch that does not wrap around (``cursor + batch_size <=
    num_data``) is handed out as a *view*: where a source lives in host
    memory (numpy input, or an ``NDArray`` on the CPU backend) the
    batch's arrays alias the source's rows, nothing is copied.  That is
    safe under the MXNet surface because an ``NDArray`` never writes
    through: ``__setitem__``, ``out=`` and the optimizers rebind the
    handle to a new buffer, so a write to a batch leaves the source as
    it was.  (The CPU backend takes a buffer as it is when it starts on
    a 64-byte boundary and copies it otherwise: rows of a few bytes at
    an odd offset.)  A source on an accelerator is sliced there, an h5py
    dataset reads the window, and the wrap-around (``pad``) batch is
    assembled as a copy."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data",
                 label_name="softmax_label"):
        super().__init__(batch_size)
        self.data = _init_data(data, allow_empty=False, default_name=data_name)
        self.label = _init_data(label, allow_empty=True, default_name=label_name)
        self.idx = np.arange(self.data[0][1].shape[0])
        if shuffle:
            if any(_is_h5_dataset(v) for _, v in self.data + self.label):
                raise MXNetError(
                    "shuffle=True cannot reorder an out-of-core h5py "
                    "dataset; pre-shuffle the file or load it into "
                    "memory (np.asarray(dset)) first")
            np.random.shuffle(self.idx)
            self.data = [(k, array(v.asnumpy()[self.idx], v.context))
                         for k, v in self.data]
            self.label = [(k, array(v.asnumpy()[self.idx], v.context))
                          for k, v in self.label]
        if last_batch_handle == "discard":
            new_n = self.data[0][1].shape[0] - self.data[0][1].shape[0] % batch_size
            self.idx = self.idx[:new_n]
        self.data_list = [x[1] for x in self.data] + [x[1] for x in self.label]
        self.num_source = len(self.data_list)
        self.num_data = self.idx.shape[0]
        assert self.num_data >= batch_size, \
            "batch_size needs to be smaller than data size."
        self.cursor = -batch_size
        self.batch_size = batch_size
        self.last_batch_handle = last_batch_handle

    @property
    def provide_data(self):
        return [DataDesc(k, tuple([self.batch_size] + list(v.shape[1:])),
                         v.dtype)
                for k, v in self.data]

    @property
    def provide_label(self):
        return [DataDesc(k, tuple([self.batch_size] + list(v.shape[1:])),
                         v.dtype)
                for k, v in self.label]

    def hard_reset(self):
        self.cursor = -self.batch_size

    def reset(self):
        if self.last_batch_handle == "roll_over" and \
                self.cursor > self.num_data:
            self.cursor = -self.batch_size + (self.cursor % self.num_data) % self.batch_size
        else:
            self.cursor = -self.batch_size

    def iter_next(self):
        self.cursor += self.batch_size
        return self.cursor < self.num_data

    def next(self):
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=None)
        raise StopIteration

    @staticmethod
    def _rows(source, lo, hi):
        """Rows [lo:hi): a view of a source in host memory, a device
        slice of one on an accelerator; h5py datasets read just that
        window."""
        if not isinstance(source, NDArray):
            return array(np.asarray(source[lo:hi]))
        host = host_view(source._h.array)
        if host is None:
            return source[lo:hi]
        # the CPU backend takes an aligned numpy buffer as it is, and
        # holds on to it (one that is not aligned it copies)
        (dev,) = source._h.array.devices()
        return NDArray(jax.device_put(host[lo:hi], dev), ctx=source._ctx)

    def _getdata(self, data_source):
        assert self.cursor < self.num_data, "DataIter needs reset."
        if self.cursor + self.batch_size <= self.num_data:
            return [self._rows(x[1], self.cursor,
                               self.cursor + self.batch_size)
                    for x in data_source]
        pad = self.batch_size - self.num_data + self.cursor
        return [
            array(np.concatenate(
                (self._rows(x[1], self.cursor, self.num_data).asnumpy(),
                 self._rows(x[1], 0, pad).asnumpy()), axis=0))
            for x in data_source]

    def getdata(self):
        return self._getdata(self.data)

    def getlabel(self):
        return self._getdata(self.label)

    def getpad(self):
        if self.last_batch_handle == "pad" and \
                self.cursor + self.batch_size > self.num_data:
            return self.cursor + self.batch_size - self.num_data
        return 0


class MNISTIter(DataIter):
    """MNIST idx-format iterator (ref: src/io/iter_mnist.cc:80)."""

    def __init__(self, image="train-images-idx3-ubyte",
                 label="train-labels-idx1-ubyte", batch_size=128, shuffle=True,
                 flat=False, seed=0, silent=False, num_parts=1, part_index=0,
                 **kwargs):
        super().__init__(batch_size)

        def _present(p):
            return os.path.exists(p) or os.path.exists(p + ".gz")

        if _present(image) and _present(label):
            self._images = self._read_images(image)
            self._labels = self._read_labels(label)
        elif _present(image) or _present(label):
            # partial dataset: a copy mistake, not a missing download
            raise MXNetError(
                "MNIST files partially present (%s / %s); place both "
                "files there" % (image, label))
        else:
            # zero-egress fallback: the reference downloads MNIST on
            # demand; without network, synthesize data in the same
            # format/shapes so train_mnist-style scripts stay runnable.
            # The loud warning lives in the shared helper and ignores
            # `silent` — that flag only suppresses dataset chatter.
            from .test_utils import synthetic_image_dataset
            train = "train" in os.path.basename(image)
            data, labels = synthetic_image_dataset(
                (28, 28), 1, 2048 if train else 512,
                seed=42 if train else 43, what="mnist",
                root=os.path.dirname(image) or ".")
            self._images = data[:, :, :, 0].astype(np.float32) / 255.0
            self._labels = labels.astype(np.float32)
        if num_parts > 1:
            n = self._images.shape[0] // num_parts
            s = part_index * n
            self._images = self._images[s:s + n]
            self._labels = self._labels[s:s + n]
        if shuffle:
            rng = np.random.RandomState(seed)
            perm = rng.permutation(self._images.shape[0])
            self._images = self._images[perm]
            self._labels = self._labels[perm]
        self._flat = flat
        self.batch_size = batch_size
        self._inner = NDArrayIter(
            self._images.reshape(len(self._images), -1) if flat else
            self._images.reshape(len(self._images), 1, 28, 28),
            self._labels, batch_size=batch_size, shuffle=False)

    @staticmethod
    def _open(path):
        if path.endswith(".gz"):
            return gzip.open(path, "rb")
        if not os.path.exists(path) and os.path.exists(path + ".gz"):
            return gzip.open(path + ".gz", "rb")
        return open(path, "rb")

    def _read_images(self, path):
        with self._open(path) as f:
            magic, n, rows, cols = struct.unpack(">IIII", f.read(16))
            if magic != 2051:
                raise MXNetError("bad MNIST image file %s" % path)
            data = np.frombuffer(f.read(n * rows * cols), dtype=np.uint8)
        return (data.reshape(n, rows, cols).astype(np.float32) / 255.0)

    def _read_labels(self, path):
        with self._open(path) as f:
            magic, n = struct.unpack(">II", f.read(8))
            if magic != 2049:
                raise MXNetError("bad MNIST label file %s" % path)
            return np.frombuffer(f.read(n), dtype=np.uint8).astype(np.float32)

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()


class CSVIter(DataIter):
    """CSV iterator (ref: src/io/iter_csv.cc)."""

    def __init__(self, data_csv, data_shape, label_csv=None, label_shape=(1,),
                 batch_size=1, round_batch=True, **kwargs):
        super().__init__(batch_size)
        data = np.loadtxt(data_csv, delimiter=",", dtype=np.float32)
        data = data.reshape((-1,) + tuple(data_shape))
        label = None
        if label_csv is not None:
            label = np.loadtxt(label_csv, delimiter=",", dtype=np.float32)
            label = label.reshape((-1,) + tuple(label_shape))
            if label_shape == (1,):
                label = label.reshape(-1)
        else:
            label = np.zeros(data.shape[0], dtype=np.float32)
        self._inner = NDArrayIter(data, label, batch_size=batch_size,
                                  last_batch_handle="pad" if round_batch else "discard",
                                  label_name="label")
        self.batch_size = batch_size

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()


def MXDataIter(name, **kwargs):
    """Create a registered iterator by name.

    The reference's MXDataIter (python/mxnet/io.py:759) wraps a C++
    iterator created through the MXDataIterCreateIter registry; here the
    registry is the Python-side table below, so reference code that
    resolves iterators by name keeps working."""
    try:
        creator = _DATA_ITER_REGISTRY[name]
    except KeyError:
        raise MXNetError(
            "unknown data iterator %r; registered: %s"
            % (name, sorted(_DATA_ITER_REGISTRY)))
    return creator(**kwargs)


def _build_rec_index(path_imgrec, path_idx):
    """Scan a bare .rec once and write a key\toffset index so shuffling and
    num_parts sharding work without a pre-built .idx (the reference's
    chunk-shuffle reads bare .rec files too).

    Written to a private temp file and atomically renamed: concurrent
    builders (pytest-xdist workers, multiple training hosts on a shared
    filesystem) must never observe a half-written index — a reader of a
    partial file would silently train on a truncated record set (same
    hardening as io_native._run_gxx's .so builds)."""
    from . import recordio as _rio
    reader = _rio.MXRecordIO(path_imgrec, "r")
    tmp = "%s.build.%d.%d" % (path_idx, os.getpid(),
                              threading.get_ident())
    try:
        with open(tmp, "w") as f:
            i = 0
            while True:
                pos = reader.tell()
                if reader.read() is None:
                    break
                f.write("%d\t%d\n" % (i, pos))
                i += 1
        os.replace(tmp, path_idx)
    finally:
        reader.close()
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def ImageRecordIter(path_imgrec=None, path_imgidx=None, data_shape=None,
                    batch_size=1, label_width=1, shuffle=False,
                    resize=0, rand_crop=False, rand_mirror=False,
                    mean_r=0.0, mean_g=0.0, mean_b=0.0,
                    std_r=0.0, std_g=0.0, std_b=0.0,
                    brightness=0.0, contrast=0.0, saturation=0.0,
                    pca_noise=0.0, num_parts=1, part_index=0,
                    data_name="data", label_name="softmax_label",
                    seed=None, preprocess_threads=0, ctx=None, **kwargs):
    """Image pipeline over packed .rec files (ref: ImageRecordIter2,
    src/io/iter_image_recordio_2.cc — the reference's C++ decode/augment/
    batch pipeline with its flat kwargs surface).  Decode runs through
    cv2 on the host; records stream through the native recordio reader
    with threaded prefetch (src/recordio.cc) when built.

    Unrecognized reference knobs are accepted and ignored (the reference
    has ~40; the load-bearing ones are mapped)."""
    import numpy as np
    from .image import CreateAugmenter, ImageIter

    if data_shape is None:
        raise MXNetError("ImageRecordIter requires data_shape")
    data_shape = tuple(int(x) for x in data_shape)
    if seed is not None:
        # NOTE: augmenters draw from the process-global RNGs, so seeding
        # here affects (and is affected by) other global-RNG users — two
        # iterators with different seeds interleave one stream.  The seed
        # is re-applied on every reset() (below) so each epoch's order is
        # reproducible even when other code draws between epochs.
        import random as _pyrandom
        _pyrandom.seed(int(seed))
        np.random.seed(int(seed) & 0x7FFFFFFF)
    mean = None
    if mean_r or mean_g or mean_b:
        mean = np.array([mean_r, mean_g, mean_b], np.float32)
    std = None
    if std_r or std_g or std_b:
        std = np.array([std_r or 1.0, std_g or 1.0, std_b or 1.0],
                       np.float32)
    if mean is not None and std is None:
        std = np.array([1.0, 1.0, 1.0], np.float32)
    if std is not None and mean is None:
        mean = np.array([0.0, 0.0, 0.0], np.float32)  # std-only: still divide
    if (shuffle or num_parts > 1) and path_imgrec and not path_imgidx:
        # shuffling/sharding needs random access; build the index once
        path_imgidx = path_imgrec + ".autoidx"
        if not os.path.exists(path_imgidx):
            _build_rec_index(path_imgrec, path_imgidx)
    aug = CreateAugmenter(data_shape, resize=resize, rand_crop=rand_crop,
                          rand_mirror=rand_mirror, mean=mean, std=std,
                          brightness=brightness, contrast=contrast,
                          saturation=saturation, pca_noise=pca_noise)
    it = ImageIter(batch_size=batch_size, data_shape=data_shape,
                   label_width=label_width, path_imgrec=path_imgrec,
                   path_imgidx=path_imgidx, shuffle=shuffle,
                   part_index=part_index, num_parts=num_parts,
                   aug_list=aug, data_name=data_name,
                   label_name=label_name)
    if seed is not None:
        # reproducible epochs: reset() re-seeds the global RNGs from
        # (seed, epoch index), so epoch k's shuffle/augment stream depends
        # only on the seed — not on interleaved global-RNG draws — while
        # successive epochs still get distinct augmentation draws
        base_reset = it.reset
        # construction already consumed the seed-0 stream (ImageIter's own
        # init-time reset/shuffle), so the first wrapped reset starts at 1
        epoch_box = [1]

        def _reset_with_seed():
            import random as _pyrandom
            epoch_seed = (int(seed) + 1000003 * epoch_box[0]) & 0x7FFFFFFF
            epoch_box[0] += 1
            _pyrandom.seed(epoch_seed)
            np.random.seed(epoch_seed)
            base_reset()

        it.reset = _reset_with_seed
    if preprocess_threads and int(preprocess_threads) > 0:
        # the reference's preprocess_threads knob (iter_image_recordio_2.cc
        # decode thread pool) maps onto the native dependency engine:
        # a serialized record-read op fans out to preprocess_threads
        # concurrent decode/augment ops (per-record-index RNG keeps
        # augmentation deterministic across thread interleavings), then an
        # assemble+upload op per batch slot — see EnginePipelineIter.
        try:
            return EnginePipelineIter(it, ctx=ctx,
                                      num_workers=int(preprocess_threads),
                                      seed=seed)
        except RuntimeError:
            pass  # no native engine: DevicePrefetchIter below still uploads
    if ctx is not None:
        return DevicePrefetchIter(it, ctx=ctx)
    return it


def ImageRecordIter_v1(**kwargs):
    return ImageRecordIter(**kwargs)


def _parse_libsvm(path):
    """Parse a libsvm file into (labels[R, L], indptr[R+1], indices, values).

    Lines are `label[,label...] idx:val idx:val ...`; feature indices are
    0-based (matching the reference's LibSVMIter contract,
    src/io/iter_libsvm.cc)."""
    labels, indptr, indices, values = [], [0], [], []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            head, *feats = line.split()
            row_labels = [float(x) for x in head.split(",")]
            if labels and len(row_labels) != len(labels[0]):
                raise MXNetError(
                    "%s:%d: %d label(s) but earlier rows have %d"
                    % (path, lineno, len(row_labels), len(labels[0])))
            labels.append(row_labels)
            for tok in feats:
                idx, val = tok.split(":")
                indices.append(int(idx))
                values.append(float(val))
            indptr.append(len(indices))
    if not labels:
        raise MXNetError("%s: no data rows" % (path,))
    return (np.asarray(labels, np.float32), np.asarray(indptr, np.int64),
            np.asarray(indices, np.int64), np.asarray(values, np.float32))


class LibSVMIter(DataIter):
    """Sparse batch iterator over libsvm files (ref: src/io/iter_libsvm.cc).

    Yields DataBatches whose data is a CSRNDArray of shape
    (batch_size,) + data_shape and whose label is dense — a single float
    per row from the data file, or vectors from a separate `label_libsvm`
    file.  The final partial batch is always served with `pad` set and
    wrapped rows as padding content (the reference's batch loader also
    returns the padded tail regardless of round_batch,
    iter_batchloader.h); `round_batch` is accepted for API parity."""

    def __init__(self, data_libsvm, data_shape, label_libsvm=None,
                 label_shape=None, batch_size=1, round_batch=True,
                 data_name="data", label_name="softmax_label", **kwargs):
        super().__init__(batch_size)
        labels, self._indptr, self._indices, self._values = \
            _parse_libsvm(data_libsvm)
        self._labels = labels[:, 0] if labels.shape[1] == 1 else labels
        if label_libsvm is not None:
            ext_labels, lptr, lidx, lval = _parse_libsvm(label_libsvm)
            if len(ext_labels) != len(labels):
                raise MXNetError(
                    "label_libsvm has %d rows but data_libsvm has %d"
                    % (len(ext_labels), len(labels)))
            dim = int(label_shape[0]) if label_shape else (
                int(lidx.max()) + 1 if lidx.size else 1)
            dense = np.zeros((len(ext_labels), dim), np.float32)
            for r in range(len(ext_labels)):
                cols = lidx[lptr[r]:lptr[r + 1]]
                dense[r, cols] = lval[lptr[r]:lptr[r + 1]]
            self._labels = dense
        self._data_shape = tuple(int(x) for x in data_shape)
        self._data_name = data_name
        self._label_name = label_name
        self._round_batch = bool(round_batch)
        self.num_rows = len(self._indptr) - 1
        self._row_nnz = np.diff(self._indptr)
        if self._indices.size and int(self._indices.max()) >= self._data_shape[0]:
            raise MXNetError(
                "libsvm feature index %d out of range for data_shape %s "
                "(indices are 0-based)"
                % (int(self._indices.max()), self._data_shape))
        self._cursor = 0

    @property
    def provide_data(self):
        return [DataDesc(self._data_name,
                         (self.batch_size,) + self._data_shape)]

    @property
    def provide_label(self):
        shape = (self.batch_size,) + (
            self._labels.shape[1:] if self._labels.ndim > 1 else ())
        return [DataDesc(self._label_name, shape)]

    def reset(self):
        self._cursor = 0

    def _row_batch(self, rows):
        """CSR slice for the given row ids (may wrap for padding)."""
        from .ndarray import sparse as _sp
        counts = self._row_nnz[rows]
        indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        spans = [np.arange(self._indptr[r], self._indptr[r + 1])
                 for r in rows]
        flat = np.concatenate(spans).astype(np.int64) if spans else \
            np.zeros((0,), np.int64)
        return _sp.CSRNDArray(
            array(self._values[flat]), self._indices[flat], indptr,
            (len(rows),) + self._data_shape)

    def next(self):
        if self._cursor >= self.num_rows:
            raise StopIteration
        end = self._cursor + self.batch_size
        pad = max(0, end - self.num_rows)
        rows = np.arange(self._cursor, end) % self.num_rows
        self._cursor = end
        data = self._row_batch(rows)
        label = array(self._labels[rows])
        return DataBatch(data=[data], label=[label], pad=pad,
                         provide_data=self.provide_data,
                         provide_label=self.provide_label)


def _upload_batch(batch, dev):
    """A DataBatch with every data/label array device_put onto `dev`."""
    import jax as _jax

    def put(arrs):
        if not arrs:
            return arrs
        return [NDArray(_jax.device_put(a._h.array, dev)) for a in arrs]

    return DataBatch(data=put(batch.data), label=put(batch.label or []),
                     pad=batch.pad, index=batch.index,
                     provide_data=batch.provide_data,
                     provide_label=batch.provide_label)


class DevicePrefetchIter(DataIter):
    """Upload batches to the device ahead of consumption.

    The reference overlaps host->device copies with compute via dedicated
    copy-lane engine threads (FnProperty::kCopyFromCPU, SURVEY.md §2.1);
    here jax's async dispatch gives the overlap for free once the
    `device_put` for batch N+1 is ISSUED while step N runs — this wrapper
    issues it one batch early, so a training loop sees device-resident
    data and the transfer rides under the previous step's compute.
    """

    def __init__(self, base_iter, ctx=None):
        super().__init__()
        from .context import current_context
        self._base = base_iter
        self._ctx = ctx or current_context()
        self._dev = self._ctx.jax_device()
        self._pending = None
        self.batch_size = getattr(base_iter, "batch_size", None)

    @property
    def provide_data(self):
        return self._base.provide_data

    @property
    def provide_label(self):
        return self._base.provide_label

    def reset(self):
        self._base.reset()
        self._pending = None

    def next(self):
        if self._pending is None:
            try:
                self._pending = _upload_batch(self._base.next(), self._dev)
            except StopIteration:
                raise
        out = self._pending
        # issue the NEXT upload now — it overlaps the caller's compute on
        # the batch being returned
        try:
            self._pending = _upload_batch(self._base.next(), self._dev)
        except StopIteration:
            self._pending = None
        return out


# name -> creator table backing MXDataIter (the C++ iterator-registry
# analog; MXNET_REGISTER_IO_ITER in the reference)
_DATA_ITER_REGISTRY = {
    "MNISTIter": MNISTIter,
    "CSVIter": CSVIter,
    "LibSVMIter": LibSVMIter,
    "ImageRecordIter": ImageRecordIter,
    "ImageRecordIter_v1": ImageRecordIter_v1,
    "NDArrayIter": NDArrayIter,
}


class EnginePipelineIter(DataIter):
    """Engine-scheduled input pipeline: record read, decode/augment, and
    device upload run as NativeEngine ops with var dependencies.

    This is the host-side analog of the reference's ImageRecordIOParser2
    pipeline (SURVEY.md §2.1/§2.4: dmlc ThreadedIter prefetch feeding a
    decode THREAD POOL, iter_image_recordio_2.cc:50, then engine-ordered
    CopyFromCPU ops).  With num_workers > 1 and a sample-capable base
    iterator the stages are:

      read op     (serialized on the iterator var) pulls a batch of raw
                  records — cheap, order-defining;
      decode ops  one per worker, each decoding a stride-W shard of the
                  batch CONCURRENTLY.  Each record's augmentation draws
                  come from a per-record-index RNG
                  (image.seed_augmenter_rng), so the augmentation a record
                  receives is a pure function of (seed, epoch, index) —
                  identical whatever the thread interleaving;
      assemble op (after every shard) builds the DataBatch and issues the
                  host->device transfer.

    The training loop only ever waits on a ready slot.  Spans appear in
    the profiler's Chrome trace under the "engine" category.
    """

    def __init__(self, base, depth=2, ctx=None, num_workers=2, engine=None,
                 seed=None):
        from .io_native import NativeEngine
        super().__init__(base.batch_size)
        self._base = base
        # workers beyond cores+2 only thrash the scheduler (measured: a
        # 1-core host collapses from 780 to 300 img/s at 4 workers)
        cap = (os.cpu_count() or 2) + 2
        self._n_workers = max(1, min(int(num_workers), cap))
        # +1 thread so the serialized read op overlaps the decode shards
        self._engine = engine or NativeEngine(self._n_workers + 1)
        self._ctx = ctx
        self._iter_var = self._engine.new_var()
        # the staged (read -> decode -> assemble) pipeline engages for ANY
        # worker count when the base supports sample access — also at
        # num_workers=1, so the per-record-seed augmentation stream is the
        # same whatever the worker count
        self._parallel = (hasattr(base, "next_sample")
                          and hasattr(base, "imdecode")
                          and hasattr(base, "augmentation_transform")
                          and hasattr(base, "data_shape"))
        self._slots = [{"var": self._engine.new_var(), "batch": None,
                        "stop": False, "error": None,
                        "shard_vars": tuple(self._engine.new_var()
                                            for _ in range(self._n_workers))
                        if self._parallel else ()}
                       for _ in range(max(1, depth))]
        self._idx = 0
        self._armed = False
        self._seed = int(seed) if seed is not None \
            else int.from_bytes(os.urandom(4), "little")
        self._epoch = 0
        self._sample_idx = 0

    @property
    def provide_data(self):
        return self._base.provide_data

    @property
    def provide_label(self):
        return self._base.provide_label

    def _arm(self, slot):
        if self._parallel:
            self._arm_parallel(slot)
            return
        from . import profiler as _profiler

        def produce():
            try:
                with _profiler.record_span("engine_decode_augment",
                                           category="engine"):
                    slot["batch"] = self._base.next()
                slot["stop"] = False
            except StopIteration:
                slot["batch"], slot["stop"] = None, True
            except Exception as e:  # surfaced on the consumer thread
                slot["error"] = e

        # produce ops serialize on _iter_var (the base iterator and the
        # augmenter RNG are single-threaded state); each writes its slot
        self._engine.push(produce, mutable_vars=(self._iter_var,
                                                 slot["var"]))
        if self._ctx is not None:
            dev = self._ctx.jax_device()

            def upload():
                if slot["batch"] is None or slot["error"] is not None:
                    return
                try:
                    with _profiler.record_span("engine_device_upload",
                                               category="engine"):
                        slot["batch"] = _upload_batch(slot["batch"], dev)
                except Exception as e:  # surfaced on the consumer thread
                    slot["error"] = e

            # write-after-write on the slot var orders upload after produce
            # while the NEXT slot's produce overlaps (the copy-lane analog)
            self._engine.push(upload, mutable_vars=(slot["var"],))

    def _record_seed(self, gidx):
        """Per-record augmentation seed: a pure function of
        (iterator seed, epoch, running sample index)."""
        return ((self._seed * 1000003 + self._epoch * 7919)
                ^ (gidx * 2654435761)) & 0x7FFFFFFF

    def _augment_plan(self):
        """Split the base augmenter list into a per-image geometry stage
        and a batch-level arithmetic stage, preferring the NATIVE kernel.

        Python's GIL is the scaling wall the reference never had (its
        decode pool is C++, iter_image_recordio_2.cc:50): per-image Python
        work serializes worker threads no matter how many run.  Three
        tiers, best available wins:

        1. native: the standard train chain (short-side resize ->
           random/center crop -> flip -> mean/std normalize) runs as ONE
           C call per worker shard (src/image_decode.cc) writing f32 CHW
           straight into the batch buffer — the GIL is released for the
           whole shard and workers scale like the reference's pool;
        2. geometry-only python: cv2 stages (which release the GIL)
           per image, normalize ONCE per batch as contiguous ufuncs;
        3. generic: any exotic augmenter list, per image.

        Returns a dict plan or None (generic)."""
        from .image import image as _im
        augs = list(getattr(self._base, "auglist", ()))
        mean = std = None
        while augs and isinstance(augs[-1], (_im.CastAug,
                                             _im.ColorNormalizeAug)):
            a = augs.pop()
            if isinstance(a, _im.ColorNormalizeAug):
                mean, std = a.mean, a.std
        geom = (_im.ResizeAug, _im.ForceResizeAug, _im.RandomCropAug,
                _im.CenterCropAug, _im.RandomSizedCropAug,
                _im.HorizontalFlipAug)
        if not all(isinstance(a, geom) for a in augs):
            return None
        plan = {"geom": augs, "mean": mean, "std": std, "native": False,
                "seq": None}
        # seq eligibility: 3-channel, and the aug sequence is at most
        # resize? -> one crop? -> flip?.  seq-able chains draw their
        # randomness as u01 triples from the per-record RNG, so the python
        # and native implementations of the SAME seq produce the SAME
        # stream — augmentation must not depend on whether the native
        # kernel compiled on this host.
        c = self._base.data_shape[0]
        seq = {"resize": 0, "interp": 2, "crop_mode": 0, "flip_p": -1.0}
        stage = 0  # 0: expect resize/crop/flip, advance monotonically
        ok = c == 3
        for a in augs:
            if isinstance(a, _im.ResizeAug) and stage == 0:
                seq["resize"], seq["interp"] = int(a.size), int(a.interp)
                stage = 1
            elif isinstance(a, _im.RandomCropAug) and stage <= 1:
                seq["crop_mode"], seq["interp"] = 1, int(a.interp)
                stage = 2
            elif isinstance(a, _im.CenterCropAug) and stage <= 1:
                seq["crop_mode"], seq["interp"] = 2, int(a.interp)
                stage = 2
            elif isinstance(a, _im.HorizontalFlipAug) and stage <= 2:
                seq["flip_p"] = float(a.p)
                stage = 3
            else:
                ok = False
                break
        if ok:
            plan["seq"] = seq
            from .io_native import get_imgdec_lib
            plan["native"] = get_imgdec_lib() is not None
        return plan

    def _arm_parallel(self, slot):
        from . import profiler as _profiler
        base = self._base
        W = self._n_workers
        B = self.batch_size
        c, h, w = base.data_shape
        lw = getattr(base, "label_width", 1)
        plan = getattr(self, "_plan_cache", "unset")
        if plan == "unset":
            plan = self._plan_cache = self._augment_plan()

        def read():
            try:
                with _profiler.record_span("engine_read",
                                           category="engine"):
                    raw = []
                    try:
                        while len(raw) < B:
                            label, s = base.next_sample()
                            raw.append((label, s, self._sample_idx))
                            self._sample_idx += 1
                    except StopIteration:
                        pass
                slot["raw"] = raw
                slot["pad"] = B - len(raw)
                slot["stop"] = not raw
                if raw:
                    # geometry stage emits uint8 CHW per image (the
                    # per-image transpose is a 150KB cache-friendly copy
                    # done on the PARALLEL workers; a batch-level NHWC->
                    # NCHW transpose would be one giant strided copy in
                    # the serial assemble); batch stage casts+normalizes
                    # in contiguous passes.  The native kernel writes
                    # normalized f32 directly (see _augment_plan).
                    if plan and plan["native"]:
                        dt = np.float32  # native writes normalized f32
                    elif plan:
                        dt = np.uint8    # batch stage casts+normalizes
                    else:
                        dt = np.float32
                    slot["data"] = np.zeros((B, c, h, w), dt)
                    slot["label"] = np.zeros(
                        (B, lw) if lw > 1 else (B,), np.float32)
            except Exception as e:  # surfaced on the consumer thread
                slot["error"] = e

        # the read op serializes on the iterator var (stream position is
        # the only single-threaded state left); decode fans out after it
        self._engine.push(read, mutable_vars=(self._iter_var, slot["var"]))

        def _u01(gidx):
            """The seq tiers' randomness: three uniforms per record (crop
            x, crop y, flip), identical for the python and native
            implementations."""
            import random as _pyrandom
            rng = _pyrandom.Random(self._record_seed(gidx))
            return rng.random(), rng.random(), rng.random()

        def decode_seq_py(lo, hi):
            """Python implementation of the seq plan — consumes the SAME
            u01 draws as the native kernel, emits u8 CHW (cv2 stages
            release the GIL; normalize runs batch-level in assemble)."""
            from .image import image as _im
            seq = plan["seq"]
            raw = slot["raw"]
            for j in range(lo, hi):
                label, s, gidx = raw[j]
                ux, uy, uflip = _u01(gidx)
                img = base.imdecode_np(s) if hasattr(base, "imdecode_np") \
                    else base.imdecode(s).asnumpy()
                if seq["resize"]:
                    img = _im.resize_short(img, seq["resize"],
                                           seq["interp"])
                ih, iw = img.shape[:2]
                if seq["crop_mode"]:
                    cw, ch = _im.scale_down((iw, ih), (w, h))
                    if seq["crop_mode"] == 1:
                        x0 = min(int(ux * (iw - cw + 1)), iw - cw)
                        y0 = min(int(uy * (ih - ch + 1)), ih - ch)
                    else:
                        x0, y0 = (iw - cw) // 2, (ih - ch) // 2
                    img = img[y0:y0 + ch, x0:x0 + cw]
                    if (cw, ch) != (w, h):
                        img = _im.imresize(img, w, h, seq["interp"])
                elif (ih, iw) != (h, w):
                    img = _im.imresize(img, w, h, seq["interp"])
                if seq["flip_p"] >= 0 and uflip < seq["flip_p"]:
                    img = img[:, ::-1]
                slot["data"][j] = img.transpose(2, 0, 1)
                slot["label"][j] = label

        def decode_native(lo, hi):
            """One C call for the contiguous shard [lo, hi): decode +
            geometry + normalize into the f32 CHW batch buffer, GIL-free
            for the whole span."""
            import ctypes
            from .base import MXNetError
            from .io_native import get_imgdec_lib
            lib = get_imgdec_lib()
            seq = plan["seq"]
            raw = slot["raw"]
            n = hi - lo
            bufs = (ctypes.c_void_p * n)()
            lens = (ctypes.c_int64 * n)()
            keep = []
            u01 = np.empty((n, 3), np.float32)
            for t in range(n):
                label, s, gidx = raw[lo + t]
                b = s if isinstance(s, bytes) else bytes(s)
                keep.append(b)
                bufs[t] = ctypes.cast(ctypes.c_char_p(b), ctypes.c_void_p)
                lens[t] = len(b)
                u01[t] = _u01(gidx)
                slot["label"][lo + t] = label
            f32p = ctypes.POINTER(ctypes.c_float)

            def fp(a):
                return a.ctypes.data_as(f32p) if a is not None else None

            mean = np.ascontiguousarray(plan["mean"], np.float32).reshape(-1) \
                if plan["mean"] is not None else None
            std = np.ascontiguousarray(plan["std"], np.float32).reshape(-1) \
                if plan["std"] is not None else None
            out = slot["data"][lo:hi]  # contiguous f32 view
            err = ctypes.create_string_buffer(256)
            rc = lib.img_decode_chain(
                bufs, lens, n, seq["resize"], seq["interp"],
                seq["crop_mode"], fp(u01), seq["flip_p"], h, w,
                fp(mean), fp(std), out.ctypes.data_as(f32p), err, 256)
            if rc != 0:
                raise MXNetError("native decode failed: %s"
                                 % err.value.decode())

        def make_decode(k):
            def decode():
                from .image import image as _image
                try:
                    raw = slot.get("raw") or ()
                    if slot["error"] is not None or not raw:
                        return
                    chunk = (len(raw) + W - 1) // W
                    lo = min(k * chunk, len(raw))
                    hi = min(lo + chunk, len(raw))
                    if lo == hi:
                        return
                    with _profiler.record_span("engine_decode_augment",
                                               category="engine"):
                        if plan and plan["seq"]:
                            if plan["native"]:
                                decode_native(lo, hi)
                            else:
                                decode_seq_py(lo, hi)
                            return
                        for j in range(lo, hi):
                            label, s, gidx = raw[j]
                            _image.seed_augmenter_rng(self._record_seed(gidx))
                            if plan:
                                # plannable but not seq-able (e.g. random-
                                # sized crop): geometry augmenters per
                                # image, normalize batch-level
                                data = base.imdecode_np(s) if hasattr(
                                    base, "imdecode_np") \
                                    else base.imdecode(s).asnumpy()
                                for a in plan["geom"]:
                                    data = a(data)
                            else:
                                # generic: full augmenter list per image;
                                # numpy when every augmenter is builtin,
                                # else the NDArray contract for
                                # user-supplied augmenters
                                if getattr(base, "_all_builtin_augs",
                                           False) and \
                                        hasattr(base, "imdecode_np"):
                                    data = base.imdecode_np(s)
                                else:
                                    data = base.imdecode(s)
                                data = base.augmentation_transform(data)
                                if hasattr(data, "asnumpy"):
                                    data = data.asnumpy()
                            slot["data"][j] = data.transpose(2, 0, 1)
                            slot["label"][j] = label
                except Exception as e:
                    slot["error"] = e
            return decode

        for k in range(W):
            self._engine.push(make_decode(k), const_vars=(slot["var"],),
                              mutable_vars=(slot["shard_vars"][k],))

        dev = self._ctx.jax_device() if self._ctx is not None else None

        def assemble():
            if slot["error"] is not None or slot.get("stop") or \
                    slot.get("raw") is None:
                return
            try:
                with _profiler.record_span("engine_device_upload",
                                           category="engine"):
                    from .context import cpu as _cpu
                    from .ndarray import array as nd_array
                    data = slot["data"]  # already CHW
                    if plan and not plan["native"]:
                        # contiguous whole-batch passes: u8 -> f32
                        # (+ mean/std) — big single ufuncs instead of
                        # per-image numpy under the GIL (the native
                        # kernel already wrote normalized f32)
                        mean, std = plan["mean"], plan["std"]
                        if mean is not None:
                            data = np.subtract(
                                data, np.asarray(mean, np.float32)
                                .reshape(1, -1, 1, 1), dtype=np.float32)
                        else:
                            data = data.astype(np.float32)
                        if std is not None:
                            data /= np.asarray(std, np.float32) \
                                .reshape(1, -1, 1, 1)
                    # batches are CPU-resident (reference iterator
                    # contract); the consumer/train loop owns the upload
                    batch = DataBatch(
                        [nd_array(data, ctx=_cpu(0))],
                        [nd_array(slot["label"], ctx=_cpu(0))],
                        pad=slot["pad"])
                    if dev is not None:
                        batch = _upload_batch(batch, dev)
                    slot["batch"] = batch
                    slot["raw"] = slot["data"] = slot["label"] = None
            except Exception as e:
                slot["error"] = e

        self._engine.push(assemble, const_vars=slot["shard_vars"],
                          mutable_vars=(slot["var"],))

    def _arm_all(self):
        for s in self._slots:
            s["batch"], s["stop"], s["error"] = None, False, None
            self._arm(s)
        self._armed = True

    def next(self):
        if not self._armed:
            self._arm_all()
        slot = self._slots[self._idx % len(self._slots)]
        self._engine.wait_for_var(slot["var"])
        if slot["error"] is not None:
            # surface the error but keep the pipeline usable: re-arm the
            # slot and advance, like the success path
            err = slot["error"]
            slot["error"], slot["batch"] = None, None
            self._arm(slot)
            self._idx += 1
            raise err
        if slot["stop"]:
            raise StopIteration
        batch = slot["batch"]
        slot["batch"] = None
        self._arm(slot)  # refill behind the consumer
        self._idx += 1
        return batch

    def reset(self):
        self._engine.wait_for_all()
        self._base.reset()
        self._armed = False
        self._idx = 0
        self._epoch += 1
        self._sample_idx = 0
