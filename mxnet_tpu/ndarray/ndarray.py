"""NDArray: the imperative tensor living in device HBM as a jax.Array.

TPU-native rebuild of include/mxnet/ndarray.h + src/ndarray/ndarray.cc
(2.8k LoC of engine/chunk plumbing) and python/mxnet/ndarray/ndarray.py.
The reference's Chunk{Storage::Handle, Engine::VarHandle} becomes a one-slot
handle holding a jax.Array: XLA's async dispatch provides the engine's
read/write ordering, jax.Array's device buffer is the storage, and mutation
(`a[:] = x`, `out=` kwargs, optimizer updates) rebinds the handle — the
observable MXNet semantics (async execution, wait_to_read, in-place API)
are preserved on immutable device buffers.
"""
from __future__ import annotations

import os
import struct

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError, np_dtype, dtype_name
from ..context import Context, current_context, cpu
from ..ops.registry import get_op, apply_op, op_registry
from .. import autograd as ag
from .. import random as _random


class _Handle:
    """Mutable slot holding the current jax.Array value (the Chunk analog)."""

    __slots__ = ("array",)

    def __init__(self, array):
        self.array = array


class NDArray:
    __slots__ = ("_h", "_ctx", "_grad", "_grad_req", "_tape_entry", "_stype",
                 "__weakref__")

    def __init__(self, handle, ctx=None):
        if isinstance(handle, _Handle):
            self._h = handle
        else:
            self._h = _Handle(handle)
        self._ctx = ctx
        self._grad = None
        self._grad_req = "null"
        self._tape_entry = None
        self._stype = "default"

    # -- basic properties ----------------------------------------------------
    @property
    def shape(self):
        return tuple(self._h.array.shape)

    @property
    def ndim(self):
        return self._h.array.ndim

    @property
    def size(self):
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def dtype(self):
        dt = self._h.array.dtype
        if dt == jnp.bfloat16:
            return jnp.bfloat16
        return np.dtype(dt).type

    @property
    def stype(self):
        return self._stype

    @property
    def context(self):
        if self._ctx is not None:
            return self._ctx
        dev = list(self._h.array.devices())[0]
        if dev.platform == "cpu":
            return Context(1, dev.id)
        return Context(6, dev.id)

    ctx = context

    @property
    def grad(self):
        return self._grad

    @property
    def T(self):
        return transpose(self)

    # -- sync / host transfer ------------------------------------------------
    def wait_to_read(self):
        self._h.array.block_until_ready()

    def asnumpy(self):
        return np.asarray(self._h.array)

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(-1)[0]

    def astype(self, dtype, copy=True):
        return _invoke("Cast", [self], {"dtype": dtype_name(np_dtype(dtype))})

    def copy(self):
        return _invoke("_copy", [self], {})

    def copyto(self, other):
        if isinstance(other, NDArray):
            if other is self:
                raise MXNetError("cannot copy an array onto itself")
            arr = jax.device_put(self._h.array, other.context.jax_device())
            other._h.array = arr.astype(other._h.array.dtype) \
                if arr.dtype != other._h.array.dtype else arr
            return other
        if isinstance(other, Context):
            return NDArray(jax.device_put(self._h.array, other.jax_device()), ctx=other)
        raise TypeError("copyto does not support type " + str(type(other)))

    def as_in_context(self, context):
        if self.context == context:
            return self
        return self.copyto(context)

    def detach(self):
        out = NDArray(self._h.array, ctx=self._ctx)
        return out

    def attach_grad(self, grad_req="write", stype=None):
        grad = NDArray(jnp.zeros_like(self._h.array), ctx=self._ctx)
        ag.mark_variables([self], [grad], grad_req)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        ag.backward([self], [out_grad] if out_grad is not None else None,
                    retain_graph, train_mode)

    # -- shape ops -----------------------------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        if kwargs.get("shape"):
            shape = tuple(kwargs["shape"])
        return _invoke("Reshape", [self], {"shape": shape})

    def expand_dims(self, axis):
        return _invoke("expand_dims", [self], {"axis": axis})

    def flatten(self):
        return _invoke("Flatten", [self], {})

    def transpose(self, axes=None):
        return _invoke("transpose", [self], {"axes": axes})

    def swapaxes(self, dim1, dim2):
        return _invoke("SwapAxis", [self], {"dim1": dim1, "dim2": dim2})

    def flip(self, axis):
        return _invoke("reverse", [self], {"axis": axis})

    def split(self, *args, **kwargs):
        from . import split as _split_fn
        return _split_fn(self, *args, **kwargs)

    def slice(self, begin, end):
        return _invoke("slice", [self], {"begin": begin, "end": end})

    def slice_axis(self, axis, begin, end):
        return _invoke("slice_axis", [self], {"axis": axis, "begin": begin, "end": end})

    def broadcast_to(self, shape):
        return _invoke("broadcast_to", [self], {"shape": shape})

    def tile(self, reps):
        return _invoke("tile", [self], {"reps": reps})

    def sum(self, axis=None, keepdims=False):
        return _invoke("sum", [self], {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False):
        return _invoke("mean", [self], {"axis": axis, "keepdims": keepdims})

    def max(self, axis=None, keepdims=False):
        return _invoke("max", [self], {"axis": axis, "keepdims": keepdims})

    def min(self, axis=None, keepdims=False):
        return _invoke("min", [self], {"axis": axis, "keepdims": keepdims})

    def argmax(self, axis=None, keepdims=False):
        return _invoke("argmax", [self], {"axis": axis, "keepdims": keepdims})

    def argmin(self, axis=None, keepdims=False):
        return _invoke("argmin", [self], {"axis": axis, "keepdims": keepdims})

    def abs(self):
        return _invoke("abs", [self], {})

    def square(self):
        return _invoke("square", [self], {})

    def sqrt(self):
        return _invoke("sqrt", [self], {})

    def norm(self):
        return _invoke("norm", [self], {})

    def clip(self, a_min, a_max):
        return _invoke("clip", [self], {"a_min": a_min, "a_max": a_max})

    def round(self):
        return _invoke("rint", [self], {})

    def sign(self):
        return _invoke("sign", [self], {})

    def log(self):
        return _invoke("log", [self], {})

    def exp(self):
        return _invoke("exp", [self], {})

    def sigmoid(self):
        return _invoke("sigmoid", [self], {})

    def tanh(self):
        return _invoke("tanh", [self], {})

    def relu(self):
        return _invoke("relu", [self], {})

    def softmax(self, axis=-1):
        return _invoke("softmax", [self], {"axis": axis})

    def one_hot(self, depth, **kwargs):
        return _invoke("one_hot", [self], dict(kwargs, depth=depth))

    def take(self, indices, axis=0, mode="clip"):
        return _invoke("take", [self, indices], {"axis": axis, "mode": mode})

    def dot(self, other, transpose_a=False, transpose_b=False):
        return _invoke("dot", [self, other],
                       {"transpose_a": transpose_a, "transpose_b": transpose_b})

    def tostype(self, stype):
        if stype != "default":
            from .sparse import cast_storage
            return cast_storage(self, stype)
        return self

    # -- python protocol -----------------------------------------------------
    def __repr__(self):
        return "\n%s\n<NDArray %s @%s>" % (
            str(self.asnumpy()), "x".join(map(str, self.shape)), self.context)

    def __len__(self):
        return self.shape[0]

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError("The truth value of an NDArray with multiple elements "
                         "is ambiguous.")

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __iter__(self):
        for i in range(self.shape[0]):
            yield self[i]

    # arithmetic — broadcast-capable like the reference's broadcast_* family
    def _binary(self, other, op_nd, op_sc, reverse=False):
        if isinstance(other, NDArray):
            lhs, rhs = (other, self) if reverse else (self, other)
            return _invoke(op_nd, [lhs, rhs], {})
        return _invoke(op_sc, [self], {"scalar": float(other)})

    def __add__(self, o):
        return self._binary(o, "broadcast_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binary(o, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, o):
        return self._binary(o, "broadcast_sub", "_rminus_scalar", reverse=True)

    def __mul__(self, o):
        return self._binary(o, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __div__(self, o):
        return self._binary(o, "broadcast_div", "_div_scalar")

    __truediv__ = __div__

    def __rdiv__(self, o):
        return self._binary(o, "broadcast_div", "_rdiv_scalar", reverse=True)

    __rtruediv__ = __rdiv__

    def __mod__(self, o):
        return self._binary(o, "broadcast_mod", "_mod_scalar")

    def __rmod__(self, o):
        return self._binary(o, "broadcast_mod", "_rmod_scalar", reverse=True)

    def __pow__(self, o):
        return self._binary(o, "broadcast_power", "_power_scalar")

    def __rpow__(self, o):
        return self._binary(o, "broadcast_power", "_rpower_scalar", reverse=True)

    def __neg__(self):
        return _invoke("negative", [self], {})

    def __abs__(self):
        return _invoke("abs", [self], {})

    def __eq__(self, o):
        if o is None:
            return False
        return self._binary(o, "broadcast_equal", "_equal_scalar")

    def __ne__(self, o):
        if o is None:
            return True
        return self._binary(o, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, o):
        return self._binary(o, "broadcast_greater", "_greater_scalar")

    def __ge__(self, o):
        return self._binary(o, "broadcast_greater_equal", "_greater_equal_scalar")

    def __lt__(self, o):
        return self._binary(o, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, o):
        return self._binary(o, "broadcast_lesser_equal", "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    # in-place: rebind the handle (engine write-dep analog)
    def __iadd__(self, o):
        out = self.__add__(o)
        self._h.array = out._h.array
        return self

    def __isub__(self, o):
        out = self.__sub__(o)
        self._h.array = out._h.array
        return self

    def __imul__(self, o):
        out = self.__mul__(o)
        self._h.array = out._h.array
        return self

    def __itruediv__(self, o):
        out = self.__truediv__(o)
        self._h.array = out._h.array
        return self

    __idiv__ = __itruediv__

    def __getstate__(self):
        return {"data": self.asnumpy(), "ctx_type": self.context.device_typeid,
                "ctx_id": self.context.device_id}

    def __setstate__(self, state):
        ctx = Context(state["ctx_type"], state["ctx_id"])
        self._h = _Handle(jax.device_put(state["data"], ctx.jax_device()))
        self._ctx = ctx
        self._grad = None
        self._grad_req = "null"
        self._tape_entry = None
        self._stype = "default"

    # indexing ---------------------------------------------------------------
    def __getitem__(self, key):
        if isinstance(key, NDArray):
            key = key.asnumpy().astype(np.int32)
        arr = self._h.array[key]
        return NDArray(arr, ctx=self._ctx)

    def __setitem__(self, key, value):
        dt = self._h.array.dtype
        if isinstance(value, NDArray):
            val = value._h.array
        elif isinstance(value, (int, float, bool)):
            val = value
        else:
            # stay in numpy until the single device_put below — an eager
            # jnp.asarray would allocate on the DEFAULT backend, not this
            # array's device
            val = np.asarray(value).astype(dt, copy=False)
        if isinstance(key, slice) and key == slice(None):
            dev = self.context.jax_device()
            if np.isscalar(val):
                self._h.array = jax.device_put(
                    np.full(self.shape, val, dt), dev)
            elif isinstance(val, np.ndarray):
                self._h.array = jax.device_put(
                    np.broadcast_to(val, self.shape).astype(dt, copy=False),
                    dev)
            else:  # jax array (possibly on another device): op-free move
                if val.dtype != dt:
                    val = val.astype(dt)
                if val.shape != self.shape:
                    val = jnp.broadcast_to(val, self.shape)
                self._h.array = jax.device_put(val, dev)
            return
        if isinstance(key, NDArray):
            key = key.asnumpy().astype(np.int32)
        self._h.array = self._h.array.at[key].set(val)


def _wrap_array(arr, ctx=None):
    return NDArray(arr, ctx=ctx)


def host_view(arr):
    """The numpy view (zero-copy, read-only) of a jax array that lives
    whole on one device of the CPU backend: host memory, which an
    upload can read rows and shards of in place.  None for any other
    array."""
    devices = arr.devices()
    if len(devices) != 1 or next(iter(devices)).platform != "cpu":
        return None
    return np.asarray(arr)


# ---------------------------------------------------------------------------
# Imperative dispatch (ref: MXImperativeInvokeEx -> Imperative::Invoke)
# ---------------------------------------------------------------------------

def _parse_ctx_attr(val):
    if val is None:
        return current_context()
    if isinstance(val, Context):
        return val
    s = str(val)
    if "(" in s:
        name, rest = s.split("(", 1)
        return Context(name.strip(), int(rest.rstrip(")") or 0))
    return Context(s, 0)


def _invoke(op_name, inputs, attrs, out=None):
    """The analog of _imperative_invoke (python/mxnet/_ctypes/ndarray.py:65):
    normalize attrs, fetch the jitted callable, run, rebind mutated handles,
    record on the autograd tape."""
    op = get_op(op_name)
    ctx_attr = attrs.pop("ctx", None)
    nattrs = op.normalize_attrs(attrs)
    if op.key_var_num_args and not nattrs.get(op.key_var_num_args):
        nattrs[op.key_var_num_args] = len(inputs)
    if op.takes_train_flag:
        nattrs["_train"] = ag.is_training()
    # sparse dispatch (FComputeEx analog / storage fallback, ref:
    # imperative_utils.h dispatch-mode selection + exec_utils.h fallback)
    stypes = [getattr(i, "stype", "default") for i in inputs]
    if any(s != "default" for s in stypes):
        outs = NotImplemented
        # the Ex path is taken only when the storage-type combination
        # matches the op's declared pattern — the reference's FComputeEx
        # dispatch checks the full stype tuple the same way; an impl may
        # also decline (NotImplemented) after inspecting attrs
        # (e.g. lazy_update=False wants dense weight-decay semantics)
        if op.sparse_impl is not None and (
                op.sparse_pattern is None
                or tuple(stypes) == tuple(op.sparse_pattern[:len(stypes)])):
            outs = op.sparse_impl(inputs, nattrs)
        if outs is NotImplemented:
            # storage fallback: densify read-only sparse inputs; a MUTATED
            # sparse input would silently lose its update, so that's an
            # error rather than a wrong answer
            for idx in op.mutate_map:
                if idx < len(inputs) and stypes[idx] != "default":
                    raise MXNetError(
                        "%s: input %d is %s storage and would be mutated; "
                        "no sparse implementation applies"
                        % (op.name, idx, inputs[idx].stype))
            _warn_storage_fallback(op.name)
            inputs = [i.todense() if s != "default" else i
                      for i, s in zip(inputs, stypes)]
            return _invoke_dense(op, inputs, nattrs, ctx_attr, out)
        if not isinstance(outs, tuple):
            outs = (outs,)
        # sparse-path ops (optimizer updates) are not differentiable
        # through the tape; record=False keeps them off it explicitly
        return _finish_invoke(op, nattrs, inputs, outs, ctx_attr, out,
                              key=None, record=False)
    return _invoke_dense(op, inputs, nattrs, ctx_attr, out)


_STORAGE_FALLBACK_WARNED = set()


def _warn_storage_fallback(name):
    if name not in _STORAGE_FALLBACK_WARNED:
        _STORAGE_FALLBACK_WARNED.add(name)
        from ..base import _logger
        _logger.info("op %s has no sparse implementation; falling back to "
                     "dense storage (ref: storage fallback)", name)


def _invoke_dense(op, inputs, nattrs, ctx_attr, out):
    raw = [i._h.array for i in inputs]
    key = None
    if op.needs_rng:
        key = _random.next_key()
        raw = [key] + raw
    outs = apply_op(op, raw, nattrs)
    return _finish_invoke(op, nattrs, inputs, outs, ctx_attr, out,
                          key=key, record=True)


def _finish_invoke(op, nattrs, inputs, outs, ctx_attr, out, key, record):
    """Shared tail of both dispatch paths: split visible outputs from state
    outputs, rebind mutated handles, tape-record, honor out=."""
    n_vis = op.str_outputs(nattrs)
    vis, extra = list(outs[:n_vis]), outs[n_vis:]
    # state updates (optimizer mom/var, BatchNorm moving stats)
    for arr, in_idx in zip(extra, op.mutate_map):
        if in_idx < len(inputs):
            inputs[in_idx]._h.array = arr
    if op.num_inputs == 0:
        dev = _parse_ctx_attr(ctx_attr).jax_device()
        vis = [jax.device_put(v, dev) for v in vis]
    # a sparse_impl may emit ready-made (sparse) NDArrays; pass them through
    out_nds = [v if isinstance(v, NDArray) else NDArray(v) for v in vis]
    if record and ag.is_recording():
        ag.record_op(op, nattrs, inputs, [i._h.array for i in inputs],
                     out_nds, key)
    if out is not None:
        outs_given = [out] if isinstance(out, NDArray) else list(out)
        for dst, src in zip(outs_given, out_nds):
            if type(src) is NDArray and type(dst) is NDArray:
                dst._h.array = src._h.array
                dst._tape_entry = src._tape_entry
            else:
                # sparse on either side: a handle swap would install the
                # empty dense placeholder; copyto knows the storage types
                src.copyto(dst)
        return out
    if len(out_nds) == 1:
        return out_nds[0]
    return out_nds


def invoke(op_name, inputs, attrs=None, out=None):
    return _invoke(op_name, list(inputs), dict(attrs or {}), out=out)


# ---------------------------------------------------------------------------
# Creation / conversion
# ---------------------------------------------------------------------------

def array(source_array, ctx=None, dtype=None):
    ctx = ctx or current_context()
    if isinstance(source_array, NDArray):
        src = source_array._h.array
        if dtype is not None:
            src = src.astype(np_dtype(dtype))
        return NDArray(jax.device_put(src, ctx.jax_device()), ctx=ctx)
    if dtype is None:
        # MXNet semantics: keep numpy dtype; python lists default to float32
        dtype = source_array.dtype if isinstance(source_array, np.ndarray) \
            else np.float32
    npa = np.asarray(source_array)
    npa = npa.astype(np_dtype(dtype), copy=False) if npa.dtype != np_dtype(dtype) else npa
    return NDArray(jax.device_put(npa, ctx.jax_device()), ctx=ctx)


def empty(shape, ctx=None, dtype="float32"):
    return zeros(shape, ctx, dtype)


def zeros(shape, ctx=None, dtype="float32", **kwargs):
    # host numpy -> one explicit placement: an eager jnp.zeros would
    # first allocate on the DEFAULT backend, which may not be the target
    # ctx (and under the driver may not even be usable)
    ctx = ctx or current_context()
    if isinstance(shape, int):
        shape = (shape,)
    arr = np.zeros(shape, np_dtype(dtype or "float32"))
    return NDArray(jax.device_put(arr, ctx.jax_device()), ctx=ctx)


def ones(shape, ctx=None, dtype="float32", **kwargs):
    ctx = ctx or current_context()
    if isinstance(shape, int):
        shape = (shape,)
    arr = np.ones(shape, np_dtype(dtype or "float32"))
    return NDArray(jax.device_put(arr, ctx.jax_device()), ctx=ctx)


def full(shape, val, ctx=None, dtype="float32", out=None):
    ctx = ctx or current_context()
    if isinstance(shape, int):
        shape = (shape,)
    arr = jnp.full(shape, val, np_dtype(dtype or "float32"))
    nd = NDArray(jax.device_put(arr, ctx.jax_device()), ctx=ctx)
    if out is not None:
        out._h.array = nd._h.array
        return out
    return nd


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype="float32"):
    ctx = ctx or current_context()
    arr = jnp.arange(start, stop, step, np_dtype(dtype or "float32"))
    if repeat > 1:
        arr = jnp.repeat(arr, int(repeat))
    return NDArray(jax.device_put(arr, ctx.jax_device()), ctx=ctx)


def zeros_like(other, **kwargs):
    return _invoke("zeros_like", [other], {})


def ones_like(other, **kwargs):
    return _invoke("ones_like", [other], {})


def moveaxis(tensor, source, destination):
    return NDArray(jnp.moveaxis(tensor._h.array, source, destination),
                   ctx=tensor._ctx)


def transpose(data, axes=None):
    return _invoke("transpose", [data], {"axes": axes})


def concatenate(arrays, axis=0, always_copy=True):
    return _invoke("Concat", list(arrays), {"dim": axis})


def stack(*arrays, **kwargs):
    return _invoke("stack", list(arrays), {"axis": kwargs.get("axis", 0)})


def waitall():
    """Block until all async computation is flushed (ref: MXNDArrayWaitAll)."""
    try:
        jax.effects_barrier()
    except Exception:
        pass


def imdecode(str_img, clip_rect=(0, 0, 0, 0), out=None, index=0, channels=3,
             mean=None):
    raise MXNetError("imdecode: use mxnet_tpu.image instead")


# ---------------------------------------------------------------------------
# Serialization (ref: NDArray::Save/Load, src/ndarray/ndarray.cc; python
# mx.nd.save/load).  Format: our own magic-numbered container with the same
# two API shapes (list or dict of NDArrays).
# ---------------------------------------------------------------------------

_NDAR_MAGIC = b"MXTPU001"


def save(fname, data):
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, dict):
        names = list(data.keys())
        arrays = [data[k] for k in names]
    else:
        names = [""] * len(data)
        arrays = list(data)
    from ..filesystem import is_remote, open_uri
    if is_remote(fname):
        # remote stream: the backend owns atomicity (object stores
        # publish on close); no tmp+rename dance
        with open_uri(fname, "wb") as f:
            _save_stream(f, names, arrays)
        return
    # atomic: write to temp + rename so a crash mid-save never leaves a
    # truncated .params file for elastic resume to trip over
    tmp = fname + ".tmp"
    with open(tmp, "wb") as f:
        _save_stream(f, names, arrays)
    os.replace(tmp, fname)


def _save_stream(f, names, arrays):
    f.write(_NDAR_MAGIC)
    f.write(struct.pack("<q", len(arrays)))
    for name, nd in zip(names, arrays):
        nb = name.encode()
        f.write(struct.pack("<q", len(nb)))
        f.write(nb)
        npa = nd.asnumpy() if isinstance(nd, NDArray) else np.asarray(nd)
        dt = dtype_name(npa.dtype).encode()
        if npa.dtype == jnp.bfloat16:
            npa = npa.astype(np.float32)
            dt = b"bfloat16"
        f.write(struct.pack("<q", len(dt)))
        f.write(dt)
        f.write(struct.pack("<q", npa.ndim))
        f.write(struct.pack("<%dq" % npa.ndim, *npa.shape))
        buf = npa.tobytes()
        f.write(struct.pack("<q", len(buf)))
        f.write(buf)


def load(fname):
    from ..filesystem import open_uri
    with open_uri(fname, "rb") as f:
        return _load_stream(f, fname)


def loads(data):
    """Parse a save()-format blob from bytes (MXPredCreate's param blob)."""
    import io
    return _load_stream(io.BytesIO(data), "<bytes>")


def _load_stream(f, fname):
    magic = f.read(8)
    if magic != _NDAR_MAGIC:
        raise MXNetError("invalid NDArray file %s" % fname)
    n = struct.unpack("<q", f.read(8))[0]
    names, arrays = [], []
    for _ in range(n):
        ln = struct.unpack("<q", f.read(8))[0]
        names.append(f.read(ln).decode())
        ld = struct.unpack("<q", f.read(8))[0]
        dt = f.read(ld).decode()
        ndim = struct.unpack("<q", f.read(8))[0]
        shape = struct.unpack("<%dq" % ndim, f.read(8 * ndim)) if ndim else ()
        lb = struct.unpack("<q", f.read(8))[0]
        buf = f.read(lb)
        if dt == "bfloat16":
            npa = np.frombuffer(buf, np.float32).reshape(shape)
            arrays.append(array(npa, dtype="bfloat16"))
        else:
            npa = np.frombuffer(buf, np_dtype(dt)).reshape(shape)
            arrays.append(array(npa, dtype=dt))
    if any(names):
        return dict(zip(names, arrays))
    return arrays


def from_dlpack(capsule):
    return NDArray(jnp.from_dlpack(capsule))


def to_dlpack_for_read(nd):
    return nd._h.array.__dlpack__()


to_dlpack_for_write = to_dlpack_for_read


def from_numpy(npa, zero_copy=False):
    return array(npa)
