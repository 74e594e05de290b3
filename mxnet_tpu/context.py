"""Device context.

TPU-native counterpart of include/mxnet/base.h:142-372 (Context / RunContext).
Device types keep the reference's numbering (kCPU=1, kGPU=2, kCPUPinned=3,
kCPUShared=5) and add kTPU=6 as a first-class device.  A Context maps onto a
concrete `jax.Device`: cpu -> jax cpu backend, tpu/gpu -> the accelerator
backend (on TPU machines, mx.gpu(i) aliases to tpu so that reference example
scripts run unchanged).
"""
from __future__ import annotations

import threading

import jax

from .base import MXNetError


class Context:
    """Device context holding device type and id."""

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if isinstance(device_type, str):
                device_type = self.devstr2type[device_type]
            self.device_typeid = device_type
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return self.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    # -- mapping onto jax devices --------------------------------------------
    def jax_device(self):
        """Resolve to a concrete jax.Device.

        Always a process-LOCAL device: under multi-process jax.distributed,
        jax.devices() lists every process's devices and placing data on a
        remote one is an error — a Context names a device of THIS worker
        (matching the reference, where ctx always meant a local device)."""
        if self.device_typeid in (1, 3, 5):
            cpus = jax.local_devices(backend="cpu")
            return cpus[self.device_id % len(cpus)]
        # tpu / gpu: use the default (accelerator) backend; alias gpu->tpu so
        # reference scripts that say mx.gpu(0) run unchanged on TPU machines.
        devs = jax.local_devices()
        if devs[0].platform == "cpu":
            # pure-CPU environment (tests): accelerator contexts map onto the
            # virtual cpu devices so multi-device code paths stay exercised.
            return devs[self.device_id % len(devs)]
        if self.device_id >= len(devs):
            raise MXNetError(
                "context %s out of range: %d device(s) visible" % (self, len(devs))
            )
        return devs[self.device_id]

    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context(1, 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    @classmethod
    def default_ctx(cls):
        if not hasattr(cls._default_ctx, "value"):
            cls._default_ctx.value = Context(1, 0)
        return cls._default_ctx.value


def cpu(device_id=0):
    return Context(1, device_id)


def gpu(device_id=0):
    return Context(2, device_id)


def tpu(device_id=0):
    return Context(6, device_id)


def cpu_pinned(device_id=0):
    return Context(3, device_id)


def current_context():
    return Context.default_ctx()


def num_gpus():
    devs = jax.local_devices()  # devices THIS worker can address
    return 0 if devs[0].platform == "cpu" else len(devs)


num_tpus = num_gpus


def on_tpu():
    """True when the process default JAX backend is a TPU.  The ONE test
    every backend-dependent default goes through (kernel dispatch, buffer
    donation, the scripts' device choice).  Creates the backend on first
    call, like any other device query."""
    return jax.default_backend() == "tpu"


def accelerator(index=0):
    """The context work lands on when the caller names none: the
    ``index``-th local TPU (wrapping past the last chip) when the default
    backend is a TPU, ``cpu(0)`` otherwise.  The serving layer's
    ``ctx=None`` resolves through this — replica *i* on device *i* — so a
    server started on a TPU host serves from the chip, not the host CPU."""
    if not on_tpu():
        return cpu(0)
    return tpu(index % len(jax.local_devices()))
