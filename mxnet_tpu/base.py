"""Base utilities for mxnet_tpu.

TPU-native re-design of the reference's base layer (dmlc-core slice:
logging/CHECK, env config, parameter reflection — ref: include/mxnet/base.h,
dmlc/parameter.h usage sites).  Here the "C ABI error handling" collapses to
Python exceptions; the dmlc::Parameter string-reflection survives as the
attr-string conventions used by the Symbol/JSON layer.
"""
from __future__ import annotations

import logging
import os

import jax

# float64 NDArrays are part of the reference API surface (test_utils
# check_consistency, linalg ops); defaults stay 32-bit via weak typing, and
# models opt into bf16/f32 explicitly, so TPU perf is unaffected.
jax.config.update("jax_enable_x64", True)

# JAX's persistent compilation cache, placed from outside: where
# JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and nothing is set
# here.  Otherwise a source checkout keeps its cache at the FIXED path
# <checkout>/.jax_cache (the path is part of the cache key, so a directory
# that moves never hits); an installed package sets none.
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    _checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if os.path.isfile(os.path.join(_checkout, "pyproject.toml")):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_checkout, ".jax_cache"))

__version__ = "1.0.1"  # capability parity target: MXNet 1.0.1 (python/mxnet/libinfo.py:64)


class MXNetError(Exception):
    """Error raised by mxnet_tpu (ref: MXGetLastError, src/c_api/c_api_error.cc)."""


def check_call(ok, msg=""):
    if not ok:
        raise MXNetError(msg)


_logger = logging.getLogger("mxnet_tpu")


def maybe_initialize_distributed_from_env():
    """Bridge the launcher env protocol (tools/launch.py sets
    JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID) to
    jax.distributed.initialize.  Must run before anything creates an XLA
    backend; no-op when the vars are absent/partial or already initialized.
    The single shared implementation — called from package import and from
    the dist kvstore (whichever comes first)."""
    addr = os.environ.get("JAX_COORDINATOR_ADDRESS")
    nproc = os.environ.get("JAX_NUM_PROCESSES")
    pid = os.environ.get("JAX_PROCESS_ID")
    if not (addr and nproc and pid) or int(nproc) <= 1:
        return
    import jax
    from jax._src import distributed
    if distributed.global_state.client is not None:
        return
    initialize_distributed_with_retry(addr, int(nproc), int(pid))


def initialize_distributed_with_retry(addr, nproc, pid, attempts=3,
                                      timeout_s=300):
    """jax.distributed.initialize with a bounded retry + backoff.

    Under host contention the coordinator process can start seconds to
    minutes after its workers; a transient connect failure (coordinator
    not yet bound, or a stale port in TIME_WAIT) must not kill the worker
    outright.  Non-transient failures (bad address) still raise after the
    attempts are exhausted."""
    import time
    import jax
    last = None
    for attempt in range(attempts):
        try:
            jax.distributed.initialize(
                coordinator_address=addr, num_processes=nproc,
                process_id=pid, initialization_timeout=timeout_s)
            return
        except Exception as e:  # noqa: BLE001 — retried, then re-raised
            last = e
            _logger.warning(
                "jax.distributed.initialize attempt %d/%d failed: %s",
                attempt + 1, attempts, e)
            time.sleep(2.0 * (attempt + 1))
    raise last


def get_env(name, default=None, typ=str):
    """dmlc::GetEnv equivalent: typed environment config (ref: docs/faq/env_var.md)."""
    val = os.environ.get(name)
    if val is None:
        return default
    if typ is bool:
        return val not in ("0", "false", "False", "")
    return typ(val)


# ---------------------------------------------------------------------------
# Attr-string reflection (dmlc::Parameter equivalent).
#
# Symbols carry attrs as strings (for JSON checkpoint-format parity with
# nnvm::Graph JSON); ops declare typed params and these helpers convert both
# ways, matching MXNet's string conventions: tuples print as "(1, 2)",
# bools as "True"/"False".
# ---------------------------------------------------------------------------

def attr_to_str(value):
    """Serialize a python attr value the way MXNet's frontends do."""
    if isinstance(value, (list, tuple)):
        return "(" + ", ".join(str(v) for v in value) + ")"
    return str(value)


def _parse_scalar(s):
    s = s.strip()
    if s in ("True", "true"):
        return True
    if s in ("False", "false"):
        return False
    if s in ("None", ""):
        return None
    try:
        return int(s)
    except ValueError:
        pass
    try:
        return float(s)
    except ValueError:
        pass
    return s


def str_to_attr(s):
    """Parse an MXNet attr string back into a python value."""
    if not isinstance(s, str):
        return s
    t = s.strip()
    if t.startswith("(") and t.endswith(")") or t.startswith("[") and t.endswith("]"):
        inner = t[1:-1].strip()
        if not inner:
            return ()
        return tuple(_parse_scalar(p) for p in inner.split(",") if p.strip() != "")
    return _parse_scalar(t)


def shape_attr(value):
    """Coerce an attr to a shape tuple of ints (accepts int, str, tuple)."""
    if value is None:
        return None
    if isinstance(value, str):
        value = str_to_attr(value)
    if isinstance(value, int):
        return (value,)
    return tuple(int(v) for v in value)


string_types = (str,)

# dtype name <-> numpy mapping used across frontends (ref: python/mxnet/base.py)
_DTYPE_ALIASES = {
    "float32": "float32", "float64": "float64", "float16": "float16",
    "bfloat16": "bfloat16", "uint8": "uint8", "int8": "int8",
    "int32": "int32", "int64": "int64", "bool": "bool_",
}


def np_dtype(dtype):
    import numpy as _np
    import jax.numpy as jnp
    if dtype is None:
        return _np.dtype("float32")
    if isinstance(dtype, str):
        if dtype == "bfloat16":
            return jnp.bfloat16
        return _np.dtype(_DTYPE_ALIASES.get(dtype, dtype))
    if dtype is jnp.bfloat16:
        return jnp.bfloat16
    return _np.dtype(dtype)


def dtype_name(dtype):
    import numpy as _np
    try:
        name = _np.dtype(dtype).name
    except TypeError:
        name = str(dtype)
    return "bfloat16" if "bfloat16" in name else name
