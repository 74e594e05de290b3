"""mxnet_tpu: a TPU-native deep-learning framework with the capabilities of
Apache MXNet v1.0.1, re-designed on JAX/XLA/Pallas/pjit.

Frontend layout mirrors python/mxnet/ for drop-in familiarity (mx.nd, mx.sym,
mx.mod, mx.gluon, mx.autograd, mx.kv, mx.io, ...); the backend is a single
XLA computation per graph instead of a per-op CUDA engine.
"""
from __future__ import annotations

# launcher bootstrap BEFORE anything can touch the XLA backend: scripts
# started by tools/launch.py get JAX_COORDINATOR_ADDRESS/NUM_PROCESSES/
# PROCESS_ID in the environment, and jax.distributed.initialize must run
# before the first backend-creating call (the reference's analog is the
# DMLC_* bootstrap at import, python/mxnet/__init__.py -> kvstore_server).
# base.py imports no XLA-touching modules, so this ordering is safe.
from .base import maybe_initialize_distributed_from_env as _minit
_minit()

from .base import MXNetError, __version__
from .context import (Context, cpu, gpu, tpu, cpu_pinned, current_context,
                      num_gpus, num_tpus, on_tpu)

from . import base
from . import context as context_mod
from . import ndarray
from . import ndarray as nd
from . import symbol
from . import symbol as sym
from .symbol import AttrScope
from .symbol.symbol import NameManager
from . import autograd
from . import random
from .random import seed  # mx.random.seed is canonical; mx.seed kept too
from . import executor
from . import executor_cache
from .executor import Executor

# submodules populated as the build proceeds
from . import optimizer
from .optimizer import Optimizer
from . import metric
from . import initializer
from .initializer import Initializer
from . import lr_scheduler
from . import callback
from . import io
from . import io_pipeline
from . import monitor
from .monitor import Monitor
from . import kvstore as kv
from . import kvstore
from . import module
from . import module as mod
from . import model
from .model import FeedForward
from . import gluon
from . import recordio
from . import filesystem
from . import log
from . import misc
from . import observability
from .observability.health import TrainingDivergedError
from . import profiler
from . import engine
from . import test_utils
from . import visualization
from .visualization import plot_network
from . import rnn
from . import attribute
from . import name
from . import elastic
from . import rtc
from . import libinfo
from . import contrib
from . import kvstore_server
from .kvstore_server import _init_kvstore_server_module

# ref: python/mxnet/__init__.py enters the server loop at import when
# DMLC_ROLE=server (via kvstore_server.py); same hook here.
_init_kvstore_server_module()
from . import image
from . import operator
from . import models
from . import parallel
from . import predict
from . import io_native
from . import checkpoint
from . import serving
