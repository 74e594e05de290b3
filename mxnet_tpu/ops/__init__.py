"""Operator registry + TPU-native op library (XLA/Pallas)."""
from .registry import (  # noqa: F401
    Op, register, get_op, list_ops, op_registry, apply_op, eval_shape_op,
)

# importing these modules populates the registry
from . import tensor  # noqa: F401
from . import nn  # noqa: F401
from . import attention  # noqa: F401
from . import lm_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import random_ops  # noqa: F401
from . import rnn_op  # noqa: F401
from . import contrib_ops  # noqa: F401
from . import extra  # noqa: F401
from . import image_ops  # noqa: F401
from . import detection_ops  # noqa: F401
from . import quantize  # noqa: F401
