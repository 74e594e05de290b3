"""Symbol-API model builders (parity: example/image-classification/symbols/).

These mirror the reference's example symbol factories so Module-based training
scripts (train_mnist.py / train_imagenet.py style) work unchanged.
"""
from . import resnet  # noqa: F401
from . import resnet_v1  # noqa: F401
from . import resnext  # noqa: F401
from . import lenet  # noqa: F401
from . import mlp  # noqa: F401
from . import alexnet  # noqa: F401
from . import vgg  # noqa: F401
from . import googlenet  # noqa: F401
from . import mobilenet  # noqa: F401
from . import inception_bn  # noqa: F401
from . import inception_v3  # noqa: F401
from . import inception_v4  # noqa: F401
from . import inception_resnet_v2  # noqa: F401
from . import qwen3_next  # noqa: F401
from . import trinity  # noqa: F401
from . import joyai_flash  # noqa: F401
from . import sdar  # noqa: F401
from . import granite_hybrid  # noqa: F401

get_symbol = resnet.get_symbol
