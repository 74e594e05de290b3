"""Gluon imperative training (parity: example/gluon/image_classification.py —
BASELINE.json config #3: gluon ResNet-18 CIFAR-10 with autograd).

With --synthetic it trains on random CIFAR-shaped data so no dataset files
are needed; point --data-dir at a CIFAR-10 python pickle directory
otherwise.
"""
from __future__ import annotations

import argparse
import logging
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon.model_zoo import vision


def get_data(args):
    if args.synthetic:
        rng = np.random.RandomState(0)
        n = args.num_examples
        X = rng.uniform(0, 1, (n, 3, 32, 32)).astype(np.float32)
        Y = rng.randint(0, args.classes, (n,)).astype(np.float32)
        train = gluon.data.DataLoader(
            gluon.data.ArrayDataset(X, Y), batch_size=args.batch_size,
            shuffle=True, last_batch="discard")
        val = gluon.data.DataLoader(
            gluon.data.ArrayDataset(X[:256], Y[:256]),
            batch_size=args.batch_size, last_batch="discard")
        return train, val
    transform = gluon.data.vision.transforms.Compose([
        gluon.data.vision.transforms.ToTensor(),
        gluon.data.vision.transforms.Normalize(
            [0.4914, 0.4822, 0.4465], [0.2023, 0.1994, 0.2010])])
    train = gluon.data.DataLoader(
        gluon.data.vision.CIFAR10(root=args.data_dir, train=True)
        .transform_first(lambda x: transform(x)),
        batch_size=args.batch_size, shuffle=True, last_batch="discard")
    val = gluon.data.DataLoader(
        gluon.data.vision.CIFAR10(root=args.data_dir, train=False)
        .transform_first(lambda x: transform(x)),
        batch_size=args.batch_size, last_batch="discard")
    return train, val


def evaluate(net, loader, ctx):
    metric = mx.metric.Accuracy()
    for data, label in loader:
        out = net(data.as_in_context(ctx))
        metric.update([label], [out])
    return metric.get()[1]


def train(args):
    ctx = mx.context.accelerator()
    net = vision.get_model(args.model, classes=args.classes, thumbnail=True) \
        if "resnet" in args.model else vision.get_model(args.model,
                                                        classes=args.classes)
    net.initialize(mx.initializer.Xavier(magnitude=2), ctx=ctx)
    if args.hybridize:
        net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": args.mom,
                             "wd": args.wd})
    train_data, val_data = get_data(args)
    metric = mx.metric.Accuracy()
    for epoch in range(args.epochs):
        metric.reset()
        tic = time.time()
        n = 0
        for data, label in train_data:
            data = data.as_in_context(ctx)
            label = label.as_in_context(ctx)
            with autograd.record():
                out = net(data)
                loss = loss_fn(out, label)
            loss.backward()
            trainer.step(data.shape[0])
            metric.update([label], [out])
            n += data.shape[0]
        name, acc = metric.get()
        logging.info("Epoch[%d] train-%s=%.4f  %.1f samples/s", epoch, name,
                     acc, n / (time.time() - tic))
        logging.info("Epoch[%d] val-acc=%.4f", epoch,
                     evaluate(net, val_data, ctx))
    return net


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description="Gluon image classification")
    parser.add_argument("--model", type=str, default="resnet18_v1")
    parser.add_argument("--classes", type=int, default=10)
    parser.add_argument("--epochs", type=int, default=5)
    parser.add_argument("--batch-size", type=int, default=128)
    parser.add_argument("--lr", type=float, default=0.1)
    parser.add_argument("--mom", type=float, default=0.9)
    parser.add_argument("--wd", type=float, default=1e-4)
    parser.add_argument("--hybridize", type=int, default=1)
    parser.add_argument("--synthetic", type=int, default=0)
    parser.add_argument("--num-examples", type=int, default=2048)
    parser.add_argument("--data-dir", type=str, default=None)
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)
    train(args)
