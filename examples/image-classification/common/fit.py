"""Shared training-loop driver (parity: example/image-classification/
common/fit.py in the reference — same CLI surface and Module workflow)."""
from __future__ import annotations

import argparse
import logging
import os
import time

import mxnet_tpu as mx


def add_fit_args(parser):
    train = parser.add_argument_group("Training")
    train.add_argument("--network", type=str, default="resnet")
    train.add_argument("--num-layers", type=int, default=50)
    train.add_argument("--gpus", type=str, default=None,
                       help="devices, e.g. '0,1' (tpu cores here)")
    train.add_argument("--kv-store", type=str, default="local")
    train.add_argument("--num-epochs", type=int, default=10)
    train.add_argument("--lr", type=float, default=0.1)
    train.add_argument("--lr-factor", type=float, default=0.1)
    train.add_argument("--lr-step-epochs", type=str, default="30,60")
    train.add_argument("--optimizer", type=str, default="sgd")
    train.add_argument("--mom", type=float, default=0.9)
    train.add_argument("--wd", type=float, default=1e-4)
    train.add_argument("--batch-size", type=int, default=128)
    train.add_argument("--disp-batches", type=int, default=20)
    train.add_argument("--model-prefix", type=str, default=None)
    train.add_argument("--load-epoch", type=int, default=None)
    train.add_argument("--top-k", type=int, default=0)
    return train


def _get_lr_scheduler(args, kv, epoch_size):
    if not args.lr_factor or args.lr_factor >= 1:
        return args.lr, None
    begin_epoch = args.load_epoch or 0
    step_epochs = [int(l) for l in args.lr_step_epochs.split(",")]
    lr = args.lr
    for s in step_epochs:
        if begin_epoch >= s:
            lr *= args.lr_factor
    steps = [epoch_size * (x - begin_epoch) for x in step_epochs
             if x - begin_epoch > 0]
    return lr, mx.lr_scheduler.MultiFactorScheduler(step=steps,
                                                   factor=args.lr_factor)


def _load_model(args, rank=0):
    if args.load_epoch is None or args.model_prefix is None:
        return None, None, None
    model_prefix = args.model_prefix
    if rank > 0 and os.path.exists("%s-%d-symbol.json" % (model_prefix,
                                                          rank)):
        model_prefix += "-%d" % rank
    return mx.model.load_checkpoint(model_prefix, args.load_epoch)


def _save_model(args, rank=0):
    if args.model_prefix is None:
        return None
    dst_dir = os.path.dirname(args.model_prefix)
    if dst_dir and not os.path.isdir(dst_dir):
        os.makedirs(dst_dir)
    return mx.callback.do_checkpoint(
        args.model_prefix if rank == 0 else
        "%s-%d" % (args.model_prefix, rank))


def _devices(args):
    if args.gpus is None or args.gpus == "":
        return [mx.context.accelerator()]
    return [mx.tpu(int(i)) for i in args.gpus.split(",")]


def fit(args, network, data_loader, **kwargs):
    """Train `network` on the iterators from data_loader(args, kv)."""
    kv = mx.kvstore.create(args.kv_store)
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)-15s Node[" + str(kv.rank) + "] %(message)s")
    logging.info("start with arguments %s", args)

    train, val = data_loader(args, kv)
    devs = _devices(args)

    # per-worker batches per epoch (the lr schedule steps on each worker's
    # own update count, so the global epoch boundary divides by num_workers)
    epoch_size = args.num_examples // args.batch_size // kv.num_workers \
        if hasattr(args, "num_examples") else 1000
    lr, lr_scheduler = _get_lr_scheduler(args, kv, epoch_size)

    sym, arg_params, aux_params = _load_model(args, kv.rank)
    if sym is not None:
        network = sym

    model = mx.mod.Module(context=devs, symbol=network)

    optimizer_params = {
        "learning_rate": lr,
        "wd": args.wd,
        "lr_scheduler": lr_scheduler}
    if args.optimizer in ("sgd", "nag", "signum"):
        optimizer_params["momentum"] = args.mom

    eval_metrics = ["accuracy"]
    if args.top_k > 0:
        eval_metrics.append(mx.metric.create("top_k_accuracy",
                                             top_k=args.top_k))

    batch_end_callbacks = [mx.callback.Speedometer(args.batch_size,
                                                   args.disp_batches)]
    checkpoint = _save_model(args, kv.rank)

    model.fit(train,
              begin_epoch=args.load_epoch or 0,
              num_epoch=args.num_epochs,
              eval_data=val,
              eval_metric=eval_metrics,
              # a single worker names its store by string: Module then
              # resolves one device to no store at all and trains through
              # the fused step; a KVStore OBJECT would pin the per-key path
              kvstore=kv if kv.num_workers > 1 else args.kv_store,
              optimizer=args.optimizer,
              optimizer_params=optimizer_params,
              initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                                factor_type="in",
                                                magnitude=2),
              arg_params=arg_params,
              aux_params=aux_params,
              batch_end_callback=batch_end_callbacks,
              epoch_end_callback=checkpoint,
              allow_missing=True,
              **kwargs)
    return model
