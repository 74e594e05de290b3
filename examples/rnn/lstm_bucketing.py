"""Bucketed LSTM language model (parity: example/rnn/lstm_bucketing.py —
BASELINE.json config #4: LSTM LM with fused RNN cell kernels).

Variable-length sequences bucket into fixed shapes; each bucket compiles
one XLA program (BucketingModule shares parameters across buckets).  With
--synthetic it generates a character-level corpus so no dataset files are
needed.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx


def synthetic_sentences(n=2000, vocab_size=50, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        length = rng.randint(5, 40)
        # markov-ish chains so there is structure to learn
        s = [int(rng.randint(1, vocab_size))]
        for _ in range(length - 1):
            s.append(int((s[-1] * 7 + rng.randint(0, 3)) % vocab_size) or 1)
        out.append(s)
    return out


if __name__ == "__main__":
    parser = argparse.ArgumentParser(
        description="Train an LSTM LM with bucketing")
    parser.add_argument("--num-layers", type=int, default=2)
    parser.add_argument("--num-hidden", type=int, default=200)
    parser.add_argument("--num-embed", type=int, default=200)
    parser.add_argument("--num-epochs", type=int, default=5)
    parser.add_argument("--lr", type=float, default=0.01)
    parser.add_argument("--optimizer", type=str, default="sgd")
    parser.add_argument("--mom", type=float, default=0.0)
    parser.add_argument("--wd", type=float, default=1e-5)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--disp-batches", type=int, default=50)
    parser.add_argument("--kv-store", type=str, default="local")
    parser.add_argument("--synthetic", type=int, default=1)
    parser.add_argument("--vocab-size", type=int, default=50)
    parser.add_argument("--num-sentences", type=int, default=2000)
    args = parser.parse_args()

    buckets = [10, 20, 30, 40]
    start_label = 1
    invalid_label = 0

    sentences = synthetic_sentences(args.num_sentences, args.vocab_size)
    vocab_size = args.vocab_size

    data_train = mx.rnn.BucketSentenceIter(
        sentences[: len(sentences) * 4 // 5], args.batch_size,
        buckets=buckets, invalid_label=invalid_label)
    data_val = mx.rnn.BucketSentenceIter(
        sentences[len(sentences) * 4 // 5:], args.batch_size,
        buckets=buckets, invalid_label=invalid_label)

    stack = mx.rnn.FusedRNNCell(args.num_hidden, num_layers=args.num_layers,
                                mode="lstm")

    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        label = mx.sym.Variable("softmax_label")
        embed = mx.sym.Embedding(data=data, input_dim=vocab_size,
                                 output_dim=args.num_embed, name="embed")
        stack.reset()
        outputs, states = stack.unroll(seq_len, inputs=embed,
                                       merge_outputs=True)
        pred = mx.sym.Reshape(outputs, shape=(-1, args.num_hidden))
        pred = mx.sym.FullyConnected(data=pred, num_hidden=vocab_size,
                                     name="pred")
        label = mx.sym.Reshape(label, shape=(-1,))
        pred = mx.sym.SoftmaxOutput(data=pred, label=label, name="softmax")
        return pred, ("data",), ("softmax_label",)

    ctx = mx.context.accelerator()
    model = mx.mod.BucketingModule(
        sym_gen=sym_gen,
        default_bucket_key=data_train.default_bucket_key,
        context=ctx)

    import logging
    logging.basicConfig(level=logging.INFO, format="%(asctime)-15s %(message)s")
    model.fit(
        train_data=data_train,
        eval_data=data_val,
        eval_metric=mx.metric.Perplexity(invalid_label),
        kvstore=args.kv_store,
        optimizer=args.optimizer,
        optimizer_params={"learning_rate": args.lr, "momentum": args.mom,
                          "wd": args.wd},
        initializer=mx.initializer.Xavier(factor_type="in", magnitude=2.34),
        num_epoch=args.num_epochs,
        batch_end_callback=mx.callback.Speedometer(args.batch_size,
                                                   args.disp_batches))
