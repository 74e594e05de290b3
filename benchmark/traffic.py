"""The one traffic generator.  A traffic mix is a data file of parameters
under ``traffic/``; this module turns it and ``--seed`` into inputs.

Two families, chosen by the file's ``driver``: training feeds (a ring of
distinct host batches) and generation requests (prompt and output lengths,
shared prefixes; the arrivals are the driver's to play).

The SIZES of a mix (lengths, their multiset) come from the file's own
``sizes_seed``, so every run of a cell does the same work; ``--seed``
decides the order, the token ids, the pixels and the labels."""
from __future__ import annotations

import numpy as np


def host_rng(seed, stream):
    seed = int(seed)
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])


# -- training feeds ---------------------------------------------------------

def fit_ring(traffic, image_shape, num_classes, seed):
    """(data [ring*batch, C, H, W] float32 in [-1, 1), labels [ring*batch]
    float32): ``ring_batches`` distinct batches, every row different."""
    rows = int(traffic["ring_batches"]) * int(traffic["batch"])
    rng = host_rng(seed, 11)
    data = rng.random((rows,) + tuple(image_shape), dtype=np.float32)
    data *= 2.0
    data -= 1.0
    labels = rng.integers(0, num_classes, rows).astype(np.float32)
    return data, labels


# -- generation requests ----------------------------------------------------

def _lengths(spec, n, rng):
    if spec["dist"] == "lognormal":
        x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
        x = np.rint(x)
    elif spec["dist"] == "uniform":
        x = rng.integers(spec["min"], spec["max"] + 1, n)
    else:
        raise ValueError("unknown length distribution %r" % spec["dist"])
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def decode_requests(traffic, vocab_size, seed):
    """The mix's requests in this run's order: a list of
    ``(prompt ids [n] int64, max_new_tokens)``.  Own tokens are uniform
    over the vocabulary; with ``shared_prefix.prompts`` > 0 every prompt
    starts with one of that many shared prefixes of ``tokens`` ids."""
    n = int(traffic["requests"])
    sizes = np.random.default_rng(int(traffic["sizes_seed"]))
    prompt_len = _lengths(traffic["prompt_tokens"], n, sizes)
    out_len = _lengths(traffic["output_tokens"], n, sizes)
    rng = host_rng(seed, 12)
    order = rng.permutation(n)
    shared = traffic.get("shared_prefix") or {}
    prefixes = [rng.integers(0, vocab_size, int(shared["tokens"]))
                for _ in range(int(shared.get("prompts", 0)))]
    out = []
    for i in order:
        own = rng.integers(0, vocab_size, int(prompt_len[i]))
        if prefixes:
            own = np.concatenate(
                [prefixes[int(rng.integers(len(prefixes)))], own])
        out.append((own.astype(np.int64), int(out_len[i])))
    return out

