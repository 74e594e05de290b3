"""Weights from ``--seed``, made on the device in one jitted call.

Every leaf is drawn from the run's key folded with a checksum of the leaf's
NAME, so the program's side and the plain reference's side, which each ask
for the leaves they know by name and shape, get the same values without
either taking anything from the other.  Values are rounded to bfloat16 where
the configuration serves bfloat16, and handed over as float32 holding those
values (the program's cast to its storage type is then exact)."""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed, stream=0):
    """A key from any whole-number seed up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31),
        stream)


def _kind(name, rules):
    for suffix, kind in rules:
        if name.endswith(suffix):
            return kind
    raise KeyError("no init rule matches parameter %r" % name)


@functools.partial(jax.jit, static_argnames=("spec", "rules", "round_bf16"))
def _make(key, spec, rules, round_bf16):
    out = {}
    for name, shape in spec:
        kind = _kind(name, rules)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        if kind == "ones":
            w = jnp.ones(shape, jnp.float32)
        elif kind == "zeros":
            w = jnp.zeros(shape, jnp.float32)
        elif kind == "he_normal":       # gaussian, fan-in, magnitude 2
            fan_in = float(np.prod(shape[1:]))
            w = jax.random.normal(k, shape, jnp.float32) \
                * float(np.sqrt(2.0 / fan_in))
        elif kind.startswith("normal:"):
            w = jax.random.normal(k, shape, jnp.float32) \
                * float(kind.split(":")[1])
        else:
            raise ValueError("unknown init kind %r" % kind)
        w = w.astype(jnp.float32)
        if round_bf16:
            # reduce_precision, not a cast there and back: XLA may drop a
            # convert pair as "excess precision" (it did on the v5e, chip
            # run of PR 24: the reference then started 2**-9 off the
            # program's weights)
            w = jax.lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)
        out[name] = w
    return out


def make_weights(seed, shapes, rules, round_bf16=False):
    """{name: float32 array on the default device} for ``shapes``
    ({name: shape}) under ``rules`` ([[name suffix, kind], ...], first match
    wins)."""
    spec = tuple(sorted((n, tuple(int(d) for d in s))
                        for n, s in shapes.items()))
    return _make(seed_key(seed, 1), spec,
                 tuple((s, k) for s, k in rules), bool(round_bf16))
