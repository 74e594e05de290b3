"""Lower-precision arithmetic for the CONTROLS: the plain references
recomputed in the nearest precision below the one a configuration states,
which the comparison has to catch.  fp8 is emulated on float32 bit
patterns (per-tensor scaled to the format's range, mantissa rounded to
nearest-even, subnormals on a fixed step, saturating), so it needs no fp8
support from the backend."""
from __future__ import annotations

import jax
import jax.numpy as jnp

E4M3 = dict(mant=3, fmax=448.0, min_normal=2.0 ** -6)
E5M2 = dict(mant=2, fmax=57344.0, min_normal=2.0 ** -14)


def fake_fp8(x, fmt=E4M3):
    """x rounded through an fp8 format under a per-tensor scale."""
    x = x.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(x)), jnp.float32(1e-30))
    scale = fmt["fmax"] / amax
    y = x * scale
    shift = 23 - fmt["mant"]
    bits = jax.lax.bitcast_convert_type(y, jnp.uint32)
    half = jnp.uint32((1 << (shift - 1)) - 1)
    keep = jnp.uint32((0xFFFFFFFF >> shift) << shift)
    rounded = (bits + half + ((bits >> shift) & jnp.uint32(1))) & keep
    normal = jax.lax.bitcast_convert_type(rounded, jnp.float32)
    step = fmt["min_normal"] * 2.0 ** -fmt["mant"]
    sub = jnp.round(y / step) * step
    y = jnp.where(jnp.abs(y) < fmt["min_normal"], sub, normal)
    return jnp.clip(y, -fmt["fmax"], fmt["fmax"]) / scale


@jax.custom_vjp
def q_operand(x):
    """An operand as an fp8 (e4m3) product would read it; the gradient
    passes straight through."""
    return fake_fp8(x, E4M3)


q_operand.defvjp(lambda x: (fake_fp8(x, E4M3), None), lambda _, g: (g,))


@jax.custom_vjp
def q_cotangent(y):
    """Identity forward; the cotangent is rounded through e5m2, as an fp8
    backward pass would carry it."""
    return y


q_cotangent.defvjp(lambda y: (y, None), lambda _, g: (fake_fp8(g, E5M2),))


def bf16(x):
    """An operand rounded to bfloat16 and held in float32
    (``reduce_precision``: a cast there and back may be optimised away)."""
    return jax.lax.reduce_precision(x.astype(jnp.float32), exponent_bits=8,
                                    mantissa_bits=7)
