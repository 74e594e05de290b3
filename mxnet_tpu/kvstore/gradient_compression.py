"""2-bit gradient compression with error-feedback residual.

Parity target: src/kvstore/gradient_compression.{h,cc,cu}
(gradient_compression.h:52-134): values above +threshold quantize to
+threshold, below -threshold to -threshold, else 0; the quantization error
accumulates into a per-key residual added before the next quantization.
Here the quantizer is a pure jitted function; the packed wire format is a
uint8 array with 4 values/byte (the reference packs 16 per uint32 —
same 2 bits/value density).

The ``GradientCompression`` class below is the kvstore's host-driven
mode (``set_gradient_compression``), residual keyed per parameter, over
the pure flat functions ``quantize_flat`` / ``dequantize_flat`` /
``dequantize_sum_flat``.

Flat-length contract: the packed stream always covers ``ceil(n/4)``
bytes.  ``_pack2`` owns the padding (codes for the pad lanes are 0 =
"no update"), and every dequantizer slices back to the caller's ``n``
— arbitrary gradient lengths round-trip (regression-tested in
tests/test_gradient_compression.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def packed_nbytes(n):
    """Wire bytes for n 2-bit values: 4 codes per byte, padded up."""
    return (int(n) + 3) // 4


class GradientCompression:
    def __init__(self, threshold=0.5):
        self.threshold = float(threshold)
        self._residuals = {}

    def get_params(self):
        return {"type": "2bit", "threshold": self.threshold}

    def quantize(self, key, grad):
        """grad: jax array.  Returns packed uint8 codes; updates residual."""
        res = self._residuals.get(key)
        if res is None:
            res = jnp.zeros_like(grad)
        codes, new_res = _quantize_2bit(grad, res, self.threshold)
        self._residuals[key] = new_res
        return codes

    def dequantize(self, codes, shape, dtype=jnp.float32):
        return dequantize_flat(codes, int(np.prod(shape)),
                               self.threshold).reshape(shape).astype(dtype)

    def dequantize_sum(self, gathered, shape, dtype=jnp.float32):
        """Sum of every participant's codes, dequantized: gathered is
        [n_participants, n_packed] uint8 (each row one worker's packed
        2-bit codes).  threshold * (#plus - #minus) per element — exactly
        the sum of the individually dequantized gradients, computed from
        the 2-bit wire payload instead of exchanged float32."""
        n = int(np.prod(shape))
        return dequantize_sum_flat(jnp.asarray(gathered), n,
                                   self.threshold) \
            .reshape(shape).astype(dtype)


@jax.jit
def _pack2(q):
    """q: int8 codes in {0,1,2} flat, ANY length -> uint8 with 4
    codes/byte (``packed_nbytes(len(q))`` of them).  Pad lanes get code
    0 ("no update"), so the pack/unpack round trip is exact for every
    length — the flat-length contract lives here, not in the callers."""
    q = jnp.pad(q.astype(jnp.uint8), (0, (-q.shape[0]) % 4)).reshape(-1, 4)
    return (q[:, 0] | (q[:, 1] << 2) | (q[:, 2] << 4) | (q[:, 3] << 6))


def quantize_flat(flat, residual, threshold):
    """Pure 2-bit quantizer over a flat array (any length, any float
    dtype).  Returns ``(packed uint8 [ceil(n/4)], new_residual)`` with
    the error-feedback residual ``flat + residual - dequantized`` in the
    input's dtype.  Usable inside jitted/shard_mapped programs."""
    g = flat.reshape(-1) + residual.reshape(-1)
    code = jnp.where(g >= threshold, 1, jnp.where(g <= -threshold, 2, 0))
    packed = _pack2(code.astype(jnp.int8))
    deq = jnp.where(code == 1, threshold,
                    jnp.where(code == 2, -threshold, 0.0)).astype(g.dtype)
    return packed, (g - deq).reshape(flat.shape)


def _quantize_2bit(grad, residual, threshold):
    packed, new_residual = quantize_flat(grad.reshape(-1),
                                         residual.reshape(-1), threshold)
    return packed, new_residual.reshape(grad.shape)


def dequantize_flat(packed, n, threshold):
    """packed uint8 [ceil(n/4)] -> float32 [n] of {-t, 0, +t}."""
    b = packed
    codes = jnp.stack([b & 3, (b >> 2) & 3, (b >> 4) & 3, (b >> 6) & 3],
                      axis=1).reshape(-1)[:n]
    return jnp.where(codes == 1, threshold,
                     jnp.where(codes == 2, -threshold, 0.0)) \
        .astype(jnp.float32)


# back-compat alias (pre-refactor private name)
_dequantize_2bit = dequantize_flat


def dequantize_sum_flat(packed_rows, n, threshold):
    """packed_rows: [w, ceil(n/4)] uint8 -> per-element sum over w of the
    dequantized values, as float32 [n] — bitwise equal to summing the
    individually dequantized rows (integer count times threshold)."""
    b = packed_rows
    codes = jnp.stack([b & 3, (b >> 2) & 3, (b >> 4) & 3, (b >> 6) & 3],
                      axis=-1).reshape(b.shape[0], -1)[:, :n]
    signed = jnp.where(codes == 1, 1, jnp.where(codes == 2, -1, 0)) \
        .astype(jnp.int32)
    return threshold * jnp.sum(signed, axis=0).astype(jnp.float32)


_dequantize_2bit_sum = dequantize_sum_flat
