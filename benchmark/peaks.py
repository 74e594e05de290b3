"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error, never a
default: a share of a peak is only as good as the peak."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.  JAX reports the
    # chip as "TPU v5 lite" (chip run, PR 21).
    "TPU v5 lite": {
        "flops_per_s": 197e12,        # bf16; the only published matmul peak
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e: 197 TFLOP/s "
                  "bf16, 819 GB/s, 16 GB per chip)",
    },
}


class UnknownDevice(LookupError):
    pass


def peak(device_kind):
    """The peak row for ``device_kind``; raises :class:`UnknownDevice`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            "no published peaks for device kind %r (known: %s)"
            % (device_kind, sorted(PEAKS))) from None
