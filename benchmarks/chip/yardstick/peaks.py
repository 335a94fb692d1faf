"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/
v5e): per chip 197 TFLOP/s bf16, 394 TOP/s int8, 16 GiB of HBM at
819 GB/s.  The loss and integral-image kernels are f32 VPU work, for which
no peak is published; the bf16 matrix-unit peak stands in as the compute
ceiling, so their compute bound is a lower bound on time.  A device that
is not in the table is an error: a roofline against a guessed peak is not
a measurement.
"""
from __future__ import annotations

__all__ = ["PEAKS", "peaks_for"]

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16 * (1 << 30),
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
