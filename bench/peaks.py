"""Published peaks per chip, keyed by JAX's ``device_kind``.

TPU v5e: 197 TFLOP/s bf16 and 819 GB/s of HBM bandwidth, 16 GB of HBM
(Google Cloud documentation, "TPU v5e").  A device that is not in the table
is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": 'Google Cloud documentation, "TPU v5e"'},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
