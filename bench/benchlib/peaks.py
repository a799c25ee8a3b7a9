"""Published per-chip peaks, keyed by JAX's ``device_kind``.

A device that is not in the table is an error: a share of a peak needs the
peak of the chip it ran on.
"""
from __future__ import annotations

V5E = {
    "flops_per_s": 197e12,       # bf16 matrix unit
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
              "16 GB HBM at 819 GB/s per chip",
}

PEAKS = {"TPU v5 lite": V5E, "TPU v5e": V5E}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
