"""Published peaks of the chips the benchmark may run on, keyed by
``device_kind`` as jax reports it.  A kind that is not here is an
error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16 per chip, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s of
inter-chip interconnect per chip (= 200 GB/s).
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e per-chip specs)",
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 200e9,
        "hbm_bytes": 16e9,
    },
}


class UnknownDevice(LookupError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
