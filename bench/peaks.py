"""Published peak figures per device kind, the denominators of every share.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

SOURCE = "Google Cloud TPU documentation, 'TPU v5e' system architecture"

_V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9}

# keyed by jax.Device.device_kind
PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peak(device_kind: str, platform: str = "tpu") -> dict:
    """The peak figures of one chip; raises for the CPU or an unknown kind."""
    if platform != "tpu":
        raise ValueError(f"no peak figures for platform {platform!r}: "
                         "the benchmark measures TPUs only")
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"unknown device kind {device_kind!r}; known: "
                         f"{sorted(PEAKS)}") from None
