"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
inter-chip interconnect. A device kind that is not in the table is an
error, never a default.
"""

from __future__ import annotations

SOURCE = "Google Cloud documentation, TPU v5e system architecture"

_V5E = {
    "bf16_flops_per_s": 197e12,
    "int8_ops_per_s": 394e12,
    "hbm_bytes": 16e9,
    "hbm_bytes_per_s": 819e9,
    "ici_bits_per_s": 1600e9,
}

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of this kind; KeyError for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


def least_time_s(flops: float, bytes_: float, device_kind: str) -> float:
    """The least time one chip needs for this work: the larger of its
    operations over the bf16 peak and its bytes over the HBM bandwidth."""
    p = peaks(device_kind)
    return max(flops / p["bf16_flops_per_s"], bytes_ / p["hbm_bytes_per_s"])
