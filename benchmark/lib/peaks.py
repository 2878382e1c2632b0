"""The one table of device peaks, keyed by the exact ``device_kind`` JAX reports.

A device that is not in the table is an error, never a default: a share of
an unknown peak means nothing.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" system architecture page:
    # 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s ICI per chip.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e, per chip)",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peak figures for device kind {device_kind!r}; add a row "
            f"with its source to benchmark/lib/peaks.py (have "
            f"{sorted(PEAKS)})")
    return PEAKS[device_kind]


def least_seconds(device_kind: str, flops: float, nbytes: float) -> float:
    """The least time the chip could take for this work: the larger of
    FLOPs over peak FLOP/s and bytes over peak bytes/s (the roofline)."""
    peak = peaks_for(device_kind)
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
