"""Published peaks of each chip the benchmark may run on, keyed by the
``device_kind`` JAX reports. A chip missing from the table is an error:
no share of a peak is ever computed against a guess.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add the "
            f"chip to bench/peaks.py with its source (known: "
            f"{sorted(PEAKS)})") from None


def least_time(ops: float, nbytes: float, op_peak: float,
               bytes_per_s: float) -> float:
    """Roofline bound of one call: the larger of compute and memory time."""
    return max(ops / op_peak, nbytes / bytes_per_s)
