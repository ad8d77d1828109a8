"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The one table every roofline number in the repo reads. A device that is
not in it has no published ceiling here: callers get ``None`` and report
no roofline share for it, never a default.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI).
"""
from __future__ import annotations

from typing import Dict, Optional

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
    },
}

#: The chip the benchmark's models are sized for (TPU v5e).
V5E = PEAKS["TPU v5 lite"]


def chip_peaks(device_kind: str) -> Optional[Dict[str, float]]:
    """The published peaks of ``device_kind``, or None when the table has
    no entry for it."""
    return PEAKS.get(device_kind)
