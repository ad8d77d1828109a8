"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of interconnect.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
    },
}


def chip_peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; raises ``KeyError`` for a
    device the table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
