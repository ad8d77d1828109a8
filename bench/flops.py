"""Operations and least bytes of one sparse product, from its shape alone.

The counts never look at a storage format: a product of an (nrows, ncols)
matrix with ``nnz`` stored nonzeros and ``nvec`` vectors does ``2 * nnz *
nvec`` flops and must at least read every value once and every x and y
element once. A format's own metadata, padding or over-read is waste the
roofline share shows, not work it credits.
"""
from __future__ import annotations

from typing import Dict

#: Bytes of one x or y element (the vectors are float32 throughout).
VECTOR_BYTES = 4


def spmv_flops(nnz: int, nvec: int = 1) -> int:
    """Multiply-adds of Y = A @ X, counted as two operations each."""
    return 2 * int(nnz) * int(nvec)


def spmv_least_bytes(nnz: int, nrows: int, ncols: int, value_bytes: int,
                     nvec: int = 1) -> int:
    """Least HBM traffic of Y = A @ X: the values once, x and y once per
    vector."""
    return (int(nnz) * int(value_bytes)
            + (int(nrows) + int(ncols)) * VECTOR_BYTES * int(nvec))


def spmv_least_seconds(nnz: int, nrows: int, ncols: int, value_bytes: int,
                       nvec: int, peaks: Dict[str, float]) -> float:
    """The least time the chip could take for one product: the larger of
    bytes over HBM bandwidth and flops over the bf16 peak (the highest
    the chip publishes, so this bound is never too long)."""
    return max(spmv_least_bytes(nnz, nrows, ncols, value_bytes, nvec)
               / peaks["hbm_bytes_per_s"],
               spmv_flops(nnz, nvec) / peaks["bf16_flops_per_s"])
