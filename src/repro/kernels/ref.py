"""Pure-jnp oracle for the SPC5 kernels (re-exported from repro.core.ref_spmv).

The oracle decodes the identical chunked layout with the identical
cumsum-rank expansion, so kernel-vs-ref comparisons isolate the Pallas
kernel (BlockSpec tiling, DMA windows, scatter) rather than format logic.
"""
from repro.core.ref_spmv import (  # noqa: F401
    SPC5Device,
    device_put,
    spmm,
    spmv,
    spmv_dense_oracle,
)
