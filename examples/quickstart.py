"""Quickstart: SPC5 block-sparse formats + kernels in 60 seconds.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core import formats as F
from repro.core import matgen
from repro.core import ref_spmv as R
from repro.core.selector import RecordStore, select_kernel
from repro.kernels import ops


def main():
    # 1. a sparse matrix (FEM-like structure, as in the paper's Set-A)
    csr = matgen.fem_blocks(3_000, 4, 6, seed=0)
    print(f"matrix: {csr.shape}, nnz={csr.nnz}")

    # 2. convert to beta(r,c) -- NO zero padding: values array == nnz
    for rc in [(1, 8), (2, 4), (4, 4), (4, 8)]:
        mat = F.csr_to_spc5(csr, *rc)
        print(f"  beta{rc}: blocks={mat.nblocks:6d} "
              f"avg nnz/block={mat.avg_nnz_per_block:5.2f} "
              f"(fill {mat.fill_ratio*100:4.1f}%) "
              f"bytes={mat.occupancy_bytes()/1e6:6.2f}MB "
              f"vs CSR {csr.occupancy_bytes()/1e6:6.2f}MB")

    # 3. SpMV through the plan's executor (the compiled Pallas kernel on a
    #    TPU, the jnp decode elsewhere) against the plain CSR reference
    mat = F.csr_to_spc5(csr, 4, 4)
    h = ops.prepare(mat)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(csr.shape[1]),
                    jnp.float32)
    y = ops.spmv(h, x)
    err = float(jnp.abs(R.csr_operator(csr)(x) - y).max())
    print(f"SpMV ({h.layout}): max err vs CSR = {err:.2e}")

    # 4. record-based kernel selection (paper §Prediction)
    store = RecordStore()
    for k, gf_per_avg in [("1x8", 0.30), ("2x4", 0.33), ("4x4", 0.26),
                          ("4x8", 0.22), ("2x8", 0.28), ("8x4", 0.2)]:
        for avg in [1.0, 4.0, 16.0, 32.0]:
            store.add(k, avg, 1, gf_per_avg * avg)    # toy records
    best, pred, _ = select_kernel(csr, store, workers=1)
    print(f"selector picks beta({best}) predicted {pred:.2f} GF/s")


if __name__ == "__main__":
    main()
