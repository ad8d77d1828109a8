"""Conjugate-gradient solver with every matvec through the SPC5 kernel --
the paper's motivating use case (Krylov subspace iterations).

    PYTHONPATH=src python examples/cg_solver.py [--n 2000] [--distributed]

--distributed runs the row-partitioned shard_map SpMV over all local devices
(launch with XLA_FLAGS=--xla_force_host_platform_device_count=8 to see it
split; the math is identical).
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import formats as F
from repro.core import matgen
from repro.kernels import ops


def make_spd(n: int, seed: int = 0) -> np.ndarray:
    csr = matgen.banded(n, 4, 1.0, seed=seed)
    a = csr.to_dense()
    a = (a + a.T) / 2
    a += np.eye(n) * (np.abs(a).sum(1).max() + 1.0)
    return a.astype(np.float32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--iters", type=int, default=300)
    ap.add_argument("--distributed", action="store_true")
    args = ap.parse_args()

    a = make_spd(args.n)
    csr = F.csr_from_dense(a)
    mat = F.csr_to_spc5(csr, 2, 4)
    print(f"A: {a.shape}, nnz={csr.nnz}, beta(2,4) "
          f"avg={mat.avg_nnz_per_block:.2f}")

    if args.distributed:
        from jax.sharding import Mesh
        from repro.core import distributed as D
        ndev = len(jax.devices())
        mesh = Mesh(np.array(jax.devices()).reshape(ndev,), ("data",))
        sh = D.shard_matrix(mat, ndev, cb=256, mesh=mesh)
        matvec = lambda p: ops.spmv(sh, p)
        print(f"distributed SpMV over {ndev} devices")
    else:
        h = ops.prepare(mat)
        matvec = lambda p: ops.spmv(h, p)

    b = jnp.asarray(np.random.default_rng(1).standard_normal(args.n),
                    jnp.float32)
    x = jnp.zeros(args.n)
    r = b
    p = r
    rs = r @ r
    for it in range(args.iters):
        ap_ = matvec(p)
        alpha = rs / (p @ ap_)
        x = x + alpha * p
        r = r - alpha * ap_
        rs_new = r @ r
        if it % 25 == 0:
            print(f"  iter {it:4d} |r| = {float(jnp.sqrt(rs_new)):.3e}")
        if float(rs_new) < 1e-10:
            break
        p = r + (rs_new / rs) * p
        rs = rs_new
    res = np.linalg.norm(a @ np.asarray(x) - np.asarray(b))
    res /= np.linalg.norm(np.asarray(b))
    print(f"converged: relative residual {res:.2e} after {it + 1} iters")


if __name__ == "__main__":
    main()
