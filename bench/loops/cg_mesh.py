"""Unpreconditioned conjugate gradients on a plan sharded over every chip of
the run, as a distributed Krylov user runs it: one slab of a global matrix
per chip.

Set-up first runs one small sharded product, so that a library without
a sharded ``ops.spmv`` fails in seconds, not after minutes of set-up.
Then it builds the plan through the library's distributed entry,
``distributed.shard_matrix(mat, <chips>, mesh=...)``, at its defaults, and
puts the right-hand side b = A x* (x* drawn from the seed) on the mesh,
replicated. The window, the CG update and the check are the ``cg`` loop's
own: each iteration calls ``ops.spmv(plan, p)``, which on this plan runs
every chip's slab through the library's kernel and all-gathers y, so the
benchmark's update runs replicated on every chip. ``gflops`` counts 2 *
the global nnz per product completed.

For the roofline reader, ``run.layer["shards"]`` holds each shard's nnz,
rows and touched columns (the entries of x its rows read).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import load_plugin

cg = load_plugin("loops", "cg")
window, release, check = cg.window, cg.release, cg.check


def _probe_sharded_spmv(mesh) -> None:
    """One product of a small sharded plan through ``ops.spmv``, before the
    minutes of set-up at full size: a program that cannot run a sharded
    plan through its one entry point fails here, within seconds."""
    from repro.core import distributed, formats as F, matgen
    from repro.kernels import ops
    mat = F.csr_to_spc5(matgen.banded(4096, 4, 1.0, seed=0), 1, 8)
    plan = distributed.shard_matrix(mat, mesh.size, mesh=mesh)
    jax.block_until_ready(ops.spmv(plan, jnp.ones(4096)))


def _shard_sizes(rowptr, colidx, row_start, shape):
    """Each shard's ``nnz``, ``nrows`` and ``ncols`` (the distinct columns
    its rows read), from the CSR arrays and the shards' first rows."""
    bounds = list(np.asarray(row_start)) + [shape[0]]
    out = []
    for a, b in zip(bounds[:-1], bounds[1:]):
        cols = colidx[rowptr[a]:rowptr[b]]
        out.append({"nnz": int(cols.shape[0]), "nrows": int(b - a),
                    "ncols": int(np.count_nonzero(
                        np.bincount(cols, minlength=shape[1])))})
    return out


def setup(run):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.core import distributed, formats as F
    from repro.kernels import ops
    cfg = run.config
    mesh = Mesh(np.asarray(run.devices), ("data",))
    _probe_sharded_spmv(mesh)
    shape, rowptr, colidx, values = run.generate()
    csr = F.CSRMatrix(tuple(shape), rowptr, colidx, values)
    with run.span("bench.convert", key="convert_s"):
        mat = F.csr_to_spc5(csr, *cfg["block"])
    plan = distributed.shard_matrix(mat, len(run.devices), mesh=mesh,
                                    vdtype=cfg["vdtype"])
    del mat
    run.layer["plan_trace"] = plan.trace
    run.layer["plan"] = {"layout": plan.layout, "lowering": plan.lowering,
                         "ndev": plan.ndev,
                         "npanels": int(plan.chunk_vbase.shape[1]),
                         "nchunks": int(plan.chunk_vbase.shape[2])}
    run.layer["shards"] = _shard_sizes(rowptr, colidx, plan.row_start, shape)
    xstar = np.random.default_rng(run.seed).standard_normal(
        shape[1]).astype(np.float64)
    rows = np.repeat(np.arange(shape[0]), np.diff(rowptr))
    b = np.bincount(rows, weights=values * xstar[colidx],
                    minlength=shape[0]).astype(np.float32)
    del rows
    b = jax.device_put(b, NamedSharding(mesh, PartitionSpec()))
    # warm every program the window runs
    st = cg.bench_cg_start(b)
    ap = ops.spmv(plan, st[2])
    x, r, p, rho = st
    jax.block_until_ready(cg.bench_cg_update(x, r, p, ap, rho))
    return {"plan": plan, "b": b, "csr": (shape, rowptr, colidx, values),
            "samples": []}
