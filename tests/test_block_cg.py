"""Block CG with 8 right-hand sides on HPCG's matrix, the deployment of the
benchmark's ``hpcg104_rhs8`` configuration, on the CPU:

* ``ops.spmm`` at nvec 8, on the plan ``ops.prepare`` builds on a TPU
  (panels, the kernel in interpret mode here), carries all 8 vectors in
  one grid step and agrees with a plain float32 CSR product within the
  configuration's ``rel_gap`` limit;
* the benchmark's block CG update (``bench/loops/bcg.py``) follows a
  NumPy float64 block CG, and at one right-hand side is the ``cg`` loop's
  iteration.
"""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.gen import hpcg_stencil
from bench.harness import ROOT, load_plugin
from bench.reference import CSRReference
from repro.core import formats as F
from repro.core import plan as P
from repro.kernels import ops, spc5_spmv

CONFIG = os.path.join(ROOT, "bench", "configs", "hpcg104_rhs8.json")


def _config():
    with open(CONFIG, encoding="utf-8") as f:
        return json.load(f)


def _stencil(nx, ny, nz):
    return hpcg_stencil.generate(dict(nx=nx, ny=ny, nz=nz), 0)


def _dense(shape, rowptr, colidx, values):
    a = np.zeros(shape, np.float64)
    rows = np.repeat(np.arange(shape[0]), np.diff(rowptr))
    a[rows, colidx] = values
    return a


def _rehearsal_grid():
    r = _config()["rehearsal"]
    return (r["nx"], r["ny"], r["nz"])


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5])
@pytest.mark.parametrize("grid", ["rehearsal", (16, 16, 6)])
def test_spmm_at_eight_vectors_on_the_stencil(grid, seed, monkeypatch,
                                              launched_grids):
    """The configuration's plan and product: 8 vectors a grid step, grid
    (1, npanels, groups), within ``limits.rel_gap`` of the float32 CSR
    reference."""
    cfg = _config()
    nrhs = int(cfg["nrhs"])
    assert nrhs == 8
    shape, rowptr, colidx, values = _stencil(
        *(_rehearsal_grid() if grid == "rehearsal" else grid))
    csr = F.CSRMatrix(tuple(shape), rowptr, colidx, values)
    monkeypatch.setattr(P, "_on_tpu", lambda: True)
    plan = ops.prepare(F.csr_to_spc5(csr, *cfg["block"]),
                       vdtype=cfg["vdtype"], nvec=nrhs)
    assert plan.layout == P.LAYOUT_PANELS
    x = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (shape[1], nrhs)), jnp.float32)
    jax.clear_caches()              # so the kernel is traced, and seen
    y = ops.spmm(plan, x, use_pallas=True, interpret=True)
    geom = dict(plan.meta)
    geom["npanels"], geom["nchunks"] = plan.chunk_vbase.shape
    group = spc5_spmv.panel_group(geom, 4, nrhs)
    assert launched_grids == [(1, plan.npanels,
                               -(-plan.nchunks // group))]
    ref = CSRReference(shape, rowptr, colidx, values)
    assert ref.rel_gap(y, x) <= cfg["limits"]["rel_gap"]


def _block_cg_f64(a, b, iters):
    """O'Leary's block CG from X0 = 0 in float64 (scaling matrices = I)."""
    x = np.zeros_like(b)
    r = b.copy()
    p = b.copy()
    rtr = r.T @ r
    for _ in range(iters):
        q = a @ p
        alpha = np.linalg.solve(p.T @ q, rtr)
        x = x + p @ alpha
        r = r - q @ alpha
        rtr_new = r.T @ r
        p = r + p @ np.linalg.solve(rtr, rtr_new)
        rtr = rtr_new
    return x, r


def _block_cg_bench(a, b, iters):
    bcg = load_plugin("loops", "bcg")
    a32 = jnp.asarray(a, jnp.float32)
    x, r, p, rtr = bcg.bench_bcg_start(jnp.asarray(b, jnp.float32))
    for _ in range(iters):
        q = jnp.matmul(a32, p, precision=jax.lax.Precision.HIGHEST)
        x, r, p, rtr = bcg.bench_bcg_update(x, r, p, q, rtr)
    return np.asarray(x, np.float64), np.asarray(r, np.float64), rtr


@pytest.mark.parametrize("nrhs", [2, 4, 8])
def test_block_cg_update_follows_float64_block_cg(nrhs):
    """Ten iterations of the benchmark's update on the 8^3 stencil track
    float64 block CG: the iterate within 1e-5 of its norm, each residual
    column within 1e-6 of its right-hand side's norm, and R^T R is the
    residual's own Gram matrix."""
    shape, rowptr, colidx, values = _stencil(8, 8, 8)
    a = _dense(shape, rowptr, colidx, values)
    rng = np.random.default_rng(nrhs)
    b = a @ rng.standard_normal((shape[1], nrhs))
    want_x, want_r = _block_cg_f64(a, b, 10)
    x, r, rtr = _block_cg_bench(a, b, 10)
    bnorm = np.linalg.norm(b, axis=0)
    assert np.linalg.norm(x - want_x) <= 1e-5 * np.linalg.norm(want_x)
    assert np.all(np.linalg.norm(r - want_r, axis=0) <= 1e-6 * bnorm)
    # ten iterations of the block method shrink every residual
    assert np.all(np.linalg.norm(want_r, axis=0) < 0.5 * bnorm)
    np.testing.assert_allclose(np.asarray(rtr, np.float64), r.T @ r,
                               rtol=1e-4, atol=1e-6 * np.max(bnorm) ** 2)


def test_block_cg_at_one_rhs_is_the_cg_iteration():
    """At s = 1 the block update is ``cg.bench_cg_update``: the same
    iterates, residuals, directions and rho over ten iterations."""
    bcg = load_plugin("loops", "bcg")
    cg = load_plugin("loops", "cg")
    shape, rowptr, colidx, values = _stencil(8, 8, 8)
    a = jnp.asarray(_dense(shape, rowptr, colidx, values), jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    b = jnp.asarray(np.random.default_rng(3).standard_normal(shape[0]),
                    jnp.float32)
    xs, rs, ps, rho = cg.bench_cg_start(b)
    xb, rb, pb, rtr = bcg.bench_bcg_start(b[:, None])
    for _ in range(10):
        ap = jnp.matmul(a, ps, precision=hi)
        xs, rs, ps, rho = cg.bench_cg_update(xs, rs, ps, ap, rho)
        q = jnp.matmul(a, pb, precision=hi)
        xb, rb, pb, rtr = bcg.bench_bcg_update(xb, rb, pb, q, rtr)
        assert rtr.shape == (1, 1)
        assert float(rtr[0, 0]) == pytest.approx(float(rho), rel=1e-5)
    scale = float(jnp.linalg.norm(b))
    for got, want in ((xb, xs), (rb, rs), (pb, ps)):
        assert float(jnp.max(jnp.abs(got[:, 0] - want))) <= 1e-5 * scale


def test_block_cg_converged_set_stays_put():
    """A set whose residual is exactly 0 keeps its iterate and reads no
    NaN, as the ``cg`` loop's guard does."""
    bcg = load_plugin("loops", "bcg")
    x = jnp.ones((16, 4), jnp.float32)
    zero = jnp.zeros((16, 4), jnp.float32)
    out = bcg.bench_bcg_update(x, zero, zero, zero,
                               jnp.zeros((4, 4), jnp.float32))
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(x))
    for a in out:
        assert bool(jnp.all(jnp.isfinite(a)))
    assert math.isfinite(float(jnp.sum(out[3])))
