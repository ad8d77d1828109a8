"""Block conjugate gradients on one plan: A X = B for ``nrhs`` right-hand
sides at once, as a multi-RHS Krylov user runs it.

O'Leary's block CG ("The block conjugate gradient algorithm and related
methods", Linear Algebra Appl. 29, 1980), unpreconditioned and with the
identity for her scaling matrices, as Trilinos Belos's ``BlockCGSolMgr``
runs it::

    Q = A P;  alpha = (P^T Q)^-1 (R^T R);  X += P alpha;  R -= Q alpha
    beta = (R^T R)_old^-1 (R^T R);  P = R + P beta

Each iteration makes one ``ops.spmm(plan, P)`` call with P of shape (n,
nrhs), then the Gram matrices, the nrhs x nrhs solves and the updates in
the benchmark's own jitted ``bench_bcg_update`` (so a trace tells its ops
from the library's). The Gram matrices run at ``precision=HIGHEST``: a
float32 matmul at the default precision runs in bfloat16 passes on a TPU.
At one right-hand side this is the ``cg`` loop's iteration. The
right-hand sides are B = A X*, with X* drawn from the seed; sets of
``set_iterations`` iterations start from X0 = 0.

The window, the queueing and the check are the ``cg`` loop's: the host
keeps one iteration queued and never reads a Gram matrix inside the
window; ``gflops`` counts 2 * nnz * nrhs per product completed; the
window's first product (A B) and three reservoir-sampled (P, A P) pairs,
each nrhs wide, are compared with the reference after the window.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

#: Products drawn for the comparison, besides the window's first.
SAMPLES = 3
_HIGHEST = jax.lax.Precision.HIGHEST


def _gram(a, b):
    """a^T b, (s, s), float32-exact on every backend."""
    return jnp.matmul(a.T, b, precision=_HIGHEST)


def _solve(a, b):
    """a^-1 b, or 0 where the solve is not finite: a converged set (R = 0)
    stays put instead of dividing 0 by 0, as in the ``cg`` loop."""
    z = jnp.linalg.solve(a, b)
    return jnp.where(jnp.all(jnp.isfinite(z)), z, jnp.zeros_like(z))


@jax.jit
def bench_bcg_update(x, r, p, q, rtr):
    """One block CG iteration after the product ``q = A p``; ``rtr`` is
    R^T R of the residual ``r`` coming in."""
    with jax.named_scope("bench_bcg"):
        alpha = _solve(_gram(p, q), rtr)
        x = x + jnp.matmul(p, alpha, precision=_HIGHEST)
        r = r - jnp.matmul(q, alpha, precision=_HIGHEST)
        rtr_new = _gram(r, r)
        beta = _solve(rtr, rtr_new)
        p = r + jnp.matmul(p, beta, precision=_HIGHEST)
    return x, r, p, rtr_new


@jax.jit
def bench_bcg_start(b):
    with jax.named_scope("bench_bcg"):
        return jnp.zeros_like(b), b, b, _gram(b, b)


def rhs(rowptr, colidx, values, xstar):
    """B = A X* in float64 on the host, one column at a time."""
    n = rowptr.shape[0] - 1
    rows = np.repeat(np.arange(n), np.diff(rowptr))
    return np.stack([np.bincount(rows, weights=values * xstar[colidx, j],
                                 minlength=n)
                     for j in range(xstar.shape[1])], axis=1)


def setup(run):
    from repro.core import formats as F
    from repro.kernels import ops
    cfg = run.config
    nrhs = int(cfg["nrhs"])
    shape, rowptr, colidx, values = run.generate()
    csr = F.CSRMatrix(tuple(shape), rowptr, colidx, values)
    with run.span("bench.convert", key="convert_s"):
        mat = F.csr_to_spc5(csr, *cfg["block"])
    plan = ops.prepare(mat, vdtype=cfg["vdtype"], nvec=nrhs)
    del mat
    run.layer["plan_trace"] = plan.trace
    run.layer["plan"] = {"layout": plan.layout, "npanels": plan.npanels,
                         "nchunks": plan.nchunks}
    xstar = np.random.default_rng(run.seed).standard_normal(
        (shape[1], nrhs))
    b = rhs(rowptr, colidx, values, xstar).astype(np.float32)
    b_norm2 = np.sum(b.astype(np.float64) ** 2, axis=0)
    b = jax.device_put(b, run.devices[0])
    # warm every program the window runs
    st = bench_bcg_start(b)
    q = ops.spmm(plan, st[2])
    jax.block_until_ready(bench_bcg_update(*st[:3], q, st[3]))
    return {"plan": plan, "b": b, "b_norm2": b_norm2, "nrhs": nrhs,
            "csr": (shape, rowptr, colidx, values), "samples": []}


def window(run, state):
    from repro.kernels import ops
    plan, b, nrhs = state["plan"], state["b"], state["nrhs"]
    set_iters = int(run.traffic["set_iterations"])
    rng = np.random.default_rng(run.seed)
    samples = state["samples"]
    done = 0
    with run.span("bench.window"):
        t0 = time.perf_counter()
        st = prev = None
        while True:
            if done % set_iters == 0:
                with run.span("bench.cg_vector_ops"):
                    st = bench_bcg_start(b)
            x, r, p, rtr = st
            with run.span("bench.dispatch"):
                q = ops.spmm(plan, p)
            with run.span("bench.cg_vector_ops"):
                st = bench_bcg_update(x, r, p, q, rtr)
            # the window's first product (A B), then reservoir sampling of
            # (P, A P) pairs over the rest of the window
            if len(samples) < SAMPLES + 1:
                samples.append((p, q))
            else:
                k = int(rng.integers(0, done))
                if k < SAMPLES:
                    samples[1 + k] = (p, q)
            done += 1
            if prev is not None:
                with run.span("bench.result_wait"):
                    prev.block_until_ready()
            prev = st[3]
            if time.perf_counter() - t0 >= run.seconds:
                break
        with run.span("bench.result_wait"):
            prev.block_until_ready()
        elapsed = time.perf_counter() - t0
    shape, _, _, values = state["csr"]
    nnz = int(values.shape[0])
    run.layer["products"] = [nrhs] * done
    run.layer["nnz"], run.layer["shape"] = nnz, shape
    run.layer["value_bytes"] = np.dtype(values.dtype).itemsize
    rtr_last = np.diag(np.asarray(prev, np.float64))
    worst = float(np.sqrt(np.max(rtr_last / state["b_norm2"])))
    return {"attempted": done, "failed": 0,
            "metrics": {"gflops": 2.0 * nnz * nrhs * done / elapsed / 1e9},
            "notes": {"plan": run.layer["plan"], "iterations": done,
                      "window_s": elapsed,
                      "iterations_in_last_set": (done - 1) % set_iters + 1,
                      "worst_rel_residual": worst}}


def release(state):
    state.pop("plan")
    state.pop("b")


def check(run, state):
    from bench.reference import CSRReference
    shape, rowptr, colidx, values = state["csr"]
    ref = CSRReference(shape, rowptr, colidx, values)
    P = jnp.concatenate([p for p, _ in state["samples"]], axis=1)
    Y = jnp.concatenate([q for _, q in state["samples"]], axis=1)
    gap = ref.rel_gap(Y, P)
    return {"rel_gap": {"value": gap,
                        "limit": float(run.config["limits"]["rel_gap"])}}
