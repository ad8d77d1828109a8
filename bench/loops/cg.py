"""Unpreconditioned conjugate gradients on one plan, as a Krylov user runs it.

Each iteration makes one ``ops.spmv(plan, p)`` call, then two dots and
three axpys in the benchmark's own jitted update (``bench_cg_update``, so
a trace tells its ops from the library's). Scalars stay on the device.
Sets of ``set_iterations`` iterations start from x0 = 0, as HPCG's
reference runs them. The right-hand side is b = A x*, with x* drawn from
the seed.

The window runs whole iterations until ``--seconds`` have passed. The host
keeps one iteration queued behind the one running (it waits for the one
before last, never for the newest), so the device is never starved by
the wait and the window closes within an iteration of its length.
``gflops`` counts 2 * nnz of the input matrix per product completed, over
the window's wall time.

What is compared: the window's first product (A b) and those of a few
more iterations, drawn from the seed as the window runs (reservoir
sampling), against the reference of the same input vectors.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

#: Products drawn for the comparison, besides the window's first.
SAMPLES = 3


@jax.jit
def bench_cg_update(x, r, p, ap, rho):
    with jax.named_scope("bench_cg"):
        # a converged set (r = 0) stays put instead of dividing 0 by 0
        pap = jnp.vdot(p, ap)
        alpha = jnp.where(pap > 0, rho / jnp.where(pap > 0, pap, 1), 0)
        x = x + alpha * p
        r = r - alpha * ap
        rho_new = jnp.vdot(r, r)
        beta = jnp.where(rho > 0, rho_new / jnp.where(rho > 0, rho, 1), 0)
        p = r + beta * p
    return x, r, p, rho_new


@jax.jit
def bench_cg_start(b):
    with jax.named_scope("bench_cg"):
        return jnp.zeros_like(b), b, b, jnp.vdot(b, b)


def setup(run):
    from repro.core import formats as F
    from repro.kernels import ops
    cfg = run.config
    shape, rowptr, colidx, values = run.generate()
    csr = F.CSRMatrix(tuple(shape), rowptr, colidx, values)
    with run.span("bench.convert", key="convert_s"):
        mat = F.csr_to_spc5(csr, *cfg["block"])
    plan = ops.prepare(mat, vdtype=cfg["vdtype"])
    del mat
    run.layer["plan_trace"] = plan.trace
    run.layer["plan"] = {"layout": plan.layout, "lowering": plan.lowering,
                         "npanels": plan.npanels, "nchunks": plan.nchunks}
    xstar = np.random.default_rng(run.seed).standard_normal(
        shape[1]).astype(np.float64)
    rows = np.repeat(np.arange(shape[0]), np.diff(rowptr))
    b = np.bincount(rows, weights=values * xstar[colidx],
                    minlength=shape[0]).astype(np.float32)
    b = jax.device_put(b, run.devices[0])
    # warm every program the window runs
    st = bench_cg_start(b)
    ap = ops.spmv(plan, st[2])
    x, r, p, rho = st
    jax.block_until_ready(bench_cg_update(x, r, p, ap, rho))
    return {"plan": plan, "b": b, "csr": (shape, rowptr, colidx, values),
            "samples": []}


def window(run, state):
    from repro.kernels import ops
    plan, b = state["plan"], state["b"]
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
                    st = bench_cg_start(b)
            x, r, p, rho = st
            with run.span("bench.dispatch"):
                ap = ops.spmv(plan, p)
            with run.span("bench.cg_vector_ops"):
                st = bench_cg_update(x, r, p, ap, rho)
            # the window's first product (A b, never zero), then reservoir
            # sampling of (p, A p) pairs over the rest of the window
            if len(samples) < SAMPLES + 1:
                samples.append((p, ap))
            else:
                k = int(rng.integers(0, done))
                if k < SAMPLES:
                    samples[1 + k] = (p, ap)
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
    run.layer["products"] = [1] * done
    run.layer["nnz"], run.layer["shape"] = nnz, shape
    run.layer["value_bytes"] = np.dtype(values.dtype).itemsize
    return {"attempted": done, "failed": 0,
            "metrics": {"gflops": 2.0 * nnz * done / elapsed / 1e9},
            "notes": {"plan": run.layer["plan"], "iterations": done,
                      "window_s": elapsed,
                      "rho_last": float(prev)}}


def release(state):
    state.pop("plan")
    state.pop("b")


def check(run, state):
    from bench.reference import CSRReference
    shape, rowptr, colidx, values = state["csr"]
    ref = CSRReference(shape, rowptr, colidx, values)
    P = jnp.stack([p for p, _ in state["samples"]], axis=1)
    Y = jnp.stack([ap for _, ap in state["samples"]], axis=1)
    gap = ref.rel_gap(Y, P)
    return {"rel_gap": {"value": gap,
                        "limit": float(run.config["limits"]["rel_gap"])}}
