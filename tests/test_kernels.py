"""Per-kernel allclose vs the pure-jnp oracle, sweeping shapes and dtypes.

Pallas kernels run in interpret=True on CPU (the kernel body executes in
Python), exactly as the assignment prescribes for kernel validation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from repro._compat.hypothesis import given, settings, strategies as st

from repro.core import formats as F
from repro.core import matgen
from repro.kernels import ops, ref

BLOCKS = [(1, 8), (2, 4), (2, 8), (4, 4), (4, 8), (8, 4)]


def make_handle(n, m, density, rc, seed, dtype=np.float32, cb=32):
    rng = np.random.default_rng(seed)
    d = ((rng.random((n, m)) < density)
         * rng.standard_normal((n, m))).astype(dtype)
    csr = F.csr_from_dense(d)
    mat = F.csr_to_spc5(csr, *rc)
    return d, ops.prepare(mat, cb=cb)


@pytest.mark.parametrize("rc", BLOCKS)
def test_spmv_pallas_vs_oracle(rc):
    d, h = make_handle(96, 80, 0.12, rc, seed=sum(rc))
    x = np.random.default_rng(1).standard_normal(80).astype(np.float32)
    tgt = d.astype(np.float64) @ x.astype(np.float64)
    y_ref = ops.spmv(h, jnp.asarray(x), use_pallas=False)
    y_pal = ops.spmv(h, jnp.asarray(x), use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(y_ref), tgt, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=1e-6)


@pytest.mark.parametrize("rc", [(1, 8), (4, 4), (8, 4)])
@pytest.mark.parametrize("nvec,nvt", [(4, 4), (16, 8)])
def test_spmm_pallas_vs_oracle(rc, nvec, nvt):
    d, h = make_handle(64, 72, 0.2, rc, seed=7)
    X = np.random.default_rng(2).standard_normal((72, nvec)).astype(np.float32)
    tgt = d.astype(np.float64) @ X.astype(np.float64)
    Y_ref = ops.spmm(h, jnp.asarray(X), use_pallas=False)
    Y_pal = ops.spmm(h, jnp.asarray(X), use_pallas=True, interpret=True,
                     nvt=nvt)
    np.testing.assert_allclose(np.asarray(Y_ref), tgt, atol=5e-4)
    # kernel unrolls (r, c) adds; oracle uses one einsum -- association only
    np.testing.assert_allclose(np.asarray(Y_pal), np.asarray(Y_ref),
                               atol=2e-5, rtol=2e-5)


def test_spmv_f32():
    d, h = make_handle(50, 60, 0.3, (2, 8), seed=3, dtype=np.float32)
    x = np.random.default_rng(3).standard_normal(60).astype(np.float32)
    y = ops.spmv(h, jnp.asarray(x), use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(y).astype(np.float64),
                               d.astype(np.float64) @ x.astype(np.float64),
                               atol=2e-4)


def test_spmv_f64_x64_mode():
    """f64 path needs jax x64 (global flag) -> isolated subprocess."""
    import os, subprocess, sys
    code = (
        "import jax; jax.config.update('jax_enable_x64', True)\n"
        "import numpy as np, jax.numpy as jnp\n"
        "from repro.core import formats as F\n"
        "from repro.kernels import ops\n"
        "rng = np.random.default_rng(0)\n"
        "d = ((rng.random((50,60)) < 0.3)"
        " * rng.standard_normal((50,60)))\n"
        "h = ops.prepare(F.csr_to_spc5(F.csr_from_dense(d), 2, 8), cb=32)\n"
        "x = rng.standard_normal(60)\n"
        "y = ops.spmv(h, jnp.asarray(x), use_pallas=True, interpret=True)\n"
        "np.testing.assert_allclose(np.asarray(y), d @ x, atol=1e-10)\n"
        "print('OK')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert res.returncode == 0, res.stderr


def test_spmv_bf16():
    import dataclasses
    d, h = make_handle(40, 40, 0.3, (1, 8), seed=4)
    hb = dataclasses.replace(
        h, arrays=tuple(a.astype(jnp.bfloat16) if a.dtype == jnp.float32
                        else a for a in h.arrays))
    x = jnp.asarray(np.random.default_rng(5).standard_normal(40),
                    dtype=jnp.bfloat16)
    y = ops.spmv(hb, x, use_pallas=True, interpret=True)
    tgt = d.astype(np.float64) @ np.asarray(x, np.float64)
    np.testing.assert_allclose(np.asarray(y, np.float64), tgt,
                               atol=0.15 * (np.abs(tgt).max() + 1))


@settings(max_examples=12, deadline=None)
@given(
    n=st.integers(8, 64),
    m=st.integers(8, 64),
    density=st.floats(0.02, 0.5),
    rc=st.sampled_from(BLOCKS),
    cb=st.sampled_from([8, 16, 64]),
    seed=st.integers(0, 2**20),
)
def test_property_kernel_matches_oracle(n, m, density, rc, cb, seed):
    d, h = make_handle(n, m, density, rc, seed, cb=cb)
    x = np.random.default_rng(seed + 1).standard_normal(m).astype(np.float32)
    y_ref = np.asarray(ops.spmv(h, jnp.asarray(x), use_pallas=False))
    y_pal = np.asarray(ops.spmv(h, jnp.asarray(x), use_pallas=True,
                                interpret=True))
    np.testing.assert_allclose(y_pal, y_ref, atol=1e-6)
    np.testing.assert_allclose(
        y_ref, d.astype(np.float64) @ x.astype(np.float64), atol=5e-4)


@pytest.mark.parametrize("rc", [(1, 8), (2, 4)])
def test_beta_test_split_kernel(rc):
    """beta(r,c)_test: singleton COO tail + block kernel == full product."""
    from repro.core import matgen
    csr = matgen.powerlaw(600, 5, seed=9)
    d = csr.to_dense()
    mat = F.csr_to_spc5(csr, *rc)
    ht = ops.prepare(mat, layout="test", cb=64, dtype=np.float32)
    assert ht.single_values.shape[0] > 0   # power-law has singletons
    x = np.random.default_rng(1).standard_normal(600).astype(np.float32)
    y = ops.spmv_test(ht, jnp.asarray(x), use_pallas=False)
    tgt = d @ x
    np.testing.assert_allclose(np.asarray(y), tgt,
                               atol=2e-4 * max(1, np.abs(tgt).max()))
    # and through the Pallas kernel for the multi part
    y2 = ops.spmv_test(ht, jnp.asarray(x), use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y), atol=1e-5)


def test_empty_and_edge_matrices():
    # all-zero matrix
    d = np.zeros((16, 16), np.float32)
    csr = F.csr_from_dense(d)
    mat = F.csr_to_spc5(csr, 2, 4)
    h = ops.prepare(mat, cb=8)
    y = ops.spmv(h, jnp.ones(16), use_pallas=False)
    np.testing.assert_allclose(np.asarray(y), 0.0)
    # single element at the far corner
    d[15, 15] = 3.0
    mat = F.csr_to_spc5(F.csr_from_dense(d), 4, 8)
    h = ops.prepare(mat, cb=8)
    y = ops.spmv(h, jnp.ones(16), use_pallas=True, interpret=True)
    assert np.asarray(y)[15] == pytest.approx(3.0)
