import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import CSRReference


def _random_csr(rng, nrows, ncols, density):
    dense = rng.standard_normal((nrows, ncols)).astype(np.float32)
    dense[rng.random((nrows, ncols)) >= density] = 0
    dense[3] = 0                                   # an empty row
    rows, cols = np.nonzero(dense)
    rowptr = np.zeros(nrows + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=nrows), out=rowptr[1:])
    return dense, (nrows, ncols), rowptr, cols.astype(np.int32), \
        dense[rows, cols]


@pytest.mark.parametrize("nvec", [0, 1, 3, 20])
def test_reference_matches_dense_float64(nvec):
    rng = np.random.default_rng(0)
    dense, *csr = _random_csr(rng, 50, 40, 0.2)
    ref = CSRReference(*csr)
    x = rng.standard_normal((40,) if nvec == 0 else (40, nvec)).astype(
        np.float32)
    want = dense.astype(np.float64) @ x.astype(np.float64)
    got = np.asarray(ref.apply(x))
    assert got.shape == want.shape and got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert ref.rel_gap(got, x) < 1e-6


def test_rel_gap_catches_a_wrong_row_a_swap_and_a_nan():
    rng = np.random.default_rng(1)
    _, *csr = _random_csr(rng, 60, 30, 0.3)
    ref = CSRReference(*csr)
    X = rng.standard_normal((30, 4)).astype(np.float32)
    Y = np.asarray(ref.apply(X))
    assert ref.rel_gap(Y, X) == 0.0
    bad = Y.copy()
    bad[7, 2] += 1e-3 * max(1.0, abs(bad[7, 2]))
    assert ref.rel_gap(bad, X) > 1e-4
    assert ref.rel_gap(Y[:, ::-1], X) > 1e-2          # answers swapped
    nan = Y.copy()
    nan[0, 0] = np.nan
    assert ref.rel_gap(nan, X) == float("inf")
    empty = Y.copy()
    empty[3, 1] = 1e-20                               # an empty row's answer
    assert ref.rel_gap(empty, X) == float("inf")
    assert ref.rel_gap(Y[:-1], X) == float("inf")     # wrong shape


def test_bfloat16_control_is_far_above_float32_rounding():
    rng = np.random.default_rng(2)
    _, *csr = _random_csr(rng, 300, 200, 0.1)
    f32 = CSRReference(*csr)
    bf16 = CSRReference(*csr, dtype=jnp.bfloat16)
    X = rng.standard_normal((200, 8)).astype(np.float32)
    assert f32.rel_gap(f32.apply(X), X) == 0.0
    assert f32.rel_gap(bf16.apply(X), X) > 1e-3
