import itertools

import numpy as np
import pytest

from bench.harness import load_plugin


def _stencil_loops(nx, ny, nz):
    """The HPCG reference's nested loops, one nonzero at a time."""
    rows, cols, vals = [], [], []
    for iz, iy, ix in itertools.product(range(nz), range(ny), range(nx)):
        row = ix + nx * (iy + ny * iz)
        for dz, dy, dx in itertools.product((-1, 0, 1), repeat=3):
            x, y, z = ix + dx, iy + dy, iz + dz
            if 0 <= x < nx and 0 <= y < ny and 0 <= z < nz:
                rows.append(row)
                cols.append(x + nx * (y + ny * z))
                vals.append(26.0 if (dx, dy, dz) == (0, 0, 0) else -1.0)
    return np.array(rows), np.array(cols), np.array(vals)


@pytest.mark.parametrize("grid", [(3, 3, 3), (5, 4, 3), (1, 2, 6), (7, 7, 7)])
def test_hpcg_stencil_matches_reference_loops(grid):
    gen = load_plugin("gen", "hpcg_stencil")
    nx, ny, nz = grid
    shape, rowptr, colidx, values = gen.generate(
        {"nx": nx, "ny": ny, "nz": nz}, seed=123)
    n = nx * ny * nz
    assert shape == (n, n)
    rows, cols, vals = _stencil_loops(nx, ny, nz)
    got_rows = np.repeat(np.arange(n), np.diff(rowptr))
    np.testing.assert_array_equal(got_rows, rows)
    np.testing.assert_array_equal(colidx, cols)
    np.testing.assert_array_equal(values, vals)
    assert colidx.dtype == np.int32 and values.dtype == np.float32


@pytest.mark.parametrize("n", [2, 3, 10, 104])
def test_hpcg_nnz_is_3n_minus_2_cubed(n):
    gen = load_plugin("gen", "hpcg_stencil")
    _, rowptr, _, values = gen.generate({"nx": n, "ny": n, "nz": n}, seed=0)
    assert int(rowptr[-1]) == values.shape[0] == (3 * n - 2) ** 3


def test_hpcg_is_symmetric_and_interior_rows_sum_to_zero():
    gen = load_plugin("gen", "hpcg_stencil")
    shape, rowptr, colidx, values = gen.generate(
        {"nx": 5, "ny": 5, "nz": 5}, seed=0)
    dense = np.zeros(shape)
    rows = np.repeat(np.arange(shape[0]), np.diff(rowptr))
    dense[rows, colidx] = values
    np.testing.assert_array_equal(dense, dense.T)
    centre = 2 + 5 * (2 + 5 * 2)
    assert dense[centre].sum() == 0 and dense[centre, centre] == 26


def test_hpcg_does_not_depend_on_the_seed():
    gen = load_plugin("gen", "hpcg_stencil")
    a = gen.generate({"nx": 4, "ny": 4, "nz": 4}, seed=1)
    b = gen.generate({"nx": 4, "ny": 4, "nz": 4}, seed=2 ** 40)
    for x, y in zip(a[1:], b[1:]):
        np.testing.assert_array_equal(x, y)


PRUNED = {"rows": 640, "cols": 256, "block": [1, 8], "tile_density": 0.025,
          "pattern_seed": 0}


def test_pruned_weight_pattern_is_fixed_and_values_follow_the_seed():
    gen = load_plugin("gen", "pruned_weight")
    s1, rp1, c1, v1 = gen.generate(PRUNED, seed=5)
    s2, rp2, c2, v2 = gen.generate(PRUNED, seed=2 ** 33 + 5)
    assert s1 == s2 == (640, 256)
    np.testing.assert_array_equal(rp1, rp2)
    np.testing.assert_array_equal(c1, c2)
    assert not np.array_equal(v1, v2)
    np.testing.assert_array_equal(v1, gen.generate(PRUNED, seed=5)[3])
    other = dict(PRUNED, pattern_seed=1)
    assert not np.array_equal(gen.generate(other, seed=5)[2], c1)


def test_pruned_weight_rows_sorted_no_duplicates_density():
    gen = load_plugin("gen", "pruned_weight")
    cfg = dict(PRUNED, rows=4000, cols=1024)
    shape, rowptr, colidx, values = gen.generate(cfg, seed=0)
    rows = np.repeat(np.arange(shape[0]), np.diff(rowptr))
    key = rows.astype(np.int64) * shape[1] + colidx
    assert np.all(np.diff(key) > 0)
    density = values.shape[0] / (shape[0] * shape[1])
    assert 0.045 < density < 0.055


def test_pruned_weight_multi_row_tiles_are_sorted():
    gen = load_plugin("gen", "pruned_weight")
    cfg = dict(PRUNED, block=[4, 8], rows=64, cols=64, tile_density=0.2)
    shape, rowptr, colidx, _ = gen.generate(cfg, seed=0)
    rows = np.repeat(np.arange(shape[0]), np.diff(rowptr))
    key = rows.astype(np.int64) * shape[1] + colidx
    assert np.all(np.diff(key) > 0)


def test_pruned_weight_pattern_is_the_library_generators():
    """The copy keeps the library's structure: with one seed for pattern
    and values, the pattern is ``matgen.pruned_weight``'s."""
    from repro.core import matgen
    gen = load_plugin("gen", "pruned_weight")
    lib = matgen.pruned_weight(640, 256, 0.025, (1, 8), seed=0)
    _, rowptr, colidx, _ = gen.generate(PRUNED, seed=0)
    np.testing.assert_array_equal(lib.rowptr, rowptr)
    np.testing.assert_array_equal(lib.colidx, colidx)
