"""Row-panel-tiled SPC5 layout + kernel tests (the VMEM-ceiling lift).

Matrices here are sized >= 8x the single-panel tile (pr) and >= 8x the x
window (xw), so the 2-D grid genuinely iterates over many panels and many
column windows -- the regime the whole-vector kernels cannot reach without
holding x and y fully VMEM-resident.
"""
import inspect

import jax.numpy as jnp
import numpy as np
import pytest

from repro._compat.hypothesis import given, settings, strategies as st

from bench.gen import hpcg_stencil
from repro.core import formats as F
from repro.core import matgen
from repro.core import plan as P
from repro.core import ref_spmv as R
from repro.core import reorder as RE
from repro.kernels import ops

PR, XW = 16, 16          # small tiles so 160x144 spans 10 panels, 9+ windows


def rand_dense(n, m, density, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return ((rng.random((n, m)) < density)
            * rng.standard_normal((n, m))).astype(dtype)


def make_panel_handle(n, m, density, rc, seed, pr=PR, cb=8, xw=XW):
    d = rand_dense(n, m, density, seed=seed)
    mat = F.csr_to_spc5(F.csr_from_dense(d), *rc)
    return d, ops.prepare(mat, layout="panels", pr=pr, cb=cb, xw=xw,
                          tune=False)


#: Chunk widths of the kernel tests: a narrow chunk, and the 128 lanes of
#: ``formats.PANEL_CB``, the default a chip runs.
CBS = (8, F.PANEL_CB)


@pytest.mark.parametrize("cb", CBS)
@pytest.mark.parametrize("rc", F.SUPPORTED_BLOCKS)
def test_panel_spmv_pallas_vs_oracle(rc, cb, rowwise_close):
    """nrows=160 >= 8*pr, ncols=144 >= 8*xw: multi-panel, multi-window.
    The kernel sums a chunk's blocks in another order than the oracle's
    scatter, so the two agree within a float32 reassociation per row."""
    d, h = make_panel_handle(160, 144, 0.12, rc, seed=sum(rc), cb=cb)
    assert h.npanels >= 8 and h.ncols >= 8 * h.xw and h.cb == cb
    x = np.random.default_rng(1).standard_normal(144).astype(np.float32)
    tgt = d.astype(np.float64) @ x.astype(np.float64)
    y_ref = ops.spmv(h, jnp.asarray(x), use_pallas=False)
    y_pal = ops.spmv(h, jnp.asarray(x), use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(y_ref), tgt, atol=2e-4)
    rowwise_close(y_pal, d, x, 1e-6, ref=y_ref)


@pytest.mark.parametrize("cb", CBS)
@pytest.mark.parametrize("rc", F.SUPPORTED_BLOCKS)
@pytest.mark.parametrize("nvec,nvt", [(8, 4)])
def test_panel_spmm_pallas_vs_oracle(rc, nvec, nvt, cb):
    d, h = make_panel_handle(160, 144, 0.15, rc, seed=7, cb=cb)
    assert h.cb == cb
    X = np.random.default_rng(2).standard_normal((144, nvec)).astype(np.float32)
    tgt = d.astype(np.float64) @ X.astype(np.float64)
    Y_ref = ops.spmm(h, jnp.asarray(X), use_pallas=False)
    Y_pal = ops.spmm(h, jnp.asarray(X), use_pallas=True, interpret=True,
                     nvt=nvt)
    np.testing.assert_allclose(np.asarray(Y_ref), tgt, atol=5e-4)
    np.testing.assert_allclose(np.asarray(Y_pal), np.asarray(Y_ref),
                               atol=2e-5, rtol=2e-5)


def dense_rows_band(n=64, m=160, pr=PR, seed=11):
    """A sparse band plus two dense rows in every panel of ``pr`` rows:
    local row 1 and local row pr - 1, which lies in the panel's last r rows
    for every r. Sorted by column, most of a chunk's blocks then sit on one
    or two rows."""
    rng = np.random.default_rng(seed)
    d = np.zeros((n, m), np.float32)
    for i in range(n):
        j = (i * m) // n
        d[i, j:j + 3] = rng.standard_normal(min(3, m - j))
    for p0 in range(0, n, pr):
        for i in (p0 + 1, p0 + pr - 1):
            d[i] = (rng.random(m) < 0.9) * rng.standard_normal(m)
    return d


@pytest.mark.parametrize("nvec", [1, 8], ids=["spmv", "spmm8"])
@pytest.mark.parametrize("rc", F.SUPPORTED_BLOCKS)
def test_panel_scatter_many_blocks_per_row(rc, nvec, rowwise_close):
    """The kernel's one-hot scatter sums every block of a chunk into its
    rows once: chunks whose blocks crowd onto one or two rows, blocks on a
    panel's last r rows, and short chunks whose padded blocks (mask 0, row
    0) must add nothing; SpMV and SpMM with kt = 8 vectors a step."""
    d = dense_rows_band()
    mat = F.csr_to_spc5(F.csr_from_dense(d), *rc)
    h = ops.prepare(mat, layout="panels", pr=PR, cb=8, xw=64, tune=False)
    mask, row = np.asarray(h.chunk_mask), np.asarray(h.chunk_row)
    real = mask != 0
    nreal = real.sum(axis=-1)
    assert ((nreal > 0) & (nreal < h.cb)).any()         # padded blocks
    assert (row[real] == h.pr - h.r).any()              # last r rows
    full = real.reshape(-1, h.cb).all(axis=1)
    assert any(len(np.unique(rw)) <= 2                  # full chunk, <= 2 rows
               for rw in row.reshape(-1, h.cb)[full])
    rng = np.random.default_rng(sum(rc) + nvec)
    if nvec == 1:
        x = rng.standard_normal(d.shape[1]).astype(np.float32)
        y = ops.spmv(h, jnp.asarray(x), use_pallas=True, interpret=True)
    else:
        x = rng.standard_normal((d.shape[1], nvec)).astype(np.float32)
        y = ops.spmm(h, jnp.asarray(x), use_pallas=True, interpret=True,
                     nvt=nvec)
    rowwise_close(y, d, x, 1e-6)


# The default chunk width on the HPCG 27-point stencil: a panel of 512
# rows holds a few blocks per column, so cb closes every chunk, never xw,
# and a chunk fills the panel kernel's 128 lanes.

STENCIL_BLOCKS = [(1, 8), (2, 4)]
STENCIL_IDS = [f"{r}x{c}" for r, c in STENCIL_BLOCKS]


def stencil_spc5(rc, nx=16, ny=16, nz=6):
    """The HPCG stencil on nx x ny x nz (three 512-row panels, the middle
    one interior) as CSR and as beta(rc)."""
    shape, rowptr, colidx, values = hpcg_stencil.generate(
        dict(nx=nx, ny=ny, nz=nz), 0)
    csr = F.CSRMatrix(tuple(shape), rowptr, colidx, values)
    return csr, F.csr_to_spc5(csr, *rc)


@pytest.mark.parametrize("rc", STENCIL_BLOCKS, ids=STENCIL_IDS)
def test_stencil_panels_fill_full_lane_chunks(rc):
    """At pr = 512 and the default cb, each panel of nb blocks has
    ceil(nb / 128) chunks, and ``count_panel_chunks`` (what reorder and
    structure score with) predicts the chunks ``to_panels`` builds."""
    _, mat = stencil_spc5(rc)
    pan = F.to_panels(mat, pr=512)
    assert pan.cb == F.PANEL_CB == 128 and pan.npanels == 3
    bounds = np.minimum(np.arange(pan.npanels + 1) * (pan.pr // mat.r),
                        mat.block_rowptr.shape[0] - 1)
    nb = np.diff(mat.block_rowptr[bounds])
    built = (pan.chunk_mask != 0).any(axis=-1).sum(axis=-1)
    np.testing.assert_array_equal(built, -(-nb // F.PANEL_CB))
    np.testing.assert_array_equal(F.count_panel_chunks(mat, pr=512), built)
    assert pan.nchunks == built.max()


@pytest.mark.parametrize("nvec", [1, 8], ids=["spmv", "spmm8"])
@pytest.mark.parametrize("rc", STENCIL_BLOCKS, ids=STENCIL_IDS)
def test_panel_kernel_full_lane_chunks_vs_reference(rc, nvec,
                                                    rowwise_close):
    """The interpret-mode panel kernel at the default cb = 128, with full
    128-block chunks, against the f32 jnp reference: SpMV, and SpMM with
    kt = 8 vectors a grid step."""
    csr, mat = stencil_spc5(rc)
    h = ops.prepare(mat, layout="panels", tune=False)
    assert (h.pr, h.cb) == (512, 128)
    assert (np.asarray(h.chunk_mask) != 0).all(axis=-1).any()
    rng = np.random.default_rng(sum(rc) + nvec)
    d = csr.to_dense()
    if nvec == 1:
        x = rng.standard_normal(d.shape[1]).astype(np.float32)
        y = ops.spmv(h, jnp.asarray(x), use_pallas=True, interpret=True)
        ref = ops.spmv(h, jnp.asarray(x), use_pallas=False)
    else:
        x = rng.standard_normal((d.shape[1], nvec)).astype(np.float32)
        y = ops.spmm(h, jnp.asarray(x), use_pallas=True, interpret=True,
                     nvt=nvec)
        ref = ops.spmm(h, jnp.asarray(x), use_pallas=False)
    rowwise_close(ref, d, x, 1e-6)
    rowwise_close(y, d, x, 1e-6, ref=ref)


def test_panels_defaults_read_one_constant(monkeypatch):
    """Every default of the panels chunk width is ``formats.PANEL_CB``,
    the kernel's lane width: the signatures' defaults, and the cb of the
    plans ``ops.prepare`` and ``distributed.shard_matrix`` build at their
    defaults on a TPU, as the benchmark's cells call them."""
    import inspect

    from repro.core import distributed as D
    from repro.core import plan as P
    from repro.core import reorder as RE
    from repro.core import structure as ST
    from repro.kernels import spc5_spmv
    assert F.PANEL_CB == spc5_spmv._LANES
    for fn in (F.to_panels, F.count_panel_chunks, ST.profile, RE.reorder,
               ops.prepare_panels, D.shard_matrix_panels):
        assert inspect.signature(fn).parameters["cb"].default == F.PANEL_CB, \
            fn.__qualname__
    assert P.get_layout(P.LAYOUT_PANELS).default_cb == F.PANEL_CB
    mat = F.csr_to_spc5(matgen.banded(1024, 4, 1.0, seed=5), 1, 8)
    assert ops.prepare(mat, layout="panels", tune=False).cb == F.PANEL_CB
    assert D.shard_matrix(mat, 4, layout="panels").cb == F.PANEL_CB
    monkeypatch.setattr(P, "_on_tpu", lambda: True)
    plan = ops.prepare(mat, vdtype="f32")
    assert (plan.layout, plan.cb) == (P.LAYOUT_PANELS, F.PANEL_CB)
    for sh in (D.shard_matrix(mat, 4, vdtype="f32"), P.shard_plan(mat, 4)):
        assert (sh.layout, sh.cb) == (P.LAYOUT_PANELS, F.PANEL_CB)


def test_panel_layout_invariants():
    csr = matgen.banded(400, 7, 0.8, seed=6)
    mat = F.csr_to_spc5(csr, 2, 8)
    pan = F.to_panels(mat, pr=32, cb=8, xw=32)
    # panels are r-aligned and chunk_row panel-relative
    assert pan.pr % pan.r == 0
    assert pan.chunk_row.min() >= 0
    assert pan.chunk_row.max() <= pan.pr - pan.r
    # window-relative columns stay inside the x window
    real = pan.chunk_mask != 0
    assert pan.chunk_col[real].min() >= 0
    assert pan.chunk_col[real].max() <= pan.xw - pan.c
    # windows are aligned and in-bounds after padding
    assert np.all(pan.chunk_xbase % 8 == 0)
    assert int(pan.chunk_xbase.max()) + pan.xw <= pan.ncols_pad
    # every nonzero survives (padding chunks are mask==0)
    assert int(F.popcount_u32(pan.chunk_mask.reshape(-1)).sum()) == mat.nnz
    # values stay packed: only chunk-alignment padding
    nch_real = int((pan.chunk_mask.any(axis=-1)).sum())
    assert pan.values.shape[0] <= mat.nnz + 8 * nch_real + pan.vmax + 8


def test_prepare_auto_layout_selection():
    small = F.csr_to_spc5(F.csr_from_dense(rand_dense(48, 40, 0.3, 1)), 2, 4)
    h = ops.prepare(small)
    assert h.layout == ops.LAYOUT_WHOLE
    # force a tiny budget so a modest matrix exceeds the whole-vector ceiling
    assert not ops.fits_whole_vector(10**6, 10**6)
    big = F.csr_to_spc5(F.csr_from_dense(rand_dense(300, 280, 0.05, 2)), 2, 4)
    hp = ops.prepare(big, layout="panels", pr=32, xw=64)
    assert hp.layout == ops.LAYOUT_PANELS
    x = np.random.default_rng(3).standard_normal(280).astype(np.float32)
    y_whole = ops.spmv(ops.prepare(big, layout="whole_vector"),
                       jnp.asarray(x), use_pallas=False)
    y_pan = ops.spmv(hp, jnp.asarray(x), use_pallas=False)
    np.testing.assert_allclose(np.asarray(y_pan), np.asarray(y_whole),
                               atol=1e-5)


def test_panel_handle_pytree_roundtrip():
    import jax
    _, h = make_panel_handle(96, 96, 0.2, (2, 8), seed=9)
    flat, tdef = jax.tree.flatten(h)
    h2 = jax.tree.unflatten(tdef, flat)
    x = jnp.ones((96,), jnp.float32)
    np.testing.assert_allclose(np.asarray(ops.spmv(h2, x, use_pallas=False)),
                               np.asarray(ops.spmv(h, x, use_pallas=False)))


def test_sparse_linear_panel_layout():
    from repro.core.sparse_linear import SparseLinear, prune_by_magnitude
    rng = np.random.default_rng(4)
    w = rng.standard_normal((160, 144)).astype(np.float32)
    sl = SparseLinear.from_dense(w, density=0.2, layout="panels", pr=16,
                                 xw=32)
    assert sl.handle.layout == ops.LAYOUT_PANELS
    wp = prune_by_magnitude(w, 0.2)
    x = rng.standard_normal((3, 144)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(sl(jnp.asarray(x))), x @ wp.T,
                               atol=1e-4)
    x1 = rng.standard_normal((1, 144)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(sl(jnp.asarray(x1))), x1 @ wp.T,
                               atol=1e-4)


def test_panel_empty_and_edge():
    d = np.zeros((64, 64), np.float32)
    mat = F.csr_to_spc5(F.csr_from_dense(d), 2, 4)
    h = ops.prepare(mat, layout="panels", pr=8, cb=4, xw=16,
                    tune=False)
    y = ops.spmv(h, jnp.ones(64), use_pallas=False)
    np.testing.assert_allclose(np.asarray(y), 0.0)
    d[63, 63] = 3.0
    mat = F.csr_to_spc5(F.csr_from_dense(d), 4, 8)
    h = ops.prepare(mat, layout="panels", pr=8, cb=4, xw=16,
                    tune=False)
    y = ops.spmv(h, jnp.ones(64), use_pallas=True, interpret=True)
    assert np.asarray(y)[63] == pytest.approx(3.0)


@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(24, 160),
    m=st.integers(24, 160),
    density=st.floats(0.02, 0.5),
    rc=st.sampled_from(list(F.SUPPORTED_BLOCKS)),
    pr=st.sampled_from([8, 16, 48]),
    xw=st.sampled_from([16, 32, 64]),
    seed=st.integers(0, 2**20),
)
def test_property_panels_match_whole(n, m, density, rc, pr, xw, seed):
    d = rand_dense(n, m, density, seed=seed)
    mat = F.csr_to_spc5(F.csr_from_dense(d), *rc)
    hp = ops.prepare(mat, layout="panels", pr=pr, cb=8, xw=xw,
                     tune=False)
    hw = ops.prepare(mat, layout="whole_vector")
    x = np.random.default_rng(seed + 1).standard_normal(m).astype(np.float32)
    y_pan = np.asarray(ops.spmv(hp, jnp.asarray(x), use_pallas=False))
    y_whole = np.asarray(ops.spmv(hw, jnp.asarray(x), use_pallas=False))
    np.testing.assert_allclose(y_pan, y_whole, atol=1e-5)
    np.testing.assert_allclose(
        y_pan, d.astype(np.float64) @ x.astype(np.float64), atol=5e-4)


# ----------------------------------------------------------------------------
# The mask kernels against the jnp decode and the dense product, at the
# chip's chunk width, over value dtypes, reorderings and layouts
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["whole_vector", "panels", "test"])
@pytest.mark.parametrize("reorder", [None, "rcm", "sigma"])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_mask_kernels_full_lane_parity(layout, reorder, dtype,
                                       rowwise_close):
    """SpMV and SpMM at cb = 128 through the interpret-mode Pallas kernels,
    the jnp decode and the float64 dense product agree: the jnp decode
    within float32 rounding of the dense product, the kernels within a
    reassociation of each row's adds of the decode. The test layout runs
    its multi-block part as panels, so its panel-bucketed tail kernel
    runs too."""
    csr = matgen.scrambled_banded(192, 5, 1.0, seed=7)
    d = csr.to_dense()
    mat = F.csr_to_spc5(csr, 2, 4)
    kw = dict(pr=16, xw=32, cb=F.PANEL_CB, dtype=dtype, reorder=reorder,
              tune=False)
    if layout == "test":
        kw["multi_layout"] = "panels"
    h = ops.prepare(mat, layout=layout, **kw)
    if layout == "test":
        assert h.single_values.size and h.multi.cb == F.PANEL_CB
    else:
        assert h.cb == F.PANEL_CB
    if reorder != "sigma":              # sigma may decline; rcm applies
        assert h.is_reordered == (reorder == "rcm")
    rng = np.random.default_rng(1)
    x = rng.standard_normal(192).astype(np.float32)
    X = rng.standard_normal((192, 4)).astype(np.float32)
    for v, op in ((x, ops.spmv), (X, ops.spmm)):
        ref = op(h, jnp.asarray(v), use_pallas=False)
        rowwise_close(ref, d, v, 1e-6)
        y = op(h, jnp.asarray(v), use_pallas=True, interpret=True)
        rowwise_close(y, d, v, 1e-6, ref=ref)


# ----------------------------------------------------------------------------
# Reordered panel plans: row fusion and the x gather
# ----------------------------------------------------------------------------

def test_panel_row_fusion_pure_panel_permutation():
    """A pure panel permutation folds into the stacked panel axis
    (rows_fused); results match the dense product."""
    nrows = 64
    pr = 16
    csr = matgen.banded(nrows, 5, 1.0, seed=23)
    mat = F.csr_to_spc5(csr, 2, 4)
    # permuted panel order (2, 0, 3, 1): an interval-contiguous, pr-aligned
    # row permutation -- the panel fusion condition
    order = np.array([2, 0, 3, 1])
    row_perm = (order[:, None] * pr + np.arange(pr)[None, :]).reshape(-1)
    reo = RE.Reordering(row_perm.astype(np.int64),
                        np.arange(nrows, dtype=np.int64), strategy="manual")
    assert P._panel_row_permutation(reo, pr, nrows, 4) is not None
    x = jnp.asarray(np.random.default_rng(8).standard_normal(nrows),
                    jnp.float32)
    d = csr.to_dense()
    h = P.make_plan(mat, layout="panels", pr=pr, xw=32, cb=8,
                    dtype=np.float32, reorder=reo)
    assert h.rows_fused and h.row_iperm is None
    np.testing.assert_allclose(
        np.asarray(ops.spmv(h, x, use_pallas=False)),
        d.astype(np.float64) @ np.asarray(x, np.float64), atol=2e-3)
    # a non-aligned permutation must NOT fuse
    bad = RE.Reordering(np.roll(np.arange(nrows), 3).astype(np.int64),
                        np.arange(nrows, dtype=np.int64), strategy="manual")
    assert P._panel_row_permutation(bad, pr, nrows, 4) is None


def test_panel_lowering_has_no_standalone_x_gather():
    """Dispatch scan for the fusion contract: the panel hooks hand the
    column map to the reference decode, and only the kernel -- which DMAs
    x windows and so cannot route them through a map -- gets x permuted
    once, up front; no ad-hoc ``jnp.take(x``."""
    for fn in (P._lower_spmv_panels, P._lower_spmm_panels):
        src = inspect.getsource(fn)
        assert src.count("_gathered_x(") == 1, fn.__name__
        assert "plan.col_perm" in src, fn.__name__
        assert "jnp.take(x" not in src, fn.__name__
    # the reference panel decode routes the gather through cmap instead of
    # consuming a pre-permuted x
    for fn in (R.spmv_panels, R.spmm_panels):
        assert "cmap" in inspect.signature(fn).parameters, fn.__name__


def test_panel_fused_x_vmem_guard(monkeypatch):
    """The panel kernel never holds x VMEM-resident: its footprint per
    grid step does not grow with ncols, and a reordered plan's Pallas
    result does not depend on the whole-vector VMEM budget."""
    from repro.kernels import spc5_spmm, spc5_spmv
    geom = dict(r=1, c=8, pr=16, xw=32, cb=8, vmax=64, nrows=160,
                nchunks=4)
    for contracts in (spc5_spmv.SPMV_VMEM_CONTRACTS,
                      spc5_spmm.SPMM_VMEM_CONTRACTS):
        fn = contracts["panels"]
        assert (fn(dict(geom, ncols=160), 4)
                == fn(dict(geom, ncols=10**8), 4))
    csr = matgen.scrambled_banded(160, 4, 1.0, seed=43)
    mat = F.csr_to_spc5(csr, 1, 8)
    h = P.make_plan(mat, layout="panels", pr=16, xw=32, cb=8,
                    dtype=np.float32, reorder="rcm")
    assert h.col_perm is not None
    x = jnp.asarray(np.random.default_rng(10).standard_normal(160),
                    jnp.float32)
    y = np.asarray(ops.spmv(h, x, use_pallas=True, interpret=True))
    monkeypatch.setattr(P, "VMEM_WHOLE_VECTOR_BUDGET", 64)
    y_small = np.asarray(ops.spmv(h, x, use_pallas=True, interpret=True))
    np.testing.assert_array_equal(y_small, y)
    np.testing.assert_allclose(
        y, csr.to_dense().astype(np.float64) @ np.asarray(x, np.float64),
        atol=2e-3)


def test_panel_fused_cmap_matches_materialised_gather():
    """The fused panel path == the old materialised-gather computation,
    bitwise (reference) and numerically (Pallas interpret)."""
    csr = matgen.scrambled_banded(160, 4, 1.0, seed=41)
    mat = F.csr_to_spc5(csr, 1, 8)
    reo = RE.reorder(mat, "rcm", r=1, c=8, pr=16, xw=32, cb=8)
    assert not reo.is_identity and not reo.identity_cols
    h = P.make_plan(mat, layout="panels", pr=16, xw=32, cb=8,
                    dtype=np.float32, reorder=reo)
    assert h.col_perm is not None
    x = jnp.asarray(np.random.default_rng(9).standard_normal(160),
                    jnp.float32)
    # old path: materialise permuted x, no cmap
    pm = reo.permute_spc5(mat)
    pan = F.to_panels(pm, pr=16, cb=8, xw=32)
    dev = R.device_put_panels(pan, dtype=np.float32)
    xg = jnp.take(x, jnp.asarray(reo.col_perm.astype(np.int32)), axis=0)
    y_old = R.spmv_panels(dev, xg, r=1, c=8, pr=pan.pr, nrows=160,
                          ncols_pad=pan.ncols_pad)
    if not reo.identity_rows:
        y_old = jnp.take(y_old,
                         jnp.asarray(reo.row_iperm.astype(np.int32)), axis=0)
    y_ref = np.asarray(ops.spmv(h, x, use_pallas=False))
    np.testing.assert_array_equal(y_ref, np.asarray(y_old))
    y_pal = np.asarray(ops.spmv(h, x, use_pallas=True, interpret=True))
    np.testing.assert_allclose(y_pal, np.asarray(y_old), atol=1e-5)


# ----------------------------------------------------------------------------
# Chunk groups: a grid step walks G chunks of one panel through a two-slot
# DMA ring; G is a panel's chunks where the step fits the VMEM ceiling
# ----------------------------------------------------------------------------

#: How the chunks a grid step walks are capped: not at all (a whole
#: panel), at a group that does not divide a panel's chunks (the last group
#: padded with mask-0 chunks), and at one chunk a step.
GROUPS = ("whole", "padded", "one")


def _padded_cap(nchunks):
    """The first cap whose balanced group leaves the last one short."""
    for most in range(2, nchunks):
        group = -(-nchunks // -(-nchunks // most))
        if nchunks % group:
            return most
    raise AssertionError(f"no padded group for {nchunks} chunks")


@pytest.fixture
def cap_group(monkeypatch):
    """``cap(plan, kt, how)`` shrinks the VMEM ceiling until the panel
    kernel's group for ``plan`` is what ``how`` asks for, and returns that
    group. Traced kernels are dropped before and after, since the group is
    not among the jitted entry points' static arguments."""
    import jax
    from repro.kernels import spc5_spmv as KV

    def cap(h, kt, how):
        geom = dict(h.meta)
        itemsize = h.values.dtype.itemsize
        if how != "whole":
            most = 1 if how == "one" else _padded_cap(h.nchunks)
            monkeypatch.setattr(KV, "VMEM_LIMIT_BYTES", KV._panel_step_bytes(
                geom, itemsize, kt, most))
        return KV.panel_group(geom, itemsize, kt)

    jax.clear_caches()
    yield cap
    jax.clear_caches()


def _run_both(h, d, nvec, seed):
    rng = np.random.default_rng(seed)
    if nvec == 1:
        x = rng.standard_normal(d.shape[1]).astype(np.float32)
        y = ops.spmv(h, jnp.asarray(x), use_pallas=True, interpret=True)
        ref = ops.spmv(h, jnp.asarray(x), use_pallas=False)
    else:
        x = rng.standard_normal((d.shape[1], nvec)).astype(np.float32)
        y = ops.spmm(h, jnp.asarray(x), use_pallas=True, interpret=True,
                     nvt=nvec)
        ref = ops.spmm(h, jnp.asarray(x), use_pallas=False)
    return x, y, ref


@pytest.mark.parametrize("how", GROUPS)
@pytest.mark.parametrize("rc", [(1, 8), (2, 4)], ids=["1x8", "2x4"])
@pytest.mark.parametrize("nvec", [1, 8], ids=["spmv", "spmm8"])
def test_panel_chunk_groups_vs_reference(nvec, rc, how, cap_group,
                                         rowwise_close):
    """The interpret-mode kernel with a whole panel, a padded group and
    one chunk a grid step, against the jnp decode: SpMV and SpMM with
    kt = 8 vectors a step."""
    d, h = make_panel_handle(160, 144, 0.15, rc, seed=3 + sum(rc))
    group = cap_group(h, nvec, how)
    assert h.nchunks >= 6
    assert {"whole": group == h.nchunks, "padded": h.nchunks % group != 0,
            "one": group == 1}[how]
    x, y, ref = _run_both(h, d, nvec, seed=nvec + sum(rc))
    rowwise_close(ref, d, x, 1e-6)
    rowwise_close(y, d, x, 1e-6, ref=ref)


@pytest.mark.parametrize("vdtype", ["bf16", "int8"])
@pytest.mark.parametrize("nvec", [1, 8], ids=["spmv", "spmm8"])
def test_panel_chunk_groups_quantised_values(nvec, vdtype, cap_group,
                                             rowwise_close):
    """bf16 and int8 stores through a padded group: the int8 store's
    per-chunk scales are read at the chunk's place in the panel, so a
    scale read from another chunk would show as a wrong row."""
    d = rand_dense(160, 144, 0.15, seed=5)
    mat = F.csr_to_spc5(F.csr_from_dense(d), 1, 8)
    h = ops.prepare(mat, layout="panels", pr=PR, cb=8, xw=XW, tune=False,
                    vdtype=vdtype)
    assert h.values.dtype == {"bf16": jnp.bfloat16, "int8": jnp.int8}[vdtype]
    if vdtype == "int8":
        scales = np.asarray(h.value_scale)
        assert len(np.unique(scales[scales > 0])) > h.nchunks
    group = cap_group(h, nvec, "padded")
    assert h.nchunks % group
    x, y, ref = _run_both(h, d, nvec, seed=11 + nvec)
    rowwise_close(y, d, x, 1e-6, ref=ref)


@pytest.mark.parametrize("how", GROUPS)
@pytest.mark.parametrize("nvec", [1, 8], ids=["spmv", "spmm8"])
def test_panel_chunk_groups_all_padding_panel(nvec, how, cap_group,
                                              rowwise_close):
    """A panel with no blocks walks chunks that are all padding (mask 0)
    and writes a zero tile; its neighbours keep their rows."""
    d = rand_dense(160, 144, 0.15, seed=9)
    d[2 * PR:3 * PR] = 0.0
    mat = F.csr_to_spc5(F.csr_from_dense(d), 1, 8)
    h = ops.prepare(mat, layout="panels", pr=PR, cb=8, xw=XW, tune=False)
    assert (np.asarray(h.chunk_mask)[2] == 0).all()
    cap_group(h, nvec, how)
    x, y, ref = _run_both(h, d, nvec, seed=13 + nvec)
    assert not np.asarray(y)[2 * PR:3 * PR].any()
    rowwise_close(y, d, x, 1e-6, ref=ref)


def test_panel_group_is_a_whole_panel_at_hpcg104():
    """At ``hpcg104``'s geometry (36 chunks of 128 blocks, pr = xw = 512)
    a grid step walks a whole panel: 2,197 steps a SpMV, for one vector
    and for a tile of 8."""
    from repro.kernels import spc5_spmv as KV
    geom = dict(r=1, c=8, pr=512, cb=F.PANEL_CB, xw=512, vmax=384,
                npanels=2197, nchunks=36)
    for nvec in (1, 8):
        assert KV.panel_group(geom, 4, nvec) == 36
        assert KV.panel_grid(geom, 4, nvec, nvec) == ((1, 2197, 1), 36)
        assert KV._vmem_panels_mask(geom, 4, nvec) <= KV.VMEM_LIMIT_BYTES


@pytest.mark.parametrize("itemsize", [4, 2, 1])
@pytest.mark.parametrize("nvec", [1, 8])
def test_panel_group_fits_the_vmem_ceiling(nvec, itemsize):
    """A panel of 5,000 chunks, whose whole (G, 4, cb) metadata block
    (double-buffered, 41 MB) would not fit, walks them in the fewest
    balanced groups that do: the contract stays under the ceiling, one
    group more would not fit, and the last group pads least."""
    from repro.kernels import spc5_spmv as KV
    nchunks = 5000
    geom = dict(r=1, c=8, pr=512, cb=F.PANEL_CB, xw=512, vmax=384,
                npanels=10, nchunks=nchunks)
    assert 2 * nchunks * 4 * 4 * F.PANEL_CB > KV.VMEM_LIMIT_BYTES
    group = KV.panel_group(geom, itemsize, nvec)
    ngroups = -(-nchunks // group)
    assert 1 < group < nchunks
    assert KV._vmem_panels_mask(geom, itemsize, nvec) <= KV.VMEM_LIMIT_BYTES
    assert KV._panel_step_bytes(geom, itemsize, nvec, -(-nchunks // (
        ngroups - 1))) > KV.VMEM_LIMIT_BYTES
    assert ngroups * group - nchunks < ngroups
    assert KV.panel_grid(geom, itemsize, nvec, nvec)[0] == (1, 10, ngroups)
