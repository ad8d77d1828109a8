"""Reordering subsystem tests (repro.core.reorder / structure + ops wiring).

The core contract under test: for every strategy and every layout,
``ops.prepare(reorder=...)`` returns a plan whose spmv/spmm equals the
dense product ON THE ORIGINAL MATRIX -- the permutation must be invisible
to callers (x in, y out, both in original index order), whether the
gather/scatter runs as explicit jnp.take or fused into the kernels' index
arrays (whole-vector col_map / interval-contiguous chunk_row).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro._compat.hypothesis import given, settings, strategies as st

from repro.core import formats as F
from repro.core import matgen
from repro.core import reorder as RE
from repro.core import selector as S
from repro.core import structure as ST
from repro.kernels import ops

GEOM = dict(pr=32, xw=64, cb=8)          # window-bound at test sizes


def scrambled(dim=240, band=6, seed=5):
    return matgen.scrambled_banded(dim, band, 1.0, seed=seed)


def perm_is_valid(perm, n):
    return sorted(perm.tolist()) == list(range(n))


# ----------------------------------------------------------------------------
# Reordering object + strategies
# ----------------------------------------------------------------------------

def test_permutation_algebra():
    csr = scrambled(120)
    reo = RE.reorder(csr, "rcm", r=2, c=4, **GEOM)
    assert perm_is_valid(reo.row_perm, 120)
    assert perm_is_valid(reo.col_perm, 120)
    d = csr.to_dense()
    x = np.random.default_rng(0).standard_normal(120)
    # A' @ x[col_perm] == (A @ x)[row_perm]; unpermute_y undoes it
    dp = reo.permute_csr(csr).to_dense()
    np.testing.assert_allclose(dp @ reo.apply_x(x), (d @ x)[reo.row_perm])
    np.testing.assert_allclose(reo.unpermute_y((d @ x)[reo.row_perm]), d @ x)
    # permute_spc5 rebuilds blocks on the permuted pattern
    mat = F.csr_to_spc5(csr, 2, 4)
    np.testing.assert_allclose(reo.permute_spc5(mat).to_dense(), dp)


@pytest.mark.parametrize("strategy", RE.STRATEGIES)
def test_strategy_permutations_valid_and_deterministic(strategy):
    csr = scrambled(200)
    a = RE.reorder(csr, strategy, r=1, c=8, **GEOM)
    b = RE.reorder(csr, strategy, r=1, c=8, **GEOM)
    assert np.array_equal(a.row_perm, b.row_perm)
    assert np.array_equal(a.col_perm, b.col_perm)
    assert a.strategy == b.strategy and a.stats == b.stats
    assert perm_is_valid(a.row_perm, 200) and perm_is_valid(a.col_perm, 200)
    assert {"bw_pre", "bw_post", "nchunks_pre", "nchunks_post",
            "applied"} <= set(a.stats)


def test_sigma_windows_bound_row_travel():
    """sigma-sorted rows never leave their sigma-window (the SELL-C-sigma
    locality property), and sorting is by descending nnz within windows."""
    csr = matgen.uniform_random(96, 4, seed=3)
    reo = RE.sigma_window_rows(csr, sigma=17, pr=8)      # rounds up to 24
    sigma = int(reo.stats["sigma"])
    assert sigma == 24
    nnz = np.diff(csr.rowptr)
    for w0 in range(0, 96, sigma):
        win = reo.row_perm[w0:w0 + sigma]
        assert win.min() >= w0 and win.max() < w0 + sigma
        lens = nnz[win]
        assert np.all(lens[:-1] >= lens[1:])             # descending


def test_rcm_recovers_scrambled_band():
    csr = scrambled(300, band=5, seed=9)
    reo = RE.reorder(csr, "rcm", r=1, c=8, **GEOM)
    assert reo.stats["applied"] == 1.0
    assert reo.stats["bw_post"] < reo.stats["bw_pre"] / 5
    assert reo.stats["nchunks_post"] < reo.stats["nchunks_pre"]
    # interval-level permutation stays fusable for r > 1 blocks
    reo2 = RE.rcm_blocks(csr, r=2, c=4)
    assert reo2.rows_interval_contiguous(2)


def test_reorder_declines_without_improvement():
    """On an already-banded matrix RCM/colwindow cannot improve the chunk
    count; the driver must return the identity with the evidence."""
    csr = matgen.banded(256, 4, 1.0, seed=1)
    reo = RE.reorder(csr, "rcm", r=1, c=8, **GEOM)
    if reo.stats["declined"]:
        assert reo.is_identity
        assert reo.stats["nchunks_post"] == reo.stats["nchunks_pre"]
    else:       # if it applied, it must have strictly improved
        assert (reo.stats["nchunks_post"], reo.stats["bw_post"]) \
            < (reo.stats["nchunks_pre"], reo.stats["bw_pre"])
    bad = RE.reorder(csr, "auto", r=1, c=8, **GEOM)
    assert bad.stats["nchunks_post"] <= bad.stats["nchunks_pre"]


def test_reorder_empty_and_tiny():
    empty = F.csr_from_dense(np.zeros((8, 8), np.float32))
    reo = RE.reorder(empty, "auto", **GEOM)
    assert reo.is_identity and reo.nrows == 8
    one = F.csr_from_dense(np.eye(1, dtype=np.float32))
    for strat in ("sigma", "rcm", "colwindow", "auto", "none"):
        r1 = RE.reorder(one, strat, **GEOM)
        assert perm_is_valid(r1.row_perm, 1) and perm_is_valid(r1.col_perm, 1)
    # 1-row matrices and unknown strategies
    with pytest.raises(ValueError):
        RE.reorder(one, "definitely-not-a-strategy")


# ----------------------------------------------------------------------------
# structure.profile
# ----------------------------------------------------------------------------

def test_profile_reports_structure():
    csr = matgen.banded(128, 4, 1.0, seed=2)
    prof = ST.profile(csr, r=1, c=8, pr=16, xw=32, cb=8)
    assert prof.nnz == csr.nnz and prof.nrows == 128
    assert prof.bandwidth_mean < 4 and prof.diag_frac > 0.1
    assert prof.panel_chunks.shape == (8,)
    assert prof.nchunks_total == int(prof.panel_chunks.sum())
    # chunk counts match what to_panels actually builds
    mat = F.csr_to_spc5(csr, 1, 8)
    pan = F.to_panels(mat, pr=16, cb=8, xw=32)
    real = (pan.chunk_mask.any(axis=-1)).sum(axis=1)
    np.testing.assert_array_equal(prof.panel_chunks, real)
    # features() feeds the selector
    feats = prof.features("1x8")
    assert isinstance(feats, S.MatrixFeatures)
    assert feats.nnz == csr.nnz and feats.avg > 1.0
    assert "nchunks" in prof.summary()


def test_profile_diag_dominance():
    d = np.diag(np.full(16, 10.0)).astype(np.float32)
    d[3, 7] = 1.0
    prof = ST.profile(F.csr_from_dense(d), pr=8, xw=16, cb=4)
    assert prof.diag_frac == 1.0 and prof.diag_dominance == 1.0


# ----------------------------------------------------------------------------
# ops integration: the permutation must be invisible to callers
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", ("sigma", "rcm", "colwindow", "auto"))
@pytest.mark.parametrize("layout", ("whole", "panels"))
def test_roundtrip_all_strategies_and_layouts(strategy, layout):
    csr = scrambled(160, band=6, seed=11)
    d = csr.to_dense()
    x = np.random.default_rng(1).standard_normal(160).astype(np.float32)
    tgt = d.astype(np.float64) @ x.astype(np.float64)
    for rc in ((1, 8), (2, 4), (4, 4)):
        mat = F.csr_to_spc5(csr, *rc)
        h = ops.prepare(mat, layout=layout, dtype=np.float32,
                        reorder=strategy, **GEOM)
        y = np.asarray(ops.spmv(h, jnp.asarray(x), use_pallas=False))
        np.testing.assert_allclose(y, tgt, atol=2e-3)
        X = np.random.default_rng(2).standard_normal((160, 4)).astype(np.float32)
        Y = np.asarray(ops.spmm(h, jnp.asarray(X), use_pallas=False))
        np.testing.assert_allclose(Y, d @ X, atol=5e-3)


def test_fused_pallas_paths_match_oracle():
    """Whole-vector Pallas kernels with fused col_map + fused chunk_row
    scatter vs the (already-verified) jnp path and the dense oracle."""
    csr = scrambled(160, band=6, seed=13)
    d = csr.to_dense()
    x = np.random.default_rng(3).standard_normal(160).astype(np.float32)
    tgt = d.astype(np.float64) @ x.astype(np.float64)
    mat = F.csr_to_spc5(csr, 2, 4)
    h = ops.prepare(mat, layout="whole_vector", dtype=np.float32,
                    reorder="rcm")
    assert h.is_reordered
    assert h.rows_fused and h.row_iperm is None     # scatter fused away
    assert h.col_perm is not None
    y = np.asarray(ops.spmv(h, jnp.asarray(x), use_pallas=True,
                            interpret=True))
    np.testing.assert_allclose(y, tgt, atol=2e-3)
    X = np.random.default_rng(4).standard_normal((160, 4)).astype(np.float32)
    Y = np.asarray(ops.spmm(h, jnp.asarray(X), use_pallas=True,
                            interpret=True, nvt=4))
    np.testing.assert_allclose(Y, d @ X, atol=5e-3)
    # panel layout: fused col_map decode (PR 5; no materialised x gather)
    hp = ops.prepare(mat, layout="panels", dtype=np.float32, reorder="rcm",
                     **GEOM)
    if hp.is_reordered:
        yp = np.asarray(ops.spmv(hp, jnp.asarray(x), use_pallas=True,
                                 interpret=True))
        np.testing.assert_allclose(yp, tgt, atol=2e-3)


def test_reordered_handle_pytree_and_stats():
    mat = F.csr_to_spc5(scrambled(96, band=4, seed=7), 1, 8)
    h = ops.prepare(mat, layout="whole_vector", dtype=np.float32,
                    reorder="rcm")
    assert h.is_reordered
    assert h.shape == (96, 96) and h.nnz == mat.nnz
    assert h.stats["applied"] == 1.0
    flat, tdef = jax.tree.flatten(h)
    h2 = jax.tree.unflatten(tdef, flat)
    x = jnp.ones((96,), jnp.float32)
    np.testing.assert_allclose(np.asarray(ops.spmv(h2, x, use_pallas=False)),
                               np.asarray(ops.spmv(h, x, use_pallas=False)))


def test_prepare_reorder_none_and_declined_stay_plain():
    mat = F.csr_to_spc5(matgen.banded(128, 4, 1.0, seed=1), 1, 8)
    h0 = ops.prepare(mat, layout="whole_vector")
    assert h0.layout == ops.LAYOUT_WHOLE and not h0.is_reordered
    h = ops.prepare(mat, layout="whole_vector", reorder="none")
    assert not h.is_reordered                   # explicit no-op
    # legacy layout spelling still accepted by the wrapper
    assert ops.prepare(mat, layout="whole").layout == ops.LAYOUT_WHOLE
    with pytest.raises(ValueError):             # shape-mismatched Reordering
        ops.prepare(mat, reorder=RE.identity((4, 4)))


def test_test_split_panel_tail_and_reorder():
    """beta_test: panel-bucketed COO tail equals the whole-vector tail, and
    composes with reordering."""
    csr = matgen.uniform_random(256, 5, seed=21)
    d = csr.to_dense()
    x = np.random.default_rng(5).standard_normal(256).astype(np.float32)
    tgt = d.astype(np.float64) @ x.astype(np.float64)
    mat = F.csr_to_spc5(csr, 2, 4)
    hw = ops.prepare(mat, layout="test", cb=64, dtype=np.float32)
    assert hw.tail_pr == 0
    hp = ops.prepare(mat, layout="test", multi_layout="panels",
                     dtype=np.float32, **GEOM)
    assert hp.tail_pr == GEOM["pr"] and hp.single_rows.ndim == 2
    assert hp.single_rows.shape[0] == hp.multi.npanels
    yw = np.asarray(ops.spmv_test(hw, jnp.asarray(x), use_pallas=False))
    yp = np.asarray(ops.spmv_test(hp, jnp.asarray(x), use_pallas=False))
    np.testing.assert_allclose(yw, tgt, atol=2e-3)
    np.testing.assert_allclose(yp, yw, atol=1e-5)
    hr = ops.prepare(mat, layout="test", multi_layout="panels",
                     dtype=np.float32, reorder="sigma", **GEOM)
    yr = np.asarray(ops.spmv_test(hr, jnp.asarray(x), use_pallas=False))
    np.testing.assert_allclose(yr, tgt, atol=2e-3)


def test_distributed_reorder_roundtrip():
    from repro.core import distributed as D
    from jax.sharding import Mesh

    csr = scrambled(192, band=5, seed=15)
    d = csr.to_dense()
    mat = F.csr_to_spc5(csr, 1, 8)
    x = np.random.default_rng(6).standard_normal(192).astype(np.float32)
    tgt = d.astype(np.float64) @ x.astype(np.float64)
    devs = np.asarray(jax.devices()[:1])
    mesh = Mesh(devs, ("data",))
    for pr in (None, 16):
        sh = D.shard_matrix(mat, len(devs), mesh=mesh, pr=pr, xw=32, cb=8,
                            reorder="rcm", tune=False)
        assert sh.reorder == "rcm" and sh.col_perm is not None
        run = D.make_distributed_spmv(sh, mesh)
        y = np.asarray(run(jnp.asarray(x)))
        np.testing.assert_allclose(y, tgt, atol=2e-3)
    # no reorder: fields stay None, path unchanged
    sh0 = D.shard_matrix(mat, len(devs), mesh=mesh, tune=False)
    assert sh0.col_perm is None and sh0.reorder == ""


def test_records_carry_reorder_fields(tmp_path):
    """Record round-trip with the v2 reorder fields + tune() returning a
    config whose reorder prepare() then applies."""
    st_ = S.RecordStore()
    feats = S.MatrixFeatures(0, 0, 0, 5.0, 2.0, 4.0, 0.5)
    cfg = S.PanelConfig("panels", 16, 32, 8, reorder="rcm")
    for avg in (1.0, 4.0, 8.0):
        f = S.MatrixFeatures(0, 0, 0, 5.0, 2.0, avg, 0.5)
        st_.add_measurement("1x8", f, cfg, 1, 9.0, matrix="m",
                            bandwidth_post=3.0, nchunks=7)
        st_.add_measurement("1x8", f, S.PanelConfig("whole", 0, 0, 256), 1,
                            1.0)
    p = str(tmp_path / "r.jsonl")
    st_.save_jsonl(p)
    back = S.load_records(p)
    assert back.records == st_.records
    rec = [r for r in back.records if r.reorder][0]
    assert (rec.reorder, rec.bandwidth_post, rec.nchunks) == ("rcm", 3.0, 7)
    tuned = S.tune(feats, store=back, kernel="1x8")
    assert tuned.reorder == "rcm"
    # clamp preserves the strategy
    assert S.clamp_config(tuned, nrows=8, ncols=8, r=1, c=8,
                          nblocks=2).reorder == "rcm"
    # prepare consumes the tuned reorder end-to-end
    csr = scrambled(96, band=4, seed=17)
    mat = F.csr_to_spc5(csr, 1, 8)
    h = ops.prepare(mat, dtype=np.float32, store=back)
    assert h.is_reordered
    assert h.strategy == "rcm"
    x = np.random.default_rng(7).standard_normal(96).astype(np.float32)
    y = np.asarray(ops.spmv(h, jnp.asarray(x), use_pallas=False))
    np.testing.assert_allclose(y, csr.to_dense() @ x, atol=2e-3)


@settings(max_examples=8, deadline=None)
@given(
    n=st.integers(20, 120),
    m=st.integers(20, 120),
    density=st.floats(0.03, 0.4),
    rc=st.sampled_from([(1, 8), (2, 4), (4, 4)]),
    strategy=st.sampled_from(["sigma", "rcm", "colwindow", "auto"]),
    seed=st.integers(0, 2**20),
)
def test_property_reorder_roundtrip(n, m, density, rc, strategy, seed):
    rng = np.random.default_rng(seed)
    d = ((rng.random((n, m)) < density)
         * rng.standard_normal((n, m))).astype(np.float32)
    csr = F.csr_from_dense(d)
    mat = F.csr_to_spc5(csr, *rc)
    x = rng.standard_normal(m).astype(np.float32)
    tgt = d.astype(np.float64) @ x.astype(np.float64)
    for layout in ("whole", "panels"):
        h = ops.prepare(mat, layout=layout, dtype=np.float32, pr=16, xw=24,
                        cb=4, reorder=strategy)
        y = np.asarray(ops.spmv(h, jnp.asarray(x), use_pallas=False))
        np.testing.assert_allclose(y, tgt, atol=2e-3)
