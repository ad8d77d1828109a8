"""Descriptor-lowering tests (PR 5).

Four concerns:

  * **Parity suite**: the "descriptor" lowering must be BIT-IDENTICAL to
    the "mask" lowering across layouts x reorder strategies x dtypes, for
    SpMV and SpMM, on both the jnp reference path and the Pallas kernels
    (interpret mode) -- the build-time expansion computes exactly the
    quantities the mask decode recomputes, so nothing may change.
  * **Record-store schema v3**: v1/v2/v3 stores round-trip; legacy records
    (no ``lowering`` field) normalise to the mask config identity; the
    tuner distinguishes lowerings and ``ops.prepare`` applies its pick.
  * **Lowering validation**: ``selector.clamp_config`` demotes a
    descriptor config on a layout that registered no descriptor variant,
    and the plan pipeline records the demotion in ``plan.trace``.
  * **Fusion scan**: the panel-layout reorder path issues no standalone
    ``jnp.take`` x-gather any more (the column map is fused into the
    decode / kernels), and the whole-vector descriptor build folds the
    permutation into its static tables outright.

Plus a unit test for the CI perf-regression gate's comparison logic.
"""
import dataclasses
import inspect
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import formats as F
from repro.core import matgen
from repro.core import plan as P
from repro.core import ref_spmv as R
from repro.core import reorder as RE
from repro.core import selector as S
from repro.kernels import ops

DTYPES = (np.float32, np.float64)
REORDERS = (None, "rcm", "sigma")
LAYOUTS = ("whole_vector", "panels")
GEOM = dict(pr=16, xw=32, cb=8)


@pytest.fixture(autouse=True)
def _no_ambient_store(monkeypatch):
    monkeypatch.delenv(S.RECORDS_ENV, raising=False)
    S.set_default_store(None)
    yield
    S.set_default_store(None)


def bit_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype
    np.testing.assert_array_equal(a, b)


def _pair(mat, layout, dtype, reorder, **kw):
    """(mask plan, descriptor plan) at identical geometry/permutation."""
    mk = lambda low: P.make_plan(mat, layout=layout, dtype=dtype,
                                 lowering=low, reorder=reorder, **GEOM, **kw)
    return mk("mask"), mk("descriptor")


# ----------------------------------------------------------------------------
# Parity suite: descriptor == mask, bitwise
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("reorder", REORDERS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_descriptor_parity_reference(layout, reorder, dtype):
    csr = matgen.scrambled_banded(192, 5, 1.0, seed=7)
    d = csr.to_dense()
    mat = F.csr_to_spc5(csr, 2, 4)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal(192).astype(dtype))
    X = jnp.asarray(rng.standard_normal((192, 4)).astype(dtype))
    hm, hd = _pair(mat, layout, dtype, reorder)
    assert hm.lowering == "mask" and hd.lowering == "descriptor"
    ym = ops.spmv(hm, x, use_pallas=False)
    yd = ops.spmv(hd, x, use_pallas=False)
    bit_equal(ym, yd)
    np.testing.assert_allclose(
        np.asarray(ym, np.float64),
        d.astype(np.float64) @ np.asarray(x, np.float64),
        atol=2e-3)
    bit_equal(ops.spmm(hm, X, use_pallas=False),
              ops.spmm(hd, X, use_pallas=False))


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("reorder", (None, "rcm"))
def test_descriptor_parity_pallas_interpret(layout, reorder):
    csr = matgen.scrambled_banded(160, 4, 1.0, seed=11)
    mat = F.csr_to_spc5(csr, 1, 8)
    x = jnp.asarray(np.random.default_rng(2).standard_normal(160),
                    jnp.float32)
    X = jnp.asarray(np.random.default_rng(3).standard_normal((160, 4)),
                    jnp.float32)
    hm, hd = _pair(mat, layout, np.float32, reorder)
    y_ref = np.asarray(ops.spmv(hd, x, use_pallas=False))
    for db in (False, True):
        for h in (hm, hd):
            y = np.asarray(ops.spmv(h, x, use_pallas=True, interpret=True,
                                    double_buffer=db))
            np.testing.assert_allclose(y, y_ref, atol=1e-5)
    Y_ref = np.asarray(ops.spmm(hd, X, use_pallas=False))
    for h in (hm, hd):
        Y = np.asarray(ops.spmm(h, X, use_pallas=True, interpret=True))
        np.testing.assert_allclose(Y, Y_ref, atol=1e-5)


def test_descriptor_parity_test_split():
    """The beta_test split threads the lowering to its multi sub-plan."""
    csr = matgen.powerlaw(320, 5, seed=13)
    mat = F.csr_to_spc5(csr, 2, 4)
    x = jnp.asarray(np.random.default_rng(4).standard_normal(320),
                    jnp.float32)
    for layout in LAYOUTS:
        hm = ops.prepare(mat, layout="test", multi_layout=layout,
                         dtype=np.float32, lowering="mask", **GEOM)
        hd = ops.prepare(mat, layout="test", multi_layout=layout,
                         dtype=np.float32, lowering="descriptor", **GEOM)
        assert hd.multi.lowering == "descriptor" == hd.lowering
        bit_equal(ops.spmv_test(hm, x, use_pallas=False),
                  ops.spmv_test(hd, x, use_pallas=False))


def test_chunk_descriptors_tables():
    """The expansion's invariants: valid == mask bits, vidx dense per
    chunk, xcol/yrow within the clip bounds, col_map folded statically."""
    csr, _ = matgen.banded(96, 3, 1.0, seed=5), None
    mat = F.csr_to_spc5(csr, 2, 4)
    ch = F.to_chunked(mat, cb=16)
    desc = F.chunk_descriptors(ch.chunk_mask, ch.chunk_voff, ch.chunk_col,
                               ch.chunk_row, r=2, c=4, vmax=ch.vmax,
                               xmax=ch.ncols, ymax=ch.nrows)
    pop = F.popcount_u32(ch.chunk_mask)
    assert np.array_equal(desc.valid.sum(axis=-1), pop)
    assert desc.vidx.min() >= 0 and desc.vidx.max() < ch.vmax
    assert desc.xcol.min() >= 0 and desc.xcol.max() < ch.ncols
    assert desc.yrow.min() >= 0 and desc.yrow.max() < ch.nrows
    # col_map folds into xcol at build time
    cmap = np.random.default_rng(0).permutation(ch.ncols).astype(np.int64)
    desc2 = F.chunk_descriptors(ch.chunk_mask, ch.chunk_voff, ch.chunk_col,
                                ch.chunk_row, r=2, c=4, vmax=ch.vmax,
                                xmax=ch.ncols, ymax=ch.nrows, col_map=cmap)
    assert np.array_equal(desc2.xcol, cmap[desc.xcol])


def test_descriptor_whole_vector_folds_col_perm():
    """Whole-vector descriptor plans carry NO col_perm: the permutation is
    static data in desc_xcol (zero runtime cost)."""
    csr = matgen.scrambled_banded(128, 4, 1.0, seed=17)
    mat = F.csr_to_spc5(csr, 1, 8)
    hd = P.make_plan(mat, layout="whole_vector", cb=32, dtype=np.float32,
                     lowering="descriptor", reorder="rcm")
    assert hd.is_reordered and hd.col_perm is None
    hm = P.make_plan(mat, layout="whole_vector", cb=32, dtype=np.float32,
                     lowering="mask", reorder="rcm")
    assert hm.col_perm is not None
    x = jnp.asarray(np.random.default_rng(6).standard_normal(128),
                    jnp.float32)
    bit_equal(ops.spmv(hm, x, use_pallas=False),
              ops.spmv(hd, x, use_pallas=False))


def test_panel_row_fusion_pure_panel_permutation():
    """A pure panel permutation folds into the stacked panel axis
    (rows_fused) for BOTH lowerings; results stay bit-identical to the
    executor's gather path."""
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
    for low in ("mask", "descriptor"):
        h = P.make_plan(mat, layout="panels", pr=pr, xw=32, cb=8,
                        dtype=np.float32, lowering=low, reorder=reo)
        assert h.rows_fused and h.row_iperm is None
        np.testing.assert_allclose(
            np.asarray(ops.spmv(h, x, use_pallas=False)),
            d.astype(np.float64) @ np.asarray(x, np.float64), atol=2e-3)
    # a non-aligned permutation must NOT fuse
    bad = RE.Reordering(np.roll(np.arange(nrows), 3).astype(np.int64),
                        np.arange(nrows, dtype=np.int64), strategy="manual")
    assert P._panel_row_permutation(bad, pr, nrows, 4) is None


# ----------------------------------------------------------------------------
# Record store: v1/v2/v3 round-trips + tuner arbitration
# ----------------------------------------------------------------------------

def _write_jsonl(path, version, records):
    with open(path, "w") as f:
        f.write(json.dumps({"spc5_records_version": version}) + "\n")
        for r in records:
            f.write(json.dumps(r) + "\n")


def test_record_store_v1_v2_v3_roundtrip(tmp_path):
    base = dict(kernel="1x8", avg=4.0, workers=1, gflops=2.0, matrix="m",
                pr=0, xw=0, cb=512, layout="whole_vector", nnz_row=5.0,
                bandwidth=2.0, fill=0.5)
    v1 = {k: v for k, v in base.items()
          if k not in ("layout",)} | {"layout": "whole"}   # legacy spelling
    v2 = base | {"reorder": "rcm", "bandwidth_post": 1.0, "nchunks": 3}
    v3 = base | {"reorder": "", "bandwidth_post": 0.0, "nchunks": 0,
                 "lowering": "descriptor"}
    _write_jsonl(tmp_path / "v1.jsonl", 1, [v1])
    _write_jsonl(tmp_path / "v2.jsonl", 2, [v2])
    _write_jsonl(tmp_path / "v3.jsonl", 3, [v3])
    store = S.load_records(str(tmp_path))
    assert len(store.records) == 3
    by_low = {r.lowering for r in store.records}
    assert by_low == {"", "descriptor"}
    # legacy records pool with v3 mask measurements: same config identity
    cfgs = {r.config() for r in store.records}
    assert S.PanelConfig("whole_vector", 0, 0, 512) in cfgs          # v1
    assert S.PanelConfig("whole_vector", 0, 0, 512,
                         lowering="descriptor") in cfgs              # v3
    assert all(c.lowering in ("mask", "descriptor") for c in cfgs)
    # round-trip through save_jsonl stamps the current version
    out = tmp_path / "out.jsonl"
    store.save_jsonl(str(out))
    with open(out) as f:
        head = json.loads(f.readline())
    assert head["spc5_records_version"] == S.RECORDS_VERSION == 4
    store2 = S.RecordStore(str(out))
    assert store2.records == store.records
    # a store claiming a NEWER version than supported refuses to load
    _write_jsonl(tmp_path / "v9.jsonl", 9, [v3])
    with pytest.raises(ValueError):
        S._load_jsonl(str(tmp_path / "v9.jsonl"))


def test_tuner_picks_between_lowerings():
    """Planted store: descriptor measures faster -> tune returns the
    descriptor config and ops.prepare applies it."""
    desc_cfg = S.PanelConfig("whole_vector", 0, 0, 32,
                             lowering="descriptor")
    mask_cfg = S.PanelConfig("whole_vector", 0, 0, 32)
    store = S.RecordStore()
    for avg in (1.0, 3.0, 6.0):
        f = S.MatrixFeatures(0, 0, 0, 5.0, 2.0, avg, avg / 8)
        store.add_measurement("1x8", f, desc_cfg, 1, 9.0)
        store.add_measurement("1x8", f, mask_cfg, 1, 1.0)
    feats = S.MatrixFeatures(0, 0, 0, 5.0, 2.0, 4.0, 0.5)
    assert S.tune(feats, store=store, kernel="1x8") == desc_cfg
    csr = matgen.banded(96, 4, 1.0, seed=29)
    h = ops.prepare(F.csr_to_spc5(csr, 1, 8), dtype=np.float32, store=store)
    assert h.lowering == "descriptor"
    assert h.trace[0]["source"] == "store"
    assert h.trace[0]["lowering"] == "descriptor"
    # and the records survive a BENCH-payload round trip (CI artifact shape)
    payload = {"version": S.RECORDS_VERSION,
               "records": [dataclasses.asdict(r) for r in store.records]}
    assert all(S.Record(**r).config() in (desc_cfg, mask_cfg)
               for r in payload["records"])


def test_clamp_config_demotes_unregistered_lowering():
    """Satellite: a layout without a descriptor variant demotes tuned
    descriptor configs to mask, and the plan pipeline traces it."""
    spec = P._REGISTRY[P.LAYOUT_WHOLE]
    P._REGISTRY[P.LAYOUT_WHOLE] = dataclasses.replace(
        spec, lowerings=(P.LOWERING_MASK,))
    try:
        cfg = S.clamp_config(
            S.PanelConfig("whole_vector", 0, 0, 64, lowering="descriptor"),
            nrows=96, ncols=96, r=1, c=8, nblocks=10)
        assert cfg.lowering == "mask"
        csr = matgen.banded(96, 4, 1.0, seed=31)
        h = ops.prepare(F.csr_to_spc5(csr, 1, 8), dtype=np.float32, cb=32,
                        layout="whole_vector", lowering="descriptor")
        assert h.lowering == "mask"
        lay = [e for e in h.trace if e["pass"] == "layout"][0]
        assert lay["lowering_demoted"] is True
    finally:
        P._REGISTRY[P.LAYOUT_WHOLE] = spec
    # unknown lowering names never enter configs at all
    with pytest.raises(ValueError):
        S.PanelConfig("whole_vector", lowering="csr5")


def test_shard_plan_serves_descriptor():
    """An explicit descriptor request survives sharding: the layout's
    shard_build_desc hook stacks descriptor tables (no demotion, no
    mask arrays) and the trace records the requested resolution."""
    from repro.core import distributed as D
    from repro.core import ref_spmv as R

    csr = matgen.banded(144, 5, 1.0, seed=37)
    sh = D.shard_matrix(F.csr_to_spc5(csr, 1, 8), 2, cb=32, tune=False,
                        lowering="descriptor")
    sentry = sh.trace[-1]
    assert sentry["pass"] == "shard"
    assert sentry["lowering"] == "descriptor"
    assert "lowering_demoted" not in sentry
    lentry = [e for e in sh.trace if e.get("pass") == "lowering"][0]
    assert lentry["reason"] == "requested"
    # the stacked arrays resolve by the DESCRIPTOR name set
    assert len(sh.arrays) == len(R.SPC5DescDevice._fields)
    assert sh.desc_valid.shape == sh.desc_vidx.shape


# ----------------------------------------------------------------------------
# Fusion scan: no standalone x-gather on the panel reorder path
# ----------------------------------------------------------------------------

def test_panel_lowering_has_no_standalone_x_gather():
    """Dispatch scan for the fusion contract: the panel lowerings hand the
    column map to the reference and descriptor decodes, and only the mask
    kernel -- which DMAs x windows and so cannot route them through a map
    -- gets x permuted once up front; no ad-hoc ``jnp.take(x``."""
    for fn in (P._lower_spmv_panels, P._lower_spmm_panels):
        src = inspect.getsource(fn)
        assert src.count("_gathered_x(") == 1, fn.__name__
        assert "plan.col_perm" in src and "cmap" in src, fn.__name__
        assert "jnp.take(x" not in src, fn.__name__
    # the reference panel decode routes the gather through cmap instead of
    # consuming a pre-permuted x
    for fn in (R.spmv_panels, R.spmm_panels, R.spmv_panels_desc,
               R.spmm_panels_desc):
        assert "cmap" in inspect.signature(fn).parameters or \
            "cmap" in inspect.getsource(fn), fn.__name__


def test_panel_fused_x_vmem_guard(monkeypatch):
    """Past the VMEM budget the pallas panel lowerings fall back to the
    materialised gather (bounded windowed-DMA footprint) instead of
    holding a too-large x + map VMEM-resident; results are unchanged."""
    csr = matgen.scrambled_banded(160, 4, 1.0, seed=43)
    mat = F.csr_to_spc5(csr, 1, 8)
    h = P.make_plan(mat, layout="panels", pr=16, xw=32, cb=8,
                    dtype=np.float32, lowering="mask", reorder="rcm")
    assert h.col_perm is not None
    x = jnp.asarray(np.random.default_rng(10).standard_normal(160),
                    jnp.float32)
    xk, cmap = P._panel_fused_x(h, x)
    assert cmap is not None and xk is x          # fits: fused path
    y_fused = np.asarray(ops.spmv(h, x, use_pallas=True, interpret=True))
    monkeypatch.setattr(P, "VMEM_WHOLE_VECTOR_BUDGET", 64)
    xk, cmap = P._panel_fused_x(h, x)
    assert cmap is None and xk is not x          # too big: materialised
    y_guard = np.asarray(ops.spmv(h, x, use_pallas=True, interpret=True))
    np.testing.assert_allclose(y_guard, y_fused, atol=1e-6)


def test_panel_fused_cmap_matches_materialised_gather():
    """The fused panel path == the old materialised-gather computation,
    bitwise (reference) and numerically (Pallas interpret)."""
    csr = matgen.scrambled_banded(160, 4, 1.0, seed=41)
    mat = F.csr_to_spc5(csr, 1, 8)
    reo = RE.reorder(mat, "rcm", r=1, c=8, pr=16, xw=32, cb=8)
    assert not reo.is_identity and not reo.identity_cols
    h = P.make_plan(mat, layout="panels", pr=16, xw=32, cb=8,
                    dtype=np.float32, lowering="mask", reorder=reo)
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
    bit_equal(ops.spmv(h, x, use_pallas=False), y_old)
    y_pal = np.asarray(ops.spmv(h, x, use_pallas=True, interpret=True))
    np.testing.assert_allclose(y_pal, np.asarray(y_old), atol=1e-5)


# ----------------------------------------------------------------------------
# Perf-regression gate logic
# ----------------------------------------------------------------------------

def test_regression_gate_compare():
    from benchmarks.regression_gate import compare, section_gflops

    def payload(scale):
        return {"sections": {
            "spmv_seq": [f"spmv_seq.m.k{i},1.0,gflops={scale * (1 + i)}"
                         for i in range(6)],
            "tiny": ["tiny.x,1.0,gflops=1.0"],          # < min_lines: skip
        }}

    assert section_gflops(payload(1.0))["spmv_seq"] == [1.0, 2.0, 3.0, 4.0,
                                                        5.0, 6.0]
    # same perf: pass
    assert compare(payload(1.0), payload(1.0)) == []
    # 10% faster: pass; 50% slower: fail; new section with no prior: skip
    assert compare(payload(1.1), payload(1.0)) == []
    failures = compare(payload(0.5), payload(1.0))
    assert len(failures) == 1 and "spmv_seq" in failures[0]
    cur = payload(0.5)
    cur["sections"]["brand_new"] = ["brand_new.x,1,gflops=1"] * 6
    assert len(compare(cur, payload(1.0))) == 1     # new section skipped
    # within threshold (20% drop < 25%): pass
    assert compare(payload(0.8), payload(1.0)) == []
