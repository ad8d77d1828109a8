"""Value-dtype axis (vdtype): parity, quantisation bounds, plan bytes.

The tolerance contract (docs/architecture.md "Value dtypes"):

  * bf16 results stay within 2^-7 RELATIVE error of the f32 product
    (bounded elementwise by ``2**-7 * (|A| @ |x|)``);
  * int8 results stay within the per-chunk scale bound: each stored value
    errs at most scale/2, so a row's error is bounded by
    ``smax/2 * (|A|>0) @ |x|`` with ``smax <= absmax(A)/127``.

Both hold across layouts x chunk widths x reorder strategies, on the
reference (jnp) path AND the interpret-mode Pallas path, for SpMV and
SpMM. Plus: the quantise->dequantise hypothesis property, the
verify-rule mutations (corrupt a scale -> exactly ``value-dtype``), the
plan-bytes accounting regression (a bf16 plan is smaller than its f32
twin; int8 scale arrays ARE counted), and the v4 record schema round
trip with v1-v3 stores loading cleanly.
"""
import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro._compat.hypothesis import given, settings, strategies as st
from repro.analysis import verify as V
from repro.core import formats as F
from repro.core import plan as P
from repro.core import selector as S
from repro.kernels import ops

FUZZ_EXAMPLES = int(os.environ.get("SPC5_FUZZ_EXAMPLES", "10"))

LAYOUTS = ("whole_vector", "panels", "test")
VDTYPES = ("bf16", "int8")
#: Chunk widths: each layout's default (256 whole-vector chunks; one
#: 512-row panel of F.PANEL_CB lanes) and F.PANEL_CB = 128 set explicitly,
#: on several 32-row panels for the panels layout.
CBS = (None, F.PANEL_CB)


def geometry(layout, cb):
    """make_plan keywords of a (layout, cb) case."""
    if cb is None:
        return {}
    return dict(cb=cb, pr=32, xw=32) if layout == "panels" else dict(cb=cb)


def make_mat(rc=(2, 4), n=96, m=80, density=0.3, seed=0):
    rng = np.random.default_rng(seed)
    dense = ((rng.random((n, m)) < density)
             * rng.standard_normal((n, m))).astype(np.float32)
    return dense, F.csr_to_spc5(F.csr_from_dense(dense), *rc)


def error_bound(dense, x, vdtype):
    """Elementwise |y - A@x| bound from the tolerance contract."""
    absA, absx = np.abs(dense), np.abs(x)
    if vdtype == "bf16":
        return (2.0 ** -7) * (absA @ absx) + 1e-5
    smax = absA.max() / 127.0          # >= any per-chunk scale
    return 0.5 * smax * ((absA > 0).astype(np.float64) @ absx) + 1e-5


def check_spmv(plan, dense, x, vdtype, use_pallas):
    ref = dense.astype(np.float64) @ x.astype(np.float64)
    kw = dict(use_pallas=use_pallas)
    if use_pallas:
        kw["interpret"] = True
    y = np.asarray(ops.spmv(plan, jnp.asarray(x), **kw))
    assert y.dtype == np.float32      # f32 accumulation, never narrowed
    bound = error_bound(dense, x, vdtype)
    assert np.all(np.abs(y - ref) <= bound), (
        f"{vdtype} SpMV outside tolerance: worst "
        f"{np.max(np.abs(y - ref) - bound):.3e} over bound")


# ----------------------------------------------------------------------------
# Parity: layouts x chunk widths x reorders x vdtypes, ref + Pallas interpret
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("vdtype", VDTYPES)
@pytest.mark.parametrize("cb", CBS)
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("reorder", [None, "sigma"])
def test_spmv_parity(layout, cb, vdtype, reorder):
    dense, mat = make_mat()
    rng = np.random.default_rng(1)
    x = rng.standard_normal(dense.shape[1]).astype(np.float32)
    plan = P.make_plan(mat, layout=layout, vdtype=vdtype, reorder=reorder,
                       tune=False, **geometry(layout, cb))
    assert dict(plan.meta).get("vdtype") in (vdtype, "")  # test split: outer
    check_spmv(plan, dense, x, vdtype, use_pallas=False)
    check_spmv(plan, dense, x, vdtype, use_pallas=True)


@pytest.mark.parametrize("vdtype", VDTYPES)
@pytest.mark.parametrize("cb", CBS)
def test_spmv_parity_rcm(cb, vdtype):
    # banded structure so RCM actually applies
    from repro.core import matgen
    csr = matgen.banded(96, 5, 0.8, seed=3)
    dense = np.zeros(csr.shape, np.float32)
    for i in range(csr.nrows):
        for k in range(csr.rowptr[i], csr.rowptr[i + 1]):
            dense[i, csr.colidx[k]] = csr.values[k]
    mat = F.csr_to_spc5(csr, 2, 4)
    rng = np.random.default_rng(2)
    x = rng.standard_normal(dense.shape[1]).astype(np.float32)
    plan = P.make_plan(mat, layout="panels", vdtype=vdtype, reorder="rcm",
                       tune=False, **geometry("panels", cb))
    check_spmv(plan, dense, x, vdtype, use_pallas=False)
    check_spmv(plan, dense, x, vdtype, use_pallas=True)


@pytest.mark.parametrize("vdtype", VDTYPES)
@pytest.mark.parametrize("cb", CBS)
@pytest.mark.parametrize("layout", ["whole_vector", "panels"])
def test_spmm_parity(layout, cb, vdtype):
    dense, mat = make_mat()
    rng = np.random.default_rng(3)
    X = rng.standard_normal((dense.shape[1], 4)).astype(np.float32)
    plan = P.make_plan(mat, layout=layout, vdtype=vdtype, tune=False,
                       nvec=4, **geometry(layout, cb))
    ref = dense.astype(np.float64) @ X.astype(np.float64)
    bound = np.stack([error_bound(dense, X[:, j], vdtype)
                      for j in range(X.shape[1])], axis=1)
    for pallas in (False, True):
        kw = {"interpret": True} if pallas else {}
        Y = np.asarray(ops.spmm(plan, jnp.asarray(X), use_pallas=pallas,
                                **kw))
        assert Y.dtype == np.float32
        assert np.all(np.abs(Y - ref) <= bound)


def test_verify_clean_across_vdtypes():
    _, mat = make_mat()
    for layout in LAYOUTS:
        for cb in CBS:
            for vdtype in VDTYPES:
                plan = P.make_plan(mat, layout=layout, vdtype=vdtype,
                                   tune=False, **geometry(layout, cb))
                report = V.verify_plan(plan)
                assert report.ok, report.summary()


def test_vdtype_and_dtype_are_mutually_exclusive():
    _, mat = make_mat()
    with pytest.raises(ValueError, match="vdtype"):
        P.make_plan(mat, vdtype="bf16", dtype=np.float32, tune=False)
    with pytest.raises(ValueError, match="vdtype"):
        P.shard_plan(mat, 1, vdtype="int8", dtype=np.float32, tune=False)


def test_legacy_default_is_byte_identical():
    """vdtype='auto' with no tuned pick is the legacy passthrough."""
    _, mat = make_mat()
    a = P.make_plan(mat, tune=False)
    b = P.make_plan(mat, vdtype="auto", tune=False)
    assert dict(a.meta).get("vdtype") == "" == dict(b.meta).get("vdtype")
    for x, y in zip(a.arrays, b.arrays):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_shard_int8_demotes_to_bf16_with_trace():
    _, mat = make_mat()
    sh = P.shard_plan(mat, 2, vdtype="int8", tune=False)
    assert dict(sh.meta)["vdtype"] == "bf16"
    entry = [e for e in sh.trace if e.get("vdtype_demoted")]
    assert entry and entry[0]["vdtype_demoted_reason"] == \
        "no-sharded-int8-scales"


# ----------------------------------------------------------------------------
# Quantise -> dequantise property (hypothesis)
# ----------------------------------------------------------------------------

@settings(max_examples=FUZZ_EXAMPLES, deadline=None)
@given(n=st.integers(16, 96), m=st.integers(16, 96),
       density=st.floats(0.05, 0.6), scale_pow=st.integers(-3, 3),
       seed=st.integers(0, 2**16))
def test_int8_roundtrip_error_bounded_by_chunk_scale(n, m, density,
                                                     scale_pow, seed):
    rng = np.random.default_rng(seed)
    dense = ((rng.random((n, m)) < density)
             * rng.standard_normal((n, m))
             * 10.0 ** scale_pow).astype(np.float32)
    mat = F.csr_to_spc5(F.csr_from_dense(dense), 2, 4)
    plan = P.make_plan(mat, layout="whole_vector", tune=False)
    dev = plan.dev
    vals = np.asarray(dev.values)
    q, scales = F.quantize_chunk_values(vals, dev.chunk_vbase,
                                        dev.chunk_mask, "int8")
    q, scales = np.asarray(q), np.asarray(scales)
    assert q.dtype == np.int8 and scales.dtype == np.float32
    assert np.all(np.isfinite(scales)) and np.all(scales > 0)
    # per-chunk: every packed value round-trips within scale/2
    vbase = np.asarray(dev.chunk_vbase).ravel()
    masks = np.asarray(dev.chunk_mask).reshape(len(vbase), -1)
    nnz = F.popcount_u32(masks).sum(axis=1)
    for i, (b, k) in enumerate(zip(vbase, nnz)):
        if k == 0:
            continue
        err = np.abs(vals[b:b + k]
                     - q[b:b + k].astype(np.float32) * scales.ravel()[i])
        assert np.all(err <= scales.ravel()[i] / 2 * (1 + 1e-5))


# ----------------------------------------------------------------------------
# Verify-rule mutations: exactly the matching rule fires
# ----------------------------------------------------------------------------

def _replace_array(plan, index, arr):
    arrays = list(plan.arrays)
    arrays[index] = jnp.asarray(arr)
    return dataclasses.replace(plan, arrays=tuple(arrays))


def assert_only(plan, rule):
    report = V.verify_plan(plan)
    assert report.rules_fired == {rule}, report.summary()


@pytest.mark.parametrize("breakage", ["negative", "nan"])
def test_corrupt_scale_fires_value_dtype(breakage):
    # (a float64 scale array is unrepresentable here: jnp.asarray downcasts
    # it back to f32 under jax's default x64-off config, so the dtype leg
    # of the rule is covered by test_wrong_values_dtype_fires_value_dtype)
    _, mat = make_mat()
    plan = P.make_plan(mat, layout="whole_vector", vdtype="int8",
                       tune=False)
    s = np.asarray(plan.arrays[-1]).copy()     # value_scale is appended last
    if breakage == "negative":
        s[0] = -1.0
    else:
        s[0] = np.nan
    assert_only(_replace_array(plan, len(plan.arrays) - 1, s),
                "value-dtype")


def test_wrong_values_dtype_fires_value_dtype():
    _, mat = make_mat()
    plan = P.make_plan(mat, layout="whole_vector", vdtype="bf16",
                       tune=False)
    names = P.get_layout("whole_vector").plan_array_names("bf16")
    i = names.index("values")
    widened = np.asarray(plan.arrays[i]).astype(np.float32)
    assert_only(_replace_array(plan, i, widened), "value-dtype")


# ----------------------------------------------------------------------------
# Plan bytes: the cache's accounting includes the int8 scales
# ----------------------------------------------------------------------------

def test_bf16_plan_smaller_than_f32_twin():
    _, mat = make_mat()
    for layout in ("whole_vector", "panels"):
        f32 = P.make_plan(mat, layout=layout, vdtype="f32", tune=False)
        bf16 = P.make_plan(mat, layout=layout, vdtype="bf16", tune=False)
        assert P.plan_nbytes(bf16) < P.plan_nbytes(f32)


def test_int8_plan_bytes_count_the_scale_array():
    _, mat = make_mat()
    plan = P.make_plan(mat, vdtype="int8", tune=False)
    total = sum(np.asarray(a).nbytes for a in plan.arrays)
    assert P.plan_nbytes(plan) >= total        # scale array included
    base = sum(np.asarray(a).nbytes for a in plan.arrays[:-1])
    assert P.plan_nbytes(plan) > base


def test_plan_cache_keys_differ_by_vdtype():
    from repro.launch import server as SV
    _, mat = make_mat()
    cache = SV.PlanCache()
    p1 = cache.get_or_build(mat, vdtype="f32", tune=False)
    p2 = cache.get_or_build(mat, vdtype="bf16", tune=False)
    p3 = cache.get_or_build(mat, vdtype="bf16", tune=False)
    assert len(cache) == 2 and cache.hits == 1 and p2 is p3
    assert p1 is not p2


def test_exec_stats_roofline_rises_with_narrow_store():
    from repro.launch import server as SV
    _, mat = make_mat()
    f32 = SV.PlanExecStats(P.make_plan(mat, vdtype="f32", tune=False),
                           device_kind="TPU v5 lite")
    bf16 = SV.PlanExecStats(P.make_plan(mat, vdtype="bf16", tune=False),
                            device_kind="TPU v5 lite")
    assert bf16.gflops_roofline > f32.gflops_roofline > 0


def test_exec_stats_has_no_ceiling_without_published_peaks():
    """A device the peak table lacks reports no roofline, not v5e's."""
    from repro.launch import server as SV
    _, mat = make_mat()
    st = SV.PlanExecStats(P.make_plan(mat, tune=False), device_kind="cpu")
    assert st.gflops_roofline is None
    assert st.as_dict()["roofline_fraction"] is None


# ----------------------------------------------------------------------------
# Records: JSONL v4 round trip; v1-v3 load with defaults
# ----------------------------------------------------------------------------

def test_records_v4_roundtrip_and_legacy_load(tmp_path):
    path = str(tmp_path / "rec.jsonl")
    store = S.RecordStore(path)
    store.add("2x4", 12.0, 1, 1.5, matrix="m", pr=32, xw=32, cb=16,
              layout="panels", vdtype="bf16")
    store.add("2x4", 12.0, 1, 2.5, matrix="m", layout="whole_vector",
              vdtype="int8")
    store.add("2x4", 12.0, 1, 1.0, matrix="m", layout="whole_vector")
    store.save_jsonl(path)
    again = S.RecordStore(path)
    assert [r.vdtype for r in again.records] == ["bf16", "int8", ""]
    assert again.records[1].config().vdtype == "int8"
    report = V.verify_records(again)
    assert report.ok, report.summary()

    # strip the v4 field + claim v3: must load with "" defaults
    lines = open(path).read().splitlines()
    hdr = json.loads(lines[0])
    hdr["version"] = 3
    old = [json.dumps(hdr)]
    for ln in lines[1:]:
        o = json.loads(ln)
        o.pop("vdtype", None)
        old.append(json.dumps(o))
    p3 = str(tmp_path / "old.jsonl")
    with open(p3, "w") as f:
        f.write("\n".join(old) + "\n")
    legacy = S.RecordStore(p3)
    assert legacy.skipped == 0
    assert [r.vdtype for r in legacy.records] == ["", "", ""]


def test_panel_config_canonicalises_vdtype():
    assert S.PanelConfig().vdtype == "f32"
    assert S.PanelConfig(vdtype="").vdtype == "f32"
    assert S.PanelConfig(vdtype="int8").vdtype == "int8"
    with pytest.raises(ValueError):
        S.PanelConfig(vdtype="fp4")
    clamped = S.clamp_config(S.PanelConfig("panels", 512, 512, 64,
                                           vdtype="bf16"),
                             nrows=96, ncols=80, r=2, c=4, nblocks=100)
    assert clamped.vdtype == "bf16"


def test_tuned_quantised_config_flows_through_prepare(tmp_path):
    """A store whose best record carries vdtype drives prepare('auto')."""
    dense, mat = make_mat()
    feats = S.spc5_features(mat)
    store = S.RecordStore()
    cfg = S.PanelConfig("whole_vector", 0, 0, 256, vdtype="bf16")
    for gf in (5.0, 5.5, 6.0):
        store.add_measurement("2x4", feats, cfg, 1, gf, matrix="m")
    plan = ops.prepare(mat, store=store)
    assert dict(plan.meta).get("vdtype") == "bf16"
    # explicit beats tuned
    plan = ops.prepare(mat, store=store, vdtype="int8")
    assert dict(plan.meta).get("vdtype") == "int8"
