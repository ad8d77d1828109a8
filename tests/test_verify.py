"""Static plan verifier (repro.analysis.verify): mutation coverage.

Two halves, mirroring the acceptance criteria:

  * every layout x chunk width x reorder combination the pipeline can
    build verifies clean (including a bounded fuzz sweep over random
    matrices);
  * corrupting a valid plan makes EXACTLY the matching rule fire --
    each invariant is individually testable, violations never alias.
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
from repro.core import matgen
from repro.core import plan as P
from repro.core import reorder as RE
from repro.core import selector as S
from repro.kernels import ops

FUZZ_EXAMPLES = int(os.environ.get("SPC5_FUZZ_EXAMPLES", "10"))


def rand_csr(n, m, density, seed):
    rng = np.random.default_rng(seed)
    d = ((rng.random((n, m)) < density)
         * rng.standard_normal((n, m))).astype(np.float32)
    return F.csr_from_dense(d)


def build(layout="whole_vector", rc=(1, 8), n=96, reorder=None, **kw):
    csr = matgen.banded(n, 5, 0.8, seed=3)
    return P.make_plan(F.csr_to_spc5(csr, *rc), layout=layout, tune=False,
                       reorder=reorder, **kw)


#: The block layouts the mask mutations run on: panels with several panels
#: and x windows over the 96-row fixture, as the chip runs it.
MUTATION_LAYOUTS = ("whole_vector", "panels")


def build_layout(layout):
    return (build(layout="panels", pr=32, xw=32) if layout == "panels"
            else build())


def host_array(plan, name):
    names = P.get_layout(plan.layout).plan_array_names()
    return np.array(plan.arrays[names.index(name)])


def corrupt_array(plan, name, fn):
    """Copy one device array to host, mutate in place, rebuild the plan."""
    names = P.get_layout(plan.layout).plan_array_names()
    arrays = list(plan.arrays)
    i = names.index(name)
    a = np.array(arrays[i])
    fn(a)
    arrays[i] = jnp.asarray(a)
    return dataclasses.replace(plan, arrays=tuple(arrays))


def edit_meta(plan, **kv):
    """Replace (or drop, with value=None) geometry keys."""
    meta = tuple((k, kv.get(k, v)) for k, v in plan.meta
                 if kv.get(k, v) is not None)
    return dataclasses.replace(plan, meta=meta)


def assert_only(plan_or_report, rule, **verify_kw):
    report = (plan_or_report if isinstance(plan_or_report, V.VerifyReport)
              else V.verify_plan(plan_or_report, **verify_kw))
    assert report.rules_fired == {rule}, report.summary()
    return report


# ----------------------------------------------------------------------------
# Clean plans verify clean: the full combination sweep
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["whole_vector", "panels", "test"])
@pytest.mark.parametrize("cb", [None, F.PANEL_CB])
@pytest.mark.parametrize("reorder", [None, "sigma"])
def test_all_combinations_verify_clean(layout, cb, reorder):
    # cb=None is each layout's default: 256 whole-vector chunks, and for
    # panels F.PANEL_CB at the default pr; cb=128 sets it explicitly, on
    # 32-row panels for the panels layout
    kw = dict(pr=32, xw=32) if (layout == "panels" and cb) else {}
    plan = build(layout=layout, cb=cb, reorder=reorder, **kw)
    report = V.verify_plan(plan)
    assert report.ok, report.summary()
    assert "layout-registered" in report.checked
    assert "trace-schema" in report.checked


def test_explicit_reordering_verifies_clean():
    rng = np.random.default_rng(7)
    reo = RE.Reordering(row_perm=np.arange(96, dtype=np.int64),
                        col_perm=rng.permutation(96).astype(np.int64),
                        strategy="explicit")
    plan = build(reorder=reo)
    report = V.verify_plan(plan)
    assert report.ok, report.summary()
    assert "permutation" in report.checked


@settings(max_examples=FUZZ_EXAMPLES, deadline=None)
@given(n=st.integers(16, 120), m=st.integers(16, 120),
       density=st.floats(0.02, 0.7),
       rc=st.sampled_from([(1, 8), (2, 4), (4, 4), (2, 8)]),
       layout=st.sampled_from(["whole_vector", "panels", "test"]),
       cb=st.sampled_from([None, 8, F.PANEL_CB]),
       reorder=st.sampled_from([None, "sigma", "rcm"]),
       seed=st.integers(0, 2**16))
def test_fuzz_random_matrices_verify_clean(n, m, density, rc, layout,
                                           cb, reorder, seed):
    csr = rand_csr(n, m, density, seed)
    plan = P.make_plan(F.csr_to_spc5(csr, *rc), layout=layout, cb=cb,
                       tune=False, reorder=reorder)
    report = V.verify_plan(plan)
    assert report.ok, report.summary()


# ----------------------------------------------------------------------------
# Mutation coverage: corrupt a valid plan -> exactly that rule fires
# ----------------------------------------------------------------------------

def _last_multibit_block(mask2d):
    """(chunk, slot) of a pop>=2 block that is the LAST real block of its
    chunk (so clearing one of its bits perturbs no later voff)."""
    pop = F.popcount_u32(mask2d)
    for ch in range(mask2d.shape[0] - 1, -1, -1):
        real = np.flatnonzero(mask2d[ch])
        if real.size and pop[ch, real[-1]] >= 2:
            return ch, int(real[-1])
    raise AssertionError("fixture matrix produced no pop>=2 tail block")


@pytest.mark.parametrize("layout", MUTATION_LAYOUTS)
def test_mutation_mask_popcount(layout):
    plan = build_layout(layout)
    mask = host_array(plan, "chunk_mask").reshape(-1, plan.cb)
    ch, sl = _last_multibit_block(mask)
    bit = int(np.flatnonzero([(mask[ch, sl] >> b) & 1 for b in range(32)])[0])

    def clear_bit(a):
        flat = a.reshape(-1, plan.cb)
        flat[ch, sl] &= ~np.uint32(1 << bit)

    assert_only(corrupt_array(plan, "chunk_mask", clear_bit),
                "mask-popcount")


@pytest.mark.parametrize("layout", MUTATION_LAYOUTS)
def test_mutation_mask_voff_window(layout):
    plan = build_layout(layout)
    mask = host_array(plan, "chunk_mask").reshape(-1, plan.cb)
    ch = 0
    sl = int(np.flatnonzero(mask[ch])[0])

    def bump(a):
        a.reshape(-1, plan.cb)[ch, sl] += 1

    assert_only(corrupt_array(plan, "chunk_voff", bump), "mask-voff-window")


@pytest.mark.parametrize("layout", MUTATION_LAYOUTS)
def test_mutation_values_window_bounds(layout):
    plan = build_layout(layout)
    nvals = int(host_array(plan, "values").shape[0])

    def overrun(a):
        a.flat[-1] = nvals     # window [nvals, nvals + vmax) is off the end

    assert_only(corrupt_array(plan, "chunk_vbase", overrun),
                "values-window-bounds")


@pytest.mark.parametrize("layout", MUTATION_LAYOUTS)
def test_mutation_chunk_row_bounds(layout):
    plan = build_layout(layout)
    mask = host_array(plan, "chunk_mask").reshape(-1, plan.cb)
    ch = 0
    sl = int(np.flatnonzero(mask[ch])[0])
    r = dict(plan.meta)["r"]
    # whole-vector rows are global, panel rows panel-relative
    rows = plan.pr if layout == "panels" else plan.nrows
    big = ((rows // r) + 4) * r          # r-aligned but out of range

    def oob(a):
        a.reshape(-1, plan.cb)[ch, sl] = big

    assert_only(corrupt_array(plan, "chunk_row", oob), "chunk-row-bounds")


@pytest.mark.parametrize("layout", MUTATION_LAYOUTS)
def test_mutation_chunk_col_bounds(layout):
    plan = build_layout(layout)
    mask = host_array(plan, "chunk_mask").reshape(-1, plan.cb)
    ch = 0
    sl = int(np.flatnonzero(mask[ch])[0])
    # whole-vector columns are global, panel columns window-relative
    cols = plan.xw if layout == "panels" else plan.ncols

    def oob(a):
        a.reshape(-1, plan.cb)[ch, sl] = cols

    assert_only(corrupt_array(plan, "chunk_col", oob), "chunk-col-bounds")


def test_mutation_panels_xbase_window():
    plan = build(layout="panels", pr=32, xw=32)
    g = dict(plan.meta)

    def overrun(a):
        a.flat[0] = g["ncols_pad"]       # xbase + xw off the padded vector

    assert_only(corrupt_array(plan, "chunk_xbase", overrun),
                "chunk-col-bounds")


def test_mutation_permutation():
    rng = np.random.default_rng(11)
    reo = RE.Reordering(row_perm=np.arange(96, dtype=np.int64),
                        col_perm=rng.permutation(96).astype(np.int64),
                        strategy="explicit")
    plan = build(reorder=reo)
    assert plan.col_perm is not None
    cp = np.array(plan.col_perm)
    cp[0] = cp[1]                        # duplicate entry: not a bijection
    assert_only(dataclasses.replace(plan, col_perm=jnp.asarray(cp)),
                "permutation")


def test_mutation_vmem_budget():
    plan = build(layout="whole_vector")
    # the registry cost can't fit a 1-byte budget: the verifier proves the
    # plan should have been demoted to panels
    assert_only(plan, "vmem-budget", budget_bytes=1)


def test_mutation_vmem_contract_missing(monkeypatch):
    from repro.kernels import spc5_spmv as KV
    plan = build(layout="whole_vector")
    contracts = dict(KV.SPMV_VMEM_CONTRACTS)
    del contracts["whole_vector"]
    monkeypatch.setattr(KV, "SPMV_VMEM_CONTRACTS", contracts)
    assert_only(plan, "vmem-budget")


def test_mutation_trace_missing_reason():
    plan = build()
    trace = plan.trace
    lay = next(e for e in trace if e["pass"] == "layout")
    lay["demoted"] = True                # flag without an explanation
    bad = dataclasses.replace(plan, trace_json=json.dumps(trace))
    assert_only(bad, "trace-schema")


def test_mutation_trace_missing_pass():
    plan = build()
    trace = [e for e in plan.trace if e["pass"] != "reorder"]
    bad = dataclasses.replace(plan, trace_json=json.dumps(trace))
    assert_only(bad, "trace-schema")


def test_mutation_trace_missing_duration():
    # every pass records its wall-time (the obs span); a trace entry
    # without duration_s is schema drift
    plan = build()
    trace = plan.trace
    tune = next(e for e in trace if e["pass"] == "tune")
    del tune["duration_s"]
    bad = dataclasses.replace(plan, trace_json=json.dumps(trace))
    assert_only(bad, "trace-schema")


def test_mutation_test_split_count():
    plan = build(layout="test")
    g = dict(plan.meta)
    bad = edit_meta(plan, n_single=g["n_single"] + 1)
    assert_only(bad, "test-split")


def test_mutation_unregistered_layout():
    plan = build()
    report = V.verify_plan(dataclasses.replace(plan, layout="bogus"))
    assert report.rules_fired == {"layout-registered"}
    # nothing else is interpretable without a registry entry
    assert report.checked == ("layout-registered",)


def test_mutation_geometry_schema_skips_array_rules():
    plan = build()
    report = V.verify_plan(edit_meta(plan, vmax=None))
    assert report.rules_fired == {"geometry-schema"}
    # array rules are skipped (their precondition failed) but the
    # geometry-independent rules still ran
    assert "mask-popcount" not in report.checked
    assert "trace-schema" in report.checked


MUTATIONS = {
    "mask-popcount": lambda: test_mutation_mask_popcount("panels"),
    "chunk-col-bounds": lambda: test_mutation_chunk_col_bounds("whole_vector"),
    "values-window-bounds":
        lambda: test_mutation_values_window_bounds("panels"),
    "trace-schema": test_mutation_trace_missing_reason,
}


@settings(max_examples=min(FUZZ_EXAMPLES, 6), deadline=None)
@given(rule=st.sampled_from(sorted(MUTATIONS)))
def test_fuzz_mutations_fire_the_right_rule(rule):
    MUTATIONS[rule]()


def test_report_api_and_raise():
    plan = build()
    good = V.verify_plan(plan)
    assert good.ok and good.raise_if_failed() is good
    assert "ok" in good.summary()
    bad = V.verify_plan(dataclasses.replace(plan, layout="bogus"))
    with pytest.raises(V.PlanVerificationError) as ei:
        bad.raise_if_failed()
    assert ei.value.report is bad
    assert "layout-registered" in str(ei.value)
    assert set(V.plan_rule_names()) >= set(good.checked)


# ----------------------------------------------------------------------------
# The opt-in hooks: make_plan(verify=...) / ops.prepare(verify=...)
# ----------------------------------------------------------------------------

def test_make_plan_verify_hook():
    csr = matgen.banded(64, 4, 1.0, seed=5)
    mat = F.csr_to_spc5(csr, 1, 8)
    P.make_plan(mat, layout="whole_vector", tune=False, verify=True)
    seen = []
    P.make_plan(mat, layout="panels", tune=False, verify=seen.append)
    assert len(seen) == 1 and seen[0].ok


def test_ops_prepare_verify_hook():
    csr = matgen.banded(64, 4, 1.0, seed=5)
    h = ops.prepare(F.csr_to_spc5(csr, 1, 8), dtype=np.float32, verify=True)
    assert V.verify_plan(h).ok


# ----------------------------------------------------------------------------
# Satellites: did-you-mean, dtype-aware budget, demotion reasons
# ----------------------------------------------------------------------------

def test_canonical_names_did_you_mean():
    with pytest.raises(ValueError, match="did you mean 'panels'"):
        P.canonical_layout("panel")
    with pytest.raises(ValueError, match="did you mean 'whole_vector'"):
        P.canonical_layout("whole_vectr")
    # garbage with no near miss raises without a suggestion
    with pytest.raises(ValueError) as ei:
        P.canonical_layout("zzzzzz")
    assert "did you mean" not in str(ei.value)


def test_fits_whole_vector_accepts_dtypes():
    n, m = 1000, 1000
    assert (P.fits_whole_vector(n, m, np.float64)
            == P.fits_whole_vector(n, m, 8))
    assert (P.fits_whole_vector(n, m, "float32")
            == P.fits_whole_vector(n, m, 4))
    assert (P.fits_whole_vector(n, m, np.dtype(np.float32))
            == P.fits_whole_vector(n, m, 4))
    # f64 halves the element budget: find a size where they disagree
    n = P.VMEM_WHOLE_VECTOR_BUDGET // (2 * 4 * 128)
    assert P.fits_whole_vector(n - 8, n, 4, nvec=128)
    assert not P.fits_whole_vector(n - 8, n, np.float64, nvec=128)


def test_layout_demotion_reason_in_trace(monkeypatch):
    """A tuned whole-vector pick on a TPU backend gives way to panels, the
    demotion explained on the layout entry; the schema rule accepts it."""
    store = S.RecordStore()
    f = S.MatrixFeatures(0, 0, 0, 4.0, 2.0, 4.0, 0.5)
    store.add_measurement("1x8", f, S.PanelConfig("whole_vector", 0, 0, 32),
                          1, 9.0)
    csr = matgen.banded(96, 4, 1.0, seed=31)
    monkeypatch.setattr(P, "_on_tpu", lambda: True)
    h = ops.prepare(F.csr_to_spc5(csr, 1, 8), dtype=np.float32, store=store)
    assert h.trace[0]["source"] == "store" and h.layout == P.LAYOUT_PANELS
    lay = next(e for e in h.trace if e["pass"] == "layout")
    assert lay["layout_demoted"] is True
    assert lay["layout_demoted_reason"] == "no-mosaic-kernel:whole_vector"
    assert V.verify_plan(h).ok


def test_tune_demotion_reason_in_trace():
    store = S.RecordStore()
    f = S.MatrixFeatures(0, 0, 0, 4.0, 2.0, 4.0, 0.5)
    store.add_measurement("1x8", f, S.PanelConfig("whole", 0, 0, 512), 1, 9.0)
    csr = matgen.banded(300_000, 4, 1.0, seed=9)
    h = ops.prepare(F.csr_to_spc5(csr, 1, 8), dtype=np.float32, store=store)
    tune = h.trace[0]
    assert tune["demoted"] is True
    assert tune["demoted_reason"] == "vmem-budget"
    assert V.verify_plan(h, nvec=128).ok


# ----------------------------------------------------------------------------
# Record-store verification
# ----------------------------------------------------------------------------

def test_verify_records_clean_and_test_suffix():
    store = S.RecordStore()
    store.add("1x8", 4.0, 1, 9.0, layout="whole_vector")
    store.add("2x4_test", 3.0, 2, 7.0, layout="test")
    report = V.verify_records(store)
    assert report.ok, report.summary()


def test_verify_records_flags_bad_fields():
    store = S.RecordStore()
    store.records.append(dataclasses.replace(
        S.Record("1x8", 4.0, 1, 9.0), kernel="9x9"))       # r*c > 32
    store.records.append(dataclasses.replace(
        S.Record("1x8", 4.0, 1, 9.0), gflops=float("nan")))
    store.records.append(dataclasses.replace(
        S.Record("1x8", 4.0, 1, 9.0), workers=0))
    report = V.verify_records(store)
    assert report.rules_fired == {"record-schema"}
    assert len(report.violations) == 3


def test_verify_records_flags_loader_skips():
    store = S.RecordStore()
    store.skipped = 2
    report = V.verify_records(store)
    assert report.rules_fired == {"store-load"}
    assert "2 malformed" in report.violations[0].message
