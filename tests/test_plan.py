"""Execution-plan architecture tests (repro.core.plan).

Three concerns:

  * **Plan mechanics**: registry key set, pytree round-trip under jit/vmap
    (including the test split's nested sub-plan), attribute resolution, and
    the ``plan.trace`` golden decisions.
  * **Equivalence suite**: each entry point (``prepare`` -- including the
    deprecated ``prepare_panels``/``prepare_test`` shims, which must warn
    AND stay bit-equal to their unified spellings --
    ``SparseLinear.from_dense``, ``shard_matrix`` -- with
    and without ``reorder=``/``config=``) must produce BIT-IDENTICAL
    spmv/spmm results to a hand-rolled replica of the pre-refactor
    computation (layout build + explicit gather/scatter exactly as the old
    handle classes did), so the refactor provably changed no numerics.
  * **Dispatch localisation**: the modules that used to duplicate
    ``if layout == "panels"``-style branching (ops, distributed,
    sparse_linear, serve) must not contain layout-literal branching any
    more -- the registry is the only dispatcher.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import distributed as D
from repro.core import formats as F
from repro.core import matgen
from repro.core import plan as P
from repro.core import ref_spmv as R
from repro.core import reorder as RE
from repro.core import selector as S
from repro.core.sparse_linear import SparseLinear, prune_by_magnitude
from repro.kernels import ops, spc5_spmv

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src", "repro")


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


def rand_csr(n, m, density, seed):
    rng = np.random.default_rng(seed)
    d = ((rng.random((n, m)) < density)
         * rng.standard_normal((n, m))).astype(np.float32)
    return F.csr_from_dense(d), d


# ----------------------------------------------------------------------------
# Registry + canonical names
# ----------------------------------------------------------------------------

def test_registry_key_set_is_canonical():
    assert P.layout_names() == ("panels", "test", "whole_vector")
    assert P.canonical_layout("whole") == P.LAYOUT_WHOLE
    assert P.canonical_layout("auto") == "auto"
    assert P.canonical_layout("") == ""
    with pytest.raises(ValueError):
        P.canonical_layout("csr5")
    # the registry's spec entries are complete
    for name in P.layout_names():
        spec = P.get_layout(name)
        for hook in ("build", "lower_spmv", "lower_spmm", "cost", "clamp"):
            assert callable(getattr(spec, hook)), (name, hook)


def test_layout_dispatch_only_in_plan_module():
    """The acceptance criterion made executable: the modules that used to
    duplicate layout branching carry none -- adding a layout is one
    registration, not five edited files. Thin wrapper over the repo lint's
    ``layout-dispatch`` rule (tools/spc5_lint.py), which generalises the
    old substring scan to an AST walk over ALL of src/repro."""
    import importlib.util
    import sys
    root = os.path.dirname(os.path.dirname(SRC))
    spec = importlib.util.spec_from_file_location(
        "spc5_lint_wrapper", os.path.join(root, "tools", "spc5_lint.py"))
    lint = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = lint
    spec.loader.exec_module(lint)
    findings = lint.check_layout_dispatch(root)
    assert findings == [], "\n".join(str(f) for f in findings)


# ----------------------------------------------------------------------------
# Pytree round-trip under jit / vmap
# ----------------------------------------------------------------------------

def test_plan_pytree_roundtrip_jit_vmap():
    csr, d = rand_csr(96, 80, 0.15, seed=1)
    mat = F.csr_to_spc5(csr, 2, 4)
    h = ops.prepare(mat, cb=32, dtype=np.float32)
    flat, tdef = jax.tree.flatten(h)
    h2 = jax.tree.unflatten(tdef, flat)
    assert h2.layout == h.layout and h2.meta == h.meta
    assert h2.trace == h.trace
    x = np.random.default_rng(2).standard_normal(80).astype(np.float32)
    bit_equal(ops.spmv(h2, jnp.asarray(x), use_pallas=False),
              ops.spmv(h, jnp.asarray(x), use_pallas=False))

    # the plan crosses a jit boundary as a pytree argument
    @jax.jit
    def f(plan, v):
        return ops.spmv(plan, v, use_pallas=False)

    bit_equal(f(h, jnp.asarray(x)),
              ops.spmv(h, jnp.asarray(x), use_pallas=False))

    # vmap over a batch of vectors with the plan closed over / unmapped
    X = np.random.default_rng(3).standard_normal((5, 80)).astype(np.float32)
    Y = jax.vmap(lambda v: ops.spmv(h, v, use_pallas=False))(jnp.asarray(X))
    np.testing.assert_allclose(np.asarray(Y), X @ d.T, atol=2e-4)
    Y2 = jax.vmap(f, in_axes=(None, 0))(h, jnp.asarray(X))
    bit_equal(Y, Y2)


def test_test_split_plan_pytree_roundtrip():
    csr = matgen.powerlaw(300, 5, seed=9)
    mat = F.csr_to_spc5(csr, 1, 8)
    ht = ops.prepare(mat, layout="test", multi_layout="panels",
                     dtype=np.float32, pr=16, xw=32, cb=8)
    assert ht.layout == P.LAYOUT_TEST and ht.multi.layout == P.LAYOUT_PANELS
    flat, tdef = jax.tree.flatten(ht)
    ht2 = jax.tree.unflatten(tdef, flat)
    assert ht2.multi.meta == ht.multi.meta
    x = np.random.default_rng(4).standard_normal(300).astype(np.float32)
    bit_equal(ops.spmv_test(ht2, jnp.asarray(x), use_pallas=False),
              ops.spmv_test(ht, jnp.asarray(x), use_pallas=False))


# ----------------------------------------------------------------------------
# Equivalence suite: legacy entry points == pre-refactor computation, bitwise
# ----------------------------------------------------------------------------

def _old_whole_spmv(mat, x, cb, reo=None):
    """The pre-refactor SPC5Handle/SPC5ReorderedHandle jnp path, verbatim:
    to_chunked (+ fused chunk_row for interval-contiguous row perms) +
    R.spmv, with explicit col gather / row scatter."""
    rows_fused = False
    if reo is not None:
        mat = reo.permute_spc5(mat)
    ch = F.to_chunked(mat, cb=cb)
    if (reo is not None and not reo.identity_rows
            and reo.rows_interval_contiguous(mat.r)):
        ch = dataclasses.replace(
            ch, chunk_row=reo.row_perm[ch.chunk_row].astype(np.int32))
        rows_fused = True
    dev = R.device_put(ch, dtype=np.float32)
    xg = x if reo is None or reo.identity_cols else \
        jnp.take(x, jnp.asarray(reo.col_perm.astype(np.int32)), axis=0)
    y = R.spmv(dev, xg, r=ch.r, c=ch.c, nrows=ch.nrows, ncols=ch.ncols)
    if reo is not None and not rows_fused and not reo.identity_rows:
        y = jnp.take(y, jnp.asarray(reo.row_iperm.astype(np.int32)), axis=0)
    return y


def _old_panels_spmv(mat, x, pr, cb, xw, reo=None):
    """The pre-refactor SPC5PanelHandle jnp path: to_panels + R.spmv_panels
    with explicit jnp.take gathers."""
    if reo is not None:
        mat = reo.permute_spc5(mat)
    pan = F.to_panels(mat, pr=pr, cb=cb, xw=xw)
    dev = R.device_put_panels(pan, dtype=np.float32)
    xg = x if reo is None or reo.identity_cols else \
        jnp.take(x, jnp.asarray(reo.col_perm.astype(np.int32)), axis=0)
    y = R.spmv_panels(dev, xg, r=pan.r, c=pan.c, pr=pan.pr, nrows=pan.nrows,
                      ncols_pad=pan.ncols_pad)
    if reo is not None and not reo.identity_rows:
        y = jnp.take(y, jnp.asarray(reo.row_iperm.astype(np.int32)), axis=0)
    return y


def test_prepare_equivalence_whole_and_panels():
    csr, d = rand_csr(160, 160, 0.12, seed=11)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(160),
                    jnp.float32)
    mat = F.csr_to_spc5(csr, 2, 4)
    # whole-vector, no reorder
    h = ops.prepare(mat, cb=64, layout="whole_vector", dtype=np.float32)
    bit_equal(ops.spmv(h, x, use_pallas=False), _old_whole_spmv(mat, x, 64))
    # panels, no reorder
    hp = ops.prepare(mat, layout="panels", pr=16, xw=32, cb=8,
                     dtype=np.float32)
    bit_equal(ops.spmv(hp, x, use_pallas=False),
              _old_panels_spmv(mat, x, 16, 8, 32))
    # the unified panels call is the same plan, bit-identical
    bit_equal(ops.spmv(ops.prepare(mat, layout="panels", pr=16, cb=8, xw=32,
                                   dtype=np.float32, tune=False), x,
                       use_pallas=False),
              ops.spmv(hp, x, use_pallas=False))
    # and the answers are right
    tgt = d.astype(np.float64) @ np.asarray(x, np.float64)
    np.testing.assert_allclose(np.asarray(ops.spmv(h, x, use_pallas=False)),
                               tgt, atol=2e-3)


def test_prepare_equivalence_with_reorder():
    csr = matgen.scrambled_banded(192, 5, 1.0, seed=7)
    d = csr.to_dense()
    x = jnp.asarray(np.random.default_rng(2).standard_normal(192),
                    jnp.float32)
    for rc, layout in (((2, 4), "whole_vector"), ((1, 8), "panels")):
        mat = F.csr_to_spc5(csr, *rc)
        # the reordering prepare() resolves, rebuilt identically here
        reo = RE.reorder(mat, "rcm", r=mat.r, c=mat.c, pr=16, xw=32, cb=8)
        assert not reo.is_identity
        h = ops.prepare(mat, layout=layout, pr=16, xw=32, cb=8,
                        dtype=np.float32, reorder="rcm")
        assert h.is_reordered
        old = (_old_whole_spmv(mat, x, 8, reo=reo)
               if layout == "whole_vector"
               else _old_panels_spmv(mat, x, 16, 8, 32, reo=reo))
        bit_equal(ops.spmv(h, x, use_pallas=False), old)
        np.testing.assert_allclose(
            np.asarray(ops.spmv(h, x, use_pallas=False)),
            d.astype(np.float64) @ np.asarray(x, np.float64), atol=2e-3)


def test_deprecated_shims_warn_and_match():
    """The pre-redesign entry points survive as DeprecationWarning shims
    whose plans are bit-identical to the unified keyword calls."""
    csr, _ = rand_csr(96, 96, 0.15, seed=31)
    mat = F.csr_to_spc5(csr, 2, 4)
    x = jnp.asarray(np.random.default_rng(8).standard_normal(96),
                    jnp.float32)
    with pytest.warns(DeprecationWarning, match="prepare_panels"):
        hs = ops.prepare_panels(mat, pr=16, cb=8, xw=32, dtype=np.float32)
    hu = ops.prepare(mat, layout="panels", pr=16, cb=8, xw=32,
                     dtype=np.float32, tune=False)
    bit_equal(ops.spmv(hs, x, use_pallas=False),
              ops.spmv(hu, x, use_pallas=False))
    with pytest.warns(DeprecationWarning, match="prepare_test"):
        hs = ops.prepare_test(mat, cb=64, dtype=np.float32)
    hu = ops.prepare(mat, layout="test", cb=64, dtype=np.float32)
    bit_equal(ops.spmv_test(hs, x, use_pallas=False),
              ops.spmv_test(hu, x, use_pallas=False))
    with pytest.warns(DeprecationWarning, match="shard_matrix_panels"):
        shs = D.shard_matrix_panels(mat, 2, pr=16, cb=8, xw=32)
    shu = D.shard_matrix(mat, 2, layout="panels", pr=16, cb=8, xw=32,
                         tune=False)
    for a, b in zip(shs.arrays, shu.arrays):
        bit_equal(a, b)
    bit_equal(shs.row_start, shu.row_start)


def test_prepare_config_takes_panelconfig_whole(rowwise_close):
    """ops.prepare(config=...) replays a tuned decision verbatim: layout,
    geometry, and value dtype come from the PanelConfig and tuning is
    bypassed (the serving tier's cache-miss build path)."""
    csr, d = rand_csr(96, 96, 0.15, seed=33)
    mat = F.csr_to_spc5(csr, 2, 4)
    cfg = S.PanelConfig("panels", 16, 32, 8, vdtype="bf16")
    h = ops.prepare(mat, config=cfg)
    assert h.layout == P.LAYOUT_PANELS
    assert h.pr == 16 and h.xw == 32 and h.cb == 8
    assert dict(h.meta)["vdtype"] == "bf16"
    assert h.trace[0]["source"] == "explicit"   # tuning bypassed
    # explicit keywords beat the config's fields
    h2 = ops.prepare(mat, config=cfg, vdtype="f32")
    assert dict(h2.meta)["vdtype"] == "f32" and h2.cb == 8
    x = np.random.default_rng(9).standard_normal(96).astype(np.float32)
    y2 = ops.spmv(h2, jnp.asarray(x), use_pallas=False)
    rowwise_close(y2, d, x, 1e-6)
    rowwise_close(ops.spmv(h, jnp.asarray(x), use_pallas=False), d, x,
                  2.0 ** -7, ref=y2)


def test_prepare_test_equivalence():
    csr = matgen.powerlaw(320, 5, seed=13)
    d = csr.to_dense()
    x = jnp.asarray(np.random.default_rng(3).standard_normal(320),
                    jnp.float32)
    mat = F.csr_to_spc5(csr, 2, 4)
    # flat tail (whole-vector multi): old path = prepare(multi) + spmv_coo
    ht = ops.prepare(mat, layout="test", cb=64, dtype=np.float32)
    assert ht.tail_pr == 0
    split = F.split_singletons(mat)
    y_old = _old_whole_spmv(split.multi, x, 64) + R.spmv_coo(
        jnp.asarray(split.single_rows), jnp.asarray(split.single_cols),
        jnp.asarray(split.single_values.astype(np.float32)), x, nrows=320)
    bit_equal(ops.spmv_test(ht, x, use_pallas=False), y_old)
    # panel tail: old path = panels multi + spmv_coo_panels buckets
    htp = ops.prepare(mat, layout="test", multi_layout="panels",
                      dtype=np.float32, pr=16, xw=32, cb=8)
    assert htp.tail_pr == 16
    y_tail = R.spmv_coo_panels(htp.single_rows, htp.single_cols,
                               htp.single_values, x, pr=16,
                               nrows=320)
    y_oldp = _old_panels_spmv(split.multi, x, 16, 8, 32) + y_tail
    bit_equal(ops.spmv_test(htp, x, use_pallas=False), y_oldp)
    np.testing.assert_allclose(
        np.asarray(ops.spmv_test(htp, x, use_pallas=False)),
        d.astype(np.float64) @ np.asarray(x, np.float64), atol=2e-3)


def test_pallas_tail_kernel_matches_oracle():
    """Satellite: the test layout's registered Pallas tail lowering vs the
    spmv_coo_panels oracle, bitwise on the shared contributions."""
    csr = matgen.powerlaw(320, 5, seed=17)
    mat = F.csr_to_spc5(csr, 2, 4)
    ht = ops.prepare(mat, layout="test", multi_layout="panels",
                     dtype=np.float32, pr=16, xw=32, cb=8)
    assert ht.tail_pr and ht.single_values.size
    assert ht.tail_xw % 8 == 0 and ht.tail_xbase.shape == (ht.multi.npanels,)
    x = jnp.asarray(np.random.default_rng(5).standard_normal(320),
                    jnp.float32)
    y_oracle = R.spmv_coo_panels(ht.single_rows, ht.single_cols,
                                 ht.single_values, x, pr=ht.tail_pr,
                                 nrows=320)
    y_pallas = spc5_spmv.spmv_tail_pallas(
        ht.tail_xbase, ht.single_rows, ht.single_cols, ht.single_values, x,
        pr=ht.tail_pr, xw=ht.tail_xw, nrows=320,
        ncols_pad=ht.tail_ncols_pad, interpret=True)
    np.testing.assert_allclose(np.asarray(y_pallas), np.asarray(y_oracle),
                               atol=1e-6)
    # and through the executor (use_pallas=True routes the tail here)
    y_exec = ops.spmv_test(ht, x, use_pallas=True, interpret=True)
    y_ref = ops.spmv_test(ht, x, use_pallas=False)
    np.testing.assert_allclose(np.asarray(y_exec), np.asarray(y_ref),
                               atol=1e-5)


def test_from_dense_equivalence():
    rng = np.random.default_rng(19)
    w = rng.standard_normal((96, 80)).astype(np.float32)
    sl = SparseLinear.from_dense(w, density=0.25, block=(2, 4), cb=32,
                                 dtype=np.float32)
    # the layer's handle is bit-identical to prepare() on the pruned matrix
    wp = prune_by_magnitude(w, 0.25)
    mat = F.csr_to_spc5(F.csr_from_dense(wp), 2, 4)
    h = ops.prepare(mat, cb=32, dtype=np.float32)
    assert sl.handle.layout == h.layout and sl.handle.meta == h.meta
    x = jnp.asarray(rng.standard_normal(80), jnp.float32)
    bit_equal(ops.spmv(sl.handle, x, use_pallas=False),
              ops.spmv(h, x, use_pallas=False))
    X = jnp.asarray(rng.standard_normal((80, 4)), jnp.float32)
    bit_equal(ops.spmm(sl.handle, X, use_pallas=False),
              ops.spmm(h, X, use_pallas=False))
    # with reorder= the layer still matches the pruned dense product
    sl_r = SparseLinear.from_dense(w, density=0.25, block=(2, 4),
                                   dtype=np.float32, reorder="sigma",
                                   layout="panels", pr=16, xw=32, cb=8)
    xb = rng.standard_normal((3, 80)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(sl_r(jnp.asarray(xb))),
                               xb @ wp.T, atol=1e-4)


def _old_make_distributed_spmv(sh, mesh, gather=True):
    """The pre-refactor make_distributed_spmv, verbatim: layout-branched
    shard_map bodies over the stacked arrays (the replica the generic
    registry-driven executor must match bitwise)."""
    from jax.sharding import PartitionSpec as PS

    panels = sh.layout == P.LAYOUT_PANELS
    axis = "data"

    def finish(y_loc, row_start):
        if not gather:
            return y_loc[None]
        ys = jax.lax.all_gather(y_loc, axis)
        starts = jax.lax.all_gather(row_start[0], axis)
        idx = starts[:, None] + jnp.arange(sh.rows_max)[None, :]
        y = jnp.zeros((sh.nrows + sh.rows_max,), dtype=ys.dtype)
        y = y.at[idx.reshape(-1)].add(ys.reshape(-1))
        return y[:sh.nrows]

    if panels:
        def body(values, col, mask, voff, row, vbase, xbase, row_start, x):
            dev = R.SPC5PanelDevice(values[0], col[0], mask[0], voff[0],
                                    row[0], vbase[0], xbase[0])
            y_loc = R.spmv_panels(dev, x, r=sh.r, c=sh.c, pr=sh.pr,
                                  nrows=sh.rows_max, ncols_pad=sh.ncols_pad)
            return finish(y_loc, row_start)
        in_specs = (PS(axis),) * 8 + (PS(),)
    else:
        def body(values, col, mask, voff, row, vbase, row_start, x):
            dev = R.SPC5Device(values[0], col[0], mask[0], voff[0], row[0],
                               vbase[0])
            y_loc = R.spmv(dev, x, r=sh.r, c=sh.c, nrows=sh.rows_max,
                           ncols=sh.ncols)
            return finish(y_loc, row_start)
        in_specs = (PS(axis),) * 7 + (PS(),)

    out_specs = PS() if gather else PS(axis)
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)

    def run(x):
        if sh.col_perm is not None:
            x = jnp.take(x, sh.col_perm, axis=0)
        y = fn(*sh.arrays, sh.row_start, x)
        if gather and sh.row_iperm is not None:
            y = jnp.take(y, sh.row_iperm, axis=0)
        return y

    return jax.jit(run)


def test_shard_matrix_equivalence():
    from jax.sharding import Mesh

    csr = matgen.scrambled_banded(144, 5, 1.0, seed=23)
    mat = F.csr_to_spc5(csr, 1, 8)
    x = jnp.asarray(np.random.default_rng(6).standard_normal(144),
                    jnp.float32)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    cases = [dict(cb=32), dict(pr=16, cb=8, xw=32),
             dict(cb=32, reorder="rcm", tune=False),
             dict(pr=16, cb=8, xw=32, reorder="rcm", tune=False),
             dict(config=S.PanelConfig("panels", 16, 32, 8), tune=False),
             dict(config=S.PanelConfig("whole_vector", 0, 0, 64),
                  tune=False)]
    tgt = csr.to_dense().astype(np.float64) @ np.asarray(x, np.float64)
    for kw in cases:
        sh = D.shard_matrix(mat, 1, mesh=mesh, **kw)
        y_new = D.make_distributed_spmv(sh, mesh)(x)
        y_old = _old_make_distributed_spmv(sh, mesh)(x)
        bit_equal(y_new, y_old)
        np.testing.assert_allclose(np.asarray(y_new), tgt, atol=2e-3)


# ----------------------------------------------------------------------------
# Trace golden
# ----------------------------------------------------------------------------

def test_plan_trace_golden():
    csr, _ = rand_csr(64, 64, 0.2, seed=29)
    mat = F.csr_to_spc5(csr, 2, 4)
    h = ops.prepare(mat, dtype=np.float32)
    assert [e["pass"] for e in h.trace] == ["tune", "reorder", "layout",
                                            "build"]
    # every pass entry records its wall-time next to its decision
    assert all(e["duration_s"] >= 0 for e in h.trace)
    tune, reo, lay, build = h.trace
    assert tune.pop("duration_s") is not None
    assert tune == {"pass": "tune", "source": "no-store"}
    assert reo.pop("duration_s") is not None
    assert reo == {"pass": "reorder", "strategy": "", "applied": False}
    assert lay["pass"] == "layout" and lay["layout"] == "whole_vector"
    assert lay["reason"] == "vmem-fit"
    # one lowering: the plan reads it as a constant, no pass decides it
    assert "lowering" not in lay and "lowering" not in build
    assert h.lowering == "mask"
    assert build["layout"] == "whole_vector" and build["cb"] == 256
    assert build["rows_fused"] is False and build["nnz"] == mat.nnz
    # the trace is stable JSON in the static aux -> jit-cache friendly
    assert h.trace_json == json.dumps(h.trace, sort_keys=True)

    # tuned + reordered golden
    store = S.RecordStore()
    cfg = S.PanelConfig("panels", 16, 32, 8, reorder="rcm")
    for avg in (1.0, 4.0, 8.0):
        f = S.MatrixFeatures(0, 0, 0, 5.0, 2.0, avg, 0.5)
        store.add_measurement("1x8", f, cfg, 1, 9.0, matrix="m")
    scr = matgen.scrambled_banded(96, 4, 1.0, seed=31)
    h2 = ops.prepare(F.csr_to_spc5(scr, 1, 8), dtype=np.float32, store=store)
    t2 = h2.trace
    assert t2[0]["source"] == "store" and t2[0]["reorder"] == "rcm"
    assert (t2[0]["layout"], t2[0]["pr"], t2[0]["xw"], t2[0]["cb"]) \
        == ("panels", 16, 32, 8)
    assert t2[1]["pass"] == "reorder" and t2[1]["applied"] is True
    assert t2[1]["strategy"] == "rcm" and t2[1]["stats"]["applied"] == 1.0
    assert "lowering" not in t2[0]
    lay2 = dict(t2[2])
    assert lay2.pop("duration_s") >= 0
    assert lay2 == {"pass": "layout", "layout": "panels",
                    "reason": "requested", "vdtype": ""}
    assert h2.strategy == "rcm" and h2.is_reordered
    # the test split delegates tuning to its multi sub-plan
    ht = ops.prepare(F.csr_to_spc5(scr, 1, 8), layout="test",
                     multi_layout="panels", dtype=np.float32, pr=16, xw=32,
                     cb=8)
    ht_tune = dict(ht.trace[0])
    assert ht_tune.pop("duration_s") >= 0
    assert ht_tune == {"pass": "tune", "source": "delegated"}
    assert [e["pass"] for e in ht.multi.trace] == ["tune", "reorder",
                                                   "layout", "build"]


def test_shard_plan_trace():
    csr = matgen.banded(200, 4, 1.0, seed=37)
    sh = D.shard_matrix(F.csr_to_spc5(csr, 1, 8), 2, cb=32, tune=False)
    assert [e["pass"] for e in sh.trace] == ["tune", "reorder",
                                            "partition", "shard"]
    # the shard pipeline's entries carry per-pass wall-time too
    assert all(e["duration_s"] >= 0 for e in sh.trace)
    part, shard = sh.trace[2:]
    assert part["mode"] in ("blocks", "nnz")
    assert "skew_blocks" in part and "skew_nnz" in part   # "auto" evidence
    assert shard["layout"] == "whole_vector"
    assert shard["ndev"] == 2 and shard["cb"] == 32
    assert not any(k.endswith("demoted") for k in shard)
    assert "lowering" not in shard and sh.lowering == "mask"


# ----------------------------------------------------------------------------
# TPU eligibility: only kernels Mosaic compiles are picked on a TPU backend
# ----------------------------------------------------------------------------

def _small_mat():
    return F.csr_to_spc5(matgen.banded(256, 4, 1.0, seed=5), 1, 8)


def test_tpu_auto_resolves_to_mosaic_kernels(monkeypatch, rowwise_close):
    """A matrix whose vectors fit the whole-vector budget resolves to the
    panels mask kernel on a TPU, with the skipped layout traced. The
    kernel reassociates a row's adds against the jnp path, so the two
    agree within ``1e-6 * |A| |x|`` per row."""
    from repro.analysis.verify import verify_plan
    mat = _small_mat()
    assert ops.prepare(mat, tune=False).layout == P.LAYOUT_WHOLE
    monkeypatch.setattr(P, "_on_tpu", lambda: True)
    plan = ops.prepare(mat, tune=False)
    assert (plan.layout, plan.lowering) == (P.LAYOUT_PANELS, P.LOWERING_MASK)
    entry = next(e for e in plan.trace if e["pass"] == "layout")
    assert entry["layout_demoted"] is True
    assert entry["layout_demoted_reason"] == "no-mosaic-kernel:whole_vector"
    assert verify_plan(plan).ok
    x = jnp.asarray(np.random.default_rng(0).standard_normal(256),
                    jnp.float32)
    rowwise_close(ops.spmv(plan, x, use_pallas=True, interpret=True),
                  mat.to_dense(), x, 1e-6,
                  ref=ops.spmv(plan, x, use_pallas=False))


@pytest.mark.parametrize("request_kw", [dict(layout="whole_vector"),
                                        dict(layout="test")])
def test_tpu_explicit_uncompilable_request_raises(monkeypatch, request_kw):
    monkeypatch.setattr(P, "_on_tpu", lambda: True)
    with pytest.raises(ValueError, match="compiles for a TPU"):
        ops.prepare(_small_mat(), tune=False, **request_kw)


@pytest.mark.parametrize("request_kw", [dict(layout="whole_vector"),
                                        dict(layout="test")])
def test_compiled_call_without_mosaic_kernel_raises(request_kw):
    """Outside interpret mode, a plan without a Mosaic kernel refuses the
    Pallas path instead of handing Mosaic a kernel it cannot lower."""
    plan = ops.prepare(_small_mat(), tune=False, **request_kw)
    with pytest.raises(NotImplementedError, match="compiles for a TPU"):
        ops.spmv(plan, jnp.ones(256, jnp.float32), use_pallas=True,
                 interpret=False)
