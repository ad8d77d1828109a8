"""The library's own spans: the executor's ``exec.*`` span and its kernel
grid-step count, and the set-up spans of the format conversions.

* every ``ops.spmv`` / ``ops.spmm`` records exactly one ``exec.spmv`` /
  ``exec.spmm`` span, whose ``grid_steps`` is the product of the grid of
  every ``pallas_call`` the call makes (0 on the jnp path), and whose
  ``chunk_steps`` and ``chunks_per_step`` count the chunks those steps
  walk;
* ``csr_to_spc5`` records one ``convert`` span and ``to_panels`` one
  ``panels`` span, each with one child per phase -- never one per panel
  or per chunk.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro import obs
from repro.core import formats as F
from repro.kernels import ops


@pytest.fixture
def registry():
    reg = obs.Registry()
    prev = obs.set_registry(reg)
    try:
        yield reg
    finally:
        obs.set_registry(prev)


@pytest.fixture
def launched_grids(monkeypatch):
    """The grid of every ``pallas_call`` traced while the test runs."""
    grids = []
    orig = pl.pallas_call

    def recording(*args, **kw):
        grid = kw["grid"] if "grid" in kw else kw["grid_spec"].grid
        grids.append(tuple(grid))
        return orig(*args, **kw)

    monkeypatch.setattr(pl, "pallas_call", recording)
    return grids


def _matrix(nrows=96, ncols=80, density=0.12, seed=0):
    rng = np.random.default_rng(seed)
    d = ((rng.random((nrows, ncols)) < density)
         * rng.standard_normal((nrows, ncols))).astype(np.float32)
    return d, F.csr_to_spc5(F.csr_from_dense(d), 1, 8)


def _plan(layout, cb, mat):
    if layout == "test":
        return ops.prepare(mat, layout="test", multi_layout="panels",
                           pr=16, cb=cb, xw=16, tune=False)
    geom = (dict(pr=16, cb=cb, xw=16) if layout == "panels"
            else dict(cb=cb))
    return ops.prepare(mat, layout=layout, tune=False, **geom)


#: (layout, chunk width, op): a narrow chunk, and the 128 lanes of
#: F.PANEL_CB that ``panel_step_us`` divides a chip's kernel time by.
CASES = [(layout, cb, op)
         for layout in ("whole_vector", "panels", "test")
         for cb in (8, F.PANEL_CB)
         for op in ("spmv", "spmm")]


@pytest.mark.parametrize("layout,cb,op", CASES)
def test_exec_span_counts_the_kernel_grid_steps(layout, cb, op,
                                                registry, launched_grids):
    d, mat = _matrix()
    plan = _plan(layout, cb, mat)
    rng = np.random.default_rng(1)
    if op == "spmv":
        x = jnp.asarray(rng.standard_normal(d.shape[1]), jnp.float32)
        call = lambda: ops.spmv(plan, x, use_pallas=True, interpret=True)
        want = d @ np.asarray(x)
    else:
        x = jnp.asarray(rng.standard_normal((d.shape[1], 16)), jnp.float32)
        call = lambda: ops.spmm(plan, x, use_pallas=True, nvt=8,
                                interpret=True)
        want = d @ np.asarray(x)
    jax.clear_caches()              # so every kernel is traced, and seen
    before = len(registry.spans())
    y = call()
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-4)
    spans = [e for e in registry.spans()[before:]
             if e.name.startswith("exec.")]
    assert [e.name for e in spans] == [f"exec.{op}"]
    attrs = spans[0].attrs
    assert attrs["layout"] == plan.layout
    assert attrs["lowering"] == "mask"
    assert attrs["nvec"] == (1 if op == "spmv" else 16)
    assert launched_grids
    assert attrs["grid_steps"] == sum(math.prod(g) for g in launched_grids)
    if layout != "test":            # whose tail kernel walks no chunks
        assert attrs["chunk_steps"] == (attrs["grid_steps"]
                                        * attrs["chunks_per_step"])


def test_exec_span_on_the_jnp_path_counts_no_steps(registry):
    d, mat = _matrix()
    plan = _plan("panels", 8, mat)
    x = jnp.ones((d.shape[1],), jnp.float32)
    ops.spmv(plan, x, use_pallas=False)
    ops.spmm(plan, jnp.ones((d.shape[1], 4), jnp.float32), use_pallas=False)
    spans = [e for e in registry.spans() if e.name.startswith("exec.")]
    assert [(e.name, e.attrs["grid_steps"], e.attrs["chunk_steps"],
             e.attrs["chunks_per_step"]) for e in spans] == [
        ("exec.spmv", 0, 0, 0), ("exec.spmm", 0, 0, 0)]


def test_panel_grid_steps_of_a_spmv(registry):
    """The panels SpMV's count is (nvec // kt) * npanels * ceil(nchunks /
    G) with one vector to a tile, where a grid step walks G chunks: a
    whole panel's at this size. ``chunk_steps`` counts the chunks walked,
    the last group's padding included."""
    from repro.kernels import spc5_spmv
    d, mat = _matrix()
    plan = _plan("panels", 8, mat)
    ops.spmv(plan, jnp.ones((d.shape[1],), jnp.float32), use_pallas=True,
             interpret=True)
    (ev,) = [e for e in registry.spans() if e.name == "exec.spmv"]
    group = spc5_spmv.panel_group(dict(plan.meta), 4, 1)
    assert group == plan.nchunks > 1
    ngroups = -(-plan.nchunks // group)
    assert ev.attrs["grid_steps"] == plan.npanels * ngroups
    assert ev.attrs["chunk_steps"] == plan.npanels * group * ngroups
    assert ev.attrs["chunks_per_step"] == group


def _children(spans, parent):
    return [e.name for e in spans if e.parent_id == parent.span_id]


@pytest.mark.parametrize("nrows", [96, 640])
def test_setup_spans_are_per_phase(registry, nrows):
    """One ``convert`` and one ``panels`` span per call, three children
    each, however many panels and chunks the matrix has."""
    _, mat = _matrix(nrows=nrows)
    (conv,) = [e for e in registry.spans() if e.name == "convert"]
    assert _children(registry.spans(), conv) == [
        "convert.block_starts", "convert.bits", "convert.values"]
    assert conv.attrs["nnz"] == mat.nnz
    plan = _plan("panels", 8, mat)
    assert plan.npanels == -(-nrows // 16)
    spans = registry.spans()
    (pan,) = [e for e in spans if e.name == "panels"]
    assert _children(spans, pan) == [
        "panels.chunk_plan", "panels.assemble", "panels.values"]
    (build,) = [e for e in spans if e.name == "plan.build"]
    assert pan.parent_id == build.span_id
    assert len(spans) == 4 + 4 + 4      # convert, panels, plan.* passes
