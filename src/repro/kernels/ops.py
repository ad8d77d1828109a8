"""Public SpMV/SpMM entry points, now thin wrappers over ``repro.core.plan``.

Historically this module owned four handle classes (whole-vector, panel,
reordered, beta_test) and three prepare entry points dispatching between
them; all of that lives in the execution-plan architecture now (layout
registry + composable passes + one executor -- see ``repro.core.plan`` and
``docs/architecture.md``), behind ONE keyword-driven entry point:

  * :func:`prepare` runs the plan pipeline (tune -> reorder -> layout ->
    build) and returns an :class:`~repro.core.plan.SPC5Plan` -- a pytree
    handle satisfying the old handle APIs (``.dev``, geometry attributes,
    ``.multi`` / ``.single_values`` for the test split, ``.strategy`` /
    ``.stats`` / ``.rows_fused`` for reordered plans). Every axis is a
    keyword: ``layout`` (incl. "test" for the beta_test split),
    ``reorder``, ``config`` (a tuned/explicit ``selector.PanelConfig``
    taken whole), ``verify``. Every layout decodes the paper's bit masks;
    which kernel runs follows from the layout alone.
  * :func:`spmv` / :func:`spmm` / :func:`spmv_test` route to the plan
    executor, which dispatches through the layout registry (the only place
    layout branching exists).

:func:`prepare_panels` and :func:`prepare_test` remain as deprecation
shims over :func:`prepare` (``DeprecationWarning``; the lint rule
``no-deprecated-entry-points`` keeps them out of in-tree non-test callers).

The legacy class names are aliases of ``SPC5Plan``; inspect ``plan.layout``
(a ``repro.core.plan`` registry key) or ``plan.trace`` to discriminate.
"""
from __future__ import annotations

import warnings
from typing import Optional, Union

import jax

from repro.core import formats as F
from repro.core import plan as P
from repro.core import reorder as RE
from repro.core import selector as S

# Canonical layout keys (re-exported for call sites and tests).
LAYOUT_WHOLE = P.LAYOUT_WHOLE
LAYOUT_PANELS = P.LAYOUT_PANELS
LAYOUT_TEST = P.LAYOUT_TEST

# The four pre-plan handle classes, now one: every entry point returns an
# SPC5Plan and the executor dispatches on its registry key.
SPC5Plan = P.SPC5Plan
SPC5Handle = P.SPC5Plan
SPC5PanelHandle = P.SPC5Plan
SPC5ReorderedHandle = P.SPC5Plan
SPC5TestHandle = P.SPC5Plan

VMEM_WHOLE_VECTOR_BUDGET = P.VMEM_WHOLE_VECTOR_BUDGET
fits_whole_vector = P.fits_whole_vector


def prepare(mat: F.SPC5Matrix, *, layout: str = "auto",
            reorder: Union[None, str, RE.Reordering] = None,
            config: Optional[S.PanelConfig] = None, verify=False,
            pr: Optional[int] = None, xw: Optional[int] = None,
            cb: Optional[int] = None, nvec: int = 1, align: int = 8,
            dtype=None, vdtype: str = "auto",
            store: Optional[S.RecordStore] = None,
            tune: bool = True, multi_layout: str = "auto") -> P.SPC5Plan:
    """Build an execution plan for ``mat`` -- the one prepare entry point.

    ``layout``: a registry key ("whole_vector", "panels", "test"), a legacy
    alias ("whole"), or "auto" (default) -- auto picks whole-vector when x
    and y fit the VMEM budget (:func:`fits_whole_vector`) and panels
    otherwise. ``layout="test"`` builds the beta(r,c)_test split (multi-nnz
    blocks in the ``multi_layout`` block layout + the singleton COO tail,
    panel-bucketed with a Pallas tail kernel when the multi part resolves
    to panels). Pass ``nvec`` (widest SpMM batch this plan will see) so
    "auto" budgets the nvt-wide SpMM tiles, not just the SpMV vectors.

    **Explicit config**: ``config`` takes a ``selector.PanelConfig`` whole
    -- its layout/geometry/reorder/vdtype fill every axis the caller left
    at its default, and tuning is bypassed (the programmatic analogue of a
    fully explicit call; the serving tier's cached-decision replay path).

    **Auto-tuning**: when nothing is requested explicitly (``layout="auto"``
    and ``pr``/``xw``/``cb`` all None) and a record store is available --
    passed as ``store``, installed via ``selector.set_default_store``, or
    named by ``$SPC5_RECORDS`` -- the configuration comes from
    ``selector.tune`` fitted on that store's measurements for this block
    geometry, clamped against this matrix's dims. Any explicit argument is
    an escape hatch that bypasses tuning entirely (``tune=False`` disables
    it outright).

    **Reordering**: ``reorder`` is a strategy name ("sigma", "rcm",
    "colwindow", "auto", "none"; see ``repro.core.reorder``) or a prebuilt
    ``Reordering``. Strategies are scored at the geometry in effect and may
    decline (the plan comes back unpermuted). When the caller passes no
    ``reorder`` and the tuner's best record carries one, that strategy is
    applied. Every decision lands in the returned ``plan.trace``.

    ``pr``/``xw`` default to 512; ``cb=None`` uses the layout's default
    chunk size (256 whole-vector, ``formats.PANEL_CB`` = 128 panels).

    **Value dtype**: ``vdtype`` selects the stored value dtype -- "f32"
    (explicit float32 store), "bf16" (half-width store, f32 accumulate),
    "int8" (per-chunk symmetric quantisation with f32 scales, f32
    accumulate), or "auto" (default: the tuner's pick when a store carries
    quantised measurements, else the legacy ``dtype=`` passthrough).
    Quantised plans upcast inside the kernel decode; the output dtype never
    narrows. ``vdtype`` and a non-default ``dtype=`` are mutually exclusive.

    **Kernels**: every layout decodes the paper's bit masks per execution
    (``plan.lowering`` reads "mask"). On a TPU backend only layouts whose
    ``LayoutSpec.mosaic`` flag is set -- the panels layout -- are picked;
    an explicit request for another raises.

    **Verification**: ``verify=True`` statically proves the finished plan's
    format/plan invariants (``repro.analysis.verify``) and raises on any
    violation; a callable receives the ``VerifyReport`` instead.
    """
    if config is not None:
        if layout == "auto":
            layout = config.layout or "auto"
        pr = pr if pr is not None else (config.pr or None)
        xw = xw if xw is not None else (config.xw or None)
        cb = cb if cb is not None else (config.cb or None)
        if reorder is None and config.reorder:
            reorder = config.reorder
        if vdtype == "auto" and config.vdtype and config.vdtype != "f32":
            vdtype = config.vdtype
        # no tune=False needed: the config's layout is explicit, which
        # already bypasses the store in the tune pass (trace: "explicit")
    layout = P.canonical_layout(layout)
    if layout == P.LAYOUT_TEST:
        return P.make_plan(mat, layout=P.LAYOUT_TEST,
                           multi_layout=multi_layout, pr=pr, xw=xw, cb=cb,
                           nvec=nvec, align=align, dtype=dtype,
                           vdtype=vdtype, store=store,
                           tune=tune, reorder=reorder, verify=verify)
    return P.make_plan(mat, layout=layout, pr=pr, xw=xw, cb=cb, nvec=nvec,
                       align=align, dtype=dtype, vdtype=vdtype, store=store,
                       tune=tune, reorder=reorder, verify=verify)


def prepare_panels(mat: F.SPC5Matrix, pr: int = 512, cb: int = F.PANEL_CB,
                   xw: int = 512, align: int = 8, dtype=None,
                   verify=False) -> P.SPC5Plan:
    """Deprecated: use ``prepare(mat, layout="panels", pr=..., cb=...,
    xw=..., tune=False)`` -- kept as a thin shim (same semantics: explicit
    geometry, no tuning)."""
    warnings.warn(
        "ops.prepare_panels is deprecated; use ops.prepare(mat, "
        "layout='panels', pr=..., cb=..., xw=..., tune=False)",
        DeprecationWarning, stacklevel=2)
    return prepare(mat, layout=P.LAYOUT_PANELS, pr=pr, cb=cb, xw=xw,
                   align=align, dtype=dtype, tune=False, verify=verify)


def prepare_test(mat: F.SPC5Matrix, cb: Optional[int] = None, align: int = 8,
                 dtype=None, layout: str = "auto", pr: Optional[int] = None,
                 xw: Optional[int] = None, nvec: int = 1,
                 store: Optional[S.RecordStore] = None, tune: bool = True,
                 reorder: Union[None, str, RE.Reordering] = None,
                 verify=False) -> P.SPC5Plan:
    """Deprecated: use ``prepare(mat, layout="test", multi_layout=...)`` --
    kept as a thin shim (its old ``layout`` argument is the multi-block
    sub-plan's layout request)."""
    warnings.warn(
        "ops.prepare_test is deprecated; use ops.prepare(mat, "
        "layout='test', multi_layout=...)",
        DeprecationWarning, stacklevel=2)
    return prepare(mat, layout=P.LAYOUT_TEST, multi_layout=layout, pr=pr,
                   xw=xw, cb=cb, nvec=nvec, align=align, dtype=dtype,
                   store=store, tune=tune, reorder=reorder, verify=verify)


def spmv(h: P.SPC5Plan, x: jax.Array, *, use_pallas: Optional[bool] = None,
         interpret: Optional[bool] = None) -> jax.Array:
    """y = A @ x for any plan layout -- x and y are always in ORIGINAL index
    order; permutation gathers happen inside the executor."""
    return P.execute_spmv(h, x, use_pallas=use_pallas, interpret=interpret)


def spmm(h: P.SPC5Plan, x: jax.Array, *, use_pallas: Optional[bool] = None,
         nvt: int = 128, interpret: Optional[bool] = None) -> jax.Array:
    """Y = A @ X, X of shape (ncols, nvec), for any plan layout."""
    return P.execute_spmm(h, x, use_pallas=use_pallas, nvt=nvt,
                          interpret=interpret)


def spmv_test(h: P.SPC5Plan, x: jax.Array, **kw) -> jax.Array:
    """y = A @ x over the beta_test split (same executor as :func:`spmv`;
    kept as a named entry point for API compatibility)."""
    return P.execute_spmv(h, x, **kw)
