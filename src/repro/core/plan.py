"""Execution plans: one layout registry + composable passes + one executor.

Before this module, every device layout came with its own handle class
(whole-vector, row-panel-tiled, reordered wrapper, beta_test split) and every
consumer -- ops, SparseLinear, the distributed path, serving, the benches --
re-implemented ``if layout == "panels"``-style dispatch. This module is the
single seam that replaces all of that:

  * **Registry** (:class:`LayoutSpec`, :func:`register_layout`): a layout is
    one registration carrying ``build`` / ``lower_spmv`` / ``lower_spmm`` /
    ``cost`` / ``clamp`` entries (plus sharding hooks). The registry's key
    set -- ``whole_vector``, ``panels``, ``test`` -- is the one source of
    truth for layout names everywhere (``selector.Record.layout``,
    ``PanelConfig.layout``, benchmark records); legacy spellings ("whole")
    are mapped by :func:`canonical_layout`.

  * **Plan** (:class:`SPC5Plan`): the single device handle. A frozen pytree
    whose leaves are the layout's device arrays (+ optional permutation
    vectors) and whose static aux holds the layout key, the geometry, and an
    inspectable ``trace`` of every pass decision. Layout-specific attributes
    (``pr``, ``vmax``, ``dev``, ``single_values``, ...) resolve through the
    geometry/registry, so the plan satisfies the legacy handle APIs.

  * **Passes** (:func:`make_plan` pipeline): ``tune`` (selector consult) ->
    ``reorder`` (permutation transform; carries ``col_map`` fusion and
    ``rows_fused`` decisions as plan metadata) -> ``layout`` (resolve "auto"
    via the registry's cost entries) -> ``build`` (registry build + fusion).
    Each pass appends its decision to ``plan.trace``.

  * **Executor** (:func:`execute_spmv` / :func:`execute_spmm`): the ONLY
    place that dispatches on the layout key -- it routes to the registered
    ``lower_*`` hook and applies the plan's inverse row permutation. The
    ``shard`` pass (:func:`shard_plan`) turns row slabs into per-device
    sub-arrays of the same registered layout; the executor runs a
    :class:`ShardedPlan` by handing each device's slab to that layout's own
    ``lower_spmv``, so the sharded path runs the single-device kernels.

Adding a layout is one :func:`register_layout` call -- see
``docs/architecture.md`` for the recipe.
"""
from __future__ import annotations

import dataclasses
import difflib
import functools
import hashlib
import json
import math
from typing import (Any, Callable, Dict, List, NamedTuple, Optional, Tuple,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import spc5_spmm, spc5_spmv

from . import formats as F
from . import ref_spmv as R
from . import reorder as RE
from . import selector as S

# ----------------------------------------------------------------------------
# Canonical layout names
# ----------------------------------------------------------------------------

LAYOUT_WHOLE = "whole_vector"
LAYOUT_PANELS = "panels"
LAYOUT_TEST = "test"

#: The one lowering: every layout's kernels decode the paper's bit masks
#: (bits -> cumsum ranks -> masked gathers) per execution. Plans still
#: report it as ``plan.lowering`` for the callers that record it.
LOWERING_MASK = "mask"


def _did_you_mean(name: str, candidates) -> str:
    """Typo hint for the canonicalizers' errors ('' when nothing is close)."""
    close = difflib.get_close_matches(str(name), list(candidates), n=1,
                                      cutoff=0.6)
    return f" -- did you mean {close[0]!r}?" if close else ""


#: Legacy spellings accepted by :func:`canonical_layout` (old JSONL stores
#: and pre-plan call sites used "whole" for the whole-vector layout).
_LAYOUT_ALIASES: Dict[str, str] = {
    "whole": LAYOUT_WHOLE,
}

#: Non-layout sentinels that pass through canonicalization untouched:
#: "auto" = let the layout pass pick, "" = unknown/legacy record.
_LAYOUT_SENTINELS = ("auto", "")


def canonical_layout(name: str) -> str:
    """Map a layout name to the registry's key set (one source of truth).

    Registry keys and the sentinels "auto"/"" pass through; legacy spellings
    are translated; anything else raises -- a tuned config or a record store
    can never smuggle an unknown layout past the pipeline.
    """
    if name in _LAYOUT_SENTINELS or name in _REGISTRY:
        return name
    if name in _LAYOUT_ALIASES:
        return _LAYOUT_ALIASES[name]
    raise ValueError(
        f"unknown layout {name!r}; expected one of {layout_names()} "
        f"(or a legacy alias {sorted(_LAYOUT_ALIASES)})"
        f"{_did_you_mean(name, list(_REGISTRY) + sorted(_LAYOUT_ALIASES))}")


# ----------------------------------------------------------------------------
# Registry
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayoutSpec:
    """One device layout, registered once, dispatched everywhere.

    ``array_names`` fixes the order of the plan's device arrays (and names
    them for attribute access); ``build(state)`` converts the host matrix to
    ``(arrays, geom, extra)``; ``lower_spmv``/``lower_spmm`` run the
    kernels (they own the column-permutation gather so layouts that can
    fuse it -- the whole-vector kernels' ``col_map`` input -- do);
    ``cost(nrows, ncols, itemsize, nvec)`` estimates the layout's VMEM
    footprint in bytes for "auto" selection; ``clamp`` validates a tuned
    configuration against a concrete matrix. ``shard_build`` is the
    distributed hook: it stacks per-device row slabs as host arrays (the
    shard pass places each slab on its device), each of which the
    layout's own ``lower_spmv`` runs inside shard_map. ``auto_eligible``
    excludes layouts (the beta_test split) from "auto" resolution.
    ``mosaic`` marks the layouts whose Pallas kernels Mosaic compiles for a
    TPU -- the one place that decides which kernel family a chip runs.
    """

    name: str
    array_names: Tuple[str, ...]
    build: Callable
    lower_spmv: Callable
    lower_spmm: Callable
    cost: Callable
    clamp: Callable
    default_cb: int
    device_view: Optional[Callable] = None
    shard_build: Optional[Callable] = None
    auto_eligible: bool = True
    #: True where the layout's Pallas kernels compile for a TPU. On a TPU
    #: backend "auto" resolves only to such layouts, an explicit request
    #: for another raises, and a compiled (non-interpret) call of another
    #: raises; the rest run in interpret mode and through the jnp path.
    mosaic: bool = False
    #: ``kernel_steps(plan, nvec, nvt, spmm)``: the :class:`KernelSteps`
    #: of the Pallas kernels one ``use_pallas`` call launches, which the
    #: executor's ``exec.*`` span reports (None reports zeros).
    kernel_steps: Optional[Callable] = None

    def plan_array_names(self, vdtype: str = "f32") -> Tuple[str, ...]:
        """Device-array names of a plan at ``vdtype``. The int8 value store
        rides a per-chunk f32 scale array alongside the layout's base
        arrays (only layouts with a packed ``values`` array quantise -- the
        test tail keeps full precision)."""
        names = self.array_names
        if vdtype == "int8" and "values" in names:
            names = names + ("value_scale",)
        return names


_REGISTRY: Dict[str, LayoutSpec] = {}

#: Preference order for "auto" resolution: the first registered layout whose
#: ``cost`` fits the VMEM budget wins (whole-vector is cheapest per chunk,
#: panels are bounded-VMEM and always fit).
_AUTO_ORDER: List[str] = []


def register_layout(spec: LayoutSpec) -> LayoutSpec:
    """Add a layout to the registry (idempotent by name, last wins)."""
    if spec.name in _LAYOUT_SENTINELS:
        raise ValueError(f"{spec.name!r} is reserved")
    if spec.name not in _REGISTRY and spec.auto_eligible:
        _AUTO_ORDER.append(spec.name)
    _REGISTRY[spec.name] = spec
    return spec


def get_layout(name: str) -> LayoutSpec:
    key = canonical_layout(name)
    if key not in _REGISTRY:
        raise ValueError(f"layout {name!r} is not registered; "
                         f"have {layout_names()}")
    return _REGISTRY[key]


def layout_names() -> Tuple[str, ...]:
    """The registry's key set -- the canonical layout names."""
    return tuple(sorted(_REGISTRY))


# Whole-vector path budget: x (ncols) + y (nrows) must sit in VMEM next to
# the decode working set. ~2 MiB of f32 leaves headroom in a 16 MiB VMEM
# for the SpMV kernels; SpMM tiles are nvec-wide, so callers that will run
# SpMM must scale the footprint by nvec (see fits_whole_vector).
VMEM_WHOLE_VECTOR_BUDGET = 2 * 2**20


def _itemsize(itemsize) -> int:
    """Normalise an itemsize-or-dtype-like to bytes, so every budget check
    runs on the plan's ACTUAL value dtype (np.float64 weights must not be
    budgeted as 4-byte -- the prep for the ROADMAP dtype axis)."""
    if isinstance(itemsize, (int, np.integer)):
        return int(itemsize)
    return int(np.dtype(itemsize).itemsize)


def fits_whole_vector(nrows: int, ncols: int, itemsize=4,
                      budget_bytes: int = VMEM_WHOLE_VECTOR_BUDGET,
                      nvec: int = 1) -> bool:
    """Layout selection rule: whole-vector only when x AND y fit the budget.

    ``itemsize`` is the value size in bytes, or anything ``np.dtype``
    accepts (a dtype, "float64", np.float32, ...) -- callers that know the
    plan dtype should pass it directly rather than assuming 4 bytes.
    ``nvec`` is the widest multi-vector batch the handle will see: the
    whole-vector SpMM kernel holds (ncols, nvt) and (nrows, nvt) tiles with
    nvt = min(nvec, 128), so the footprint scales by that factor.
    """
    return _cost_whole(nrows, ncols, _itemsize(itemsize),
                       nvec) <= budget_bytes


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _no_mosaic_kernel(spec: "LayoutSpec", explicit: bool,
                      entry: dict) -> bool:
    """The TPU rule of the plan and shard passes for a pick of layout
    ``spec``: on a TPU backend only layouts whose kernels Mosaic compiles
    (:attr:`LayoutSpec.mosaic`) are eligible. True where the pick has
    none, with the demotion recorded in ``entry``; an ``explicit`` request
    for one raises instead. Off a TPU nothing is demoted."""
    if not _on_tpu() or spec.mosaic:
        return False
    if explicit:
        raise ValueError(f"layout {spec.name!r} has no Pallas kernel that "
                         f"compiles for a TPU; use layout='panels' or 'auto'")
    entry["layout_demoted"] = True
    entry["layout_demoted_reason"] = f"no-mosaic-kernel:{spec.name}"
    return True


def _meta_vdtype(meta) -> str:
    """The plan's resolved value dtype ("" = legacy ``dtype=`` passthrough,
    indistinguishable from f32 for sizing purposes on f32 matrices)."""
    for k, v in meta:
        if k == "vdtype":
            return v
    return ""


def _resolve_attr(obj, name):
    """Shared attribute resolution for plan containers: geometry meta keys
    first, then the layout's named device arrays (per-vdtype name set)."""
    meta = object.__getattribute__(obj, "meta")
    for k, v in meta:
        if k == name:
            return v
    layout = object.__getattribute__(obj, "layout")
    spec = _REGISTRY.get(layout)
    if spec is not None:
        names = spec.plan_array_names(_meta_vdtype(meta))
        if name in names:
            arrays = object.__getattribute__(obj, "arrays")
            return arrays[names.index(name)]
    raise AttributeError(
        f"{type(obj).__name__} ({layout!r}) has no attribute {name!r}")


# ----------------------------------------------------------------------------
# The plan
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SPC5Plan:
    """The single device handle: layout key + device arrays + geometry +
    permutation metadata + the pass trace.

    Registered as a pytree (device arrays, sub-plans, and permutation
    vectors are leaves; layout/geometry/trace are static aux), so plans live
    inside model parameter pytrees and cross jit boundaries exactly like the
    four handle classes they replace. Geometry keys (``r``, ``c``, ``cb``,
    ``pr``, ``vmax``, ...) and the layout's array names
    (``single_values``, ...) resolve as attributes, which is what keeps the
    legacy handle APIs intact.
    """

    layout: str
    arrays: Tuple[jax.Array, ...]
    meta: Tuple[Tuple[str, Any], ...]
    children: Tuple["SPC5Plan", ...] = ()
    col_perm: Optional[jax.Array] = None
    row_iperm: Optional[jax.Array] = None
    rows_fused: bool = False
    trace_json: str = "[]"

    # -- attribute resolution through geometry / layout array names --------
    def __getattr__(self, name):
        return _resolve_attr(self, name)

    # -- generic handle API ------------------------------------------------
    @property
    def shape(self) -> Tuple[int, int]:
        return (self.nrows, self.ncols)

    @property
    def lowering(self) -> str:
        """Always "mask", the one lowering (kept for callers that record
        it)."""
        return LOWERING_MASK

    @property
    def dev(self):
        """The layout's device-array view (legacy ``handle.dev`` API). The
        int8 value store's trailing scale array is not part of the
        NamedTuple view -- the ``lower_*`` hooks fetch ``plan.value_scale``
        separately."""
        spec = get_layout(self.layout)
        if spec.device_view is None:
            raise AttributeError(f"layout {self.layout!r} has no dev view")
        return spec.device_view(self.arrays[:len(spec.array_names)])

    @property
    def multi(self) -> "SPC5Plan":
        """The beta_test split's multi-nnz-block sub-plan."""
        if not self.children:
            raise AttributeError(f"layout {self.layout!r} has no sub-plans")
        return self.children[0]

    @property
    def trace(self) -> List[dict]:
        """Every pass decision that produced this plan, in pipeline order."""
        return json.loads(self.trace_json)

    @property
    def is_reordered(self) -> bool:
        """True when a reordering pass actually permuted this plan."""
        return (self.col_perm is not None or self.row_iperm is not None
                or self.rows_fused)

    @property
    def strategy(self) -> str:
        """The applied reordering strategy ("" when none applied)."""
        for e in self.trace:
            if e.get("pass") == "reorder" and e.get("applied"):
                return e.get("strategy", "")
        return ""

    @property
    def stats(self) -> dict:
        """The reorder pass's scalar evidence (legacy reordered-handle API)."""
        for e in self.trace:
            if e.get("pass") == "reorder" and "stats" in e:
                return e["stats"]
        return {}

    def apply(self, x: jax.Array, **kw) -> jax.Array:
        """y = A @ x (SpMV for 1-D x, SpMM for 2-D x), original index order."""
        return (execute_spmv if x.ndim == 1 else execute_spmm)(self, x, **kw)


def _plan_flatten(p: SPC5Plan):
    return ((p.arrays, p.children, p.col_perm, p.row_iperm),), \
        (p.layout, p.meta, p.rows_fused, p.trace_json)


def _plan_unflatten(aux, ch):
    arrays, children, col_perm, row_iperm = ch[0]
    return SPC5Plan(aux[0], arrays, aux[1], children, col_perm, row_iperm,
                    aux[2], aux[3])


jax.tree_util.register_pytree_node(SPC5Plan, _plan_flatten, _plan_unflatten)


# ----------------------------------------------------------------------------
# Pipeline state + passes
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class PlanState:
    """Mutable pipeline state threaded through the passes."""

    mat: F.SPC5Matrix
    layout: str = "auto"            # requested (canonical or "auto")
    multi_layout: str = "auto"      # the test split's inner-layout request
    pr: Optional[int] = None
    xw: Optional[int] = None
    cb: Optional[int] = None
    nvec: int = 1
    align: int = 8
    dtype: Any = None
    vdtype: str = "auto"            # value-dtype axis ("" = legacy dtype=)
    store: Optional[S.RecordStore] = None
    tune: bool = True
    reorder: Union[None, str, RE.Reordering] = None
    reo: Optional[RE.Reordering] = None     # resolved + applied reordering
    rows_fusible: bool = False
    layout_tuned: bool = False      # the layout came from the store
    trace: List[dict] = dataclasses.field(default_factory=list)

    @property
    def itemsize(self) -> int:
        """Bytes per stored value under the vdtype in effect -- every VMEM
        budget and cost-model decision downstream runs on this, so a bf16 /
        int8 request is sized at its real footprint from the first pass."""
        if self.vdtype in F.VDTYPES:
            return F.value_itemsize(self.vdtype)
        return np.dtype(self.dtype or self.mat.values.dtype).itemsize


def _tune_pass(st: PlanState) -> None:
    """Selector consult: fill (layout, pr, xw, cb, reorder) from a record
    store when the caller requested nothing explicit."""
    entry: dict = {"pass": "tune"}
    explicit = (st.layout != "auto" or st.pr is not None
                or st.xw is not None or st.cb is not None)
    if st.layout == LAYOUT_TEST:
        # the split's multi sub-plan runs its own pipeline (incl. tuning)
        entry["source"] = "delegated"
    elif not st.tune:
        entry["source"] = "disabled"
    elif explicit:
        entry["source"] = "explicit"
    else:
        tstore = st.store if st.store is not None else S.get_default_store()
        if tstore is None or not tstore.records:
            entry["source"] = "no-store"
        else:
            mat = st.mat
            tuned = S.tune(S.spc5_features(mat), store=tstore,
                           kernel=f"{mat.r}x{mat.c}")
            cfg = S.clamp_config(tuned, nrows=mat.nrows, ncols=mat.ncols,
                                 r=mat.r, c=mat.c, nblocks=mat.nblocks,
                                 align=st.align)
            demoted = False
            if (cfg.layout == LAYOUT_WHOLE
                    and not fits_whole_vector(*mat.shape, st.itemsize,
                                              nvec=st.nvec)):
                # a tuned whole-vector pick must never blow the VMEM budget;
                # drop its geometry too -- a whole-layout cb (256/512) is an
                # unmeasured, oversized panel chunk (vmax ~ cb*r*c elements)
                cfg = S.PanelConfig(layout=LAYOUT_PANELS)
                demoted = True
            st.layout = cfg.layout
            st.pr = cfg.pr or None
            st.xw = cfg.xw or None
            st.cb = cfg.cb
            st.layout_tuned = True
            # only a QUANTISED tuned pick flips the value-dtype axis: a
            # tuned "f32" is the neutral default and must leave an
            # untuned-equivalent plan byte-identical (legacy passthrough)
            if st.vdtype == "auto" and cfg.vdtype in ("bf16", "int8"):
                st.vdtype = cfg.vdtype
            if st.reorder is None and cfg.reorder:
                st.reorder = cfg.reorder
            entry.update(source="store", layout=cfg.layout,
                         pr=int(cfg.pr or 0), xw=int(cfg.xw or 0),
                         cb=int(cfg.cb or 0), reorder=cfg.reorder,
                         vdtype=cfg.vdtype, demoted=demoted)
            if demoted:
                entry["demoted_reason"] = "vmem-budget"
    st.trace.append(entry)


def _scalar_stats(stats: dict) -> dict:
    return {k: v for k, v in stats.items()
            if isinstance(v, (int, float, str, bool))}


def _reorder_pass(st: PlanState) -> None:
    """Permutation transform: resolve the ``reorder`` request (strategy
    names are built AND scored at the geometry in effect, and may decline),
    permute the matrix, and record the fusion decision
    (``rows_fusible`` -> the whole-vector build folds the inverse row
    scatter into ``chunk_row``)."""
    entry: dict = {"pass": "reorder", "strategy": "", "applied": False}
    reo = st.reorder
    if isinstance(reo, RE.Reordering):
        if (reo.nrows, reo.ncols) != st.mat.shape:
            raise ValueError(
                f"reordering is for shape {(reo.nrows, reo.ncols)}, "
                f"matrix is {st.mat.shape}")
    elif reo is not None:
        reo = RE.reorder(st.mat, str(reo), r=st.mat.r, c=st.mat.c,
                         pr=512 if st.pr is None else st.pr,
                         xw=512 if st.xw is None else st.xw,
                         cb=st.cb if st.cb else F.PANEL_CB,
                         align=st.align)
    if reo is not None and not reo.is_identity:
        st.mat = reo.permute_spc5(st.mat)
        st.reo = reo
        st.rows_fusible = (not reo.identity_rows
                           and reo.rows_interval_contiguous(st.mat.r))
        entry.update(strategy=reo.strategy, applied=True,
                     rows_fusible=st.rows_fusible,
                     stats=_scalar_stats(reo.stats))
    elif reo is not None:               # declined / explicit identity
        entry.update(strategy=reo.strategy, stats=_scalar_stats(reo.stats))
    st.trace.append(entry)


def _layout_pass(st: PlanState) -> None:
    """Resolve "auto" through the registry's cost entries: the first
    auto-eligible layout whose VMEM cost fits the budget wins.

    On a TPU backend only layouts whose kernels Mosaic compiles are
    eligible (:attr:`LayoutSpec.mosaic`): "auto" and tuned picks skip or
    demote the others, with the reason traced, and an explicit request
    for one raises."""
    entry: dict = {"pass": "layout"}
    # Resolve the value-dtype axis FIRST: "auto" with no tuned pick falls
    # back to "" (legacy dtype= passthrough, byte-identical to pre-axis
    # plans), so st.itemsize is final before the VMEM costs below.
    if st.vdtype == "auto":
        st.vdtype = ""
    entry["vdtype"] = st.vdtype
    if (st.layout in _REGISTRY
            and _no_mosaic_kernel(_REGISTRY[st.layout],
                                  not st.layout_tuned, entry)):
        st.layout = "auto"
    if st.layout == "auto":
        entry["reason"] = "vmem-fit"
        for name in _AUTO_ORDER:
            spec = _REGISTRY[name]
            if spec.cost(st.mat.nrows, st.mat.ncols, st.itemsize,
                         st.nvec) > VMEM_WHOLE_VECTOR_BUDGET:
                continue
            if _no_mosaic_kernel(spec, False, entry):
                continue
            st.layout = name
            break
        else:                           # pragma: no cover - panels always fit
            raise RuntimeError("no registered layout fits the VMEM budget")
    else:
        entry["reason"] = "requested"
    entry["layout"] = st.layout
    st.trace.append(entry)


def _build_pass(st: PlanState) -> SPC5Plan:
    """Registry build + permutation attachment -> the finished plan.

    ``extra["rows_fused"]`` means the build folded the inverse row
    permutation into its scatter indices, so no ``row_iperm`` rides on the
    plan."""
    obs.faults.get_faults().maybe_fail("plan.build")
    spec = get_layout(st.layout)
    with obs.span("plan.build", layout=st.layout) as sp:
        arrays, geom, extra = spec.build(st)
    rows_fused = bool(extra.get("rows_fused", False))
    col_perm = row_iperm = None
    if st.reo is not None:
        reo = st.reo
        col_perm = (None if reo.identity_cols
                    else jnp.asarray(reo.col_perm.astype(np.int32)))
        row_iperm = (None if (rows_fused or reo.identity_rows)
                     else jnp.asarray(reo.row_iperm.astype(np.int32)))
    st.trace.append({"pass": "build", "layout": st.layout,
                     "duration_s": sp.duration_s,
                     "rows_fused": rows_fused,
                     **{k: v for k, v in sorted(geom.items())
                        if isinstance(v, (int, float, str, bool))}})
    return SPC5Plan(layout=st.layout, arrays=tuple(arrays),
                    meta=tuple(sorted(geom.items())),
                    children=tuple(extra.get("children", ())),
                    col_perm=col_perm, row_iperm=row_iperm,
                    rows_fused=rows_fused,
                    trace_json=json.dumps(st.trace, sort_keys=True))


def make_plan(mat: F.SPC5Matrix, *, layout: str = "auto",
              pr: Optional[int] = None, xw: Optional[int] = None,
              cb: Optional[int] = None, nvec: int = 1, align: int = 8,
              dtype=None, vdtype: str = "auto",
              store: Optional[S.RecordStore] = None,
              tune: bool = True,
              reorder: Union[None, str, RE.Reordering] = None,
              multi_layout: str = "auto",
              verify: Union[bool, Callable] = False) -> SPC5Plan:
    """The plan pipeline: tune -> reorder -> layout -> build.

    This is the single entry point behind ``ops.prepare`` (and its
    deprecation shims) / ``SparseLinear.from_dense``; every pass records
    its decision in the
    returned plan's ``trace``. ``layout`` accepts a registry key, a legacy
    alias, or "auto"; ``multi_layout`` is the beta_test split's inner-layout
    request (only meaningful with ``layout="test"``).

    ``vdtype`` is the value-dtype axis ("f32" | "bf16" | "int8" | "auto"):
    how the plan STORES values, with the kernels always accumulating in
    f32 (quantised plans return f32 outputs regardless). "auto" takes a
    quantised tuned pick when the store has one, else the legacy behaviour
    (values kept at the matrix dtype, or cast by the ``dtype=``
    passthrough -- the two knobs are mutually exclusive). int8 plans carry
    a per-chunk f32 scale array (``plan.value_scale``) computed at build
    time.

    ``verify`` is the opt-in static-analysis hook: ``True`` runs
    ``repro.analysis.verify.verify_plan`` on the finished plan and raises
    :class:`~repro.analysis.verify.PlanVerificationError` on any invariant
    violation; a callable receives the :class:`VerifyReport` instead (for
    cache-admission policies that want to log rather than raise).
    """
    vdtype = F.canonical_vdtype(vdtype)
    if vdtype not in ("", "auto") and dtype is not None:
        raise ValueError(
            f"pass either dtype= (legacy passthrough) or vdtype={vdtype!r}, "
            f"not both -- the value-dtype axis owns the cast")
    st = PlanState(mat=mat, layout=canonical_layout(layout),
                   multi_layout=canonical_layout(multi_layout),
                   pr=pr, xw=xw, cb=cb, nvec=nvec, align=align, dtype=dtype,
                   vdtype=vdtype, store=store, tune=tune, reorder=reorder)
    # Each pass runs under an obs span and stamps its wall-time into its
    # own trace entry, so plan.trace records durations alongside decisions
    # (the trace-schema verify rule requires duration_s on every entry).
    for pass_name, pass_fn in (("tune", _tune_pass),
                               ("reorder", _reorder_pass),
                               ("layout", _layout_pass)):
        with obs.span(f"plan.{pass_name}") as sp:
            pass_fn(st)
        st.trace[-1]["duration_s"] = sp.duration_s
    plan = _build_pass(st)
    if verify:
        from repro.analysis.verify import verify_plan
        report = verify_plan(plan, nvec=nvec)
        if callable(verify):
            verify(report)
        else:
            report.raise_if_failed()
    return plan


# ----------------------------------------------------------------------------
# Executor (the ONLY layout dispatch)
# ----------------------------------------------------------------------------

def execute_spmv(plan: Union[SPC5Plan, "ShardedPlan"], x: jax.Array, *,
                 use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """y = A @ x through the layout's registered ``lower_spmv``.

    x and y are always in ORIGINAL index order: the hook owns the
    column-permutation gather (fused into the whole-vector kernels'
    ``col_map`` decode where possible) and this executor applies the
    inverse row permutation -- unless the build fused it into the scatter
    indices (``rows_fused``). The dispatch runs under an ``exec.spmv``
    span (see :func:`_exec_span`).

    A :class:`ShardedPlan` runs as one program over its mesh
    (:func:`sharded_spmv_program`, built once per plan and setting): each
    device runs its slab through the layout's ``lower_spmv``, and y comes
    back all-gathered and replicated.
    """
    obs.faults.get_faults().maybe_fail("exec.spmv")
    use_pallas, interpret = _resolve_pallas(use_pallas, interpret)
    with _exec_span("exec.spmv", plan, 1, 1, use_pallas, spmm=False):
        if isinstance(plan, ShardedPlan):
            return plan.program(use_pallas=use_pallas,
                                interpret=interpret)(x)
        return _dispatch_spmv(plan, x, use_pallas=use_pallas,
                              interpret=interpret)


def execute_spmm(plan: SPC5Plan, x: jax.Array, *,
                 use_pallas: Optional[bool] = None, nvt: int = 128,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Y = A @ X, X of shape (ncols, nvec), through the layout's
    ``lower_spmm``, under an ``exec.spmm`` span (see :func:`_exec_span`)."""
    obs.faults.get_faults().maybe_fail("exec.spmm")
    if isinstance(plan, ShardedPlan):
        raise NotImplementedError("a ShardedPlan runs SpMV only")
    use_pallas, interpret = _resolve_pallas(use_pallas, interpret)
    with _exec_span("exec.spmm", plan, x.shape[1], nvt, use_pallas,
                    spmm=True):
        return _dispatch_spmm(plan, x, use_pallas=use_pallas, nvt=nvt,
                              interpret=interpret)


def _resolve_pallas(use_pallas: Optional[bool],
                   interpret: Optional[bool]) -> Tuple[bool, bool]:
    """The executors' defaults: the compiled Pallas kernels on a TPU, the
    jnp reference elsewhere, and interpret mode where Pallas is asked for
    off a TPU."""
    return (_on_tpu() if use_pallas is None else use_pallas,
            not _on_tpu() if interpret is None else interpret)


class KernelSteps(NamedTuple):
    """What one device's Pallas kernels walk in one executor call: the
    grid steps launched, the chunks decoded, and the chunks one step of
    the chunk kernel walks (1 where each step takes one chunk)."""
    grid_steps: int
    chunk_steps: int
    chunks_per_step: int


def _exec_span(name: str, plan, nvec: int, nvt: int,
               use_pallas: bool, *, spmm: bool):
    """The executor's span: ``layout``, ``lowering`` (always "mask"),
    ``nvec``, ``ndev`` and the call's :class:`KernelSteps` on one device
    (zeros on the jnp path). Host work only: no device op, no sync. Under
    a ``jit`` trace it times the tracing, once."""
    spec = get_layout(plan.layout)
    ndev = 1
    if isinstance(plan, ShardedPlan):
        ndev = plan.ndev
        plan = _shard_view(plan, [jax.ShapeDtypeStruct(a.shape[1:], a.dtype)
                                  for a in plan.arrays])
    steps = (spec.kernel_steps(plan, nvec, nvt, spmm)
             if use_pallas and spec.kernel_steps is not None
             else KernelSteps(0, 0, 0))
    return obs.span(name, layout=plan.layout,
                    lowering=LOWERING_MASK, nvec=int(nvec), ndev=int(ndev),
                    **{k: int(v) for k, v in steps._asdict().items()})


def _dispatch_spmv(plan: SPC5Plan, x, *, use_pallas, interpret):
    y = get_layout(plan.layout).lower_spmv(
        plan, x, use_pallas=use_pallas, interpret=interpret)
    if plan.row_iperm is not None:
        y = jnp.take(y, plan.row_iperm, axis=0)
    return y


def _dispatch_spmm(plan: SPC5Plan, x, *, use_pallas, nvt, interpret):
    y = get_layout(plan.layout).lower_spmm(
        plan, x, use_pallas=use_pallas, nvt=nvt, interpret=interpret)
    if plan.row_iperm is not None:
        y = jnp.take(y, plan.row_iperm, axis=0)
    return y


def _steps_chunked(plan: SPC5Plan, nvec: int, nvt: int,
                   spmm: bool) -> KernelSteps:
    """The whole-vector kernels: a grid ``(nvec // nvt, nchunks)``, one
    chunk a step."""
    steps = nvec // min(nvt, nvec) * math.prod(plan.chunk_vbase.shape)
    return KernelSteps(steps, steps, 1)


def _require_mosaic(plan: SPC5Plan, interpret: bool) -> None:
    """A compiled (non-interpret) Pallas call exists only for the kernels
    Mosaic accepts; the others run in interpret mode or on the jnp path."""
    if not interpret and not get_layout(plan.layout).mosaic:
        raise NotImplementedError(
            f"layout {plan.layout!r} has no Pallas kernel that compiles for "
            f"a TPU; build the plan with layout='panels' (what 'auto' picks "
            f"on a TPU) or call with use_pallas=False")


def _gathered_x(plan: SPC5Plan, x: jax.Array) -> jax.Array:
    return x if plan.col_perm is None else jnp.take(x, plan.col_perm, axis=0)


def _value_store(values: np.ndarray, chunk_vbase: np.ndarray,
                 chunk_mask: np.ndarray, st: PlanState):
    """Apply the resolved value-dtype axis to a build's packed value array:
    legacy ``dtype=`` passthrough when no vdtype is in effect, else the
    formats-layer store (bf16 cast / int8 + per-chunk f32 scales keyed by
    the chunk's OWN nnz). Returns ``(values, scales_or_None)``."""
    if not st.vdtype:
        return (values if st.dtype is None
                else values.astype(st.dtype)), None
    return F.quantize_chunk_values(values, chunk_vbase, chunk_mask,
                                   st.vdtype)


def _plan_scale(plan: SPC5Plan):
    """The per-chunk dequantisation scales of an int8 plan (None otherwise)
    -- every ``lower_*`` hook threads this into its kernel / reference
    oracle."""
    if _meta_vdtype(plan.meta) == "int8":
        return plan.value_scale
    return None


# ----------------------------------------------------------------------------
# Fingerprints + plan footprint (the serving tier's cache substrate)
# ----------------------------------------------------------------------------

def matrix_fingerprint(mat: F.SPC5Matrix) -> str:
    """Content hash of a beta(r,c) matrix: structure (block geometry,
    row/col/mask/voffset arrays) + values + value dtype.

    Two matrices with identical content hash identically regardless of how
    their arrays were produced (fresh conversion, a copy, a checkpoint
    round-trip); any structural or numeric change -- one flipped mask bit,
    one edited value -- changes the digest. This is the build-once half of
    the plan-cache key (:func:`plan_cache_key`)."""
    h = hashlib.blake2b(digest_size=16)
    h.update(np.asarray([mat.shape[0], mat.shape[1], mat.r, mat.c],
                        dtype=np.int64).tobytes())
    h.update(str(np.dtype(mat.values.dtype)).encode())
    for a in (mat.block_rowptr, mat.block_colidx, mat.block_masks,
              mat.block_voffset, mat.values):
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def plan_cache_key(mat: F.SPC5Matrix, **request) -> str:
    """The serving tier's cache key: matrix fingerprint + the prepare-path
    request (layout / reorder / geometry / dtype / nvec / ...).

    Every decision that changes the built plan is part of the key, so a
    cached plan is only ever reused for the exact (matrix, request) pair it
    was built for; omitted/None/"auto" knobs normalise away, so spelling a
    default explicitly does not split the cache."""
    norm = {}
    for k in sorted(request):
        v = request[k]
        if v is None or v == "auto" or v == "" or v is False:
            continue                    # defaults don't split the cache
        if k == "dtype":
            v = str(np.dtype(v))
        elif not isinstance(v, (bool, int, float, str)):
            v = str(v)                  # PanelConfig / Reordering reprs
        norm[k] = v
    h = hashlib.blake2b(digest_size=16)
    h.update(matrix_fingerprint(mat).encode())
    h.update(json.dumps(norm, sort_keys=True).encode())
    return h.hexdigest()


def append_trace_entries(plan: SPC5Plan, entries: List[dict]) -> SPC5Plan:
    """A copy of ``plan`` with ``entries`` appended to its pass trace.

    The degradation ladder uses this to stamp ``{"pass": "degrade", ...}``
    entries onto a plan that was rebuilt on a lower rung, so the demotion
    history is inspectable on the plan itself (the trace-schema verify
    rule admits trailing ``degrade`` entries and requires each to carry
    ``rung``/``reason``/``duration_s``)."""
    return dataclasses.replace(
        plan, trace_json=json.dumps(plan.trace + list(entries),
                                    sort_keys=True))


def plan_nbytes(plan: SPC5Plan) -> int:
    """Device-array footprint of a plan in bytes (sub-plans and permutation
    vectors included) -- the LRU currency of the serving tier's plan cache."""
    n = sum(int(a.nbytes) for a in plan.arrays)
    for child in plan.children:
        n += plan_nbytes(child)
    for p in (plan.col_perm, plan.row_iperm):
        if p is not None:
            n += int(p.nbytes)
    return n


# ----------------------------------------------------------------------------
# whole_vector layout
# ----------------------------------------------------------------------------

_WHOLE_ARRAYS = tuple(R.SPC5Device._fields)      # values, chunk_col, ...


def _cost_whole(nrows: int, ncols: int, itemsize: int, nvec: int) -> int:
    return (nrows + ncols) * itemsize * min(max(nvec, 1), 128)


def _build_whole(st: PlanState):
    ch = F.to_chunked(st.mat, cb=256 if st.cb is None else st.cb,
                      align=st.align)
    rows_fused = False
    if st.reo is not None and st.rows_fusible:
        # fuse the inverse row permutation into the scatter indices: each
        # block's r permuted rows map to r consecutive ORIGINAL rows, so
        # chunk_row can point straight at the original base row and y needs
        # no output gather at all
        ch = dataclasses.replace(
            ch, chunk_row=st.reo.row_perm[ch.chunk_row].astype(np.int32))
        rows_fused = True
    geom = dict(r=ch.r, c=ch.c, cb=ch.cb, vmax=ch.vmax, nrows=ch.nrows,
                ncols=ch.ncols, nnz=ch.nnz, nblocks=int(st.mat.nblocks),
                vdtype=st.vdtype)
    values, scales = _value_store(ch.values, ch.chunk_vbase, ch.chunk_mask,
                                  st)
    dev = R.device_put(ch)._replace(values=jnp.asarray(values))
    arrays = tuple(dev)
    if scales is not None:
        arrays = arrays + (jnp.asarray(scales),)
    return arrays, geom, {"rows_fused": rows_fused}


def _lower_spmv_whole(plan: SPC5Plan, x, *, use_pallas, interpret):
    dev = plan.dev
    scale = _plan_scale(plan)
    if not use_pallas:
        return R.spmv(dev, _gathered_x(plan, x), scale, r=plan.r, c=plan.c,
                      nrows=plan.nrows, ncols=plan.ncols)
    _require_mosaic(plan, interpret)
    # fused x gather: the whole-vector kernels route their decode through
    # col_map, so x never materialises in permuted order
    return spc5_spmv.spmv_pallas(
        dev.chunk_vbase, dev.chunk_col, dev.chunk_mask, dev.chunk_voff,
        dev.chunk_row, dev.values, x, plan.col_perm, scale,
        r=plan.r, c=plan.c, cb=plan.cb, vmax=plan.vmax,
        nrows=plan.nrows, ncols=plan.ncols, interpret=interpret)


def _lower_spmm_whole(plan: SPC5Plan, x, *, use_pallas, nvt, interpret):
    dev = plan.dev
    scale = _plan_scale(plan)
    if not use_pallas:
        return R.spmm(dev, _gathered_x(plan, x), scale, r=plan.r, c=plan.c,
                      nrows=plan.nrows, ncols=plan.ncols)
    _require_mosaic(plan, interpret)
    return spc5_spmm.spmm_pallas(
        dev.chunk_vbase, dev.chunk_col, dev.chunk_mask, dev.chunk_voff,
        dev.chunk_row, dev.values, x, plan.col_perm, scale,
        r=plan.r, c=plan.c, cb=plan.cb, vmax=plan.vmax, nrows=plan.nrows,
        ncols=plan.ncols, nvt=min(nvt, x.shape[1]), interpret=interpret)


def _clamp_whole(cfg: S.PanelConfig, *, nrows, ncols, r, c, nblocks,
                 align=8) -> S.PanelConfig:
    return S.clamp_config(cfg, nrows=nrows, ncols=ncols, r=r, c=c,
                          nblocks=nblocks, align=align)


def _shard_build_whole(st: "ShardState"):
    """Stack per-device chunked arrays (padded to uniform shapes)."""
    cb = 256 if st.cb is None else st.cb
    chunked = [F.to_chunked(p, cb=cb) for p in st.parts]
    nch = max(ch.nchunks for ch in chunked)
    vmax = max(ch.vmax for ch in chunked)
    nvals = max(ch.values.shape[0] + vmax for ch in chunked)
    rows_max = max(p.shape[0] for p in st.parts)

    def pad2(a, n):  # pad axis0 of (nchunks, cb)
        return np.pad(a, ((0, n - a.shape[0]), (0, 0)))

    dt = st.dtype or st.mat.values.dtype
    arrays = (
        np.stack([
            np.pad(ch.values, (0, nvals - ch.values.shape[0]))
            for ch in chunked]).astype(dt),
        np.stack([pad2(ch.chunk_col, nch) for ch in chunked]),
        np.stack([pad2(ch.chunk_mask, nch).astype(np.int32)
                  for ch in chunked]),
        np.stack([pad2(ch.chunk_voff, nch) for ch in chunked]),
        np.stack([pad2(ch.chunk_row, nch) for ch in chunked]),
        np.stack([
            np.pad(ch.chunk_vbase, (0, nch - ch.chunk_vbase.shape[0]))
            for ch in chunked]),
    )
    geom = dict(r=st.mat.r, c=st.mat.c, cb=cb, vmax=vmax, rows_max=rows_max,
                nrows=st.mat.shape[0], ncols=st.mat.shape[1], nnz=st.mat.nnz)
    return arrays, geom


register_layout(LayoutSpec(
    name=LAYOUT_WHOLE,
    array_names=_WHOLE_ARRAYS,
    build=_build_whole,
    lower_spmv=_lower_spmv_whole,
    lower_spmm=_lower_spmm_whole,
    cost=_cost_whole,
    clamp=_clamp_whole,
    default_cb=256,
    device_view=lambda arrays: R.SPC5Device(*arrays),
    shard_build=_shard_build_whole,
    kernel_steps=_steps_chunked,
))


# ----------------------------------------------------------------------------
# panels layout
# ----------------------------------------------------------------------------

_PANEL_ARRAYS = tuple(R.SPC5PanelDevice._fields)


def _cost_panels(nrows: int, ncols: int, itemsize: int, nvec: int) -> int:
    # VMEM per grid step is pr + xw + vmax elements regardless of matrix
    # size -- the bounded-VMEM layout always fits the budget
    return 0


def _panel_row_permutation(reo: RE.Reordering, pr: int, nrows: int,
                           npanels: int) -> Optional[np.ndarray]:
    """The panel layout's row-fusion condition: when every pr-row panel of
    the *permuted* matrix maps to one pr-aligned ascending slab of original
    rows, the row permutation is a pure PANEL permutation -- the build can
    reorder the stacked panel axis outright and the executor's inverse row
    gather disappears (the panel analogue of the whole-vector layout's
    ``chunk_row`` fold). Returns ``pperm`` with ``pperm[p]`` the original
    panel index of permuted panel ``p``, or None when not fusible."""
    if reo.identity_rows:
        return None
    rp = reo.row_perm
    pperm = np.empty(npanels, dtype=np.int64)
    for p in range(npanels):
        lo, hi = p * pr, min((p + 1) * pr, nrows)
        if lo >= hi:
            pperm[p] = p
            continue
        s = int(rp[lo])
        if s % pr:
            return None
        if not np.array_equal(rp[lo:hi], np.arange(s, s + hi - lo)):
            return None
        if hi - lo < pr and s != (npanels - 1) * pr:
            return None                 # a partial panel must stay last
        pperm[p] = s // pr
    return pperm


def _build_panels(st: PlanState):
    pan = F.to_panels(st.mat, pr=512 if st.pr is None else st.pr,
                      cb=F.PANEL_CB if st.cb is None else st.cb,
                      xw=512 if st.xw is None else st.xw, align=st.align)
    rows_fused = False
    if st.reo is not None:
        pperm = _panel_row_permutation(st.reo, pan.pr, pan.nrows,
                                       pan.npanels)
        if pperm is not None:
            # interval-fused row scatter: put permuted panel p's arrays at
            # grid position pperm[p], so panel q of the output IS original
            # rows [q*pr, (q+1)*pr) and no inverse row gather remains
            # (chunk_vbase stays valid -- it indexes the values array
            # absolutely)
            inv = np.empty_like(pperm)
            inv[pperm] = np.arange(pperm.shape[0])
            pan = dataclasses.replace(
                pan, chunk_col=pan.chunk_col[inv],
                chunk_mask=pan.chunk_mask[inv],
                chunk_voff=pan.chunk_voff[inv],
                chunk_row=pan.chunk_row[inv],
                chunk_vbase=pan.chunk_vbase[inv],
                chunk_xbase=pan.chunk_xbase[inv])
            rows_fused = True
    geom = dict(r=pan.r, c=pan.c, pr=pan.pr, cb=pan.cb, xw=pan.xw,
                vmax=pan.vmax, npanels=pan.npanels, nchunks=pan.nchunks,
                nrows=pan.nrows, ncols=pan.ncols, ncols_pad=pan.ncols_pad,
                nnz=pan.nnz, nblocks=int(st.mat.nblocks),
                vdtype=st.vdtype)
    values, scales = _value_store(pan.values, pan.chunk_vbase,
                                  pan.chunk_mask, st)
    dev = R.device_put_panels(pan)._replace(values=jnp.asarray(values))
    arrays = tuple(dev)
    if scales is not None:
        arrays = arrays + (jnp.asarray(scales),)
    return arrays, geom, {"rows_fused": rows_fused}


def _lower_spmv_panels(plan: SPC5Plan, x, *, use_pallas, interpret):
    dev = plan.dev
    scale = _plan_scale(plan)
    if not use_pallas:
        # the reference decode routes its x gather through col_perm, so x
        # is never materialised in permuted order
        return R.spmv_panels(dev, x, plan.col_perm, scale, r=plan.r,
                             c=plan.c, pr=plan.pr, nrows=plan.nrows,
                             ncols_pad=plan.ncols_pad)
    _require_mosaic(plan, interpret)
    # the kernel DMAs x windows, so a permutation is applied to x here
    return spc5_spmv.spmv_pallas_panels(
        dev.chunk_vbase, dev.chunk_xbase, dev.chunk_col, dev.chunk_mask,
        dev.chunk_voff, dev.chunk_row, dev.values, _gathered_x(plan, x),
        scale, r=plan.r, c=plan.c, cb=plan.cb, vmax=plan.vmax, xw=plan.xw,
        pr=plan.pr, nrows=plan.nrows, ncols_pad=plan.ncols_pad,
        interpret=interpret)


def _lower_spmm_panels(plan: SPC5Plan, x, *, use_pallas, nvt, interpret):
    dev = plan.dev
    scale = _plan_scale(plan)
    if not use_pallas:
        return R.spmm_panels(dev, x, plan.col_perm, scale, r=plan.r,
                             c=plan.c, pr=plan.pr, nrows=plan.nrows,
                             ncols_pad=plan.ncols_pad)
    _require_mosaic(plan, interpret)
    return spc5_spmm.spmm_pallas_panels(
        dev.chunk_vbase, dev.chunk_xbase, dev.chunk_col, dev.chunk_mask,
        dev.chunk_voff, dev.chunk_row, dev.values, _gathered_x(plan, x),
        scale, r=plan.r, c=plan.c, cb=plan.cb, vmax=plan.vmax, xw=plan.xw,
        pr=plan.pr, nrows=plan.nrows, ncols_pad=plan.ncols_pad,
        nvt=min(nvt, x.shape[1]), interpret=interpret)


def _steps_panels(plan: SPC5Plan, nvec: int, nvt: int,
                  spmm: bool) -> KernelSteps:
    geom = dict(plan.meta)
    geom["npanels"], geom["nchunks"] = plan.chunk_vbase.shape
    grid, group = spc5_spmv.panel_grid(geom, plan.values.dtype.itemsize,
                                       nvec, min(nvt, nvec))
    return KernelSteps(math.prod(grid), math.prod(grid) * group, group)


def _shard_build_panels(st: "ShardState"):
    """Row-shard + panel-tile each shard + stack (padded to uniform grids)."""
    pr = 512 if st.pr is None else st.pr
    cb = F.PANEL_CB if st.cb is None else st.cb
    xw = 512 if st.xw is None else st.xw
    pans = [F.to_panels(p, pr=pr, cb=cb, xw=xw) for p in st.parts]
    pr = pans[0].pr                        # normalised to a multiple of r
    npan = max(p.npanels for p in pans)
    nch = max(p.nchunks for p in pans)
    vmax = max(p.vmax for p in pans)
    nvals = max(int(p.chunk_vbase.max()) + vmax for p in pans)
    ncols_pad = max(p.ncols_pad for p in pans)

    def pad3(a):   # (npanels, nchunks, cb) -> (npan, nch, cb)
        return np.pad(a, ((0, npan - a.shape[0]), (0, nch - a.shape[1]),
                          (0, 0)))

    def pad2(a):           # (npanels, nchunks) -> (npan, nch)
        return np.pad(a, ((0, npan - a.shape[0]), (0, nch - a.shape[1])))

    dt = st.dtype or st.mat.values.dtype
    arrays = (
        np.stack([
            np.pad(p.values, (0, nvals - p.values.shape[0]))
            for p in pans]).astype(dt),
        np.stack([pad3(p.chunk_col) for p in pans]),
        np.stack([pad3(p.chunk_mask).astype(np.int32) for p in pans]),
        np.stack([pad3(p.chunk_voff) for p in pans]),
        np.stack([pad3(p.chunk_row) for p in pans]),
        np.stack([pad2(p.chunk_vbase) for p in pans]),
        np.stack([pad2(p.chunk_xbase) for p in pans]),
    )
    geom = dict(r=st.mat.r, c=st.mat.c, pr=pr, cb=pans[0].cb, xw=pans[0].xw,
                vmax=vmax, rows_max=npan * pr, nrows=st.mat.shape[0],
                ncols=st.mat.shape[1], ncols_pad=ncols_pad, nnz=st.mat.nnz)
    return arrays, geom


register_layout(LayoutSpec(
    name=LAYOUT_PANELS,
    array_names=_PANEL_ARRAYS,
    build=_build_panels,
    lower_spmv=_lower_spmv_panels,
    lower_spmm=_lower_spmm_panels,
    cost=_cost_panels,
    clamp=_clamp_whole,                 # same generic dim clamp
    default_cb=F.PANEL_CB,
    device_view=lambda arrays: R.SPC5PanelDevice(*arrays),
    shard_build=_shard_build_panels,
    mosaic=True,
    kernel_steps=_steps_panels,
))


# ----------------------------------------------------------------------------
# test layout: beta(r,c)_test split (multi-block sub-plan + COO tail)
# ----------------------------------------------------------------------------

_TEST_ARRAYS = ("single_rows", "single_cols", "single_values", "tail_xbase")


def _bucket_tail_by_panel(rows: np.ndarray, cols: np.ndarray,
                          vals: np.ndarray, pr: int, npanels: int,
                          align: int = 8):
    """Sort the singleton COO tail into per-panel buckets padded to the max
    per-panel count (mask-free analogue of the panel layout's uniform chunk
    padding), plus one aligned x window per panel covering the bucket's
    column span -- the Pallas tail kernel DMAs x per panel exactly like the
    block kernels window it per chunk. Callers must not pass an empty tail
    (the flat zero-length arrays already encode 'no singletons')."""
    n = rows.shape[0]
    panel = rows.astype(np.int64) // pr
    order = np.lexsort((cols, rows, panel))
    counts = np.bincount(panel, minlength=npanels).astype(np.int64)
    smax = int(counts.max())
    brows = np.zeros((npanels, smax), dtype=np.int32)
    bcols = np.zeros((npanels, smax), dtype=np.int32)
    bvals = np.zeros((npanels, smax), dtype=vals.dtype)
    cum = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot = np.arange(n, dtype=np.int64) - np.repeat(cum, counts)
    p_sorted = panel[order]
    brows[p_sorted, slot] = (rows[order].astype(np.int64) % pr).astype(np.int32)
    bcols[p_sorted, slot] = cols[order]
    bvals[p_sorted, slot] = vals[order]
    # per-panel x windows: xbase aligned down, width = max span (one static
    # window width keeps the kernel's DMA tile uniform across panels)
    cmin = np.full(npanels, np.iinfo(np.int64).max, dtype=np.int64)
    cmax = np.zeros(npanels, dtype=np.int64)
    np.minimum.at(cmin, panel, cols.astype(np.int64))
    np.maximum.at(cmax, panel, cols.astype(np.int64))
    cmin[counts == 0] = 0
    cmax[counts == 0] = 0
    xbase = (cmin // align) * align
    span = int((cmax - xbase + 1).max())
    tail_xw = max(align, -(-span // align) * align)
    ncols_pad = int(xbase.max()) + tail_xw
    return brows, bcols, bvals, xbase.astype(np.int32), tail_xw, ncols_pad


def _build_test(st: PlanState):
    split = F.split_singletons(st.mat)
    # tail value store: bf16 tails store bf16 (the COO tail paths upcast
    # before accumulating); int8 tails STAY full precision -- the singleton
    # tail has no chunk structure to hang per-chunk scales off, and its nnz
    # share is too small for the bytes to matter
    if st.vdtype == "bf16":
        dt = F.value_dtype("bf16")
    elif st.vdtype == "int8":
        dt = np.float32
    else:
        dt = st.dtype or st.mat.values.dtype
    multi = make_plan(split.multi, layout=st.multi_layout, pr=st.pr,
                      xw=st.xw, cb=st.cb, nvec=st.nvec, align=st.align,
                      dtype=st.dtype, vdtype=st.vdtype or "auto",
                      store=st.store, tune=st.tune, reorder=None)
    n_single = int(split.single_values.shape[0])
    if multi.layout == LAYOUT_PANELS and n_single:
        brows, bcols, bvals, xbase, tail_xw, tail_pad = \
            _bucket_tail_by_panel(split.single_rows, split.single_cols,
                                  split.single_values.astype(dt), multi.pr,
                                  multi.npanels, align=st.align)
        arrays = (jnp.asarray(brows), jnp.asarray(bcols), jnp.asarray(bvals),
                  jnp.asarray(xbase))
        tail_pr = multi.pr
    else:       # flat tail; zero-length == no singletons, skipped per call
        arrays = (jnp.asarray(split.single_rows),
                  jnp.asarray(split.single_cols),
                  jnp.asarray(split.single_values.astype(dt)),
                  jnp.zeros((0,), jnp.int32))
        tail_pr, tail_xw, tail_pad = 0, 0, 0
    geom = dict(nrows=st.mat.nrows, ncols=st.mat.ncols, nnz=st.mat.nnz,
                tail_pr=tail_pr, tail_xw=tail_xw, tail_ncols_pad=tail_pad,
                n_single=n_single, vdtype=_meta_vdtype(multi.meta))
    return arrays, geom, {"children": (multi,)}


def _tail_spmv(plan: SPC5Plan, xg, *, use_pallas, interpret):
    """The singleton tail's contribution (permuted index space)."""
    rows, cols, vals, xbase = plan.arrays
    if plan.tail_pr:
        if use_pallas:
            _require_mosaic(plan, interpret)
            return spc5_spmv.spmv_tail_pallas(
                xbase, rows, cols, vals, xg, pr=plan.tail_pr,
                xw=plan.tail_xw, nrows=plan.nrows,
                ncols_pad=plan.tail_ncols_pad, interpret=interpret)
        return R.spmv_coo_panels(rows, cols, vals, xg, pr=plan.tail_pr,
                                 nrows=plan.nrows)
    return R.spmv_coo(rows, cols, vals, xg, nrows=plan.nrows)


def _lower_spmv_test(plan: SPC5Plan, x, *, use_pallas, interpret):
    xg = _gathered_x(plan, x)
    y = _dispatch_spmv(plan.multi, xg, use_pallas=use_pallas,
                       interpret=interpret)
    if plan.single_values.size:
        y = y + _tail_spmv(plan, xg, use_pallas=use_pallas,
                           interpret=interpret)
    return y


def _lower_spmm_test(plan: SPC5Plan, x, *, use_pallas, nvt, interpret):
    xg = _gathered_x(plan, x)
    y = _dispatch_spmm(plan.multi, xg, use_pallas=use_pallas, nvt=nvt,
                       interpret=interpret)
    if plan.single_values.size:
        rows, cols, vals = (plan.single_rows, plan.single_cols,
                            plan.single_values)
        if plan.tail_pr:                # bucketed: panel-local -> global rows
            npanels = rows.shape[0]
            rows = (jnp.arange(npanels, dtype=rows.dtype)[:, None]
                    * plan.tail_pr + rows)
            tail = R.spmm_coo(rows.reshape(-1), cols.reshape(-1),
                              vals.reshape(-1), xg,
                              nrows=npanels * plan.tail_pr)[:plan.nrows]
        else:
            tail = R.spmm_coo(rows, cols, vals, xg, nrows=plan.nrows)
        y = y + tail
    return y


def _steps_test(plan: SPC5Plan, nvec: int, nvt: int,
               spmm: bool) -> KernelSteps:
    """The multi-block sub-plan's steps, plus one grid step per panel
    bucket of the SpMV tail kernel (the SpMM tail runs on the jnp path)."""
    multi = plan.multi
    steps = get_layout(multi.layout).kernel_steps(multi, nvec, nvt, spmm)
    if not spmm and plan.tail_pr and plan.single_values.size:
        steps = steps._replace(
            grid_steps=steps.grid_steps + plan.single_rows.shape[0])
    return steps


register_layout(LayoutSpec(
    name=LAYOUT_TEST,
    array_names=_TEST_ARRAYS,
    build=_build_test,
    lower_spmv=_lower_spmv_test,
    lower_spmm=_lower_spmm_test,
    cost=lambda nrows, ncols, itemsize, nvec: 0,
    clamp=_clamp_whole,
    default_cb=256,
    auto_eligible=False,
    kernel_steps=_steps_test,
))


# ----------------------------------------------------------------------------
# Shard pass: distributed slabs as per-device sub-plans
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedPlan:
    """Per-device sub-plans of one registered layout, stacked.

    ``arrays`` hold the layout's device arrays with a leading ``ndev``
    dimension (per-device shapes padded to the max across shards; padding
    chunks have mask == 0 and contribute nothing), in the layout's
    ``array_names`` order -- so the generic distributed executor can squeeze
    one device's slice and run it through the layout's own ``lower_spmv``
    (:func:`local_execute_spmv`) without knowing which layout it is. A
    reordering applied before partitioning rides along exactly as on
    :class:`SPC5Plan`. ``mesh``/``axis`` are the mesh the arrays are sharded
    over (None where the plan was built without one); :func:`execute_spmv`
    runs the plan there.
    """

    layout: str
    arrays: Tuple[jax.Array, ...]
    row_start: jax.Array            # (ndev,) global first row of each shard
    meta: Tuple[Tuple[str, Any], ...]
    col_perm: Optional[jax.Array] = None
    row_iperm: Optional[jax.Array] = None
    reorder: str = ""
    trace_json: str = "[]"
    mesh: Any = None
    axis: str = "data"
    _programs: Dict[Tuple[bool, bool], Callable] = dataclasses.field(
        default_factory=dict, compare=False, repr=False)

    def __getattr__(self, name):
        return _resolve_attr(self, name)

    @property
    def lowering(self) -> str:
        """Always "mask", as :attr:`SPC5Plan.lowering`."""
        return LOWERING_MASK

    @property
    def ndev(self) -> int:
        return int(self.arrays[0].shape[0])

    @property
    def trace(self) -> List[dict]:
        return json.loads(self.trace_json)

    def program(self, *, use_pallas: bool, interpret: bool) -> Callable:
        """The jitted y = A @ x over ``mesh``, y all-gathered and
        replicated; built once per setting, so repeated products compile
        nothing."""
        key = (use_pallas, interpret)
        if key not in self._programs:
            if self.mesh is None:
                raise ValueError("this ShardedPlan was built without a mesh; "
                                 "pass shard_matrix(..., mesh=...) to run it")
            self._programs[key] = sharded_spmv_program(
                self, self.mesh, self.axis, use_pallas=use_pallas,
                interpret=interpret)
        return self._programs[key]


@dataclasses.dataclass
class ShardState:
    """Build context handed to a layout's ``shard_build`` hook."""

    mat: F.SPC5Matrix
    parts: List[F.SPC5Matrix]
    pr: Optional[int] = None
    xw: Optional[int] = None
    cb: Optional[int] = None
    dtype: Any = None


def _shard_layout(mat: F.SPC5Matrix, ndev: int, req_layout: str,
                  config: Optional[S.PanelConfig], pr, xw, cb, entry: dict):
    """The shard pass's layout pick, ``(layout, pr, xw, cb)``: an explicit
    request wins over the tuned config (clamped against the per-shard
    slab), which wins over a ``pr``-derived panels pick and the flat
    whole-vector default. On a TPU a pick without a Mosaic kernel gives
    way to the first shardable layout of the auto order that has one, at
    that layout's default geometry (traced in ``entry``); an explicit
    request for one raises."""
    layout = LAYOUT_WHOLE
    spr, sxw, scb = pr, xw, cb
    if config is not None:
        # clamp against the per-shard slab, not the global matrix: each
        # device tiles only ~nrows/ndev rows
        rows_loc = -(-mat.nrows // max(ndev, 1))
        clayout = (config.layout if config.layout in _REGISTRY
                   else LAYOUT_WHOLE)
        config = get_layout(clayout).clamp(
            config, nrows=max(rows_loc, mat.r), ncols=mat.ncols, r=mat.r,
            c=mat.c, nblocks=max(1, -(-mat.nblocks // max(ndev, 1))))
        if config.layout == LAYOUT_PANELS:
            layout = LAYOUT_PANELS
            spr = config.pr or 512
            sxw = config.xw or 512
            scb = config.cb or F.PANEL_CB
        else:
            scb = config.cb if cb is None else cb
    if layout != LAYOUT_PANELS and pr is not None:
        layout = LAYOUT_PANELS
        spr, scb = pr, (F.PANEL_CB if scb is None else scb)
    if req_layout not in _LAYOUT_SENTINELS:
        layout = req_layout
        if layout == LAYOUT_PANELS and spr is None:
            spr, scb = 512, (F.PANEL_CB if scb is None else scb)
    if _no_mosaic_kernel(get_layout(layout),
                         req_layout not in _LAYOUT_SENTINELS, entry):
        layout = next(n for n in _AUTO_ORDER if _REGISTRY[n].mosaic
                      and _REGISTRY[n].shard_build is not None)
        spr, sxw, scb = None, xw, cb
    if get_layout(layout).shard_build is None:
        raise ValueError(
            f"layout {layout!r} registers no sharded stacking hook; "
            f"shardable layouts: "
            f"{[n for n in _REGISTRY if _REGISTRY[n].shard_build]}")
    return layout, spr, sxw, scb


def shard_plan(mat: F.SPC5Matrix, ndev: int, *, layout: str = "auto",
               cb: Optional[int] = None,
               mesh=None, axis: str = "data", dtype=None,
               vdtype: str = "auto",
               pr: Optional[int] = None, xw: int = 512,
               store: Optional[S.RecordStore] = None,
               config: Optional[S.PanelConfig] = None, tune: bool = True,
               reorder=None, partition: str = "auto") -> ShardedPlan:
    """The shard pass: tune -> reorder -> partition -> per-layout stacking.

    Mirrors :func:`make_plan` for the distributed path: the global matrix is
    (optionally) tuned at ``workers=ndev`` and reordered, then row-
    partitioned into balanced slabs, and each slab is built in the resolved
    layout and stacked by the registry's ``shard_build`` hook. ``layout``
    requests a per-device layout by registry key; "auto" resolves it from
    the tuned/explicit config, a panel height (``pr`` selects the
    row-panel-tiled layout), or the flat whole-vector default. On a TPU
    backend the layout follows :func:`make_plan`'s TPU rule: only layouts
    with a Mosaic kernel are picked (the panels layout), a demoted pick is
    traced on the ``shard`` entry, and an explicit request for another
    raises.

    ``vdtype`` follows :func:`make_plan`'s axis with one restriction: the
    shard hooks stack plain value casts, so "bf16" is served natively and
    "int8" demotes to "bf16" (traced as ``vdtype_demoted``) -- per-chunk
    scale arrays have no per-device stacking story yet.

    ``partition`` picks the row-slab balance objective: "blocks" (the
    paper's equal-block split), "nnz" (equal-nonzero split for skewed
    structure), or "auto", which reads the structure profile's per-part nnz
    skew and switches to "nnz" when the block split would leave the
    heaviest shard straggling the mesh (evidence in the trace). The
    returned :class:`ShardedPlan` carries the permutation, the pass trace
    and ``mesh``; ``ops.spmv`` runs it there through
    :func:`sharded_spmv_program`, which hands each device's slab to the
    layout's own ``lower_spmv`` (:func:`local_execute_spmv`).
    """
    from . import partition as P
    from jax.sharding import NamedSharding, PartitionSpec

    vdtype = F.canonical_vdtype(vdtype)
    if vdtype not in ("", "auto") and dtype is not None:
        raise ValueError(
            f"pass either dtype= (legacy passthrough) or vdtype={vdtype!r}, "
            f"not both -- the value-dtype axis owns the cast")
    if vdtype == "auto":
        vdtype = ""
    # The shard hooks stack plain casts; per-chunk int8 scales have no
    # per-device stacking story yet, so int8 demotes to the nearest
    # scale-free narrow store (bf16) with the demotion traced.
    vdtype_demoted = vdtype == "int8"
    if vdtype_demoted:
        vdtype = "bf16"
    if vdtype:
        dtype = F.value_dtype(vdtype)
    if partition not in P.PARTITION_MODES + ("auto",):
        raise ValueError(
            f"unknown partition mode {partition!r}; expected one of "
            f"{P.PARTITION_MODES + ('auto',)}")
    trace: List[dict] = []
    # The tune/reorder passes here intentionally differ from make_plan's:
    # tuning runs at workers=ndev and clamps against the PER-SHARD slab (not
    # the global matrix), and there is no whole-vector VMEM demotion because
    # each device's local kernel only ever sees its rows_max-row slab.
    with obs.span("shard.tune", workers=int(ndev)) as sp:
        tentry: dict = {"pass": "tune", "workers": int(ndev)}
        if config is None and tune and pr is None and cb is None:
            tstore = store if store is not None else S.get_default_store()
            if tstore is not None and tstore.records:
                config = S.tune(S.spc5_features(mat), store=tstore,
                                kernel=f"{mat.r}x{mat.c}", workers=ndev)
                tentry.update(source="store", layout=config.layout,
                              pr=int(config.pr or 0), xw=int(config.xw or 0),
                              cb=int(config.cb or 0), reorder=config.reorder)
            else:
                tentry["source"] = "no-store"
        else:
            tentry["source"] = ("explicit" if (config is not None
                                               or pr is not None
                                               or cb is not None)
                                else "disabled")
    tentry["duration_s"] = sp.duration_s
    trace.append(tentry)
    if reorder is None and config is not None and config.reorder:
        reorder = config.reorder

    with obs.span("shard.reorder") as sp:
        rentry: dict = {"pass": "reorder", "strategy": "", "applied": False}
        reo = None
        if reorder is not None:
            reo = (reorder if isinstance(reorder, RE.Reordering)
                   else RE.reorder(mat, str(reorder), r=mat.r, c=mat.c,
                                   pr=(config.pr if config is not None
                                       and config.layout == LAYOUT_PANELS
                                       else pr) or 512,
                                   xw=xw, cb=cb or F.PANEL_CB))
            rentry.update(strategy=reo.strategy,
                          stats=_scalar_stats(reo.stats))
            if reo.is_identity:
                reo = None
            else:
                mat = reo.permute_spc5(mat)
                rentry["applied"] = True
    rentry["duration_s"] = sp.duration_s
    trace.append(rentry)

    # layout resolution: explicit > tuned > pr-derived > whole-vector, under
    # the TPU rule; its decisions land on the "shard" entry
    lentry: dict = {}
    layout, spr, sxw, scb = _shard_layout(mat, ndev, canonical_layout(layout),
                                          config, pr, xw, cb, lentry)
    spec = get_layout(layout)
    if vdtype_demoted:
        lentry["vdtype_demoted"] = True
        lentry["vdtype_demoted_reason"] = "no-sharded-int8-scales"

    # partition-mode resolution: "auto" compares the nnz skew (max-shard nnz
    # over the ideal share) of the paper's block-balanced split against the
    # nnz-balanced one and switches when rebalancing meaningfully helps --
    # the arXiv:1805.11938 load-imbalance criterion, with the evidence
    # traced.
    with obs.span("shard.partition", ndev=int(ndev)) as sp:
        pentry: dict = {"pass": "partition", "requested": partition,
                        "ndev": int(ndev)}
        mode = partition
        if partition == "auto":
            skew_blocks = P.nnz_skew(mat, ndev, "blocks")
            skew_nnz = P.nnz_skew(mat, ndev, "nnz")
            mode = "nnz" if skew_nnz < 0.95 * skew_blocks else "blocks"
            pentry.update(skew_blocks=round(skew_blocks, 4),
                          skew_nnz=round(skew_nnz, 4))
        pentry["mode"] = mode
    pentry["duration_s"] = sp.duration_s
    trace.append(pentry)

    with obs.span("shard.build", layout=layout, ndev=int(ndev)) as sp:
        parts = P.partition_matrix(mat, ndev, mode)
        row_starts = P.partition_row_starts(mat, ndev, mode)
        sstate = ShardState(mat=mat, parts=parts, pr=spr, xw=sxw, cb=scb,
                            dtype=dtype)
        arrays, geom = spec.shard_build(sstate)
    geom["vdtype"] = vdtype
    sentry = {"pass": "shard", "layout": layout, "ndev": int(ndev),
              "duration_s": sp.duration_s, **lentry,
              **{k: v for k, v in sorted(geom.items())
                 if isinstance(v, (int, float, str, bool))}}
    trace.append(sentry)
    # straight from the host to each device's slab: the stack never lands
    # whole on one device
    put = (jnp.asarray if mesh is None else lambda a: jax.device_put(
        a, NamedSharding(mesh, PartitionSpec(axis))))
    arrays = tuple(put(a) for a in arrays)
    row_start = put(row_starts)
    rep = (jnp.asarray if mesh is None else lambda a: jax.device_put(
        a, NamedSharding(mesh, PartitionSpec())))
    col_perm = row_iperm = None
    reorder_name = ""
    if reo is not None:
        col_perm = rep(reo.col_perm.astype(np.int32))
        row_iperm = rep(reo.row_iperm.astype(np.int32))
        reorder_name = reo.strategy
    return ShardedPlan(layout=layout, arrays=arrays, row_start=row_start,
                       meta=tuple(sorted(geom.items())), col_perm=col_perm,
                       row_iperm=row_iperm, reorder=reorder_name,
                       trace_json=json.dumps(trace, sort_keys=True),
                       mesh=mesh, axis=axis)


def _shard_view(sh: ShardedPlan, local) -> SPC5Plan:
    """One device's slab of ``sh`` as a plan of the same layout over
    ``local`` (one device's arrays, or their shapes): ``rows_max`` rows, x
    already in permuted order."""
    meta = dict(sh.meta, nrows=sh.rows_max)
    return SPC5Plan(layout=sh.layout, arrays=tuple(local),
                    meta=tuple(sorted(meta.items())))


def local_execute_spmv(sh: ShardedPlan, local: Tuple[jax.Array, ...],
                       x: jax.Array, *, use_pallas: bool,
                       interpret: bool = False) -> jax.Array:
    """One shard's SpMV inside shard_map: the slab runs through the
    layout's own ``lower_spmv``, as a single-device plan would, so a TPU
    runs the same Mosaic kernel (the panels mask kernel) on every device.
    ``local`` is one device's slice of ``sh.arrays`` (leading ``ndev`` axis
    squeezed), ``x`` the full (permuted) input vector."""
    return get_layout(sh.layout).lower_spmv(
        _shard_view(sh, local), x, use_pallas=use_pallas,
        interpret=interpret)


def sharded_spmv_program(sh: ShardedPlan, mesh, axis: str = "data",
                         gather: bool = True, *, use_pallas: bool,
                         interpret: bool = False) -> Callable:
    """y = A @ x over ``mesh`` for ``sh``: a ``shard_map`` of
    :func:`local_execute_spmv` over each device's slab, then (``gather``)
    one all_gather of the row slabs into the full replicated y.

    The returned callable takes x alone; it is the jitted program applied
    to the plan's arrays, which it takes as ARGUMENTS, so the executable
    holds no copy of the matrix (closed over, jit would bake every slab
    into the program as a constant). ``col_perm`` gathers x before the
    shard_map (x is replicated, so collective-free) and, gathered,
    ``row_iperm`` puts y back in original row order; with gather=False
    the (ndev, rows_max) slabs stay sharded over ``axis``, in permuted row
    order.
    """
    narr = len(sh.arrays)
    nrows, rows_max = sh.nrows, sh.rows_max

    def finish(y_loc, row_start):
        if not gather:
            return y_loc[None]
        ys = jax.lax.all_gather(y_loc, axis)               # (ndev, rows_max)
        starts = jax.lax.all_gather(row_start[0], axis)    # (ndev,)
        # copy the slabs into the global vector in ascending row order: a
        # slab's padding rows are the next slab's first rows, which
        # overwrite them, and the last slab's fall past nrows. (A scatter-add
        # here compiles to a sort of all the rows: on four TPU v5e chips at
        # 4.5M rows, 46 ms a product beside the kernel's 247 ms.)
        y = jnp.zeros((nrows + rows_max,), dtype=ys.dtype)
        for d in range(ys.shape[0]):
            y = jax.lax.dynamic_update_slice(y, ys[d], (starts[d],))
        return y[:nrows]

    def body(*args):
        arrs, row_start, x = args[:narr], args[narr], args[narr + 1]
        y_loc = local_execute_spmv(
            sh, tuple(a[0] for a in arrs), x, use_pallas=use_pallas,
            interpret=interpret)
        return finish(y_loc, row_start)

    P = jax.sharding.PartitionSpec
    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(axis),) * (narr + 1)
                       + (P(),), out_specs=P() if gather else P(axis),
                       check_vma=False)

    @jax.jit
    def program(arrays, row_start, col_perm, row_iperm, x):
        if col_perm is not None:
            x = jnp.take(x, col_perm, axis=0)
        y = fn(*arrays, row_start, x)
        if gather and row_iperm is not None:
            y = jnp.take(y, row_iperm, axis=0)
        return y

    return functools.partial(program, sh.arrays, sh.row_start, sh.col_perm,
                             sh.row_iperm)
