"""Distributed SPC5 SpMV over a jax mesh (paper §Parallelization on TPU).

Mapping of the paper's shared-memory design onto SPMD devices:

  paper                                  | here
  ---------------------------------------+--------------------------------
  OpenMP threads, static block balance   | mesh devices, same interval algo
  per-NUMA-node copies of the 4 arrays   | per-device shards (shard_map)
  x allocated on master, read by all     | x replicated across the mesh
  y merged without synchronisation       | disjoint row slabs; one
                                         | all_gather AFTER compute (only
                                         | when the caller needs the full
                                         | vector, e.g. between CG steps)

The sharding itself is the plan pipeline's ``shard`` pass
(:func:`repro.core.plan.shard_plan`): the global matrix is tuned/reordered,
row-partitioned (block- or nnz-balanced), and each slab is stacked by its
layout's registered ``shard_build`` hook into a
:class:`~repro.core.plan.ShardedPlan` that remembers its mesh.

Its entry point is the one every plan has: ``ops.spmv(sh, x)``
(``plan.execute_spmv``) runs the program :func:`make_distributed_spmv`
also returns (``plan.sharded_spmv_program``), built once per plan, under
one ``exec.spmv`` span; the program takes the slabs as arguments, so it
holds no copy of the matrix. Each device runs its
slab through the layout's own ``lower_spmv`` (:func:`repro.core.plan.
local_execute_spmv`), so on a TPU every chip runs the Pallas panels mask
kernel (``spc5_spmv.spmv_pallas_panels``) the single-device plans run; on
the CPU the jnp reference runs unless Pallas is asked for (interpret
mode). No ``if layout == ...`` branching anywhere in this module.
"""
from __future__ import annotations

import warnings
from typing import Optional

from jax.sharding import Mesh

from . import plan as PL
from . import formats as F
from . import selector as S

# Legacy names: both sharded containers are the one ShardedPlan now
# (inspect ``sh.layout`` -- a plan-registry key -- to discriminate).
ShardedPlan = PL.ShardedPlan
ShardedSPC5 = PL.ShardedPlan
ShardedSPC5Panels = PL.ShardedPlan


def shard_matrix(mat: F.SPC5Matrix, ndev: int, *, layout: str = "auto",
                 cb: Optional[int] = None, mesh: Optional[Mesh] = None,
                 axis: str = "data", dtype=None, vdtype: str = "auto",
                 pr: Optional[int] = None,
                 xw: int = 512, store: Optional[S.RecordStore] = None,
                 config: Optional[S.PanelConfig] = None, tune: bool = True,
                 reorder=None,
                 partition: str = "auto") -> PL.ShardedPlan:
    """Partition + build + stack + (optionally) device_put with sharding --
    the one distributed prepare entry point.

    Thin wrapper over the plan pipeline's shard pass
    (:func:`repro.core.plan.shard_plan`). ``layout`` picks the per-device
    layout by registry key ("auto" resolves it from the tuned/explicit
    config, a panel height ``pr``, or the flat whole-vector default);
    ``cb=None`` uses the layout's default chunk size.

    **Auto-tuning**: when neither ``pr`` nor ``cb`` is given and a record
    store is available (``store``, or the selector's default store), the
    per-device layout comes from ``selector.tune`` at ``workers=ndev``,
    clamped to the per-shard row count. Passing ``config`` is the explicit
    escape hatch; ``tune=False`` keeps the fixed defaults.

    **Reordering**: ``reorder`` (strategy name or a prebuilt
    ``repro.core.reorder.Reordering``) permutes the GLOBAL matrix before
    row partitioning; the permutation rides on the returned plan and
    :func:`make_distributed_spmv` applies it transparently. A tuned config
    carrying ``config.reorder`` applies the same way.

    **Value dtype**: ``vdtype`` = "f32" | "bf16" | "int8" | "auto", as on
    ``ops.prepare``. bf16 shards are served natively; int8 demotes to bf16
    (per-chunk scale arrays have no stacked-shard story yet -- the
    demotion is recorded on the ``shard`` trace entry).

    **Partitioning**: ``partition`` = "blocks" (the paper's equal-block
    split) | "nnz" (equal-nonzero slabs for skewed structure) | "auto"
    (switch to "nnz" when the structure profile's skew says the block split
    would straggle the mesh; evidence in ``sh.trace``).
    """
    return PL.shard_plan(mat, ndev, layout=layout, cb=cb, mesh=mesh,
                         axis=axis, dtype=dtype, vdtype=vdtype, pr=pr,
                         xw=xw, store=store,
                         config=config, tune=tune, reorder=reorder,
                         partition=partition)


def shard_matrix_panels(mat: F.SPC5Matrix, ndev: int, pr: int = 512,
                        cb: int = F.PANEL_CB, xw: int = 512,
                        mesh: Optional[Mesh] = None, axis: str = "data",
                        dtype=None) -> PL.ShardedPlan:
    """Deprecated: use ``shard_matrix(mat, ndev, layout="panels", pr=...,
    tune=False)`` -- kept as a thin shim (same semantics: explicit panel
    geometry, no tuning)."""
    warnings.warn(
        "distributed.shard_matrix_panels is deprecated; use "
        "shard_matrix(mat, ndev, layout='panels', pr=..., cb=..., xw=..., "
        "tune=False)",
        DeprecationWarning, stacklevel=2)
    return shard_matrix(mat, ndev, layout=PL.LAYOUT_PANELS, pr=pr, cb=cb,
                        xw=xw, mesh=mesh, axis=axis, dtype=dtype,
                        tune=False)


def make_distributed_spmv(sh: PL.ShardedPlan, mesh: Mesh,
                          axis: str = "data", gather: bool = True, *,
                          use_pallas: Optional[bool] = None,
                          interpret: Optional[bool] = None):
    """A jit'd y = A @ x over the mesh from a :class:`ShardedPlan`: the
    program ``ops.spmv(sh, x)`` runs (:func:`repro.core.plan.
    sharded_spmv_program`, which ``ops.spmv`` builds once per plan,
    gathered).

    Layout-agnostic: each device's slab runs through the
    layout's own ``lower_spmv`` (:func:`repro.core.plan.local_execute_spmv`) --
    on a TPU the panels mask kernel (``spc5_spmv.spmv_pallas_panels``) on
    every device. ``use_pallas`` and ``interpret`` default as in
    ``ops.spmv``: the compiled kernel on a TPU, the jnp reference
    elsewhere. With gather=True the result is the full replicated y (one
    all_gather at the end -- the only collective; the paper's no-sync
    merge). With gather=False the caller keeps the row-slab layout (ndev,
    rows_max), sharded over ``axis``.

    A reordering attached by ``shard_matrix(reorder=...)`` is applied
    transparently: x is gathered by ``col_perm`` before the shard_map (x is
    replicated, so the gather is collective-free) and, with gather=True, y
    is scattered back to original row order after the all_gather. With
    gather=False the row slabs stay in PERMUTED row order (``sh.row_iperm``
    is the map back).
    """
    use_pallas, interpret = PL._resolve_pallas(use_pallas, interpret)
    return PL.sharded_spmv_program(sh, mesh, axis, gather,
                                   use_pallas=use_pallas,
                                   interpret=interpret)
