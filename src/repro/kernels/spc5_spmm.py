"""Pallas TPU kernel: SPC5 block-sparse x dense multi-vector (SpMM).

The paper names "multiplication by multiple vectors" as the natural extension
of the block kernels; in the LM framework this is the SparseLinear matmul
(sparse pruned weight @ dense activations). The value-window DMA pattern is
identical to the SpMV kernel, x/y are tiled over the vector dimension in
lane-aligned (…, nvt) tiles, and the per-block product unrolls the (r, c)
geometry into VPU multiply-adds (tiny r*c GEMMs would waste the 128x128
MXU -- DESIGN.md §2).

Two layouts:

  * ``spmm_pallas`` -- whole-vector layout, grid (nvec tiles, chunks); the
    full (ncols, nvt) x tile and (nrows, nvt) y tile are VMEM-resident
    (interpret mode only: Mosaic lowers neither its gathers nor its
    scatter).
  * ``spmm_pallas_panels`` -- row-panel-tiled layout, grid
    (vec tiles, panels, chunk groups), the kernel body ``spc5_spmv``
    shares with SpMV; each step holds a (tile, pr) y tile and a ring of
    DMA'd value and x windows, so VMEM stays bounded for arbitrarily large
    matrices (see repro.core.formats.SPC5Panels). This is the SpMM kernel
    a TPU runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spc5_spmv import (_acc_itemsize, _expand_vals,
                                     _mask_rest, _out_dtype,
                                     _vmem_panels_mask, panel_mask_call)

# ----------------------------------------------------------------------------
# VMEM contracts (read by repro.analysis.verify's "vmem-budget" rule)
# ----------------------------------------------------------------------------


def _nvt(nvec: int) -> int:
    return min(max(int(nvec), 1), 128)


def _vmem_whole_mask(geom, itemsize, nvec=1):
    # (ncols, nvt) x tile + (nrows, nvt) y tile (both at the f32 accumulation
    # width) + the value window at the storage ``itemsize`` (budgeted
    # twice) + chunk metadata + a potential fused col_map
    return ((geom["nrows"] + geom["ncols"])
            * _acc_itemsize(itemsize) * _nvt(nvec)
            + 2 * geom["vmax"] * itemsize + 4 * 4 * geom["cb"]
            + 4 * geom["ncols"])


#: layout -> fn(geom_dict, itemsize, nvec=1) -> resident bytes per grid
#: step; the SpMM side of the contracts in
#: ``spc5_spmv.SPMV_VMEM_CONTRACTS`` (``nvec`` scales the x/y tiles by
#: nvt = min(nvec, 128), exactly as ``plan.fits_whole_vector`` budgets).
SPMM_VMEM_CONTRACTS = {
    "whole_vector": _vmem_whole_mask,
    "panels": _vmem_panels_mask,   # one kernel body for both
}


def _spmm_kernel(vbase_ref, col_ref, mask_ref, voff_ref, row_ref, values_hbm,
                 x_ref, *rest, r: int, c: int, cb: int,
                 vmax: int, nrows: int, ncols: int, fused_cols: bool = False,
                 has_scale: bool = False):
    cmap_ref, scale_ref, (y_ref, vwin, sem) = _mask_rest(rest, fused_cols,
                                                         has_scale)
    i = pl.program_id(1)  # chunk index (inner, sequential)

    @pl.when(i == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    base = vbase_ref[i]
    copy = pltpu.make_async_copy(values_hbm.at[pl.ds(base, vmax)], vwin, sem)
    copy.start()
    copy.wait()

    rc = r * c
    mask = mask_ref[0]
    voff = voff_ref[0]
    col = col_ref[0]
    row = row_ref[0]
    k = jnp.arange(rc, dtype=jnp.int32)
    bits = ((mask[:, None] >> k[None, :]) & 1).astype(jnp.int32)    # (cb, rc)
    ranks = jnp.cumsum(bits, axis=1) - bits
    vidx = jnp.clip(voff[:, None] + ranks, 0, vmax - 1)
    vals = _expand_vals(jnp.take(vwin[...], vidx, axis=0),
                        None if scale_ref is None else scale_ref[0])
    vals = vals * bits.astype(vals.dtype)

    # Gather the c columns of x once: (cb, c, nvt). Block columns are
    # contiguous in permuted space, so a fused column permutation routes the
    # gather through cmap (x stays in original order, see spc5_spmv).
    xcol = jnp.clip(col[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :],
                    0, ncols - 1)
    if cmap_ref is not None:
        xcol = jnp.take(cmap_ref[...], xcol, axis=0)
    xg = jnp.take(x_ref[...], xcol, axis=0)                          # (cb,c,nvt)

    # (r, c)-unrolled block FMA, then the row scatter of each block row
    y = y_ref[...]
    for lr in range(r):
        acc = jnp.zeros((cb, y.shape[1]), dtype=y.dtype)
        for lc in range(c):
            acc = acc + vals[:, lr * c + lc, None] * xg[:, lc, :]
        y = y.at[jnp.clip(row + lr, 0, nrows - 1)].add(acc)
    y_ref[...] = y


@functools.partial(
    jax.jit,
    static_argnames=("r", "c", "cb", "vmax", "nrows", "ncols", "nvt",
                     "interpret"))
def spmm_pallas(chunk_vbase, chunk_col, chunk_mask, chunk_voff, chunk_row,
                values, x, col_map=None, value_scale=None, *, r: int, c: int,
                cb: int, vmax: int, nrows: int, ncols: int, nvt: int = 128,
                interpret: bool = False):
    """Y = A @ X with A chunked beta(r,c) and X of shape (ncols, nvec).

    ``col_map`` (optional, (ncols,) int32) fuses a column permutation into
    the decode -- X stays in original row order and the kernel gathers
    ``x[col_map[col]]`` (the reordering subsystem's zero-copy path).
    ``value_scale`` (optional, (nchunks,) f32) dequantises int8 storage.
    """
    nchunks = chunk_col.shape[0]
    nvec = x.shape[1]
    nvt = min(nvt, nvec)
    if nvec % nvt:
        raise ValueError(f"nvec={nvec} not divisible by tile {nvt}")
    fused_cols = col_map is not None
    kernel = functools.partial(_spmm_kernel, r=r, c=c, cb=cb, vmax=vmax,
                               nrows=nrows, ncols=ncols,
                               fused_cols=fused_cols,
                               has_scale=value_scale is not None)
    in_specs = [
        pl.BlockSpec((1, cb), lambda j, i, vb: (i, 0)),
        pl.BlockSpec((1, cb), lambda j, i, vb: (i, 0)),
        pl.BlockSpec((1, cb), lambda j, i, vb: (i, 0)),
        pl.BlockSpec((1, cb), lambda j, i, vb: (i, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec((ncols, nvt), lambda j, i, vb: (0, j)),
    ]
    operands = [chunk_vbase, chunk_col, chunk_mask.astype(jnp.int32),
                chunk_voff, chunk_row, values, x]
    if fused_cols:
        in_specs.append(pl.BlockSpec((ncols,), lambda j, i, vb: (0,)))
        operands.append(col_map.astype(jnp.int32))
    if value_scale is not None:
        in_specs.append(pl.BlockSpec((1,), lambda j, i, vb: (i,)))
        operands.append(value_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nvec // nvt, nchunks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((nrows, nvt), lambda j, i, vb: (0, j)),
        scratch_shapes=[
            pltpu.VMEM((vmax,), values.dtype),
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nrows, nvec), _out_dtype(values, x)),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(*operands)


@functools.partial(
    jax.jit,
    static_argnames=("r", "c", "cb", "vmax", "xw", "pr", "nrows", "ncols_pad",
                     "nvt", "interpret"))
def spmm_pallas_panels(chunk_vbase, chunk_xbase, chunk_col, chunk_mask,
                       chunk_voff, chunk_row, values, x, value_scale=None, *,
                       r: int, c: int, cb: int, vmax: int, xw: int, pr: int,
                       nrows: int, ncols_pad: int, nvt: int = 128,
                       interpret: bool = False):
    """Row-panel-tiled Y = A @ X; X (ncols, nvec), padded to ncols_pad rows.

    The kernel body is ``spc5_spmv``'s panel mask kernel: grid
    (vec-tiles, panels, chunk groups), one DMA'd x window per vector of
    the tile and a (tile, pr) y tile per panel. A column permutation is
    applied to X by the caller; ``value_scale`` dequantises int8
    storage."""
    nvec = x.shape[1]
    nvt = min(nvt, nvec)
    if nvec % nvt:
        raise ValueError(f"nvec={nvec} not divisible by tile {nvt}")
    xp = jnp.pad(x, ((0, max(0, ncols_pad - x.shape[0])), (0, 0)))
    yt = panel_mask_call(chunk_vbase, chunk_xbase, chunk_col, chunk_mask,
                         chunk_voff, chunk_row, values, xp.T, value_scale,
                         r=r, c=c, cb=cb, vmax=vmax, xw=xw, pr=pr, nvt=nvt,
                         interpret=interpret)
    y = jnp.transpose(yt, (0, 3, 1, 2)).reshape(-1, nvec)[:nrows]
    return y.astype(_out_dtype(values, x))
