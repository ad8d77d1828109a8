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
    (vec tiles, panels, chunks), the kernel body ``spc5_spmv`` shares with
    SpMV; each step holds a (tile, pr) y tile and DMA'd value and x
    windows, so VMEM stays bounded for arbitrarily large matrices (see
    repro.core.formats.SPC5Panels). This is the SpMM kernel a TPU runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spc5_spmv import (_acc_itemsize, _desc_rest,
                                     _desc_tile_bytes, _expand_vals,
                                     _mask_rest, _out_dtype, _panel_scratch,
                                     _vmem_panels_mask, panel_mask_call)

# ----------------------------------------------------------------------------
# VMEM contracts (read by repro.analysis.verify's "vmem-budget" rule)
# ----------------------------------------------------------------------------


def _nvt(nvec: int) -> int:
    return min(max(int(nvec), 1), 128)


def _vmem_whole_mask(geom, itemsize, nvec=1):
    # (ncols, nvt) x tile + (nrows, nvt) y tile (both at the f32 accumulation
    # width) + double-buffered value window at the storage ``itemsize`` +
    # chunk metadata + a potential fused col_map
    return ((geom["nrows"] + geom["ncols"])
            * _acc_itemsize(itemsize) * _nvt(nvec)
            + 2 * geom["vmax"] * itemsize + 4 * 4 * geom["cb"]
            + 4 * geom["ncols"])


def _vmem_whole_desc(geom, itemsize, nvec=1):
    rc = geom["r"] * geom["c"]
    return ((geom["nrows"] + geom["ncols"])
            * _acc_itemsize(itemsize) * _nvt(nvec)
            + 2 * geom["vmax"] * itemsize
            + _desc_tile_bytes(geom) * geom["cb"] * rc)


def _vmem_panels_desc(geom, itemsize, nvec=1):
    rc = geom["r"] * geom["c"]
    return ((geom["pr"] + 2 * geom["xw"])
            * _acc_itemsize(itemsize) * _nvt(nvec)
            + 2 * geom["vmax"] * itemsize
            + _desc_tile_bytes(geom) * geom["cb"] * rc)


#: (layout, lowering) -> fn(geom_dict, itemsize, nvec=1) -> resident bytes
#: per grid step; the SpMM side of the contracts in
#: ``spc5_spmv.SPMV_VMEM_CONTRACTS`` (``nvec`` scales the x/y tiles by
#: nvt = min(nvec, 128), exactly as ``plan.fits_whole_vector`` budgets).
SPMM_VMEM_CONTRACTS = {
    ("whole_vector", "mask"): _vmem_whole_mask,
    ("whole_vector", "descriptor"): _vmem_whole_desc,
    ("panels", "mask"): _vmem_panels_mask,   # one kernel body for both
    ("panels", "descriptor"): _vmem_panels_desc,
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

    y_ref[...] = _spmm_block_accumulate(
        y_ref[...], vals, xg, lambda lr: jnp.clip(row + lr, 0, nrows - 1),
        r, c, cb)


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


def _spmm_block_accumulate(y, vals, xg, row_of_lr, r, c, cb):
    """Shared (r, c)-unrolled block FMA + row scatter of the SpMM kernels.

    ``row_of_lr(lr)`` supplies the per-block scatter rows for block row
    ``lr`` -- clipped ``row + lr`` for the mask kernels, the precomputed
    ``desc_yrow[:, lr*c]`` lane for the descriptor kernels."""
    for lr in range(r):                      # static unroll over block rows
        acc = jnp.zeros((cb, y.shape[1]), dtype=y.dtype)
        for lc in range(c):                  # static unroll over block cols
            acc = acc + vals[:, lr * c + lc, None] * xg[:, lc, :]
        y = y.at[row_of_lr(lr)].add(acc)
    return y


def _panel_fused_operands_mm(x, col_map, ncols_pad, nvt):
    """SpMM analogue of the SpMV panel kernels' fused-cols plumbing: with a
    column map, the (ncols_pad, nvt) x tile and the map are VMEM-resident
    and the window DMA is skipped (x never materialises permuted)."""
    fused = col_map is not None
    if fused:
        cm = jnp.pad(col_map.astype(jnp.int32),
                     (0, max(0, ncols_pad - col_map.shape[0])))
        specs = [pl.BlockSpec((ncols_pad, nvt),
                              lambda j, p, i, vb, xb: (0, j)),   # x (VMEM)
                 pl.BlockSpec((ncols_pad,),
                              lambda j, p, i, vb, xb: (0,))]     # cmap
        return specs, [x, cm], fused
    return [pl.BlockSpec(memory_space=pl.ANY)], [x], fused


def _append_panel_scale_mm(xspecs, xops, value_scale):
    """SpMM analogue of ``spc5_spmv._append_panel_scale``: one (1, 1) tile of
    the (npanels, nchunks) scales per grid step, appended after the optional
    fused column map (the ``_mask_rest`` unpack order)."""
    if value_scale is None:
        return xspecs, xops
    return (xspecs
            + [pl.BlockSpec((1, 1), lambda j, p, i, vb, xb: (p, i))],
            xops + [value_scale])


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
    (vec-tiles, panels, chunks), one DMA'd x window per vector of the tile
    and a (tile, pr) y tile per panel. A column permutation is applied to X by the
    caller; ``value_scale`` dequantises int8 storage."""
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


# ----------------------------------------------------------------------------
# Descriptor lowering: precomputed gather tables, no in-kernel mask decode
# ----------------------------------------------------------------------------
#
# The per-lane descriptor tables (repro.core.formats.chunk_descriptors)
# carry validity, value index, x column and y row. SpMM consumes them at
# block granularity: lanes k and k+c share a column, so ``desc_xcol[:, :c]``
# is exactly the mask kernel's per-block column gather (with any fused
# column permutation already folded in) and ``desc_yrow[:, ::c]`` the
# per-block-row scatter targets -- the expand is one gather + mask multiply.

def _spmm_desc_vals(vwin, valid, vidx, scale=None):
    vals = _expand_vals(jnp.take(vwin, vidx.astype(jnp.int32), axis=0), scale)
    return vals * valid.astype(vals.dtype)


def _spmm_desc_kernel(vbase_ref, valid_ref, vidx_ref, xcol_ref, yrow_ref,
                      values_hbm, x_ref, *rest, r: int, c: int,
                      cb: int, vmax: int, has_scale: bool = False):
    """Whole-vector descriptor SpMM step (grid: vec-tiles x chunks)."""
    scale_ref, (y_ref, vwin, sem) = _desc_rest(rest, has_scale)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    copy = pltpu.make_async_copy(values_hbm.at[pl.ds(vbase_ref[i], vmax)],
                                 vwin, sem)
    copy.start()
    copy.wait()

    vals = _spmm_desc_vals(vwin[...], valid_ref[0], vidx_ref[0],
                           None if scale_ref is None else scale_ref[0])
    xg = jnp.take(x_ref[...], xcol_ref[0][:, :c].astype(jnp.int32),
                  axis=0)                                       # (cb, c, nvt)
    yrow = yrow_ref[0].astype(jnp.int32)
    y_ref[...] = _spmm_block_accumulate(
        y_ref[...], vals, xg, lambda lr: yrow[:, lr * c], r, c, cb)


@functools.partial(
    jax.jit,
    static_argnames=("r", "c", "cb", "vmax", "nrows", "ncols", "nvt",
                     "interpret"))
def spmm_pallas_desc(chunk_vbase, desc_valid, desc_vidx, desc_xcol,
                     desc_yrow, values, x, value_scale=None, *, r: int,
                     c: int, cb: int, vmax: int, nrows: int, ncols: int,
                     nvt: int = 128, interpret: bool = False):
    """Whole-vector Y = A @ X over build-time descriptors
    (lowering="descriptor"; column permutations are folded into
    ``desc_xcol`` at build time, so there is no ``col_map`` input)."""
    nchunks = desc_valid.shape[0]
    nvec = x.shape[1]
    nvt = min(nvt, nvec)
    if nvec % nvt:
        raise ValueError(f"nvec={nvec} not divisible by tile {nvt}")
    rc = r * c
    in_specs = [
        pl.BlockSpec((1, cb, rc), lambda j, i, vb: (i, 0, 0)),
        pl.BlockSpec((1, cb, rc), lambda j, i, vb: (i, 0, 0)),
        pl.BlockSpec((1, cb, rc), lambda j, i, vb: (i, 0, 0)),
        pl.BlockSpec((1, cb, rc), lambda j, i, vb: (i, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),                  # values
        pl.BlockSpec((ncols, nvt), lambda j, i, vb: (0, j)),  # x tile
    ]
    operands = [chunk_vbase, desc_valid, desc_vidx, desc_xcol, desc_yrow,
                values, x]
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
        functools.partial(_spmm_desc_kernel, r=r, c=c, cb=cb, vmax=vmax,
                          has_scale=value_scale is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nrows, nvec), _out_dtype(values, x)),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(*operands)


def _spmm_panel_desc_kernel(vbase_ref, xbase_ref, valid_ref, vidx_ref,
                            xcol_ref, yrow_ref, values_hbm, x_ref, *rest,
                            r: int, c: int, cb: int, vmax: int, xw: int,
                            pr: int, nvt: int, ncols_pad: int,
                            fused_cols: bool = False,
                            has_scale: bool = False):
    """Panel descriptor SpMM step (grid: vec-tiles x panels x chunks)."""
    cmap_ref, scale_ref, rest = _mask_rest(rest, fused_cols, has_scale)
    if fused_cols:
        y_ref, vwin, vsem = rest
    else:
        y_ref, vwin, xwin, vsem, xsem = rest
    j = pl.program_id(0)
    p = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    vcopy = pltpu.make_async_copy(
        values_hbm.at[pl.ds(vbase_ref[p, i], vmax)], vwin, vsem)
    vcopy.start()
    if not fused_cols:
        xcopy = pltpu.make_async_copy(
            x_ref.at[pl.ds(xbase_ref[p, i], xw), pl.ds(j * nvt, nvt)],
            xwin, xsem)
        xcopy.start()
    vcopy.wait()
    if not fused_cols:
        xcopy.wait()

    vals = _spmm_desc_vals(vwin[...], valid_ref[0, 0], vidx_ref[0, 0],
                           None if scale_ref is None else scale_ref[0, 0])
    if fused_cols:
        xcol = jnp.clip(xcol_ref[0, 0][:, :c].astype(jnp.int32)
                        + xbase_ref[p, i], 0, ncols_pad - 1)
        xcol = jnp.take(cmap_ref[...], xcol, axis=0)
        xg = jnp.take(x_ref[...], xcol, axis=0)
    else:
        xg = jnp.take(xwin[...], xcol_ref[0, 0][:, :c].astype(jnp.int32),
                      axis=0)
    yrow = yrow_ref[0, 0].astype(jnp.int32)
    y_ref[...] = _spmm_block_accumulate(
        y_ref[...], vals, xg, lambda lr: yrow[:, lr * c], r, c, cb)


def _spmm_desc_panel_specs(cb, rc, xspecs):
    return [
        pl.BlockSpec((1, 1, cb, rc), lambda j, p, i, vb, xb: (p, i, 0, 0)),
        pl.BlockSpec((1, 1, cb, rc), lambda j, p, i, vb, xb: (p, i, 0, 0)),
        pl.BlockSpec((1, 1, cb, rc), lambda j, p, i, vb, xb: (p, i, 0, 0)),
        pl.BlockSpec((1, 1, cb, rc), lambda j, p, i, vb, xb: (p, i, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),                    # values (HBM)
    ] + xspecs


@functools.partial(
    jax.jit,
    static_argnames=("r", "c", "cb", "vmax", "xw", "pr", "nrows", "ncols_pad",
                     "nvt", "interpret"))
def spmm_pallas_panels_desc(chunk_vbase, chunk_xbase, desc_valid, desc_vidx,
                            desc_xcol, desc_yrow, values, x, col_map=None,
                            value_scale=None, *,
                            r: int, c: int, cb: int, vmax: int, xw: int,
                            pr: int, nrows: int, ncols_pad: int,
                            nvt: int = 128, interpret: bool = False):
    """Row-panel-tiled descriptor Y = A @ X (lowering="descriptor")."""
    npanels, nchunks = chunk_vbase.shape
    nvec = x.shape[1]
    nvt = min(nvt, nvec)
    if nvec % nvt:
        raise ValueError(f"nvec={nvec} not divisible by tile {nvt}")
    xp = jnp.pad(x, ((0, max(0, ncols_pad - x.shape[0])), (0, 0)))
    xspecs, xops, fused = _panel_fused_operands_mm(xp, col_map, ncols_pad,
                                                   nvt)
    xspecs, xops = _append_panel_scale_mm(xspecs, xops, value_scale)
    scratch = _panel_scratch(fused, 1, vmax, values.dtype, (xw, nvt),
                             x.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                  # chunk_vbase, chunk_xbase
        grid=(nvec // nvt, npanels, nchunks),
        in_specs=_spmm_desc_panel_specs(cb, r * c, xspecs),
        out_specs=pl.BlockSpec((pr, nvt), lambda j, p, i, vb, xb: (p, j)),
        scratch_shapes=scratch,
    )
    y = pl.pallas_call(
        functools.partial(_spmm_panel_desc_kernel, r=r, c=c, cb=cb,
                          vmax=vmax, xw=xw, pr=pr, nvt=nvt,
                          ncols_pad=ncols_pad, fused_cols=fused,
                          has_scale=value_scale is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((npanels * pr, nvec),
                                       _out_dtype(values, x)),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
    )(chunk_vbase, chunk_xbase, desc_valid, desc_vidx, desc_xcol, desc_yrow,
      values, *xops)
    return y[:nrows]


def _spmm_panel_desc_db_kernel(vbase_ref, xbase_ref, valid_ref, vidx_ref,
                               xcol_ref, yrow_ref, values_hbm, x_ref, *rest,
                               r: int, c: int, cb: int, vmax: int, xw: int,
                               pr: int, nvt: int, ncols_pad: int,
                               npanels: int, nchunks: int, nsteps: int,
                               fused_cols: bool = False,
                               has_scale: bool = False):
    """Double-buffered panel descriptor SpMM (same linearised-step
    pipelining as ``_spmm_panel_db_kernel``)."""
    cmap_ref, scale_ref, rest = _mask_rest(rest, fused_cols, has_scale)
    if fused_cols:
        y_ref, vwin, vsem = rest
    else:
        y_ref, vwin, xwin, vsem, xsem = rest
    j = pl.program_id(0)
    p = pl.program_id(1)
    i = pl.program_id(2)
    t = (j * npanels + p) * nchunks + i
    slot = jax.lax.rem(t, jnp.int32(2))

    @pl.when(i == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(t == 0)
    def _first():
        pltpu.make_async_copy(values_hbm.at[pl.ds(vbase_ref[0, 0], vmax)],
                              vwin.at[0], vsem.at[0]).start()
        if not fused_cols:
            pltpu.make_async_copy(
                x_ref.at[pl.ds(xbase_ref[0, 0], xw), pl.ds(0, nvt)],
                xwin.at[0], xsem.at[0]).start()

    @pl.when(t + 1 < nsteps)
    def _prefetch_next():
        nxt = jax.lax.rem(t + jnp.int32(1), jnp.int32(2))
        inn = jax.lax.rem(t + jnp.int32(1), jnp.int32(nchunks))
        jp = (t + jnp.int32(1)) // jnp.int32(nchunks)   # j * npanels + p
        pn = jax.lax.rem(jp, jnp.int32(npanels))
        jn = jp // jnp.int32(npanels)
        pltpu.make_async_copy(values_hbm.at[pl.ds(vbase_ref[pn, inn], vmax)],
                              vwin.at[nxt], vsem.at[nxt]).start()
        if not fused_cols:
            pltpu.make_async_copy(
                x_ref.at[pl.ds(xbase_ref[pn, inn], xw), pl.ds(jn * nvt, nvt)],
                xwin.at[nxt], xsem.at[nxt]).start()

    pltpu.make_async_copy(values_hbm.at[pl.ds(vbase_ref[p, i], vmax)],
                          vwin.at[slot], vsem.at[slot]).wait()
    if not fused_cols:
        pltpu.make_async_copy(
            x_ref.at[pl.ds(xbase_ref[p, i], xw), pl.ds(j * nvt, nvt)],
            xwin.at[slot], xsem.at[slot]).wait()

    vals = _spmm_desc_vals(vwin[slot], valid_ref[0, 0], vidx_ref[0, 0],
                           None if scale_ref is None else scale_ref[0, 0])
    if fused_cols:
        xcol = jnp.clip(xcol_ref[0, 0][:, :c].astype(jnp.int32)
                        + xbase_ref[p, i], 0, ncols_pad - 1)
        xcol = jnp.take(cmap_ref[...], xcol, axis=0)
        xg = jnp.take(x_ref[...], xcol, axis=0)
    else:
        xg = jnp.take(xwin[slot], xcol_ref[0, 0][:, :c].astype(jnp.int32),
                      axis=0)
    yrow = yrow_ref[0, 0].astype(jnp.int32)
    y_ref[...] = _spmm_block_accumulate(
        y_ref[...], vals, xg, lambda lr: yrow[:, lr * c], r, c, cb)


@functools.partial(
    jax.jit,
    static_argnames=("r", "c", "cb", "vmax", "xw", "pr", "nrows", "ncols_pad",
                     "nvt", "interpret"))
def spmm_pallas_panels_desc_db(chunk_vbase, chunk_xbase, desc_valid,
                               desc_vidx, desc_xcol, desc_yrow, values, x,
                               col_map=None, value_scale=None, *,
                               r: int, c: int, cb: int,
                               vmax: int, xw: int, pr: int, nrows: int,
                               ncols_pad: int, nvt: int = 128,
                               interpret: bool = False):
    """Double-buffered :func:`spmm_pallas_panels_desc`."""
    npanels, nchunks = chunk_vbase.shape
    nvec = x.shape[1]
    nvt = min(nvt, nvec)
    if nvec % nvt:
        raise ValueError(f"nvec={nvec} not divisible by tile {nvt}")
    xp = jnp.pad(x, ((0, max(0, ncols_pad - x.shape[0])), (0, 0)))
    xspecs, xops, fused = _panel_fused_operands_mm(xp, col_map, ncols_pad,
                                                   nvt)
    xspecs, xops = _append_panel_scale_mm(xspecs, xops, value_scale)
    scratch = _panel_scratch(fused, 2, vmax, values.dtype, (xw, nvt),
                             x.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                  # chunk_vbase, chunk_xbase
        grid=(nvec // nvt, npanels, nchunks),
        in_specs=_spmm_desc_panel_specs(cb, r * c, xspecs),
        out_specs=pl.BlockSpec((pr, nvt), lambda j, p, i, vb, xb: (p, j)),
        scratch_shapes=scratch,
    )
    y = pl.pallas_call(
        functools.partial(_spmm_panel_desc_db_kernel, r=r, c=c, cb=cb,
                          vmax=vmax, xw=xw, pr=pr, nvt=nvt,
                          ncols_pad=ncols_pad, npanels=npanels,
                          nchunks=nchunks,
                          nsteps=(nvec // nvt) * npanels * nchunks,
                          fused_cols=fused,
                          has_scale=value_scale is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((npanels * pr, nvec),
                                       _out_dtype(values, x)),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
    )(chunk_vbase, chunk_xbase, desc_valid, desc_vidx, desc_xcol, desc_yrow,
      values, *xops)
    return y[:nrows]
