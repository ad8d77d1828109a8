"""Pallas TPU kernel: SPC5 mask-expand SpMV (beta(r,c), no zero padding).

TPU adaptation of the paper's AVX-512 ``vexpandpd`` kernel (DESIGN.md §2):

  * the packed ``values`` array lives in HBM (``pl.ANY``) with no zero
    padding inside a chunk, and a window holding one chunk's values is
    DMA'd into a VMEM scratch (the panel kernel's window starts on an
    8-row tile boundary, so it reads up to 1023 values ahead of the
    chunk);
  * the expand is ``rank = cumsum(mask_bits) - mask_bits`` + a VMEM gather,
    replacing the in-register expand (identical semantics, zero HBM cost);
  * a chunk of ``cb`` blocks is decoded at a time; the panel kernel
    lays the blocks along lanes, so its default chunk
    (``formats.PANEL_CB``) fills the 128 lanes of one vreg row -- a
    narrower one pays for the padded lanes on more chunks;
  * y is accumulated across sequential grid steps in VMEM and written once
    (the paper's "merge without synchronization" -- rows are owned uniquely);
    the panel kernel adds a chunk's blocks into its y tile with one one-hot
    MXU contraction, so it agrees with the reference scatter within a
    float32 reassociation of each row's adds, not bit for bit.

Scalars in SMEM carry the per-chunk value-window offsets, the analogue of
the asm kernel's running value cursor (%r12 in the paper's code 1).

Two layouts:

**Row-panel-tiled** (``spmv_pallas_panels``; SpMM in ``spc5_spmm``): grid
``(vec-tiles, panels, chunk groups)`` over
:class:`repro.core.formats.SPC5Panels`. Each step holds one panel's y tile
(written back once per panel) and walks a group of the panel's chunks --
all of them wherever that fits VMEM (``panel_group``) -- through a
two-slot ring of value and x windows: chunk g + 1's windows are DMA'd at
its scalar bases while chunk g is decoded. VMEM per step is independent
of matrix size. This is the kernel Mosaic compiles for a TPU (see "Panel
mask kernel" below) and the only one ``ops.prepare`` picks there.

**Whole-vector** (``spmv_pallas``): grid ``(nchunks,)``, ``x`` (ncols)
and ``y`` (nrows) fully VMEM-resident, a full-vector scatter per chunk. Its
per-lane ``jnp.take`` and ``.at[].add`` run in interpret mode only; Mosaic
lowers neither.
"""
from __future__ import annotations

import functools
import math
import operator
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ----------------------------------------------------------------------------
# VMEM contracts (read by repro.analysis.verify's "vmem-budget" rule)
# ----------------------------------------------------------------------------

#: Per-core VMEM ceiling the kernels budget against (v5e: 16 MiB minus
#: compiler headroom; the contracts below must stay safely under it).
VMEM_LIMIT_BYTES = 16 * 2**20


def _acc_itemsize(itemsize):
    """x/y vector bytes: quantised values upcast to f32 before touching the
    vectors, so those terms never shrink below 4 bytes per element."""
    return max(int(itemsize), 4)


def _vmem_whole_mask(geom, itemsize, nvec=1):
    # x (ncols) + y (nrows) at accumulation width + the value window at the
    # STORAGE itemsize (budgeted twice) + chunk metadata (4 int32 tables of
    # cb) + a potential fused col_map (ncols int32)
    return ((geom["nrows"] + geom["ncols"]) * _acc_itemsize(itemsize)
            + 2 * geom["vmax"] * itemsize
            + 4 * 4 * geom["cb"] + 4 * geom["ncols"])


def _panel_step_bytes(geom, itemsize, kt, group):
    # the panel kernel (``_panel_kernel``) with kt vectors and ``group``
    # chunks per step: the (kt, pr) y tile (double-buffered, plus the
    # loop's carry), two ring slots of the kt (rows, 128) x windows and of
    # the (rows, 128) value window at the storage itemsize, the
    # (group, 4, cb) chunk metadata (double-buffered), the decode's one-hot
    # and (256, cb) select temporaries plus its r*c per-lane products, and
    # the scatter's (pr, cb) one-hot (lanes padded to 128), r (kt, cb)
    # block sums and (r * kt, pr) contraction result -- matrix-size
    # independent
    cb, acc = geom["cb"], _acc_itemsize(itemsize)
    pr, r = geom["pr"], geom["r"]
    xrows = _x_rows(geom["xw"])
    return ((3 * kt * pr + 2 * kt * xrows * _LANES
             + 2 * _LANES * 2 * cb * (kt + 1)
             + r * geom["c"] * kt * cb
             + pr * -(-cb // _LANES) * _LANES + r * kt * (cb + pr)) * acc
            + 2 * _value_rows(geom["vmax"], itemsize) * _LANES * itemsize
            + 2 * group * 4 * 4 * cb)


def panel_group(geom, itemsize, kt) -> int:
    """Chunks one panel-kernel grid step walks: a whole panel's
    ``geom["nchunks"]`` where that step fits :data:`VMEM_LIMIT_BYTES`, else
    the fewest groups that fit, balanced so the last one pads least."""
    nchunks = geom["nchunks"]
    fixed = _panel_step_bytes(geom, itemsize, kt, 0)
    most = max(1, (VMEM_LIMIT_BYTES - fixed) // (2 * 4 * 4 * geom["cb"]))
    return -(-nchunks // -(-nchunks // most))


def _vmem_panels_mask(geom, itemsize, nvec=1):
    kt = min(max(int(nvec), 1), _MAX_VEC_TILE)
    return _panel_step_bytes(geom, itemsize, kt,
                             panel_group(geom, itemsize, kt))


#: layout -> fn(geom_dict, itemsize, nvec=1) -> resident bytes per grid
#: step. Every registered layout with a packed value store MUST declare its
#: contract here; the static verifier refuses plans whose declared footprint
#: exceeds :data:`VMEM_LIMIT_BYTES` and the lint's registry-consistency rule
#: cross-checks coverage against the registry.
SPMV_VMEM_CONTRACTS = {
    "whole_vector": _vmem_whole_mask,
    "panels": _vmem_panels_mask,
}


def _quantised(dtype) -> bool:
    """True when the storage dtype needs an in-decode upcast to f32 (int8,
    or any sub-4-byte float such as bf16)."""
    dt = np.dtype(dtype)
    return dt.kind in "iu" or dt.itemsize < 4


def _out_dtype(values, x):
    """Kernel output dtype: quantised storage accumulates (and returns) in
    f32 -- promoted with x so f64 inputs keep their width -- while full-width
    storage keeps the pre-dtype-axis behaviour (values.dtype) exactly."""
    if _quantised(values.dtype):
        return jnp.promote_types(jnp.float32, x.dtype)
    return values.dtype


def _expand_vals(vals, scale=None):
    """The f32-accumulation contract: quantised values upcast inside the
    decode, then the per-chunk dequantisation ``scale`` (a scalar here --
    one chunk per grid step) applies. f32 storage passes through untouched.
    """
    if _quantised(vals.dtype):
        vals = vals.astype(jnp.float32)
    if scale is not None:
        vals = vals * scale
    return vals


def _decode_chunk(mask, voff, col, vwin, x, *, r: int, c: int, ncols: int,
                  vmax: int, cmap=None, scale=None):
    """Mask-expand one chunk: returns contrib (cb, r*c) and local row offsets.

    ``cmap`` is the fused column-permutation map of the reordering subsystem
    (repro.core.reorder): block columns are contiguous in *permuted* column
    space, so a column permutation cannot be folded into ``chunk_col``
    itself -- instead the decode routes its gather through ``cmap`` (one
    extra VMEM-resident int32 vector), reading original-order x with zero
    HBM cost. None keeps the pre-reorder index path bit-for-bit intact.
    ``scale`` is the chunk's scalar dequantisation factor (int8 storage).
    """
    rc = r * c
    k = jnp.arange(rc, dtype=jnp.int32)
    bits = ((mask[:, None] >> k[None, :]) & 1).astype(jnp.int32)   # (cb, rc)
    ranks = jnp.cumsum(bits, axis=1) - bits
    vidx = jnp.clip(voff[:, None] + ranks, 0, vmax - 1)
    vals = _expand_vals(jnp.take(vwin, vidx, axis=0), scale)
    vals = vals * bits.astype(vals.dtype)
    xcol = jnp.clip(col[:, None] + (k % c)[None, :], 0, ncols - 1)
    if cmap is not None:
        xcol = jnp.take(cmap, xcol, axis=0)
    xg = jnp.take(x, xcol, axis=0)
    return vals * xg


def _mask_rest(rest, fused_cols, has_scale):
    """Uniform ``*rest`` unpacking of the mask kernels: the optional fused
    column map then the optional per-chunk scale tile lead the input refs,
    followed by the output and scratch refs."""
    rest = list(rest)
    cmap_ref = rest.pop(0) if fused_cols else None
    scale_ref = rest.pop(0) if has_scale else None
    return cmap_ref, scale_ref, rest


def _spmv_kernel(vbase_ref, col_ref, mask_ref, voff_ref, row_ref, values_hbm,
                 x_ref, *rest, r: int, c: int, cb: int,
                 vmax: int, nrows: int, ncols: int, fused_cols: bool = False,
                 has_scale: bool = False):
    cmap_ref, scale_ref, (y_ref, vwin, sem) = _mask_rest(rest, fused_cols,
                                                         has_scale)
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    # Stream this chunk's packed value window HBM -> VMEM (dynamic offset).
    base = vbase_ref[i]
    copy = pltpu.make_async_copy(values_hbm.at[pl.ds(base, vmax)], vwin, sem)
    copy.start()
    copy.wait()

    mask = mask_ref[0]
    contrib = _decode_chunk(mask, voff_ref[0], col_ref[0], vwin[...],
                            x_ref[...], r=r, c=c, ncols=ncols, vmax=vmax,
                            cmap=None if cmap_ref is None else cmap_ref[...],
                            scale=None if scale_ref is None else scale_ref[0])
    k = jnp.arange(r * c, dtype=jnp.int32)
    yrow = jnp.clip(row_ref[0][:, None] + (k // c)[None, :], 0, nrows - 1)
    y = y_ref[...]
    y_ref[...] = y.at[yrow.reshape(-1)].add(contrib.reshape(-1))


@functools.partial(
    jax.jit,
    static_argnames=("r", "c", "cb", "vmax", "nrows", "ncols", "interpret"))
def spmv_pallas(chunk_vbase, chunk_col, chunk_mask, chunk_voff, chunk_row,
                values, x, col_map=None, value_scale=None, *, r: int, c: int,
                cb: int, vmax: int, nrows: int, ncols: int,
                interpret: bool = False) -> jax.Array:
    """``col_map`` (optional, (ncols,) int32) fuses a column permutation into
    the decode: x stays in original order in VMEM and the kernel gathers
    ``x[col_map[col]]`` -- the reordering subsystem's zero-copy path (see
    ``_decode_chunk``). ``value_scale`` (optional, (nchunks,) f32) is the
    int8 store's per-chunk dequantisation factor."""
    nchunks = chunk_col.shape[0]
    fused_cols = col_map is not None
    has_scale = value_scale is not None
    kernel = functools.partial(_spmv_kernel, r=r, c=c, cb=cb, vmax=vmax,
                               nrows=nrows, ncols=ncols,
                               fused_cols=fused_cols, has_scale=has_scale)
    in_specs = [
        pl.BlockSpec((1, cb), lambda i, vb: (i, 0)),   # chunk_col
        pl.BlockSpec((1, cb), lambda i, vb: (i, 0)),   # chunk_mask
        pl.BlockSpec((1, cb), lambda i, vb: (i, 0)),   # chunk_voff
        pl.BlockSpec((1, cb), lambda i, vb: (i, 0)),   # chunk_row
        pl.BlockSpec(memory_space=pl.ANY),             # values (HBM)
        pl.BlockSpec((ncols,), lambda i, vb: (0,)),    # x (VMEM, full)
    ]
    operands = [chunk_vbase, chunk_col, chunk_mask.astype(jnp.int32),
                chunk_voff, chunk_row, values, x]
    if fused_cols:
        in_specs.append(pl.BlockSpec((ncols,), lambda i, vb: (0,)))
        operands.append(col_map.astype(jnp.int32))
    if has_scale:
        in_specs.append(pl.BlockSpec((1,), lambda i, vb: (i,)))
        operands.append(value_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nchunks,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((nrows,), lambda i, vb: (0,)),
        scratch_shapes=[
            pltpu.VMEM((vmax,), values.dtype),
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nrows,), _out_dtype(values, x)),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(*operands)


# ----------------------------------------------------------------------------
# Panel mask kernel: one Mosaic-compilable kernel body for SpMV and SpMM
# ----------------------------------------------------------------------------
#
# Mosaic lowers neither a per-lane ``jnp.take`` nor a scatter-add, and it
# tiles VMEM blocks by (8, 128). So the panel kernel lays a chunk's blocks
# along LANES -- its metadata is one (4, cb) tile [col, mask, voff, row]:
#
#   * the packed values and each vector of X^T are DMA'd as (rows, 128)
#     windows of a row-major view; ``_flat_gather`` picks the row of each
#     block's start with a one-hot MXU matmul, then the lane with a
#     sublane select -- exact at ``precision=HIGHEST``;
#   * the decode (bit k -> rank -> value) runs on (1, cb) rows;
#   * y is a (kt, pr) tile; each block's lanes of one row are summed, and
#     one one-hot MXU contraction over the chunk's blocks scatters the sums
#     into the tile (``_scatter_rows``) -- exact per term at
#     ``precision=HIGHEST``, but the f32 adds of a row are reassociated
#     against the reference scatter's (block, lane) order.

#: Lanes of one VMEM row: the flat windows are (rows, 128) slabs.
_LANES = 128
#: Value windows start on an 8-row boundary so packed dtypes (bf16, int8)
#: DMA on a tile boundary; up to 8 * 128 - 1 values precede the chunk.
_VROW_ALIGN = 8
_HIGHEST = jax.lax.Precision.HIGHEST


def _value_rows(vmax: int, itemsize: int) -> int:
    """Rows of one chunk's (rows, 128) value window: covers the worst-case
    in-window offset plus ``vmax`` values, rounded to the dtype's row tile
    (8 rows of f32, 16 of bf16, 32 of int8)."""
    rows = -(-(_VROW_ALIGN * _LANES - 1 + vmax) // _LANES)
    pack = 8 * max(1, 4 // int(itemsize))
    return -(-rows // pack) * pack


def _x_rows(xw: int) -> int:
    """Rows of one vector's (rows, 128) x window: any in-row offset + xw."""
    rows = -(-(_LANES - 1 + xw) // _LANES)
    return -(-rows // 8) * 8


def _as_rows(a, nrows: int):
    """A 1-D array as a zero-padded (nrows, 128) row-major slab."""
    return jnp.pad(a, (0, nrows * _LANES - a.shape[0])).reshape(nrows, _LANES)


def _onehot(cond, dtype):
    return jnp.where(cond, jnp.ones((), dtype), jnp.zeros((), dtype))


def _tn(a, b):
    """a^T @ b (contract the leading axes) at f32-exact precision."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=a.dtype)


def _flat_gather(win, start, width: int):
    """``[flat[start + s] for s < width]`` as (1, cb) rows, where ``win`` is
    the (R, 128) window of a flat vector and ``start`` (1, cb) holds one
    flat offset per block. Two one-hot matmuls fetch the rows holding
    ``start`` and ``start + 128`` (width <= 128 never spans more), then a
    masked sublane sum picks each lane."""
    nr, cb = win.shape[0], start.shape[1]
    row, lane = start // _LANES, start % _LANES
    ridx = jax.lax.broadcasted_iota(jnp.int32, (nr, cb), 0)
    both = jnp.concatenate(
        [_tn(win, _onehot(ridx == row, win.dtype)),
         _tn(win, _onehot(ridx == row + 1, win.dtype))], axis=0)
    lidx = jax.lax.broadcasted_iota(jnp.int32, both.shape, 0)
    return [jnp.sum(jnp.where(lidx == lane + s, both, 0), axis=0,
                    keepdims=True) for s in range(width)]


def _decode_values(mask, vstart, vwin, scale, *, rc: int):
    """Mask-expand one chunk: row k (1, cb) holds block b's lane-k value,
    ``vwin[vstart + rank]`` where bit k is set and 0 elsewhere."""
    packed = _flat_gather(vwin, vstart, rc)
    vals = []
    rank = jnp.zeros_like(mask)
    for k in range(rc):
        bit = (mask >> k) & 1
        v = packed[0]
        for s in range(1, k + 1):       # rank of lane k is at most k
            v = jnp.where(rank == s, packed[s], v)
        v = jnp.where(bit == 1, v, 0)
        vals.append(v if scale is None else v * scale)
        rank = rank + bit
    return vals


def _panel_kernel(vbase_ref, xbase_ref, meta_ref, values_hbm, x_hbm, *rest,
                  r: int, c: int, pr: int, kt: int, vrows: int, xrows: int,
                  group: int, has_scale: bool):
    """One (vec-tile, panel, chunk-group) grid step: walk the group's
    ``group`` chunks in order, each one's value window and the x windows
    of ``kt`` vectors DMA'd into one slot of a two-slot ring while the
    chunk before it is decoded, and accumulate them into the panel's
    (kt, pr) y tile."""
    if has_scale:
        scale_ref, y_ref, vwin, xwin, sem = rest
    else:
        scale_ref = None
        y_ref, vwin, xwin, sem = rest
    j = pl.program_id(0)
    first = pl.program_id(2) * group

    @pl.when(pl.program_id(2) == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    def bases(g):
        vbase = vbase_ref[0, 0, first + g]
        xbase = xbase_ref[0, 0, first + g]
        vrow = pl.multiple_of(
            vbase // (_VROW_ALIGN * _LANES) * _VROW_ALIGN, _VROW_ALIGN)
        return vbase, xbase, vrow, xbase // _LANES

    def copies(g, slot):
        _, _, vrow, xrow = bases(g)
        return (pltpu.make_async_copy(values_hbm.at[pl.ds(vrow, vrows)],
                                      vwin.at[slot], sem.at[slot, 0]),
                pltpu.make_async_copy(
                    x_hbm.at[pl.ds(j * kt, kt), pl.ds(xrow, xrows)],
                    xwin.at[slot], sem.at[slot, 1]))

    for cp in copies(0, 0):
        cp.start()

    def chunk(g, y):
        slot = g % 2
        for cp in copies(g, slot):
            cp.wait()

        @pl.when(g + 1 < group)
        def _prefetch():
            for cp in copies(g + 1, 1 - slot):
                cp.start()

        vbase, xbase, vrow, xrow = bases(g)
        acc = y.dtype
        meta = meta_ref[0, g]                               # (4, cb)
        col, mask, voff, row = (meta[q:q + 1] for q in range(4))
        scale = None if scale_ref is None else scale_ref[0, 0, first + g]
        vals = _decode_values(mask, voff + (vbase - vrow * _LANES),
                              vwin[slot].astype(acc), scale, rc=r * c)
        xwins = xwin[slot]
        xstart = col + (xbase - xrow * _LANES)
        per_vec = [_flat_gather(xwins[v], xstart, c) for v in range(kt)]
        xg = [per_vec[0][lc] if kt == 1 else
              jnp.concatenate([gv[lc] for gv in per_vec], axis=0)
              for lc in range(c)]                           # (kt, cb) each
        prods = [vals[k] * xg[k % c] for k in range(r * c)]
        return y + _scatter_rows(prods, row, r=r, c=c, pr=pr)

    y_ref[0, 0] = jax.lax.fori_loop(0, group, chunk, y_ref[0, 0])


def _scatter_rows(prods, row, *, r: int, c: int, pr: int):
    """The chunk's products summed into a (kt, pr) tile: block b's lane k
    lands on tile row ``row[b] + k // c``. Each block's c lanes of one row
    offset are added first, then one one-hot MXU contraction over the
    chunk's blocks adds the sums that share a row -- reassociated against
    the reference scatter's (block, lane) order, and exact per term at
    ``precision=HIGHEST``. Row offset rr's result is rolled rr lanes down
    the tile; no row wraps, since ``row[b] <= pr - r``. A padded block's
    lanes are 0 (mask 0), so its row adds nothing."""
    kt, cb = prods[0].shape
    sums = [functools.reduce(operator.add, prods[rr * c:(rr + 1) * c])
            for rr in range(r)]
    stacked = jnp.concatenate(sums, axis=0)                 # (r * kt, cb)
    pidx = jax.lax.broadcasted_iota(jnp.int32, (pr, cb), 0)
    onehot = _onehot(pidx == row, stacked.dtype)            # (pr, cb)
    rows = jax.lax.dot_general(stacked, onehot, (((1,), (1,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=stacked.dtype)
    out = rows[:kt]                                         # (kt, pr)
    for rr in range(1, r):
        out = out + pltpu.roll(rows[rr * kt:(rr + 1) * kt], rr, 1)
    return out


def _acc_dtype(values, x):
    """Accumulation dtype of the panel kernels: the output dtype, at least
    f32 (the one-hot contractions run in it)."""
    return jnp.promote_types(jnp.float32, _out_dtype(values, x))


#: Most vectors one panel-kernel grid step carries: its x windows and y
#: tile then fill one (8, 128) vreg tile per 128 lanes.
_MAX_VEC_TILE = 8


def panel_grid(geom, itemsize: int, nvec: int,
               nvt: int) -> Tuple[Tuple[int, int, int], int]:
    """The grid of the panel mask kernel, (vector tiles, panels, chunk
    groups), and the chunks a group holds (:func:`panel_group`):
    ``kt = gcd(nvec, min(nvt, 8))`` vectors share a grid step. ``geom``
    holds the plan's ``npanels`` and ``nchunks`` and its window geometry.
    The executor's ``exec.*`` spans count the kernel's steps from this
    too."""
    kt = math.gcd(nvec, min(nvt, _MAX_VEC_TILE))
    group = panel_group(geom, itemsize, kt)
    return ((nvec // kt, geom["npanels"], -(-geom["nchunks"] // group)),
            group)


def panel_mask_call(chunk_vbase, chunk_xbase, chunk_col, chunk_mask,
                    chunk_voff, chunk_row, values, xt, value_scale, *,
                    r: int, c: int, cb: int, vmax: int, xw: int, pr: int,
                    nvt: int, interpret: bool):
    """The shared panel mask pallas_call over ``xt`` = X^T, (nvec,
    ncols_pad); returns Y^T as (npanels, nvec // kt, kt, pr), where ``kt``
    vectors share a grid step. A panel's chunks are padded to whole groups
    with mask-0 chunks, which add nothing."""
    npanels, nchunks = chunk_vbase.shape
    nvec, ncols_pad = xt.shape
    geom = dict(r=r, c=c, cb=cb, vmax=vmax, xw=xw, pr=pr, npanels=npanels,
                nchunks=nchunks)
    grid, group = panel_grid(geom, values.dtype.itemsize, nvec, nvt)
    kt = nvec // grid[0]
    acc = _acc_dtype(values, xt)
    vrows = _value_rows(vmax, values.dtype.itemsize)
    vals2d = _as_rows(values, -(-values.shape[0] // (_VROW_ALIGN * _LANES))
                      * _VROW_ALIGN + vrows)
    xrows = _x_rows(xw)
    nxr = -(-ncols_pad // _LANES) + xrows
    x3d = jnp.pad(xt.astype(acc), ((0, 0), (0, nxr * _LANES - ncols_pad))
                  ).reshape(nvec, nxr, _LANES)
    meta = jnp.stack([chunk_col, chunk_mask.astype(jnp.int32), chunk_voff,
                      chunk_row], axis=2)               # (P, C, 4, cb)
    per_panel = [chunk_vbase, chunk_xbase] + (
        [] if value_scale is None else [value_scale])
    pad = grid[2] * group - nchunks
    if pad:
        meta = jnp.pad(meta, ((0, 0), (0, pad), (0, 0), (0, 0)))
        per_panel = [jnp.pad(a, ((0, 0), (0, pad))) for a in per_panel]
    smem = functools.partial(pl.BlockSpec, (1, 1, nchunks + pad),
                             lambda j, p, i: (p, 0, 0),
                             memory_space=pltpu.SMEM)
    in_specs = [smem(), smem(),
                pl.BlockSpec((1, group, 4, cb), lambda j, p, i: (p, i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),       # values (HBM)
                pl.BlockSpec(memory_space=pl.ANY)]       # x (HBM)
    per_panel = [a.reshape(npanels, 1, nchunks + pad) for a in per_panel]
    operands = per_panel[:2] + [meta, vals2d, x3d] + per_panel[2:]
    if value_scale is not None:
        in_specs.append(smem())
    kernel = functools.partial(
        _panel_kernel, r=r, c=c, pr=pr, kt=kt, vrows=vrows, xrows=xrows,
        group=group, has_scale=value_scale is not None)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, kt, pr),
                               lambda j, p, i: (p, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((npanels, nvec // kt, kt, pr), acc),
        scratch_shapes=[pltpu.VMEM((2, vrows, _LANES), values.dtype),
                        pltpu.VMEM((2, kt, xrows, _LANES), acc),
                        pltpu.SemaphoreType.DMA((2, 2))],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        name="spc5_panel_mask",
    )(*operands)


@functools.partial(
    jax.jit,
    static_argnames=("r", "c", "cb", "vmax", "xw", "pr", "nrows",
                     "ncols_pad", "interpret"))
def spmv_pallas_panels(chunk_vbase, chunk_xbase, chunk_col, chunk_mask,
                       chunk_voff, chunk_row, values, x, value_scale=None, *,
                       r: int, c: int, cb: int, vmax: int, xw: int, pr: int,
                       nrows: int, ncols_pad: int,
                       interpret: bool = False) -> jax.Array:
    """Row-panel-tiled SpMV. x is padded to ncols_pad; returns y[:nrows].
    ``value_scale`` (optional, (npanels, nchunks) f32) dequantises int8
    values. A column permutation is applied to x by the caller."""
    xp = jnp.pad(x, (0, max(0, ncols_pad - x.shape[0])))
    yt = panel_mask_call(chunk_vbase, chunk_xbase, chunk_col, chunk_mask,
                         chunk_voff, chunk_row, values, xp[None], value_scale,
                         r=r, c=c, cb=cb, vmax=vmax, xw=xw, pr=pr, nvt=1,
                         interpret=interpret)
    return yt.reshape(-1)[:nrows].astype(_out_dtype(values, x))


def _spmv_tail_kernel(xbase_ref, rows_ref, cols_ref, vals_ref, x_hbm, y_ref,
                      xwin, sem, *, pr: int, xw: int):
    """One grid row per panel bucket of the beta(r,c)_test singleton tail.

    The panel's x window is DMA'd exactly like the block kernels' chunk
    windows (``xbase_ref`` is scalar-prefetched, one aligned ``xw``-wide
    slab per panel); rows are PANEL-LOCAL so the scatter target is the
    panel's own (pr,) y tile. Padding entries (vals == 0) land on local row
    0 / window column 0 and contribute nothing.
    """
    p = pl.program_id(0)
    copy = pltpu.make_async_copy(x_hbm.at[pl.ds(xbase_ref[p], xw)], xwin, sem)
    copy.start()
    copy.wait()
    vals = _expand_vals(vals_ref[0])
    rel = jnp.clip(cols_ref[0] - xbase_ref[p], 0, xw - 1)
    prod = vals * jnp.take(xwin[...], rel, axis=0)
    rows = jnp.clip(rows_ref[0], 0, pr - 1)
    y = jnp.zeros((pr,), dtype=vals.dtype)
    y_ref[...] = y.at[rows].add(prod)


@functools.partial(
    jax.jit,
    static_argnames=("pr", "xw", "nrows", "ncols_pad", "interpret"))
def spmv_tail_pallas(tail_xbase, rows, cols, vals, x, *, pr: int, xw: int,
                     nrows: int, ncols_pad: int,
                     interpret: bool = False) -> jax.Array:
    """Panel-segmented COO tail of the beta(r,c)_test split as a Pallas
    kernel: grid ``(npanels,)``, one (pr,) output tile per panel bucket,
    x windowed per panel (``rows``/``cols``/``vals`` are the (npanels, smax)
    buckets; ``tail_xbase`` the per-panel window starts; numerics match
    ``ref_spmv.spmv_coo_panels``, the oracle)."""
    npanels, smax = rows.shape
    xp = jnp.pad(x, (0, max(0, ncols_pad - x.shape[0])))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                  # tail_xbase
        grid=(npanels,),
        in_specs=[
            pl.BlockSpec((1, smax), lambda p, xb: (p, 0)),   # rows
            pl.BlockSpec((1, smax), lambda p, xb: (p, 0)),   # cols
            pl.BlockSpec((1, smax), lambda p, xb: (p, 0)),   # vals
            pl.BlockSpec(memory_space=pl.ANY),  # x (HBM, windowed DMA)
        ],
        out_specs=pl.BlockSpec((pr,), lambda p, xb: (p,)),
        scratch_shapes=[
            pltpu.VMEM((xw,), x.dtype),
            pltpu.SemaphoreType.DMA,
        ],
    )
    y = pl.pallas_call(
        functools.partial(_spmv_tail_kernel, pr=pr, xw=xw),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((npanels * pr,), _out_dtype(vals, x)),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(tail_xbase.astype(jnp.int32), rows, cols, vals, xp)
    return y[:nrows]
