"""Pallas TPU kernel: SPC5 mask-expand SpMV (beta(r,c), no zero padding).

TPU adaptation of the paper's AVX-512 ``vexpandpd`` kernel (DESIGN.md §2):

  * the packed ``values`` array lives in HBM (``pl.ANY``) with no zero
    padding inside a chunk, and each grid step DMAs a window holding one
    chunk's values into a VMEM scratch (the panel kernel's window starts on
    an 8-row tile boundary, so it reads up to 1023 values ahead of the
    chunk);
  * the expand is ``rank = cumsum(mask_bits) - mask_bits`` + a VMEM gather,
    replacing the in-register expand (identical semantics, zero HBM cost);
  * per grid step a chunk of ``cb`` blocks is decoded; the panel kernel
    lays the blocks along lanes, so its default chunk
    (``formats.PANEL_CB``) fills the 128 lanes of one vreg row -- a
    narrower one pays for the padded lanes on more grid steps;
  * y is accumulated across sequential grid steps in VMEM and written once
    (the paper's "merge without synchronization" -- rows are owned uniquely);
    the panel kernel adds a chunk's blocks into its y tile with one one-hot
    MXU contraction, so it agrees with the reference scatter within a
    float32 reassociation of each row's adds, not bit for bit.

Scalars in SMEM carry the per-chunk value-window offsets, the analogue of
the asm kernel's running value cursor (%r12 in the paper's code 1).

Two layouts:

**Row-panel-tiled** (``spmv_pallas_panels``; SpMM in ``spc5_spmm``): grid
``(vec-tiles, npanels, nchunks)`` over :class:`repro.core.formats.SPC5Panels`.
Each step holds one panel's y tile (written back once per panel) and the
chunk's value and x windows, DMA'd at the chunk's scalar bases; VMEM per
step is independent of matrix size. This is the kernel Mosaic compiles for
a TPU (see "Panel mask lowering" below) and the only one ``ops.prepare``
picks there.

**Whole-vector** (``spmv_pallas`` / ``spmv_pallas_db``): grid ``(nchunks,)``,
``x`` (ncols) and ``y`` (nrows) fully VMEM-resident, a full-vector scatter
per chunk. Its per-lane ``jnp.take`` and ``.at[].add`` run in interpret
mode only; Mosaic lowers neither.

Each layout also has a **descriptor** variant (``spmv_pallas_desc[_db]``,
``spmv_pallas_panels_desc[_db]``): the mask decode is hoisted to build time
(``repro.core.formats.chunk_descriptors``) into per-lane gather tables, so
the inner loop is two gathers + a masked FMA -- no bit expansion, no rank
cumsum -- at an r*c-fold index-bytes inflation. ``lowering="descriptor"``
selects them, in interpret mode only, like the whole-vector kernels. The
panel descriptor kernels accept a fused ``col_map`` (see
``_panel_fused_operands`` for the VMEM trade).
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

#: Un-narrowed descriptor bytes per block lane (4 int32 valid/vidx/xcol/yrow
#: tiles) -- the fallback when a geometry predates ``desc_lane_nbytes``.
_DESC_TILE_BYTES = 4 * 4


def _acc_itemsize(itemsize):
    """x/y vector bytes: quantised values upcast to f32 before touching the
    vectors, so those terms never shrink below 4 bytes per element."""
    return max(int(itemsize), 4)


def _desc_tile_bytes(geom):
    """Descriptor tile bytes per lane from the plan's narrowed tables."""
    return int(geom.get("desc_lane_nbytes", _DESC_TILE_BYTES))


def _vmem_whole_mask(geom, itemsize, nvec=1):
    # x (ncols) + y (nrows) at accumulation width + double-buffered value
    # window at the STORAGE itemsize + chunk metadata (4 int32 tables of cb)
    # + a potential fused col_map (ncols int32)
    return ((geom["nrows"] + geom["ncols"]) * _acc_itemsize(itemsize)
            + 2 * geom["vmax"] * itemsize
            + 4 * 4 * geom["cb"] + 4 * geom["ncols"])


def _vmem_whole_desc(geom, itemsize, nvec=1):
    rc = geom["r"] * geom["c"]
    return ((geom["nrows"] + geom["ncols"]) * _acc_itemsize(itemsize)
            + 2 * geom["vmax"] * itemsize
            + _desc_tile_bytes(geom) * geom["cb"] * rc)


def _vmem_panels_mask(geom, itemsize, nvec=1):
    # the panel kernel (``_panel_kernel``) with kt = min(nvec, 8) vectors
    # per step: the (kt, pr) y tile (double-buffered), kt (rows, 128) x
    # windows, the (rows, 128) value window at the storage itemsize, the
    # (4, cb) chunk metadata (double-buffered), the decode's one-hot and
    # (256, cb) select temporaries plus its r*c per-lane products, and the
    # scatter's (pr, cb) one-hot (lanes padded to 128), r (kt, cb) block
    # sums and (r * kt, pr) contraction result -- matrix-size independent
    kt = min(max(int(nvec), 1), _MAX_VEC_TILE)
    cb, acc = geom["cb"], _acc_itemsize(itemsize)
    pr, r = geom["pr"], geom["r"]
    xrows = _x_rows(geom["xw"])
    return ((2 * kt * pr + kt * xrows * _LANES
             + 2 * _LANES * 2 * cb * (kt + 1)
             + r * geom["c"] * kt * cb
             + pr * -(-cb // _LANES) * _LANES + r * kt * (cb + pr)) * acc
            + _value_rows(geom["vmax"], itemsize) * _LANES
            * itemsize + 2 * 4 * 4 * cb)


def _vmem_panels_desc(geom, itemsize, nvec=1):
    rc = geom["r"] * geom["c"]
    return ((geom["pr"] + 2 * geom["xw"]) * _acc_itemsize(itemsize)
            + 2 * geom["vmax"] * itemsize
            + _desc_tile_bytes(geom) * geom["cb"] * rc)


#: (layout, lowering) -> fn(geom_dict, itemsize, nvec=1) -> resident bytes
#: per grid step. Every (layout, lowering) pair a registered layout can
#: lower MUST declare its contract here; the static verifier refuses plans
#: whose declared footprint exceeds :data:`VMEM_LIMIT_BYTES` and the lint's
#: registry-consistency rule cross-checks coverage against the registry.
SPMV_VMEM_CONTRACTS = {
    ("whole_vector", "mask"): _vmem_whole_mask,
    ("whole_vector", "descriptor"): _vmem_whole_desc,
    ("panels", "mask"): _vmem_panels_mask,
    ("panels", "descriptor"): _vmem_panels_desc,
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
    int8 lowering's per-chunk dequantisation factor."""
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


def _panel_fused_operands(x, col_map, ncols_pad):
    """Shared wrapper plumbing for the panel kernels' two x paths.

    Non-fused: x (padded to ncols_pad) stays in HBM and each chunk DMAs its
    ``xw``-wide window. Fused (``col_map`` given, the reordering
    subsystem's zero-copy path): the window DMA cannot follow a
    permutation, so x and the map live fully VMEM-resident like the
    whole-vector kernels (the bounded-VMEM property is kept for y; the x
    budget reverts to whole-vector -- the plan pipeline only picks this
    path when a permutation is attached). Returns (in_specs tail, operands
    tail, fused flag)."""
    fused = col_map is not None
    if fused:
        cm = jnp.pad(col_map.astype(jnp.int32),
                     (0, max(0, ncols_pad - col_map.shape[0])))
        specs = [pl.BlockSpec((ncols_pad,), lambda *a: (0,)),   # x (VMEM)
                 pl.BlockSpec((ncols_pad,), lambda *a: (0,))]   # cmap (VMEM)
        return specs, [x, cm], fused
    return [pl.BlockSpec(memory_space=pl.ANY)], [x], fused


def _append_panel_scale(xspecs, xops, value_scale):
    """Append the (npanels, nchunks) per-chunk dequantisation scales as one
    (1, 1) tile per grid step, AFTER the optional fused column map (the
    ``_mask_rest`` unpack order every panel kernel shares)."""
    if value_scale is None:
        return xspecs, xops
    return (xspecs + [pl.BlockSpec((1, 1), lambda p, i, vb, xb: (p, i))],
            xops + [value_scale])


def _panel_scratch(fused, nbuf, vmax, vdtype, xshape, xdtype):
    """Scratch shapes of the panel kernels (shared by the mask/descriptor x
    SpMV/SpMM x single/double-buffered wrappers): ``nbuf`` value windows +
    DMA semaphore(s), plus the x window pair only when the x DMA path is
    live (non-fused). Order matches the kernels' ``*rest`` unpacking."""
    def sem():
        return (pltpu.SemaphoreType.DMA if nbuf == 1
                else pltpu.SemaphoreType.DMA((nbuf,)))

    vshape = (vmax,) if nbuf == 1 else (nbuf, vmax)
    if fused:
        return [pltpu.VMEM(vshape, vdtype), sem()]
    xs = xshape if nbuf == 1 else (nbuf,) + tuple(xshape)
    return [pltpu.VMEM(vshape, vdtype), pltpu.VMEM(xs, xdtype),
            sem(), sem()]


# ----------------------------------------------------------------------------
# Panel mask lowering: one Mosaic-compilable kernel body for SpMV and SpMM
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
                  has_scale: bool):
    """One (vec-tile, panel, chunk) grid step: DMA the chunk's value window
    and the x windows of ``kt`` vectors, decode, and accumulate into the
    panel's (kt, pr) y tile."""
    if has_scale:
        scale_ref, y_ref, vwin, xwin, sem = rest
    else:
        scale_ref = None
        y_ref, vwin, xwin, sem = rest
    j = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    vbase = vbase_ref[0, 0, i]
    xbase = xbase_ref[0, 0, i]
    vrow = pl.multiple_of(
        vbase // (_VROW_ALIGN * _LANES) * _VROW_ALIGN, _VROW_ALIGN)
    xrow = xbase // _LANES
    vcopy = pltpu.make_async_copy(values_hbm.at[pl.ds(vrow, vrows)], vwin,
                                  sem.at[0])
    xcopy = pltpu.make_async_copy(
        x_hbm.at[pl.ds(j * kt, kt), pl.ds(xrow, xrows)], xwin, sem.at[1])
    vcopy.start()
    xcopy.start()
    vcopy.wait()
    xcopy.wait()

    acc = y_ref.dtype
    meta = meta_ref[0, 0]                                   # (4, cb)
    col, mask, voff, row = (meta[q:q + 1] for q in range(4))
    scale = None if scale_ref is None else scale_ref[0, 0, i]
    vals = _decode_values(mask, voff + (vbase - vrow * _LANES),
                          vwin[...].astype(acc), scale, rc=r * c)
    xwins = xwin[...]
    xstart = col + (xbase - xrow * _LANES)
    per_vec = [_flat_gather(xwins[v], xstart, c) for v in range(kt)]
    xg = [per_vec[0][lc] if kt == 1 else
          jnp.concatenate([g[lc] for g in per_vec], axis=0)
          for lc in range(c)]                               # (kt, cb) each
    prods = [vals[k] * xg[k % c] for k in range(r * c)]
    y_ref[0, 0] = y_ref[0, 0] + _scatter_rows(prods, row, r=r, c=c, pr=pr)


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


def panel_grid(npanels: int, nchunks: int, nvec: int,
               nvt: int) -> Tuple[int, int, int]:
    """The grid of the panel mask kernel, (vector tiles, panels, chunks):
    ``kt = gcd(nvec, min(nvt, 8))`` vectors share a grid step. The
    executor's ``exec.*`` spans count the kernel's steps from this too."""
    kt = math.gcd(nvec, min(nvt, _MAX_VEC_TILE))
    return (nvec // kt, npanels, nchunks)


def panel_mask_call(chunk_vbase, chunk_xbase, chunk_col, chunk_mask,
                    chunk_voff, chunk_row, values, xt, value_scale, *,
                    r: int, c: int, cb: int, vmax: int, xw: int, pr: int,
                    nvt: int, interpret: bool):
    """The shared panel mask pallas_call over ``xt`` = X^T, (nvec,
    ncols_pad); returns Y^T as (npanels, nvec // kt, kt, pr), where ``kt``
    vectors share a grid step."""
    npanels, nchunks = chunk_vbase.shape
    nvec, ncols_pad = xt.shape
    grid = panel_grid(npanels, nchunks, nvec, nvt)
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
    smem = functools.partial(pl.BlockSpec, (1, 1, nchunks),
                             lambda j, p, i: (p, 0, 0),
                             memory_space=pltpu.SMEM)
    in_specs = [smem(), smem(),
                pl.BlockSpec((1, 1, 4, cb), lambda j, p, i: (p, i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),       # values (HBM)
                pl.BlockSpec(memory_space=pl.ANY)]       # x (HBM)
    operands = [chunk_vbase.reshape(npanels, 1, nchunks),
                chunk_xbase.reshape(npanels, 1, nchunks), meta, vals2d, x3d]
    if value_scale is not None:
        in_specs.append(smem())
        operands.append(value_scale.reshape(npanels, 1, nchunks))
    kernel = functools.partial(
        _panel_kernel, r=r, c=c, pr=pr, kt=kt, vrows=vrows, xrows=xrows,
        has_scale=value_scale is not None)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, kt, pr),
                               lambda j, p, i: (p, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((npanels, nvec // kt, kt, pr), acc),
        scratch_shapes=[pltpu.VMEM((vrows, _LANES), values.dtype),
                        pltpu.VMEM((kt, xrows, _LANES), acc),
                        pltpu.SemaphoreType.DMA((2,))],
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


# ----------------------------------------------------------------------------
# Descriptor lowering: precomputed gather tables, no in-kernel mask decode
# ----------------------------------------------------------------------------

def _desc_contrib(valid, vidx, xcol, vwin, x, scale=None):
    """The descriptor inner loop: two gathers + a masked FMA. The bit
    expansion and rank cumsum of ``_decode_chunk`` were hoisted to build
    time (``repro.core.formats.chunk_descriptors``); a fused column
    permutation is already folded into ``xcol``. The narrowed int8/int16
    tables promote to int32 in-VMEM before the gathers (HBM read the narrow
    bytes); ``scale`` dequantises int8 values after the f32 upcast."""
    vals = _expand_vals(jnp.take(vwin, vidx.astype(jnp.int32), axis=0), scale)
    vals = vals * valid.astype(vals.dtype)
    return vals * jnp.take(x, xcol.astype(jnp.int32), axis=0)


def _desc_rest(rest, has_scale):
    """``*rest`` unpacking of the whole-vector descriptor kernels: the
    optional per-chunk scale tile leads the output/scratch refs."""
    rest = list(rest)
    scale_ref = rest.pop(0) if has_scale else None
    return scale_ref, rest


def _spmv_desc_kernel(vbase_ref, valid_ref, vidx_ref, xcol_ref, yrow_ref,
                      values_hbm, x_ref, *rest, vmax: int,
                      has_scale: bool = False):
    """Whole-vector descriptor SpMV: one chunk per grid step, value window
    DMA'd exactly like the mask kernel, but the decode is gone."""
    scale_ref, (y_ref, vwin, sem) = _desc_rest(rest, has_scale)
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    copy = pltpu.make_async_copy(values_hbm.at[pl.ds(vbase_ref[i], vmax)],
                                 vwin, sem)
    copy.start()
    copy.wait()

    contrib = _desc_contrib(valid_ref[0], vidx_ref[0], xcol_ref[0],
                            vwin[...], x_ref[...],
                            scale=None if scale_ref is None else scale_ref[0])
    y = y_ref[...]
    y_ref[...] = y.at[yrow_ref[0].astype(jnp.int32).reshape(-1)].add(
        contrib.reshape(-1))


def _desc_whole_specs(cb, rc, ncols):
    return [
        pl.BlockSpec((1, cb, rc), lambda i, vb: (i, 0, 0)),   # desc_valid
        pl.BlockSpec((1, cb, rc), lambda i, vb: (i, 0, 0)),   # desc_vidx
        pl.BlockSpec((1, cb, rc), lambda i, vb: (i, 0, 0)),   # desc_xcol
        pl.BlockSpec((1, cb, rc), lambda i, vb: (i, 0, 0)),   # desc_yrow
        pl.BlockSpec(memory_space=pl.ANY),                    # values (HBM)
        pl.BlockSpec((ncols,), lambda i, vb: (0,)),           # x (VMEM)
    ]


@functools.partial(
    jax.jit,
    static_argnames=("r", "c", "cb", "vmax", "nrows", "ncols", "interpret"))
def spmv_pallas_desc(chunk_vbase, desc_valid, desc_vidx, desc_xcol,
                     desc_yrow, values, x, value_scale=None, *, r: int,
                     c: int, cb: int, vmax: int, nrows: int, ncols: int,
                     interpret: bool = False) -> jax.Array:
    """Whole-vector SpMV over build-time descriptors (lowering="descriptor").

    The per-chunk tables carry everything the mask kernel recomputes
    (validity, value index, x column, y row -- column permutations already
    folded in), so there is no ``col_map`` input and no bit/cumsum work."""
    nchunks = desc_valid.shape[0]
    in_specs = _desc_whole_specs(cb, r * c, ncols)
    operands = [chunk_vbase, desc_valid, desc_vidx, desc_xcol, desc_yrow,
                values, x]
    if value_scale is not None:
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
        functools.partial(_spmv_desc_kernel, vmax=vmax,
                          has_scale=value_scale is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nrows,), _out_dtype(values, x)),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(*operands)


def _spmv_desc_db_kernel(vbase_ref, valid_ref, vidx_ref, xcol_ref, yrow_ref,
                         values_hbm, x_ref, *rest, vmax: int,
                         nchunks: int, has_scale: bool = False):
    """Double-buffered whole-vector descriptor SpMV (same pipelining as
    ``_spmv_db_kernel``)."""
    scale_ref, (y_ref, vwin, sem) = _desc_rest(rest, has_scale)
    i = pl.program_id(0)
    slot = jax.lax.rem(i, jnp.int32(2))

    @pl.when(i == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)
        pltpu.make_async_copy(values_hbm.at[pl.ds(vbase_ref[0], vmax)],
                              vwin.at[0], sem.at[0]).start()

    @pl.when(i + 1 < nchunks)
    def _prefetch_next():
        nxt = jax.lax.rem(i + jnp.int32(1), jnp.int32(2))
        pltpu.make_async_copy(values_hbm.at[pl.ds(vbase_ref[i + 1], vmax)],
                              vwin.at[nxt], sem.at[nxt]).start()

    pltpu.make_async_copy(values_hbm.at[pl.ds(vbase_ref[i], vmax)],
                          vwin.at[slot], sem.at[slot]).wait()

    contrib = _desc_contrib(valid_ref[0], vidx_ref[0], xcol_ref[0],
                            vwin[slot], x_ref[...],
                            scale=None if scale_ref is None else scale_ref[0])
    y = y_ref[...]
    y_ref[...] = y.at[yrow_ref[0].astype(jnp.int32).reshape(-1)].add(
        contrib.reshape(-1))


@functools.partial(
    jax.jit,
    static_argnames=("r", "c", "cb", "vmax", "nrows", "ncols", "interpret"))
def spmv_pallas_desc_db(chunk_vbase, desc_valid, desc_vidx, desc_xcol,
                        desc_yrow, values, x, value_scale=None, *, r: int,
                        c: int, cb: int, vmax: int, nrows: int, ncols: int,
                        interpret: bool = False) -> jax.Array:
    """Double-buffered :func:`spmv_pallas_desc`."""
    nchunks = desc_valid.shape[0]
    in_specs = _desc_whole_specs(cb, r * c, ncols)
    operands = [chunk_vbase, desc_valid, desc_vidx, desc_xcol, desc_yrow,
                values, x]
    if value_scale is not None:
        in_specs.append(pl.BlockSpec((1,), lambda i, vb: (i,)))
        operands.append(value_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nchunks,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((nrows,), lambda i, vb: (0,)),
        scratch_shapes=[
            pltpu.VMEM((2, vmax), values.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_spmv_desc_db_kernel, vmax=vmax, nchunks=nchunks,
                          has_scale=value_scale is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nrows,), _out_dtype(values, x)),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(*operands)


def _spmv_panel_desc_kernel(vbase_ref, xbase_ref, valid_ref, vidx_ref,
                            xcol_ref, yrow_ref, values_hbm, x_ref, *rest,
                            vmax: int, xw: int, ncols_pad: int,
                            fused_cols: bool = False,
                            has_scale: bool = False):
    """Panel descriptor SpMV step: value window DMA + two gathers + masked
    FMA into the panel's (pr,) tile. ``desc_xcol`` is window-relative; the
    fused variant globalises it with ``xbase`` and routes through the
    column map against fully-VMEM-resident original-order x."""
    cmap_ref, scale_ref, rest = _mask_rest(rest, fused_cols, has_scale)
    if fused_cols:
        y_ref, vwin, vsem = rest
    else:
        y_ref, vwin, xwin, vsem, xsem = rest
    scale = None if scale_ref is None else scale_ref[0, 0]
    p = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    vcopy = pltpu.make_async_copy(
        values_hbm.at[pl.ds(vbase_ref[p, i], vmax)], vwin, vsem)
    vcopy.start()
    if not fused_cols:
        xcopy = pltpu.make_async_copy(
            x_ref.at[pl.ds(xbase_ref[p, i], xw)], xwin, xsem)
        xcopy.start()
    vcopy.wait()
    if not fused_cols:
        xcopy.wait()

    if fused_cols:
        xcol = jnp.clip(xcol_ref[0, 0].astype(jnp.int32) + xbase_ref[p, i],
                        0, ncols_pad - 1)
        xcol = jnp.take(cmap_ref[...], xcol, axis=0)
        contrib = _desc_contrib(valid_ref[0, 0], vidx_ref[0, 0], xcol,
                                vwin[...], x_ref[...], scale=scale)
    else:
        contrib = _desc_contrib(valid_ref[0, 0], vidx_ref[0, 0],
                                xcol_ref[0, 0], vwin[...], xwin[...],
                                scale=scale)
    y = y_ref[...]
    y_ref[...] = y.at[yrow_ref[0, 0].astype(jnp.int32).reshape(-1)].add(
        contrib.reshape(-1))


def _desc_panel_specs(cb, rc, xspecs):
    return [
        pl.BlockSpec((1, 1, cb, rc), lambda p, i, vb, xb: (p, i, 0, 0)),
        pl.BlockSpec((1, 1, cb, rc), lambda p, i, vb, xb: (p, i, 0, 0)),
        pl.BlockSpec((1, 1, cb, rc), lambda p, i, vb, xb: (p, i, 0, 0)),
        pl.BlockSpec((1, 1, cb, rc), lambda p, i, vb, xb: (p, i, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),                    # values (HBM)
    ] + xspecs


@functools.partial(
    jax.jit,
    static_argnames=("r", "c", "cb", "vmax", "xw", "pr", "nrows",
                     "ncols_pad", "interpret"))
def spmv_pallas_panels_desc(chunk_vbase, chunk_xbase, desc_valid, desc_vidx,
                            desc_xcol, desc_yrow, values, x, col_map=None,
                            value_scale=None, *,
                            r: int, c: int, cb: int, vmax: int, xw: int,
                            pr: int, nrows: int, ncols_pad: int,
                            interpret: bool = False) -> jax.Array:
    """Row-panel-tiled descriptor SpMV (lowering="descriptor")."""
    npanels, nchunks = chunk_vbase.shape
    xp = jnp.pad(x, (0, max(0, ncols_pad - x.shape[0])))
    xspecs, xops, fused = _panel_fused_operands(xp, col_map, ncols_pad)
    xspecs, xops = _append_panel_scale(xspecs, xops, value_scale)
    scratch = _panel_scratch(fused, 1, vmax, values.dtype, (xw,), x.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                  # chunk_vbase, chunk_xbase
        grid=(npanels, nchunks),
        in_specs=_desc_panel_specs(cb, r * c, xspecs),
        out_specs=pl.BlockSpec((pr,), lambda p, i, vb, xb: (p,)),
        scratch_shapes=scratch,
    )
    y = pl.pallas_call(
        functools.partial(_spmv_panel_desc_kernel, vmax=vmax, xw=xw,
                          ncols_pad=ncols_pad, fused_cols=fused,
                          has_scale=value_scale is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((npanels * pr,),
                                       _out_dtype(values, x)),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(chunk_vbase, chunk_xbase, desc_valid, desc_vidx, desc_xcol, desc_yrow,
      values, *xops)
    return y[:nrows]


def _spmv_panel_desc_db_kernel(vbase_ref, xbase_ref, valid_ref, vidx_ref,
                               xcol_ref, yrow_ref, values_hbm, x_ref, *rest,
                               vmax: int, xw: int, ncols_pad: int,
                               nchunks: int, nsteps: int,
                               fused_cols: bool = False,
                               has_scale: bool = False):
    """Double-buffered panel descriptor SpMV (pipelining as the mask db
    kernel; with fused cols only the value window double-buffers)."""
    cmap_ref, scale_ref, rest = _mask_rest(rest, fused_cols, has_scale)
    if fused_cols:
        y_ref, vwin, vsem = rest
    else:
        y_ref, vwin, xwin, vsem, xsem = rest
    scale = None if scale_ref is None else scale_ref[0, 0]
    p = pl.program_id(0)
    i = pl.program_id(1)
    t = p * nchunks + i
    slot = jax.lax.rem(t, jnp.int32(2))

    @pl.when(i == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(t == 0)
    def _first():
        pltpu.make_async_copy(values_hbm.at[pl.ds(vbase_ref[0, 0], vmax)],
                              vwin.at[0], vsem.at[0]).start()
        if not fused_cols:
            pltpu.make_async_copy(x_ref.at[pl.ds(xbase_ref[0, 0], xw)],
                                  xwin.at[0], xsem.at[0]).start()

    @pl.when(t + 1 < nsteps)
    def _prefetch_next():
        nxt = jax.lax.rem(t + jnp.int32(1), jnp.int32(2))
        pn = (t + jnp.int32(1)) // jnp.int32(nchunks)
        inn = jax.lax.rem(t + jnp.int32(1), jnp.int32(nchunks))
        pltpu.make_async_copy(values_hbm.at[pl.ds(vbase_ref[pn, inn], vmax)],
                              vwin.at[nxt], vsem.at[nxt]).start()
        if not fused_cols:
            pltpu.make_async_copy(x_ref.at[pl.ds(xbase_ref[pn, inn], xw)],
                                  xwin.at[nxt], xsem.at[nxt]).start()

    pltpu.make_async_copy(values_hbm.at[pl.ds(vbase_ref[p, i], vmax)],
                          vwin.at[slot], vsem.at[slot]).wait()
    if not fused_cols:
        pltpu.make_async_copy(x_ref.at[pl.ds(xbase_ref[p, i], xw)],
                              xwin.at[slot], xsem.at[slot]).wait()

    if fused_cols:
        xcol = jnp.clip(xcol_ref[0, 0].astype(jnp.int32) + xbase_ref[p, i],
                        0, ncols_pad - 1)
        xcol = jnp.take(cmap_ref[...], xcol, axis=0)
        contrib = _desc_contrib(valid_ref[0, 0], vidx_ref[0, 0], xcol,
                                vwin[slot], x_ref[...], scale=scale)
    else:
        contrib = _desc_contrib(valid_ref[0, 0], vidx_ref[0, 0],
                                xcol_ref[0, 0], vwin[slot], xwin[slot],
                                scale=scale)
    y = y_ref[...]
    y_ref[...] = y.at[yrow_ref[0, 0].astype(jnp.int32).reshape(-1)].add(
        contrib.reshape(-1))


@functools.partial(
    jax.jit,
    static_argnames=("r", "c", "cb", "vmax", "xw", "pr", "nrows",
                     "ncols_pad", "interpret"))
def spmv_pallas_panels_desc_db(chunk_vbase, chunk_xbase, desc_valid,
                               desc_vidx, desc_xcol, desc_yrow, values, x,
                               col_map=None, value_scale=None, *,
                               r: int, c: int, cb: int,
                               vmax: int, xw: int, pr: int, nrows: int,
                               ncols_pad: int,
                               interpret: bool = False) -> jax.Array:
    """Double-buffered :func:`spmv_pallas_panels_desc`."""
    npanels, nchunks = chunk_vbase.shape
    xp = jnp.pad(x, (0, max(0, ncols_pad - x.shape[0])))
    xspecs, xops, fused = _panel_fused_operands(xp, col_map, ncols_pad)
    xspecs, xops = _append_panel_scale(xspecs, xops, value_scale)
    scratch = _panel_scratch(fused, 2, vmax, values.dtype, (xw,), x.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(npanels, nchunks),
        in_specs=_desc_panel_specs(cb, r * c, xspecs),
        out_specs=pl.BlockSpec((pr,), lambda p, i, vb, xb: (p,)),
        scratch_shapes=scratch,
    )
    y = pl.pallas_call(
        functools.partial(_spmv_panel_desc_db_kernel, vmax=vmax, xw=xw,
                          ncols_pad=ncols_pad, nchunks=nchunks,
                          nsteps=npanels * nchunks, fused_cols=fused,
                          has_scale=value_scale is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((npanels * pr,),
                                       _out_dtype(values, x)),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(chunk_vbase, chunk_xbase, desc_valid, desc_vidx, desc_xcol, desc_yrow,
      values, *xops)
    return y[:nrows]


def _spmv_db_kernel(vbase_ref, col_ref, mask_ref, voff_ref, row_ref,
                    values_hbm, x_ref, *rest, r: int, c: int,
                    cb: int, vmax: int, nrows: int, ncols: int, nchunks: int,
                    fused_cols: bool = False, has_scale: bool = False):
    """Double-buffered variant: overlap chunk i+1's value DMA with chunk i's
    compute (the Pallas analogue of the asm kernel's software pipelining)."""
    cmap_ref, scale_ref, (y_ref, vwin, sem) = _mask_rest(
        rest, fused_cols, has_scale)
    i = pl.program_id(0)
    slot = jax.lax.rem(i, jnp.int32(2))

    @pl.when(i == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)
        pltpu.make_async_copy(values_hbm.at[pl.ds(vbase_ref[0], vmax)],
                              vwin.at[0], sem.at[0]).start()

    @pl.when(i + 1 < nchunks)
    def _prefetch_next():
        nxt = jax.lax.rem(i + jnp.int32(1), jnp.int32(2))
        pltpu.make_async_copy(values_hbm.at[pl.ds(vbase_ref[i + 1], vmax)],
                              vwin.at[nxt], sem.at[nxt]).start()

    pltpu.make_async_copy(values_hbm.at[pl.ds(vbase_ref[i], vmax)],
                          vwin.at[slot], sem.at[slot]).wait()

    contrib = _decode_chunk(mask_ref[0], voff_ref[0], col_ref[0], vwin[slot],
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
def spmv_pallas_db(chunk_vbase, chunk_col, chunk_mask, chunk_voff, chunk_row,
                   values, x, col_map=None, value_scale=None, *, r: int,
                   c: int, cb: int, vmax: int, nrows: int, ncols: int,
                   interpret: bool = False):
    """``col_map`` fuses a column permutation into the decode, exactly as in
    :func:`spmv_pallas`."""
    nchunks = chunk_col.shape[0]
    fused_cols = col_map is not None
    kernel = functools.partial(_spmv_db_kernel, r=r, c=c, cb=cb, vmax=vmax,
                               nrows=nrows, ncols=ncols, nchunks=nchunks,
                               fused_cols=fused_cols,
                               has_scale=value_scale is not None)
    in_specs = [
        pl.BlockSpec((1, cb), lambda i, vb: (i, 0)),
        pl.BlockSpec((1, cb), lambda i, vb: (i, 0)),
        pl.BlockSpec((1, cb), lambda i, vb: (i, 0)),
        pl.BlockSpec((1, cb), lambda i, vb: (i, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec((ncols,), lambda i, vb: (0,)),
    ]
    operands = [chunk_vbase, chunk_col, chunk_mask.astype(jnp.int32),
                chunk_voff, chunk_row, values, x]
    if fused_cols:
        in_specs.append(pl.BlockSpec((ncols,), lambda i, vb: (0,)))
        operands.append(col_map.astype(jnp.int32))
    if value_scale is not None:
        in_specs.append(pl.BlockSpec((1,), lambda i, vb: (i,)))
        operands.append(value_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nchunks,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((nrows,), lambda i, vb: (0,)),
        scratch_shapes=[
            pltpu.VMEM((2, vmax), values.dtype),
            pltpu.SemaphoreType.DMA((2,)),
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
