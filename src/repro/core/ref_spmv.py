"""Pure-jnp SpMV / SpMM oracle over the chunked SPC5 device layout.

This is the numerics reference the Pallas kernels are validated against, and
also the portable fallback used on backends without Pallas. The mask decode
is the TPU-native replacement of AVX-512 ``vexpandpd``:

    ranks = cumsum(mask_bits) - mask_bits        # rank of each set bit
    expanded[k] = values[voffset + ranks[k]]     # gather == in-register expand

so HBM reads exactly the packed values, as in the paper.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .formats import SPC5Chunked, SPC5Panels


class SPC5Device(NamedTuple):
    """jnp view of :class:`SPC5Chunked` (static meta kept python-side)."""

    values: jax.Array      # (nvals_padded,)
    chunk_col: jax.Array   # (nchunks, cb) int32
    chunk_mask: jax.Array  # (nchunks, cb) uint32
    chunk_voff: jax.Array  # (nchunks, cb) int32
    chunk_row: jax.Array   # (nchunks, cb) int32
    chunk_vbase: jax.Array  # (nchunks,) int32


def device_put(chunked: SPC5Chunked, dtype=None) -> SPC5Device:
    values = chunked.values.astype(dtype) if dtype is not None else chunked.values
    return SPC5Device(
        values=jnp.asarray(values),
        chunk_col=jnp.asarray(chunked.chunk_col),
        chunk_mask=jnp.asarray(chunked.chunk_mask),
        chunk_voff=jnp.asarray(chunked.chunk_voff),
        chunk_row=jnp.asarray(chunked.chunk_row),
        chunk_vbase=jnp.asarray(chunked.chunk_vbase),
    )


def _upcast(vals: jax.Array, scale=None) -> jax.Array:
    """The f32-accumulation contract shared by every decode.

    Quantised storage (int8, or any sub-4-byte float such as bf16) is
    upcast to f32 INSIDE the decode, and the optional per-chunk ``scale``
    (leading chunk dims, broadcast over the trailing (cb, r*c) lane dims)
    is applied right after -- so HBM reads narrow values but every multiply
    and accumulate downstream runs in f32. f32 storage passes through
    untouched (bit-identical to the pre-dtype-axis paths).
    """
    if vals.dtype.kind in "iu" or vals.dtype.itemsize < 4:
        vals = vals.astype(jnp.float32)
    if scale is not None:
        vals = vals * scale[..., None, None].astype(vals.dtype)
    return vals


def _decode(dev: SPC5Device, r: int, c: int, ncols: int, scale=None):
    """Shared mask-decode: returns (vals, xcol, yrow) all (nchunks, cb, r*c)."""
    rc = r * c
    k = jnp.arange(rc, dtype=jnp.uint32)
    bits = ((dev.chunk_mask[..., None] >> k[None, None, :])
            & jnp.uint32(1)).astype(jnp.int32)          # (nch, cb, rc)
    ranks = jnp.cumsum(bits, axis=-1) - bits
    vidx = (dev.chunk_vbase[:, None, None].astype(jnp.int32)
            + dev.chunk_voff[..., None] + ranks)
    vidx = jnp.clip(vidx, 0, dev.values.shape[0] - 1)
    vals = _upcast(dev.values[vidx], scale)
    vals = vals * bits.astype(vals.dtype)
    kk = jnp.arange(rc, dtype=jnp.int32)
    xcol = jnp.clip(dev.chunk_col[..., None] + (kk % c)[None, None, :],
                    0, ncols - 1)
    yrow = dev.chunk_row[..., None] + (kk // c)[None, None, :]
    return vals, xcol, yrow


@functools.partial(jax.jit, static_argnames=("r", "c", "nrows", "ncols"))
def spmv(dev: SPC5Device, x: jax.Array, value_scale=None, *, r: int, c: int,
         nrows: int, ncols: int) -> jax.Array:
    """y = A @ x with A in chunked beta(r, c); ``value_scale`` (nchunks,)
    dequantises int8 values (see :func:`_upcast`)."""
    vals, xcol, yrow = _decode(dev, r, c, ncols, scale=value_scale)
    contrib = vals * x[xcol]
    y = jnp.zeros((nrows,), dtype=contrib.dtype)
    return y.at[yrow.reshape(-1)].add(contrib.reshape(-1))


@functools.partial(jax.jit, static_argnames=("r", "c", "nrows", "ncols"))
def spmm(dev: SPC5Device, x: jax.Array, value_scale=None, *, r: int, c: int,
         nrows: int, ncols: int) -> jax.Array:
    """Y = A @ X, X (ncols, nvec) -- the paper's 'multiple vectors' extension."""
    vals, xcol, yrow = _decode(dev, r, c, ncols, scale=value_scale)
    contrib = vals[..., None] * x[xcol]                  # (nch, cb, rc, nvec)
    y = jnp.zeros((nrows, x.shape[1]), dtype=contrib.dtype)
    return y.at[yrow.reshape(-1)].add(
        contrib.reshape(-1, x.shape[1]))


# ----------------------------------------------------------------------------
# Row-panel-tiled layout oracle
# ----------------------------------------------------------------------------

class SPC5PanelDevice(NamedTuple):
    """jnp view of :class:`SPC5Panels` (static meta kept python-side)."""

    values: jax.Array       # (nvals_padded,)
    chunk_col: jax.Array    # (npanels, nchunks, cb) int32, window-relative
    chunk_mask: jax.Array   # (npanels, nchunks, cb) uint32
    chunk_voff: jax.Array   # (npanels, nchunks, cb) int32
    chunk_row: jax.Array    # (npanels, nchunks, cb) int32, panel-relative
    chunk_vbase: jax.Array  # (npanels, nchunks) int32
    chunk_xbase: jax.Array  # (npanels, nchunks) int32


def device_put_panels(panels: SPC5Panels, dtype=None) -> SPC5PanelDevice:
    values = (panels.values.astype(dtype) if dtype is not None
              else panels.values)
    return SPC5PanelDevice(
        values=jnp.asarray(values),
        chunk_col=jnp.asarray(panels.chunk_col),
        chunk_mask=jnp.asarray(panels.chunk_mask),
        chunk_voff=jnp.asarray(panels.chunk_voff),
        chunk_row=jnp.asarray(panels.chunk_row),
        chunk_vbase=jnp.asarray(panels.chunk_vbase),
        chunk_xbase=jnp.asarray(panels.chunk_xbase),
    )


def _decode_panels(dev: SPC5PanelDevice, r: int, c: int, pr: int,
                   ncols_pad: int, cmap=None, scale=None):
    """Panel decode with global index reconstruction.

    Returns (vals, xcol, yrow), each (npanels, nchunks, cb, r*c); xcol is a
    global column into x padded to ncols_pad, yrow a global row into y
    padded to npanels*pr. ``cmap`` is the reordering subsystem's fused
    column map (padded to ncols_pad): block columns are contiguous in
    *permuted* column space, so the decode routes its x gather through
    ``cmap`` and x stays in ORIGINAL order -- no materialised permuted
    copy (the panel analogue of the whole-vector kernels' ``col_map``).
    """
    npanels = dev.chunk_mask.shape[0]
    rc = r * c
    k = jnp.arange(rc, dtype=jnp.uint32)
    bits = ((dev.chunk_mask[..., None] >> k[None, None, None, :])
            & jnp.uint32(1)).astype(jnp.int32)
    ranks = jnp.cumsum(bits, axis=-1) - bits
    vidx = (dev.chunk_vbase[..., None, None].astype(jnp.int32)
            + dev.chunk_voff[..., None] + ranks)
    vidx = jnp.clip(vidx, 0, dev.values.shape[0] - 1)
    vals = _upcast(dev.values[vidx], scale)
    vals = vals * bits.astype(vals.dtype)
    kk = jnp.arange(rc, dtype=jnp.int32)
    xcol = (dev.chunk_xbase[..., None, None] + dev.chunk_col[..., None]
            + (kk % c)[None, None, None, :])
    xcol = jnp.clip(xcol, 0, ncols_pad - 1)
    if cmap is not None:
        xcol = jnp.take(cmap, xcol, axis=0)
    panel_row0 = (jnp.arange(npanels, dtype=jnp.int32) * pr)[:, None, None, None]
    yrow = panel_row0 + dev.chunk_row[..., None] + (kk // c)[None, None, None, :]
    yrow = jnp.clip(yrow, 0, npanels * pr - 1)
    return vals, xcol, yrow


def pad_cmap(cmap: jax.Array, ncols_pad: int) -> jax.Array:
    """Pad a column map to the layout's padded width (pad entries gather
    x[0]; they are only ever hit by mask-0 lanes, whose products are
    zeroed)."""
    return jnp.pad(cmap, (0, max(0, ncols_pad - cmap.shape[0])))


@functools.partial(jax.jit,
                   static_argnames=("r", "c", "pr", "nrows", "ncols_pad"))
def spmv_panels(dev: SPC5PanelDevice, x: jax.Array, cmap=None,
                value_scale=None, *, r: int, c: int, pr: int, nrows: int,
                ncols_pad: int) -> jax.Array:
    """y = A @ x with A in the row-panel-tiled layout; x (ncols,).

    ``cmap`` (optional, (ncols,) int32) fuses a column permutation into the
    decode -- x stays in original order (see :func:`_decode_panels`);
    ``value_scale`` (npanels, nchunks) dequantises int8 values."""
    npanels = dev.chunk_mask.shape[0]
    xp = jnp.pad(x, (0, max(0, ncols_pad - x.shape[0])))
    cm = None if cmap is None else pad_cmap(cmap, ncols_pad)
    vals, xcol, yrow = _decode_panels(dev, r, c, pr, ncols_pad, cmap=cm,
                                      scale=value_scale)
    contrib = vals * xp[xcol]
    y = jnp.zeros((npanels * pr,), dtype=contrib.dtype)
    y = y.at[yrow.reshape(-1)].add(contrib.reshape(-1))
    return y[:nrows]


@functools.partial(jax.jit,
                   static_argnames=("r", "c", "pr", "nrows", "ncols_pad"))
def spmm_panels(dev: SPC5PanelDevice, x: jax.Array, cmap=None,
                value_scale=None, *, r: int, c: int, pr: int, nrows: int,
                ncols_pad: int) -> jax.Array:
    """Y = A @ X with A panel-tiled; X (ncols, nvec). ``cmap`` and
    ``value_scale`` as in :func:`spmv_panels`."""
    npanels = dev.chunk_mask.shape[0]
    xp = jnp.pad(x, ((0, max(0, ncols_pad - x.shape[0])), (0, 0)))
    cm = None if cmap is None else pad_cmap(cmap, ncols_pad)
    vals, xcol, yrow = _decode_panels(dev, r, c, pr, ncols_pad, cmap=cm,
                                      scale=value_scale)
    contrib = vals[..., None] * xp[xcol]
    y = jnp.zeros((npanels * pr, x.shape[1]), dtype=contrib.dtype)
    y = y.at[yrow.reshape(-1)].add(contrib.reshape(-1, x.shape[1]))
    return y[:nrows]


def csr_operator(csr, dtype=jnp.float32):
    """The plain jnp reference of Y = A @ X straight from a
    :class:`~repro.core.formats.CSRMatrix` -- one gather and one segment
    sum per call, no SPC5 format involved -- at ``dtype`` (f32 by default).
    Returns ``apply(x)`` for x of shape (ncols,) or (ncols, nvec)."""
    rows = jnp.asarray(np.repeat(np.arange(csr.nrows, dtype=np.int32),
                                 np.diff(csr.rowptr)))
    cols = jnp.asarray(csr.colidx.astype(np.int32))
    vals = jnp.asarray(csr.values.astype(dtype))

    def apply(x: jax.Array) -> jax.Array:
        fn = spmv_coo if x.ndim == 1 else spmm_coo
        return fn(rows, cols, vals, x, nrows=csr.nrows)

    return apply


def spmv_dense_oracle(dense: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Ground-truth product for tests (numpy, f64 accumulate)."""
    return dense.astype(np.float64) @ x.astype(np.float64)


@functools.partial(jax.jit, static_argnames=("nrows",))
def spmv_coo(rows: jax.Array, cols: jax.Array, vals: jax.Array,
             x: jax.Array, *, nrows: int) -> jax.Array:
    """Scalar tail of the beta(r,c)_test split: singleton blocks as COO.

    The TPU equivalent of the paper's scalar loop -- a gather+segment-sum
    touches exactly one x element per nonzero, none of the c-wide vector
    loads the block kernel would waste on 1-nnz blocks.
    """
    prod = _upcast(vals) * x[cols]
    return jax.ops.segment_sum(prod, rows, num_segments=nrows)


@functools.partial(jax.jit, static_argnames=("nrows",))
def spmm_coo(rows: jax.Array, cols: jax.Array, vals: jax.Array,
             x: jax.Array, *, nrows: int) -> jax.Array:
    """Multi-vector COO tail: Y contribution for X of shape (ncols, nvec)."""
    prod = _upcast(vals)[:, None] * x[cols]
    return jax.ops.segment_sum(prod, rows, num_segments=nrows)


@functools.partial(jax.jit, static_argnames=("pr", "nrows"))
def spmv_coo_panels(rows: jax.Array, cols: jax.Array, vals: jax.Array,
                    x: jax.Array, *, pr: int, nrows: int) -> jax.Array:
    """Row-panel-segmented COO tail of the beta(r,c)_test split.

    ``rows`` are PANEL-LOCAL (in [0, pr)) and the arrays are bucketed
    ``(npanels, smax)`` with zero-value padding, mirroring the panel
    layout's uniform chunk padding: each panel's singletons are one fixed-
    shape segment whose output is a (pr,) slab -- the shape a future Pallas
    tail kernel would give one grid row, and what keeps the test variant's
    working set bounded past the whole-vector VMEM ceiling. Padding entries
    (vals == 0) land on local row 0 of their panel and add nothing.
    """
    npanels = rows.shape[0]
    prod = _upcast(vals) * x[cols]                          # (npanels, smax)
    seg = jax.vmap(
        lambda r_, p_: jax.ops.segment_sum(p_, r_, num_segments=pr))(rows,
                                                                     prod)
    return seg.reshape(npanels * pr)[:nrows]
