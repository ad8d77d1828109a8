"""SPC5 block-sparse matrix formats without zero padding (paper: Bramas & Kus 2018).

Host-side (numpy) storage + conversion, mirroring the paper's CSR -> beta(r,c)
preprocessing, plus the chunked device layout consumed by the Pallas kernels.

The beta(r,c) format (paper fig. 2):
  * blocks are r-row aligned (top row of a block is a multiple of r) but may
    start at ANY column;
  * ``values`` holds ONLY the nonzeros (no padding), in block order and
    row-major inside each block;
  * ``block_colidx`` holds the leftmost column of each block;
  * ``block_rowptr[i]`` is the index of the first block of row-interval i
    (interval = rows [i*r, (i+1)*r));
  * ``block_masks`` holds one r*c-bit mask per block; bit (lr*c + j) set means
    position (row lr, col j) inside the block is a nonzero.

We additionally precompute ``block_voffset`` (exclusive prefix popcount of the
masks) so kernels can address a block's values in O(1); this is derived data,
not extra storage semantics (the paper's asm kernel tracks the same quantity
in a register as it streams blocks).

Two device-facing layouts are derived from :class:`SPC5Matrix`:

  * :func:`to_chunked` -> :class:`SPC5Chunked`: flat chunks of CB blocks,
    consumed by the whole-vector kernels (x/y fully VMEM-resident; grid
    ``(nchunks,)``). Fastest when ``nrows + ncols`` fits the VMEM budget.
  * :func:`to_panels` -> :class:`SPC5Panels`: row-panel-tiled chunks for the
    2-D-grid kernels (``(npanels, nchunks)``); VMEM per grid step is
    ``pr + xw + vmax`` elements regardless of matrix size, lifting the
    whole-vector ceiling. ``repro.kernels.ops.prepare`` selects between the
    two automatically (:func:`repro.kernels.ops.fits_whole_vector`).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from repro import obs

SUPPORTED_BLOCKS: Tuple[Tuple[int, int], ...] = (
    (1, 4), (1, 8), (2, 4), (2, 8), (4, 4), (4, 8), (8, 4),
)

_SENTINEL = np.int32(0)


# ----------------------------------------------------------------------------
# Value dtypes: the storage axis (f32 raw, bf16 raw, int8 + per-chunk scales)
# ----------------------------------------------------------------------------

#: Canonical value-storage dtypes. Every layout accepts any of these;
#: kernels upcast to f32 inside the decode and accumulate in f32, so
#: the dtype only changes HBM traffic, never the accumulation precision.
VDTYPES: Tuple[str, ...] = ("f32", "bf16", "int8")

_VDTYPE_ALIASES = {
    "f32": "f32", "float32": "f32", "fp32": "f32",
    "bf16": "bf16", "bfloat16": "bf16",
    "int8": "int8", "i8": "int8", "s8": "int8",
}


def canonical_vdtype(name: str) -> str:
    """Normalise a value-dtype name to one of :data:`VDTYPES`.

    The sentinels ``""`` (legacy ``dtype=`` passthrough) and ``"auto"``
    (tuner-resolved) pass through unchanged -- resolution is the plan
    pipeline's job, not the format layer's.
    """
    if name in ("", "auto"):
        return name
    key = str(name).strip().lower()
    if key not in _VDTYPE_ALIASES:
        raise ValueError(f"unknown vdtype {name!r}; expected one of "
                         f"{VDTYPES + ('auto', '')}")
    return _VDTYPE_ALIASES[key]


def value_dtype(vdtype: str) -> np.dtype:
    """The numpy storage dtype of a canonical vdtype.

    bfloat16 comes from ``ml_dtypes`` (a jax dependency, always present in
    this toolchain); int8 values carry per-chunk f32 scales alongside.
    """
    vd = canonical_vdtype(vdtype)
    if vd == "bf16":
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16)
    if vd == "int8":
        return np.dtype(np.int8)
    return np.dtype(np.float32)


def value_itemsize(vdtype: str) -> int:
    """Bytes per stored value for a canonical vdtype ('' -> f32's 4)."""
    if vdtype in ("", "auto", "f32"):
        return 4
    return int(value_dtype(vdtype).itemsize)


def quantize_chunk_values(values: np.ndarray, chunk_vbase: np.ndarray,
                          chunk_mask: np.ndarray, vdtype: str
                          ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Quantise a chunked/panelled packed values array to ``vdtype``.

    Returns ``(qvalues, scales)`` where ``scales`` is ``None`` except for
    int8, which gets one symmetric f32 scale per chunk (``absmax / 127``
    over the chunk's OWN nnz -- the popcount of its masks, NOT the full
    aligned vmax window, which overlaps the next chunk's values). Chunks
    with no values (or all zeros) get scale 1.0 so dequantisation is always
    well-defined. Works on any leading chunk shape (flat or panel-tiled):
    ``chunk_vbase`` and the per-chunk mask rows are raveled in step.
    """
    vd = canonical_vdtype(vdtype)
    if vd in ("", "auto", "f32"):
        return values.astype(np.float32), None
    if vd == "bf16":
        return values.astype(value_dtype("bf16")), None
    vbase = np.asarray(chunk_vbase).ravel().astype(np.int64)
    nnz_per_chunk = popcount_u32(
        np.asarray(chunk_mask).reshape(vbase.shape[0], -1)
    ).sum(axis=1).astype(np.int64)
    scales = np.ones(vbase.shape[0], dtype=np.float32)
    q = np.zeros(values.shape[0], dtype=np.int8)
    v32 = values.astype(np.float32)
    for i in range(vbase.shape[0]):
        lo, hi = int(vbase[i]), int(vbase[i]) + int(nnz_per_chunk[i])
        if hi <= lo:
            continue
        absmax = float(np.max(np.abs(v32[lo:hi])))
        if absmax > 0.0:
            scales[i] = np.float32(absmax / 127.0)
        q[lo:hi] = np.clip(np.round(v32[lo:hi] / scales[i]),
                           -127, 127).astype(np.int8)
    return q, scales.reshape(np.asarray(chunk_vbase).shape)


@dataclasses.dataclass
class CSRMatrix:
    """Compressed sparse row, the de-facto baseline format (paper fig. 1)."""

    shape: Tuple[int, int]
    rowptr: np.ndarray  # int32/int64, (nrows + 1,)
    colidx: np.ndarray  # int32, (nnz,)
    values: np.ndarray  # float, (nnz,)

    @property
    def nnz(self) -> int:
        return int(self.values.shape[0])

    @property
    def nrows(self) -> int:
        return self.shape[0]

    @property
    def ncols(self) -> int:
        return self.shape[1]

    def to_dense(self) -> np.ndarray:
        out = np.zeros(self.shape, dtype=self.values.dtype)
        for i in range(self.nrows):
            lo, hi = int(self.rowptr[i]), int(self.rowptr[i + 1])
            out[i, self.colidx[lo:hi]] = self.values[lo:hi]
        return out

    def occupancy_bytes(self, s_int: int = 4) -> int:
        """Paper eq. (3): O_CSR = NNZ*S_f + N_rows*S_i + NNZ*S_i."""
        s_float = self.values.dtype.itemsize
        return self.nnz * s_float + (self.nrows + 1) * s_int + self.nnz * s_int


@dataclasses.dataclass
class SPC5Matrix:
    """The paper's beta(r, c) block format with bitmasks, no zero padding."""

    shape: Tuple[int, int]
    r: int
    c: int
    block_rowptr: np.ndarray   # int32, (ceil(nrows/r) + 1,)
    block_colidx: np.ndarray   # int32, (nblocks,)
    block_masks: np.ndarray    # uint32, (nblocks,)  (r*c <= 32 bits used)
    block_voffset: np.ndarray  # int64, (nblocks,)  exclusive prefix popcount
    values: np.ndarray         # float, (nnz,) -- exactly nnz, no padding

    @property
    def nnz(self) -> int:
        return int(self.values.shape[0])

    @property
    def nblocks(self) -> int:
        return int(self.block_colidx.shape[0])

    @property
    def nrows(self) -> int:
        return self.shape[0]

    @property
    def ncols(self) -> int:
        return self.shape[1]

    @property
    def avg_nnz_per_block(self) -> float:
        """Avg(r, c) = NNZ / N_blocks(r, c) -- the paper's selection feature."""
        return self.nnz / max(self.nblocks, 1)

    @property
    def fill_ratio(self) -> float:
        """Average block fill in [0, 1] (paper tables 1-2 percentages)."""
        return self.avg_nnz_per_block / (self.r * self.c)

    def occupancy_bytes(self, s_int: int = 4) -> int:
        """Paper eq. (1)/(2) measured exactly on this instance."""
        s_float = self.values.dtype.itemsize
        n_intervals = self.block_rowptr.shape[0] - 1
        mask_bytes = self.nblocks * max(1, (self.r * self.c) // 8)
        return (self.nnz * s_float
                + (n_intervals + 1) * s_int
                + self.nblocks * s_int
                + mask_bytes)

    def to_dense(self) -> np.ndarray:
        out = np.zeros(self.shape, dtype=self.values.dtype)
        r, c = self.r, self.c
        vi = 0
        n_intervals = self.block_rowptr.shape[0] - 1
        for it in range(n_intervals):
            row0 = it * r
            for b in range(int(self.block_rowptr[it]), int(self.block_rowptr[it + 1])):
                col0 = int(self.block_colidx[b])
                mask = int(self.block_masks[b])
                for k in range(r * c):
                    if (mask >> k) & 1:
                        lr, lc = divmod(k, c)
                        out[row0 + lr, col0 + lc] = self.values[vi]
                        vi += 1
        assert vi == self.nnz
        return out


def occupancy_model_spc5(nnz: int, nrows: int, avg: float, r: int, c: int,
                         s_float: int = 8, s_int: int = 4) -> float:
    """Paper eq. (2): the closed-form occupancy model (bytes)."""
    return (nnz * s_float
            + nrows * s_int / r
            + nnz * (8 * s_int + r * c) / (8 * max(avg, 1e-12)))


def occupancy_model_csr(nnz: int, nrows: int, s_float: int = 8,
                        s_int: int = 4) -> float:
    """Paper eq. (3)."""
    return nnz * s_float + nrows * s_int + nnz * s_int


def beta_breakeven_avg(r: int, c: int, s_int: int = 4) -> float:
    """Paper eq. (4): minimum Avg(r,c) for beta(r,c) to beat CSR's last term."""
    return 1.0 + (r * c) / (8.0 * s_int)


# ----------------------------------------------------------------------------
# SpMV byte model
# ----------------------------------------------------------------------------

def spmv_bytes_per_nnz(r: int, c: int, avg: float, s_float: int = 4,
                       s_int: int = 4) -> float:
    """HBM bytes per nonzero of one SpMV pass, per value dtype.

    Shared by the roofline bench and the server's :class:`PlanExecStats`
    ceiling, so the reported arithmetic intensity uses one model. A pass
    streams the packed values (``s_float`` -- the VALUE itemsize: 4 for
    f32, 2 for bf16, 1 for int8), 4 int32 of metadata per block (mask,
    voffset, colidx, row) and one chunk-base int per block.
    """
    return s_float + (4 * s_int + s_int) / max(avg, 1e-12)


# ----------------------------------------------------------------------------
# Construction / conversion
# ----------------------------------------------------------------------------

def csr_from_dense(dense: np.ndarray) -> CSRMatrix:
    nrows, _ = dense.shape
    rowptr = np.zeros(nrows + 1, dtype=np.int64)
    cols, vals = [], []
    for i in range(nrows):
        nz = np.nonzero(dense[i])[0]
        rowptr[i + 1] = rowptr[i] + nz.shape[0]
        cols.append(nz.astype(np.int32))
        vals.append(dense[i, nz])
    colidx = (np.concatenate(cols) if cols else np.zeros(0, np.int32))
    values = (np.concatenate(vals) if vals else np.zeros(0, dense.dtype))
    return CSRMatrix((nrows, dense.shape[1]), rowptr, colidx, values)


def csr_from_coo(shape: Tuple[int, int], rows: np.ndarray, cols: np.ndarray,
                 vals: np.ndarray) -> CSRMatrix:
    """Build CSR from COO triplets (duplicates summed)."""
    nrows, ncols = shape
    order = np.lexsort((cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    # collapse duplicates
    if rows.shape[0]:
        key = rows.astype(np.int64) * ncols + cols.astype(np.int64)
        uniq, inv = np.unique(key, return_inverse=True)
        summed = np.zeros(uniq.shape[0], dtype=vals.dtype)
        np.add.at(summed, inv, vals)
        rows = (uniq // ncols).astype(np.int64)
        cols = (uniq % ncols).astype(np.int32)
        vals = summed
    rowptr = np.zeros(nrows + 1, dtype=np.int64)
    np.add.at(rowptr, rows + 1, 1)
    rowptr = np.cumsum(rowptr)
    return CSRMatrix(shape, rowptr, cols.astype(np.int32), vals)


def csr_to_spc5(csr: CSRMatrix, r: int, c: int) -> SPC5Matrix:
    """Convert CSR to beta(r, c).

    Greedy left-to-right block construction per r-row interval, exactly the
    coverage the paper's figures show: a block opens at the leftmost uncovered
    nonzero column of the interval and spans c columns.

    Runs under a ``convert`` span with one child per phase:
    ``convert.block_starts``, ``convert.bits`` and ``convert.values``.
    """
    if r * c > 32:
        raise ValueError(f"mask must fit uint32, got r*c={r*c}")
    nrows, ncols = csr.shape
    n_intervals = -(-nrows // r)
    with obs.span("convert", nrows=nrows, nnz=int(csr.colidx.shape[0]),
                  r=r, c=c):
        with obs.span("convert.block_starts"):
            rows = np.repeat(np.arange(nrows, dtype=np.int64),
                             np.diff(csr.rowptr).astype(np.int64))
            cols = csr.colidx.astype(np.int64)
            interval = rows // r
            # one sorted key per distinct (interval, column): the greedy
            # scan below runs over these, for every interval at once
            key = interval * ncols + cols
            ukeys = np.unique(key)
            seg_end = np.searchsorted(
                ukeys, (np.arange(n_intervals, dtype=np.int64) + 1) * ncols,
                side="left")
            ptr = np.concatenate([[0], seg_end[:-1]]).astype(np.int64)
            # Greedy block starts: each step opens, in every interval that
            # still has uncovered columns, a block at the leftmost one (one
            # step per block of the widest interval, not per nonzero)
            bkeys = []
            live = np.nonzero(ptr < seg_end)[0]
            while live.shape[0]:
                start = ukeys[ptr[live]]
                bkeys.append(start)
                ptr[live] = np.searchsorted(ukeys, start + c, side="left")
                live = live[ptr[live] < seg_end[live]]
            bkeys = (np.sort(np.concatenate(bkeys)) if bkeys
                     else np.zeros(0, np.int64))
        with obs.span("convert.bits"):
            # each nonzero's block, its bit (row-major inside the block),
            # and the order of the values: block order, row-major inside
            # each block
            bidx = np.searchsorted(bkeys, key, side="right") - 1
            starts = bkeys % ncols if ncols else bkeys
            bit = (rows % r) * c + (cols - starts[bidx])
            order = np.lexsort((cols, rows, bidx))
            masks = np.zeros(bkeys.shape[0], dtype=np.uint32)
            np.bitwise_or.at(masks, bidx, np.left_shift(
                np.uint32(1), bit.astype(np.uint32)))
            rowptr = np.zeros(n_intervals + 1, dtype=np.int64)
            np.cumsum(np.bincount(bkeys // max(ncols, 1),
                                  minlength=n_intervals), out=rowptr[1:])
        with obs.span("convert.values"):
            voffset = (exclusive_prefix_popcount(masks) if masks.shape[0]
                       else np.zeros(0, np.int64))
            values = csr.values[order]
    return SPC5Matrix((nrows, ncols), r, c, rowptr, starts.astype(np.int32),
                      masks, voffset.astype(np.int64), values)


def spc5_to_coo(mat: SPC5Matrix) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Decode beta(r,c) back to COO triplets, fully vectorized.

    Values are stored in block order, row-major inside each block -- exactly
    ``np.nonzero``'s order over the (nblocks, r*c) bit matrix -- so
    ``mat.values`` maps 1:1 onto the decoded (row, col) pairs with no
    per-element loop. This keeps matrix-level transforms (permutation,
    re-blocking) sparse: nothing ever materializes an (nrows, ncols) dense
    array.
    """
    r, c = mat.r, mat.c
    if mat.nblocks == 0:
        return (np.zeros(0, np.int64), np.zeros(0, np.int64),
                np.zeros(0, mat.values.dtype))
    n_intervals = mat.block_rowptr.shape[0] - 1
    interval_of_block = np.repeat(
        np.arange(n_intervals, dtype=np.int64), np.diff(mat.block_rowptr))
    k = np.arange(r * c, dtype=np.uint32)
    bits = ((mat.block_masks[:, None] >> k[None, :]) & np.uint32(1)) != 0
    b_idx, k_idx = np.nonzero(bits)          # block-major, bit-ascending
    rows = interval_of_block[b_idx] * r + k_idx // c
    cols = mat.block_colidx[b_idx].astype(np.int64) + k_idx % c
    return rows, cols, mat.values.copy()


def spc5_to_csr(mat: SPC5Matrix) -> CSRMatrix:
    """Exact inverse of :func:`csr_to_spc5` (used by round-trip tests and
    matrix-level transforms); sparse throughout via :func:`spc5_to_coo`."""
    rows, cols, vals = spc5_to_coo(mat)
    return csr_from_coo(mat.shape, rows, cols, vals)


def as_csr(m) -> CSRMatrix:
    """Normalise a CSRMatrix-or-SPC5Matrix argument to CSR (the shared
    entry-point dispatch of the structure/reorder analysis modules)."""
    return spc5_to_csr(m) if isinstance(m, SPC5Matrix) else m


def popcount_u32(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint32)
    out = np.zeros(x.shape, dtype=np.int32)
    for k in range(32):
        out += ((x >> np.uint32(k)) & np.uint32(1)).astype(np.int32)
    return out


def exclusive_prefix_popcount(masks: np.ndarray, axis: int = -1) -> np.ndarray:
    """Exclusive prefix sum of mask popcounts along ``axis``: the offset
    each block's packed values start at (the paper's voffset). The single
    definition shared by the builders and the static verifier
    (``repro.analysis.verify``), so "voff is the exclusive prefix popcount"
    is an invariant with one implementation to agree with."""
    pop = popcount_u32(np.asarray(masks)).astype(np.int64)
    return np.cumsum(pop, axis=axis) - pop


def block_stats(csr: CSRMatrix, r: int, c: int) -> Tuple[int, float]:
    """(N_blocks(r,c), Avg(r,c)) without materializing the format's values.

    This is the cheap statistic the paper's selector uses *before* conversion.
    """
    nrows = csr.shape[0]
    n_intervals = -(-nrows // r)
    nblocks = 0
    for it in range(n_intervals):
        row0, row1 = it * r, min((it + 1) * r, nrows)
        lo, hi = int(csr.rowptr[row0]), int(csr.rowptr[row1])
        if lo == hi:
            continue
        ucols = np.unique(csr.colidx[lo:hi].astype(np.int64))
        i = 0
        while i < ucols.shape[0]:
            i = int(np.searchsorted(ucols, ucols[i] + c, side="left"))
            nblocks += 1
    return nblocks, csr.nnz / max(nblocks, 1)


# ----------------------------------------------------------------------------
# beta_test variant: segregate singleton blocks (paper's `test` kernels)
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class SPC5TestSplit:
    """Storage-level equivalent of the paper's beta(r,c)_test dual-loop kernel.

    Blocks whose mask has a single set bit are pulled out into a COO tail
    (rows/cols/values); the remaining multi-nnz blocks stay in beta(r,c).
    On TPU the specialisation is done at storage level because in-kernel
    branching has no benefit on a divergence-free SIMD machine (DESIGN.md §2).
    """

    multi: SPC5Matrix
    single_rows: np.ndarray   # int32 (n_single,)
    single_cols: np.ndarray   # int32 (n_single,)
    single_values: np.ndarray  # float (n_single,)

    @property
    def nnz(self) -> int:
        return self.multi.nnz + int(self.single_values.shape[0])


def split_singletons(mat: SPC5Matrix) -> SPC5TestSplit:
    pop = popcount_u32(mat.block_masks)
    is_single = pop == 1
    r, c = mat.r, mat.c
    n_intervals = mat.block_rowptr.shape[0] - 1
    interval_of_block = np.repeat(
        np.arange(n_intervals, dtype=np.int64), np.diff(mat.block_rowptr))

    # Singleton extraction (vectorized)
    sblocks = np.nonzero(is_single)[0]
    if sblocks.shape[0]:
        smask = mat.block_masks[sblocks].astype(np.uint32)
        bitpos = np.zeros(sblocks.shape[0], dtype=np.int64)
        tmp = smask.copy()
        for k in range(r * c):
            bitpos[(tmp == np.uint32(1) << np.uint32(k))] = k
        srow = interval_of_block[sblocks] * r + bitpos // c
        scol = mat.block_colidx[sblocks].astype(np.int64) + bitpos % c
        svals = mat.values[mat.block_voffset[sblocks]]
    else:
        srow = np.zeros(0, np.int64)
        scol = np.zeros(0, np.int64)
        svals = np.zeros(0, mat.values.dtype)

    # Remaining multi blocks
    keep = np.nonzero(~is_single)[0]
    rowptr = np.zeros(n_intervals + 1, dtype=np.int64)
    np.add.at(rowptr, interval_of_block[keep] + 1, 1)
    rowptr = np.cumsum(rowptr)
    # gather values of kept blocks
    if keep.shape[0]:
        lens = popcount_u32(mat.block_masks[keep]).astype(np.int64)
        starts = mat.block_voffset[keep]
        vidx = np.concatenate([np.arange(s, s + l) for s, l in zip(starts, lens)])
        kvals = mat.values[vidx]
        kvoff = np.concatenate([[0], np.cumsum(lens)[:-1]])
    else:
        kvals = np.zeros(0, mat.values.dtype)
        kvoff = np.zeros(0, np.int64)
    multi = SPC5Matrix(mat.shape, r, c, rowptr,
                       mat.block_colidx[keep], mat.block_masks[keep],
                       kvoff.astype(np.int64), kvals)
    return SPC5TestSplit(multi, srow.astype(np.int32), scol.astype(np.int32),
                         svals)


# ----------------------------------------------------------------------------
# Chunked device layout for the Pallas kernels
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class SPC5Chunked:
    """Fixed-size chunks of CB blocks each, value windows 8-value aligned.

    This is the device-facing layout: every per-chunk tile has a static shape
    so Pallas BlockSpecs are uniform; the values array stays packed except
    chunk starts are rounded up to ``align`` values (<0.5%% overhead, see
    DESIGN.md "alignment padding note"). Pad blocks have mask == 0 (they load
    nothing and contribute nothing).
    """

    shape: Tuple[int, int]
    r: int
    c: int
    cb: int                 # blocks per chunk
    vmax: int               # max values per chunk window (static tile size)
    nchunks: int
    chunk_col: np.ndarray   # int32 (nchunks, cb)   block left column
    chunk_mask: np.ndarray  # uint32 (nchunks, cb)  0 => padding block
    chunk_voff: np.ndarray  # int32 (nchunks, cb)   value offset within window
    chunk_row: np.ndarray   # int32 (nchunks, cb)   global top row of block
    chunk_vbase: np.ndarray  # int32 (nchunks,)     aligned start into values
    values: np.ndarray      # float (nvals_padded,)
    nnz: int

    @property
    def nrows(self) -> int:
        return self.shape[0]

    @property
    def ncols(self) -> int:
        return self.shape[1]


# ----------------------------------------------------------------------------
# Row-panel-tiled device layout (2-D grid: panels x chunks)
# ----------------------------------------------------------------------------

#: Default blocks per chunk of the panels layout. The panel kernel lays a
#: chunk's blocks along lanes, so a chunk fills one vreg row of 128 lanes:
#: a narrower chunk leaves lanes of padding in every vector op and one-hot
#: contraction of a grid step, and costs as much per step on more steps.
PANEL_CB = 128


@dataclasses.dataclass
class SPC5Panels:
    """Row-panel-tiled chunked layout for the 2-D-grid Pallas kernels.

    The whole-vector :class:`SPC5Chunked` layout needs all of ``x`` (ncols)
    and ``y`` (nrows) VMEM-resident, which caps matrix size at a few hundred
    thousand rows. This layout lifts that ceiling:

      * rows are cut into panels of ``pr`` rows (``pr`` a multiple of ``r``,
        so the r-row-aligned blocks NEVER straddle a panel boundary);
      * within a panel, blocks are sorted by left column and greedily packed
        into chunks of at most ``cb`` blocks whose columns all fall inside
        one ``xw``-wide window of ``x`` (``chunk_xbase`` is the window start,
        aligned down to ``align``);
      * a kernel grid step ``(panel, chunk)`` therefore touches only a
        ``(pr,)`` slice of ``y`` (accumulated in VMEM, written once per
        panel) and one ``(xw,)`` window of ``x`` (DMA'd like the values
        window) -- VMEM per step is ``pr + xw + vmax`` elements regardless
        of matrix size;
      * ``chunk_row`` is panel-relative (in ``[0, pr - r]``) and
        ``chunk_col`` window-relative (in ``[0, xw - c]``), so the kernel
        scatters/gathers with small bounded indices;
      * ``values`` stays packed with only chunk-alignment padding, exactly
        as in the flat layout -- the paper's no-zero-padding property is
        untouched; per-panel column sorting only permutes whole blocks.

    Chunk counts are padded to the per-panel maximum so the grid is uniform;
    padding chunks have ``mask == 0`` and contribute nothing. ``x`` must be
    padded to ``ncols_pad`` so every window load stays in bounds (the ops
    wrapper does this).
    """

    shape: Tuple[int, int]
    r: int
    c: int
    pr: int                  # panel height in rows, multiple of r
    cb: int                  # blocks per chunk
    xw: int                  # x-window width per chunk, multiple of align
    vmax: int                # values per chunk window (static tile size)
    npanels: int
    nchunks: int             # chunks per panel (uniform, padded)
    ncols_pad: int           # pad x to this length for in-bounds windows
    chunk_col: np.ndarray    # int32 (npanels, nchunks, cb)  window-relative
    chunk_mask: np.ndarray   # uint32 (npanels, nchunks, cb) 0 => padding
    chunk_voff: np.ndarray   # int32 (npanels, nchunks, cb)  offset in window
    chunk_row: np.ndarray    # int32 (npanels, nchunks, cb)  panel-relative
    chunk_vbase: np.ndarray  # int32 (npanels, nchunks)      into values
    chunk_xbase: np.ndarray  # int32 (npanels, nchunks)      x window start
    values: np.ndarray       # float (nvals_padded,)
    nnz: int

    @property
    def nrows(self) -> int:
        return self.shape[0]

    @property
    def ncols(self) -> int:
        return self.shape[1]


def _panel_chunk_plan(mat: SPC5Matrix, pr: int, cb: int, xw: int,
                      align: int = 8):
    """Pass 1 of :func:`to_panels`: per panel, column-sort blocks and find
    chunk boundaries. Returns ``(panels, pr, xw, npanels)`` where ``panels``
    holds one ``(order, chunk_starts, xbases, nb)`` tuple per panel (None
    for empty panels) and pr/xw are normalised to the layout's alignment
    invariants. Shared with :func:`count_panel_chunks` so locality analysis
    (repro.core.structure) predicts exactly the chunking the layout builds.
    """
    r, c = mat.r, mat.c
    nrows = mat.shape[0]
    pr = max(r, -(-pr // r) * r)                 # multiple of r
    # a window must hold one block wherever it lands after aligning down
    xw = max(xw, c + align)
    xw = -(-xw // align) * align
    npanels = max(1, -(-nrows // pr))
    intervals_per_panel = pr // r
    n_intervals = mat.block_rowptr.shape[0] - 1
    interval_of_block = np.repeat(
        np.arange(n_intervals, dtype=np.int64), np.diff(mat.block_rowptr))

    panels = []          # (order, chunk_starts, xbases, nb) per panel
    for p in range(npanels):
        it0 = min(p * intervals_per_panel, n_intervals)
        it1 = min((p + 1) * intervals_per_panel, n_intervals)
        b0, b1 = int(mat.block_rowptr[it0]), int(mat.block_rowptr[it1])
        nb = b1 - b0
        if nb == 0:
            panels.append(None)
            continue
        cols = mat.block_colidx[b0:b1].astype(np.int64)
        ivl = interval_of_block[b0:b1]
        order = np.lexsort((ivl, cols)) + b0     # by column, then interval
        scols = mat.block_colidx[order].astype(np.int64)
        starts, xbases = [], []
        s = 0
        while s < nb:
            xbase = (int(scols[s]) // align) * align
            e = min(s + cb, int(np.searchsorted(scols, xbase + xw - c,
                                                side="right")))
            starts.append(s)
            xbases.append(xbase)
            s = e
        panels.append((order, np.asarray(starts, dtype=np.int64),
                       np.asarray(xbases, dtype=np.int64), nb))
    return panels, pr, xw, npanels


def count_panel_chunks(mat: SPC5Matrix, pr: int = 512, cb: int = PANEL_CB,
                       xw: int = 512, align: int = 8) -> np.ndarray:
    """Per-panel chunk counts of the (pr, cb, xw) panel layout -- the DMA
    cost proxy: each chunk is one value-window + one x-window DMA.

    Runs only pass 1 of the conversion (no value movement), so it is cheap
    enough for reordering strategies to score candidate permutations with
    and for ``structure.profile`` to report per-panel locality.
    """
    panels, _, _, npanels = _panel_chunk_plan(mat, pr, cb, xw, align)
    return np.asarray([0 if pp is None else len(pp[1]) for pp in panels],
                      dtype=np.int64)


def to_panels(mat: SPC5Matrix, pr: int = 512, cb: int = PANEL_CB,
              xw: int = 512, align: int = 8) -> SPC5Panels:
    """Convert beta(r,c) to the row-panel-tiled layout (see SPC5Panels).

    The only per-element Python loop is over CHUNKS (boundary discovery via
    searchsorted); block/value assembly is vectorized, so conversion stays
    fast on million-nnz matrices.

    Runs under a ``panels`` span with one child per phase:
    ``panels.chunk_plan``, ``panels.assemble`` and ``panels.values``.
    """
    r, c = mat.r, mat.c
    nrows, ncols = mat.shape
    with obs.span("panels", nrows=nrows, nblocks=int(mat.nblocks),
                  pr=pr, cb=cb, xw=xw):
        with obs.span("panels.chunk_plan"):
            panels, pr, xw, npanels = _panel_chunk_plan(mat, pr, cb, xw,
                                                        align)
        intervals_per_panel = pr // r
        n_intervals = mat.block_rowptr.shape[0] - 1
        nchunks = max(1, max((len(pp[1]) for pp in panels if pp is not None),
                             default=1))
        chunk_col = np.zeros((npanels, nchunks, cb), dtype=np.int32)
        chunk_mask = np.zeros((npanels, nchunks, cb), dtype=np.uint32)
        chunk_voff = np.zeros((npanels, nchunks, cb), dtype=np.int32)
        chunk_row = np.zeros((npanels, nchunks, cb), dtype=np.int32)
        chunk_vbase = np.zeros((npanels, nchunks), dtype=np.int32)
        chunk_xbase = np.zeros((npanels, nchunks), dtype=np.int32)

        with obs.span("panels.assemble"):
            pop = popcount_u32(mat.block_masks).astype(np.int64)
            interval_of_block = np.repeat(
                np.arange(n_intervals, dtype=np.int64),
                np.diff(mat.block_rowptr))
            # -- pass 2: vectorized per-panel assembly
            per_panel = []   # deferred value scatters: (dst_base-less data)
            vmax = 0
            ncols_pad = xw
            for p, pp in enumerate(panels):
                if pp is None:
                    continue
                order, starts, xbases, nb = pp
                nch_p = starts.shape[0]
                sizes = np.diff(np.append(starts, nb))
                chunk_of = np.repeat(np.arange(nch_p, dtype=np.int64), sizes)
                slot = np.arange(nb, dtype=np.int64) - np.repeat(starts, sizes)
                lens = pop[order]
                cum_excl = np.concatenate([[0], np.cumsum(lens)[:-1]])
                chunk_nnz = (np.add.reduceat(lens, starts) if nb
                             else np.zeros(0, np.int64))

                chunk_mask[p, chunk_of, slot] = mat.block_masks[order]
                chunk_col[p, chunk_of, slot] = (
                    mat.block_colidx[order].astype(np.int64)
                    - np.repeat(xbases, sizes)).astype(np.int32)
                chunk_row[p, chunk_of, slot] = (
                    (interval_of_block[order] - p * intervals_per_panel) * r
                ).astype(np.int32)
                chunk_voff[p, chunk_of, slot] = (
                    cum_excl - np.repeat(cum_excl[starts], sizes)
                ).astype(np.int32)
                chunk_xbase[p, :nch_p] = xbases
                ncols_pad = max(ncols_pad, int(xbases.max()) + xw)
                vmax = max(vmax, int(chunk_nnz.max()) if nch_p else 0)
                # packed panel values in chunk order (no inter-chunk
                # padding yet)
                total = int(lens.sum())
                src = (np.repeat(mat.block_voffset[order] - cum_excl, lens)
                       + np.arange(total, dtype=np.int64))
                per_panel.append((p, nch_p, chunk_nnz, cum_excl[starts], src))

        with obs.span("panels.values"):
            vmax = max(align, vmax + (-vmax) % align)
            # chunk value windows: aligned exclusive cumsum across
            # (panel, chunk)
            all_nnz = (np.concatenate([pp[2] for pp in per_panel])
                       if per_panel else np.zeros(0, np.int64))
            aligned = -(-all_nnz // align) * align
            vbases = (np.concatenate([[0], np.cumsum(aligned)[:-1]])
                      if aligned.shape[0] else np.zeros(0, np.int64))
            # every chunk's [vbase, vbase + vmax) DMA window must be in
            # bounds, and the last chunk has the largest vbase
            nvals = (int(vbases[-1]) + vmax) if aligned.shape[0] else vmax
            values = np.zeros(nvals, mat.values.dtype)
            ci0 = 0
            for p, nch_p, chunk_nnz, cum_chunk, src in per_panel:
                vb = vbases[ci0:ci0 + nch_p]
                chunk_vbase[p, :nch_p] = vb.astype(np.int32)
                dst = (np.repeat(vb - cum_chunk, chunk_nnz)
                       + np.arange(int(chunk_nnz.sum()), dtype=np.int64))
                values[dst] = mat.values[src]
                ci0 += nch_p
    return SPC5Panels(mat.shape, r, c, pr, cb, int(xw), int(vmax), npanels,
                      nchunks, int(ncols_pad), chunk_col, chunk_mask,
                      chunk_voff, chunk_row, chunk_vbase, chunk_xbase, values,
                      mat.nnz)


def to_chunked(mat: SPC5Matrix, cb: int = 256, align: int = 8) -> SPC5Chunked:
    r, c = mat.r, mat.c
    nblocks = mat.nblocks
    nchunks = max(1, -(-nblocks // cb))
    n_intervals = mat.block_rowptr.shape[0] - 1
    interval_of_block = np.repeat(
        np.arange(n_intervals, dtype=np.int64), np.diff(mat.block_rowptr))
    pop = popcount_u32(mat.block_masks).astype(np.int64)

    chunk_col = np.zeros((nchunks, cb), dtype=np.int32)
    chunk_mask = np.zeros((nchunks, cb), dtype=np.uint32)
    chunk_voff = np.zeros((nchunks, cb), dtype=np.int32)
    chunk_row = np.zeros((nchunks, cb), dtype=np.int32)
    chunk_vbase = np.zeros((nchunks,), dtype=np.int32)

    vals_out = []
    vcursor = 0
    vmax = 0
    for ch in range(nchunks):
        b0, b1 = ch * cb, min((ch + 1) * cb, nblocks)
        n = b1 - b0
        if n <= 0:
            chunk_vbase[ch] = vcursor
            continue
        lens = pop[b0:b1]
        local_off = np.concatenate([[0], np.cumsum(lens)[:-1]])
        total = int(lens.sum())
        chunk_col[ch, :n] = mat.block_colidx[b0:b1]
        chunk_mask[ch, :n] = mat.block_masks[b0:b1]
        chunk_voff[ch, :n] = local_off
        chunk_row[ch, :n] = (interval_of_block[b0:b1] * r).astype(np.int32)
        chunk_vbase[ch] = vcursor
        v0 = int(mat.block_voffset[b0])
        vals_out.append(mat.values[v0:v0 + total])
        vmax = max(vmax, total)
        vcursor += total
        pad = (-vcursor) % align
        if pad:
            vals_out.append(np.zeros(pad, mat.values.dtype))
            vcursor += pad
    # round the static window up to alignment, at least one vector
    vmax = max(align, vmax + (-vmax) % align)
    values = (np.concatenate(vals_out) if vals_out
              else np.zeros(0, mat.values.dtype))
    # tail padding so the last window load stays in bounds
    tail_need = (int(chunk_vbase[-1]) + vmax) - values.shape[0]
    if tail_need > 0:
        values = np.concatenate([values, np.zeros(tail_need, mat.values.dtype)])
    return SPC5Chunked(mat.shape, r, c, cb, int(vmax), nchunks, chunk_col,
                       chunk_mask, chunk_voff, chunk_row, chunk_vbase, values,
                       mat.nnz)
