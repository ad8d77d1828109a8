"""SparseLinear: pruned weight matrices in beta(r,c) as a drop-in layer.

The framework-level integration of the paper's kernels (DESIGN.md §3):
``y = W_sparse @ x`` over batched activations is the paper's SpMM; batch-1
decode is its SpMV. Block geometry is chosen per-matrix by the paper's
selector when a record store is available, else by Avg(r,c) breakeven
(paper eq. 4).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from . import formats as F
from . import selector as S


def prune_by_magnitude(w: np.ndarray, density: float) -> np.ndarray:
    """Keep the top ``density`` fraction of |w| entries (global threshold)."""
    if density >= 1.0:
        return w
    k = max(1, int(w.size * density))
    thresh = np.partition(np.abs(w).ravel(), w.size - k)[w.size - k]
    return np.where(np.abs(w) >= thresh, w, 0.0)


def choose_block(csr: F.CSRMatrix, store: Optional[S.RecordStore] = None,
                 workers: int = 1) -> Tuple[int, int]:
    """Selector-driven (r,c) choice; falls back to eq.-4 breakeven argmax."""
    if store is not None and store.records:
        kernel, _, _ = S.select_kernel(csr, store, workers=workers)
        return S.kernel_block(kernel)
    best, best_score = (1, 8), -np.inf
    for (r, c) in F.SUPPORTED_BLOCKS:
        _, avg = F.block_stats(csr, r, c)
        # margin over the paper's breakeven filling, normalised by block area
        score = avg / F.beta_breakeven_avg(r, c)
        if score > best_score:
            best, best_score = (r, c), score
    return best


@dataclasses.dataclass(frozen=True)
class SparseLinear:
    """y = A x (+ b) with A stored in chunked beta(r,c).

    The handle is an execution plan (:class:`repro.core.plan.SPC5Plan`) in
    whichever layout the plan pipeline selected: whole-vector for layers
    whose in/out vectors fit VMEM, row-panel-tiled beyond that ceiling
    (huge vocab projections, extreme-width MLPs). ``handle.layout`` names
    the registry key; ``handle.trace`` records every pipeline decision.
    """

    handle: object  # repro.core.plan.SPC5Plan
    bias: Optional[jax.Array] = None

    @property
    def shape(self):
        return self.handle.shape

    @property
    def density(self) -> float:
        return self.handle.nnz / (self.shape[0] * self.shape[1])

    @classmethod
    def from_dense(cls, w: np.ndarray, density: float = 1.0,
                   block: Optional[Tuple[int, int]] = None,
                   store: Optional[S.RecordStore] = None,
                   bias: Optional[np.ndarray] = None,
                   cb: Optional[int] = None, dtype=None,
                   vdtype: str = "auto", layout: str = "auto",
                   pr: Optional[int] = None, xw: Optional[int] = None,
                   nvec: int = 128, tune: bool = True,
                   reorder=None, verify=False) -> "SparseLinear":
        """``nvec``: widest activation batch this layer will see -- feeds
        the auto layout's VMEM budget (SpMM tiles are nvt=min(nvec,128)
        wide). Defaults to 128 (one full lane tile) since batch size is
        unknown at build time; pass nvec=1 for strictly-SpMV layers.

        The record ``store`` drives both the (r,c) block choice and the
        (layout, pr, xw, cb) auto-tune in ``ops.prepare``; explicit
        ``layout``/``pr``/``xw``/``cb`` arguments are the escape hatch that
        overrides tuning (``tune=False`` disables it).

        ``reorder`` (strategy name or ``repro.core.reorder.Reordering``)
        permutes the pruned weight before the layout is built; the layer's
        ``__call__`` is unchanged -- activations go in and come out in
        original feature order (the handle gathers/scatters internally).

        ``vdtype`` ("f32" | "bf16" | "int8" | "auto") selects the stored
        value dtype (quantised stores accumulate
        in f32 -- useful for pruned-weight layers where activations stay
        full precision); ``verify`` is the static plan checker hook
        (``repro.analysis.verify``), also as on ``ops.prepare``."""
        w = prune_by_magnitude(np.asarray(w), density)
        csr = F.csr_from_dense(w)
        if block is None:
            block = choose_block(csr, store)
        mat = F.csr_to_spc5(csr, *block)
        h = ops.prepare(mat, cb=cb, dtype=dtype, vdtype=vdtype,
                        layout=layout, pr=pr, xw=xw,
                        nvec=nvec, store=store, tune=tune, reorder=reorder,
                        verify=verify)
        b = None if bias is None else jnp.asarray(bias)
        return cls(handle=h, bias=b)

    def __call__(self, x: jax.Array, *, use_pallas: Optional[bool] = None
                 ) -> jax.Array:
        """x: (..., d_in) -> (..., d_out)."""
        d_in = self.handle.ncols
        lead = x.shape[:-1]
        xf = x.reshape(-1, d_in).T                      # (d_in, batch)
        if xf.shape[1] == 1:
            y = ops.spmv(self.handle, xf[:, 0], use_pallas=use_pallas)[:, None]
        else:
            y = ops.spmm(self.handle, xf, use_pallas=use_pallas)
        y = y.T.reshape(*lead, self.handle.nrows)
        if self.bias is not None:
            y = y + self.bias
        return y


jax.tree_util.register_pytree_node(
    SparseLinear,
    lambda sl: ((sl.handle, sl.bias), None),
    lambda aux, ch: SparseLinear(handle=ch[0], bias=ch[1]),
)
