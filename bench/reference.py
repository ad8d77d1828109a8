"""The plain reference of Y = A @ X, and the number that compares with it.

A straight CSR product in ``jax.numpy``: one gather of x per nonzero, an
elementwise multiply and a segment sum into rows. It takes the CSR arrays
the benchmark's own generator made, imports nothing of the library, and
runs on the device once the measured window has closed.

The compared number is the worst relative gap over every row of every
compared answer::

    rel_gap = max_i |y_i - ref_i| / (|A| |x|)_i

A row whose ``(|A| |x|)_i`` is zero must read exactly zero; a non-finite
answer reads infinity. In float32, reassociating a row of ``k`` products
moves a row by at most about ``k * 2^-24`` of ``(|A| |x|)_i``; storing x
in bfloat16 alone moves it by up to ``2^-9``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: Widest block of vectors one reference call takes: bounds the gathered
#: (nnz, block) intermediate at the sizes the cells run.
MAX_BLOCK = 8


@functools.partial(jax.jit, static_argnames=("nrows", "dtype"))
def _csr_product(rows, cols, vals, x, *, nrows: int, dtype):
    prod = vals.astype(dtype)[:, None] * x.astype(dtype)[cols]
    return jax.ops.segment_sum(prod, rows, num_segments=nrows,
                               indices_are_sorted=True).astype(jnp.float32)


@jax.jit
def _worst_gap(y, ref, scale):
    """max over entries of |y - ref| / scale, with zero-scale entries
    required to be exact and non-finite answers reading infinity."""
    gap = jnp.abs(y.astype(jnp.float32) - ref)
    rel = jnp.where(scale > 0, gap / jnp.where(scale > 0, scale, 1.0),
                    jnp.where(gap > 0, jnp.inf, 0.0))
    rel = jnp.where(jnp.isfinite(y), rel, jnp.inf)
    return jnp.max(rel)


class CSRReference:
    """Y = A @ X from CSR arrays, on the device, at ``dtype`` (float32 is
    the reference; bfloat16 is the control that must fail the check)."""

    def __init__(self, shape, rowptr: np.ndarray, colidx: np.ndarray,
                 values: np.ndarray, dtype=jnp.float32):
        self.nrows, self.ncols = int(shape[0]), int(shape[1])
        counts = np.diff(rowptr).astype(np.int64)
        self._rows = jnp.asarray(np.repeat(
            np.arange(self.nrows, dtype=np.int32), counts))
        self._cols = jnp.asarray(colidx.astype(np.int32))
        self._vals = jnp.asarray(values.astype(np.float32))
        self._abs = jnp.abs(self._vals)
        self.dtype = dtype

    def _apply(self, vals, X, dtype):
        X = jnp.asarray(X)
        vec = X.ndim == 1
        X = X[:, None] if vec else X
        out = [_csr_product(self._rows, self._cols, vals,
                            X[:, j:j + MAX_BLOCK], nrows=self.nrows,
                            dtype=dtype)
               for j in range(0, X.shape[1], MAX_BLOCK)]
        Y = out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)
        return Y[:, 0] if vec else Y

    def apply(self, X):
        """A @ X at this reference's dtype, returned as float32."""
        return self._apply(self._vals, X, self.dtype)

    def rel_gap(self, Y, X) -> float:
        """The worst relative gap of answers ``Y`` to ``A @ X`` (see the
        module docstring), always against the float32 reference."""
        X = jnp.asarray(X)
        ref = self._apply(self._vals, X, jnp.float32)
        scale = self._apply(self._abs, jnp.abs(X), jnp.float32)
        Y = jnp.asarray(Y)
        if Y.shape != ref.shape:
            return float("inf")
        return float(_worst_gap(Y, ref, scale))
