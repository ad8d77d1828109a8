"""The HPCG sparse matrix: a 27-point stencil on an nx x ny x nz grid.

As the HPCG reference code builds it (Heroux, Dongarra & Luszczek, HPCG
Technical Specification, SAND2013-8752): one row per grid point, ordered
x fastest, then y, then z; a nonzero for every neighbour (and the point
itself) that lies inside the grid; 26 on the diagonal and -1 elsewhere.
An n^3 grid has (3n - 2)^3 nonzeros. The matrix does not depend on the
seed: only the right-hand side does (see the ``cg`` loop).
"""
from __future__ import annotations

import numpy as np

SEEDED = False


def generate(config: dict, seed: int):
    """CSR arrays ``(shape, rowptr int64, colidx int32, values float32)``
    of the stencil the config's ``nx``, ``ny``, ``nz`` describe; rows are
    sorted by column."""
    del seed
    nx, ny, nz = (int(config[k]) for k in ("nx", "ny", "nz"))
    n = nx * ny * nz
    idx = np.arange(n, dtype=np.int64)
    ix, iy, iz = idx % nx, (idx // nx) % ny, idx // (nx * ny)
    # the 27 offsets in increasing column order: dz, then dy, then dx
    offs = [(dz, dy, dx) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
            for dx in (-1, 0, 1)]
    valid = np.empty((n, 27), dtype=bool)
    cols = np.empty((n, 27), dtype=np.int32)
    for k, (dz, dy, dx) in enumerate(offs):
        valid[:, k] = ((ix + dx >= 0) & (ix + dx < nx) & (iy + dy >= 0)
                       & (iy + dy < ny) & (iz + dz >= 0) & (iz + dz < nz))
        cols[:, k] = idx + dx + nx * (dy + ny * dz)
    diag = np.array([o == (0, 0, 0) for o in offs])
    vals = np.where(diag, np.float32(26.0), np.float32(-1.0))
    rowptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(valid.sum(axis=1), out=rowptr[1:])
    colidx = cols[valid]
    values = np.broadcast_to(vals, (n, 27))[valid]
    return (n, n), rowptr, colidx, values
