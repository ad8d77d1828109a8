"""A magnitude-pruned weight matrix with tile-clustered nonzeros.

The same structure as the library's ``matgen.pruned_weight``: the matrix
is cut into ``block`` tiles, a tile is kept with probability ``4 *
tile_density``, and each kept tile keeps each of its entries with
probability one half, so about ``2 * tile_density`` of all entries are
nonzero. The pattern comes from the config's fixed ``pattern_seed``, as a
deployed checkpoint has one pattern; the values come from the run's seed.
"""
from __future__ import annotations

import numpy as np

SEEDED = True


def generate(config: dict, seed: int):
    """CSR arrays ``(shape, rowptr int64, colidx int32, values float32)``
    of the config's ``rows`` x ``cols`` pruned weight."""
    rows, cols = int(config["rows"]), int(config["cols"])
    br, bc = (int(v) for v in config["block"])
    density = float(config["tile_density"])
    rng = np.random.default_rng(int(config["pattern_seed"]))
    tile_on = rng.random((rows // br, cols // bc)) < min(1.0, density * 4)
    rr, cc = np.nonzero(tile_on)
    t, lr, lc = np.nonzero(rng.random((rr.shape[0], br, bc)) < 0.5)
    r = rr[t] * br + lr
    c = (cc[t] * bc + lc).astype(np.int32)
    if br > 1:      # tiles of several rows interleave rows: sort them
        order = np.lexsort((c, r))
        r, c = r[order], c[order]
    rowptr = np.zeros(rows + 1, dtype=np.int64)
    np.cumsum(np.bincount(r, minlength=rows), out=rowptr[1:])
    values = np.random.default_rng(seed).standard_normal(
        r.shape[0], dtype=np.float32)
    return (rows, cols), rowptr, c, values
