"""Paper Fig. 4: parallel SpMV with the block-balanced shard_map kernel.

Runs in the calling process, over every device it sees: a chip belongs to
one process, so the benchmark never hands the devices to a child. On a CPU
host, start the process with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for N devices. The
NUMA-analogue per-device array shards are exercised by construction
(shard_matrix places each row-interval's four arrays on its owning device).
"""
from __future__ import annotations

from typing import List, Optional, Sequence

from repro.core.selector import RecordStore

from .timing import time_fn


def run(quick: bool = False, store: Optional[RecordStore] = None,
        names: Optional[Sequence[str]] = None) -> List[str]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.core import distributed as D
    from repro.core import formats as F
    from repro.core import matgen
    from repro.core import selector as S

    if names is None:
        names = ["atmosmodd", "bone010", "pdb1HYS"] if quick else [
            "atmosmodd", "bone010", "pdb1HYS", "HV15R", "ldoor", "cage15"]
    devices = jax.devices()
    ndev = len(devices)
    mesh = Mesh(np.asarray(devices), ("data",))
    lines = []
    for name in names:
        csr = matgen.SET_A[name]()
        mat = F.csr_to_spc5(csr, 1, 8)
        feats = S.spc5_features(mat)
        x = jnp.asarray(
            np.random.default_rng(0).standard_normal(csr.shape[1]),
            jnp.float32)
        # pr sweep: None == flat whole-vector shards, else per-device row
        # panels (cb: 512 tuned for flat shards; panels keep their layout
        # default of 64 so the numbers compare with bench_spmv_seq's rows)
        for pr in (None, 1024):
            sh = D.shard_matrix(mat, ndev, cb=512 if pr is None else None,
                                mesh=mesh, pr=pr)
            spmv = D.make_distributed_spmv(sh, mesh)
            t = time_fn(lambda: spmv(x), iters=4, repeats=3)
            gf = 2.0 * csr.nnz / t / 1e9
            tag = "" if pr is None else f"_pr{pr}"
            lines.append(f"spmv_par.{name}.1x8_dev{ndev}{tag},{t * 1e6:.1f},"
                         f"gflops={gf:.3f}")
            if store is not None:
                # full-schema record for the auto-tuner (workers=ndev point)
                cfg = (S.PanelConfig("whole_vector", 0, 0, 512) if pr is None
                       else S.PanelConfig("panels", pr, 512, 64))
                store.add_measurement("1x8", feats, cfg, ndev, gf,
                                      matrix=name)
    return lines


if __name__ == "__main__":
    for line in run(quick=True):
        print(line)
