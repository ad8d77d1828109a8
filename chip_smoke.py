#!/usr/bin/env python3
"""Chip smoke: drive the SPC5 main path once on a TPU and check every answer.

    python chip_smoke.py            # panels SpMV/SpMM, tall blocks,
                                    # serving; sharded if 4 chips are there
    python chip_smoke.py --chips 4  # only the sharded path, over 4 chips

One process, one chip owner. Phases (any failure exits non-zero):

  1. device  -- the first JAX device must be a TPU;
  2. panels  -- ``matgen.banded(2_000_000, 16, 0.75)`` as beta(1,8)
     (~24M nnz, ~100 MB of f32 values: larger than VMEM, so "auto" picks the
     panels layout) through ``ops.prepare`` defaults, then ``ops.spmv`` and
     ``ops.spmm`` (nvec=8), repeated at ``vdtype`` bf16 and int8;
  3. tall blocks -- ``matgen.banded(200_000, 16, 0.75)`` as beta(2,4) and
     beta(8,4), whose panel kernel rolls each row offset's sums down the
     y tile, through ``ops.spmv`` and ``ops.spmm`` (nvec=8);
  4. serving -- a pruned yi-6b vocab projection (64000 x 4096, density
     ~0.05) behind ``launch.server.start``; 64 concurrent requests coalesce
     into SpMM batches; the degradation ladder is off and every counter of
     it must read 0;
  5. sharded -- the phase-2 matrix sharded over a 4-chip mesh
     (``distributed.shard_matrix`` defaults), one SpMV through ``ops.spmv``:
     the panels mask kernel on every chip, y all-gathered; compared with
     the reference and with the single-device plan. Skipped, with a
     message, where fewer than 4 chips are present; ``--chips 4`` runs
     only this phase.

Every result is compared with the plain f32 jnp CSR reference
(``ref_spmv.csr_operator``) under an elementwise bound: f32 reassociation
(2 * nnz_row * 2^-24 * |A||x|) plus, for quantised stores, the value-dtype
contract ``tests/test_vdtype.py`` pins.

Times printed are smoke timings of one call, not benchmarks. The last line
of stdout is the JSON result; nothing is printed there unless every phase
passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

SEED = 0


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check_tpu():
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found platform {d0.platform!r} "
              f"({d0.device_kind}, {len(devs)} device(s))", file=sys.stderr)
        sys.exit(2)
    print(f"device: {d0.platform} kind={d0.device_kind!r} count={len(devs)}",
          flush=True)
    return devs


def import_repro():
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    sys.path.insert(0, src)
    try:
        from repro.launch import compile_cache
    except ImportError as e:
        fail(f"the repro package is not importable from {src}: {e}")
    print(f"compile cache: {compile_cache.enable()}", flush=True)


class Reference:
    """The f32 CSR reference plus the |A| and pattern operators its error
    bounds need, all on the device."""

    def __init__(self, csr):
        import numpy as np
        from repro.core import ref_spmv as R
        self._apply = R.csr_operator(csr)
        self._abs_apply = R.csr_operator(
            dataclasses.replace(csr, values=np.abs(csr.values)))
        self._pattern_apply = R.csr_operator(
            dataclasses.replace(csr, values=np.ones_like(csr.values)))
        self.nnz_row = int(np.diff(csr.rowptr).max())
        self.amax = float(np.abs(csr.values).max())

    @staticmethod
    def _columns(op, x):
        """One SpMV per column: a multi-vector gather of every nonzero
        would hold nnz x 128 lanes of HBM."""
        import jax.numpy as jnp
        if x.ndim == 1:
            return op(x)
        return jnp.stack([op(x[:, j]) for j in range(x.shape[1])], axis=1)

    def apply(self, x):
        return self._columns(self._apply, x)

    def abs_apply(self, x):
        return self._columns(self._abs_apply, x)

    def check(self, y, x, vdtype: str, label: str) -> None:
        import jax.numpy as jnp
        import numpy as np
        ref = self.apply(x)
        ax = self.abs_apply(jnp.abs(x))
        bound = 2.0 * self.nnz_row * 2.0 ** -24 * ax + 1e-6
        if vdtype == "bf16":
            bound = bound + 2.0 ** -7 * ax + 1e-5
        elif vdtype == "int8":
            bound = bound + (0.5 * self.amax / 127.0
                             * self._columns(self._pattern_apply, jnp.abs(x))
                             + 1e-5)
        if y.shape != ref.shape or y.dtype != jnp.float32:
            fail(f"{label}: got {y.shape} {y.dtype}, want {ref.shape} "
                 f"float32")
        err = jnp.abs(y - ref)
        worst = float(jnp.max(err - bound))
        if not bool(jnp.all(jnp.isfinite(y))) or worst > 0:
            fail(f"{label}: outside the {vdtype} bound by {worst:.3e}")
        print(f"  {label}: ok (max |y - ref| = "
              f"{float(np.asarray(jnp.max(err))):.3e})", flush=True)


def timed_call(fn, *args):
    """First call (compiles), then one more call timed to completion."""
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def panels_matrix():
    import numpy as np
    from repro.core import formats as F
    from repro.core import matgen
    t0 = time.perf_counter()
    csr = matgen.banded(2_000_000, 16, 0.75, seed=SEED)
    csr = dataclasses.replace(csr, values=csr.values.astype(np.float32))
    mat = F.csr_to_spc5(csr, 1, 8)
    print(f"panels matrix: {csr.shape} nnz={csr.nnz} blocks={mat.nblocks} "
          f"values={mat.values.nbytes / 1e6:.1f} MB "
          f"(host build {time.perf_counter() - t0:.1f} s)", flush=True)
    return csr, mat


def phase_panels(csr, mat) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    ref = Reference(csr)
    key = jax.random.PRNGKey(SEED)
    x = jax.random.normal(key, (csr.ncols,), jnp.float32)
    X = jax.random.normal(jax.random.fold_in(key, 1), (csr.ncols, 8),
                          jnp.float32)
    for vdtype in ("f32", "bf16", "int8"):
        t0 = time.perf_counter()
        plan = (ops.prepare(mat) if vdtype == "f32"
                else ops.prepare(mat, vdtype=vdtype))
        build_s = time.perf_counter() - t0
        if plan.layout != "panels":
            fail(f"prepare picked {plan.layout} on a TPU")
        y, t_spmv = timed_call(lambda v: ops.spmv(plan, v), x)
        Y, t_spmm = timed_call(lambda v: ops.spmm(plan, v), X)
        print(f"panels[{vdtype}]: layout={plan.layout} "
              f"npanels={plan.npanels} "
              f"nchunks={plan.nchunks} plan build {build_s:.2f} s; smoke "
              f"timing (one call, not a benchmark): spmv {t_spmv * 1e3:.1f} "
              f"ms, spmm(nvec=8) {t_spmm * 1e3:.1f} ms", flush=True)
        ref.check(y, x, vdtype, f"spmv[{vdtype}]")
        ref.check(Y, X, vdtype, f"spmm[{vdtype}]")
        del plan, y, Y
        gc.collect()


def phase_tall_blocks() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import formats as F
    from repro.core import matgen
    from repro.kernels import ops
    csr = matgen.banded(200_000, 16, 0.75, seed=SEED)
    csr = dataclasses.replace(csr, values=csr.values.astype(np.float32))
    ref = Reference(csr)
    key = jax.random.PRNGKey(SEED + 2)
    x = jax.random.normal(key, (csr.ncols,), jnp.float32)
    X = jax.random.normal(jax.random.fold_in(key, 1), (csr.ncols, 8),
                          jnp.float32)
    for rc in ((2, 4), (8, 4)):
        plan = ops.prepare(F.csr_to_spc5(csr, *rc))
        if plan.layout != "panels":
            fail(f"prepare picked {plan.layout} on a TPU")
        label = f"beta({rc[0]},{rc[1]})"
        print(f"tall blocks {label}: npanels={plan.npanels} "
              f"nchunks={plan.nchunks}", flush=True)
        ref.check(ops.spmv(plan, x), x, "f32", f"spmv[{label}]")
        ref.check(ops.spmm(plan, X), X, "f32", f"spmm[{label}]")
        del plan
        gc.collect()


def phase_serving() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import formats as F
    from repro.core import matgen
    from repro.launch import server as SV
    t0 = time.perf_counter()
    # yi-6b's vocab projection (configs/yi_6b.py: vocab 64000, d_model
    # 4096); pruned_weight keeps half of the (1, 8) tiles it turns on, so
    # density 0.025 gives ~5% nonzeros
    csr = matgen.pruned_weight(64000, 4096, 0.025, (1, 8), seed=SEED)
    csr = dataclasses.replace(csr, values=csr.values.astype(np.float32))
    mat = F.csr_to_spc5(csr, 1, 8)
    print(f"serving matrix: {csr.shape} nnz={csr.nnz} "
          f"density={csr.nnz / (csr.nrows * csr.ncols):.4f} "
          f"(host build {time.perf_counter() - t0:.1f} s)", flush=True)
    ref = Reference(csr)
    config = SV.ServeConfig(no_degrade=True, max_batch=8)
    server = SV.start(config, mat=mat)
    try:
        plan = server.plan
        print(f"serving plan: layout={plan.layout}", flush=True)
        xs = jax.random.normal(jax.random.PRNGKey(SEED + 1), (64, csr.ncols),
                               jnp.float32)
        futures = [server.submit(xs[i]) for i in range(xs.shape[0])]
        ys = [f.result(timeout=900) for f in futures]
        ref.check(jnp.stack(ys, axis=1), xs.T, "f32", "served answers")
        st = server.stats()
    finally:
        server.close()
    print(f"serving: requests={st['requests']} batches={st['batches']} "
          f"widest={st['widest_batch']} coalesced={st['coalesced']} "
          f"degraded={st['degraded']} cache_degraded="
          f"{st['cache']['degraded']} worker_restarts="
          f"{st['worker_restarts']}", flush=True)
    if st["requests"] != xs.shape[0]:
        fail(f"served {st['requests']} of {xs.shape[0]} requests")
    if st["coalesced"] == 0 or st["widest_batch"] < 2:
        fail("no request was coalesced: the SpMM kernel was not driven")
    for name, v in (("server degraded", st["degraded"]),
                    ("cache degraded", st["cache"]["degraded"]),
                    ("worker_restarts", st["worker_restarts"])):
        if v:
            fail(f"{name} = {v}")


def phase_sharded(devs, csr, mat) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import distributed as D
    from repro.kernels import ops
    ndev = len(devs)

    def in_use():
        return [d.memory_stats()["bytes_in_use"] for d in devs]

    before = in_use()
    mesh = Mesh(np.asarray(devs), ("data",))
    sh = D.shard_matrix(mat, ndev, mesh=mesh)
    gc.collect()
    grown = [a - b for a, b in zip(in_use(), before)]
    share = sum(int(a.nbytes) for a in sh.arrays) / ndev
    print(f"sharded: layout={sh.layout} "
          f"bytes per device {grown} (even share {share:.0f})", flush=True)
    if sh.layout != "panels":
        fail(f"shard_matrix picked {sh.layout} on a TPU")
    if min(grown) < 0.5 * share:
        fail(f"slabs are not spread over the {ndev} devices: {grown}")
    x = jax.random.normal(jax.random.PRNGKey(SEED), (csr.ncols,),
                          jnp.float32)
    y, t = timed_call(lambda v: ops.spmv(sh, v), x)
    print(f"sharded spmv: smoke timing (one call, not a benchmark) "
          f"{t * 1e3:.1f} ms", flush=True)
    ref = Reference(csr)
    ref.check(y, x, "f32", f"sharded spmv ({ndev} devices) vs reference")
    plan = ops.prepare(mat)
    y1 = ops.spmv(plan, x)
    ref.check(y1, x, "f32", "single-device plan vs reference")
    gap = float(jnp.max(jnp.abs(y - y1) - 4.0 * ref.nnz_row * 2.0 ** -24
                        * ref.abs_apply(jnp.abs(x)) - 1e-6))
    if gap > 0:
        fail(f"sharded and single-device results differ by {gap:.3e} over "
             f"the f32 bound")
    print("  sharded vs single-device plan: ok", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded path over 4 chips")
    args = ap.parse_args(argv)
    all_devs = check_tpu()
    if len(all_devs) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, JAX found "
             f"{len(all_devs)}")
    import_repro()
    t0 = time.perf_counter()
    csr, mat = panels_matrix()
    if args.chips == 1:
        phase_panels(csr, mat)
        phase_tall_blocks()
        phase_serving()
    if len(all_devs) >= 4:
        phase_sharded(all_devs[:4], csr, mat)
    else:
        print(f"sharded: skipped (needs 4 chips, JAX found "
              f"{len(all_devs)})", flush=True)
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s",
          flush=True)
    d0 = all_devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(all_devs)}}), flush=True)


if __name__ == "__main__":
    main()
