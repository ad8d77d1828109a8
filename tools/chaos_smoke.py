#!/usr/bin/env python
"""End-to-end chaos smoke for the SPC5_FAULTS environment arming path.

The resilience suite (tests/test_resilience.py) arms its own fault
registries programmatically; this script is the one consumer that goes
through the REAL deployment path -- ``SPC5_FAULTS`` in the environment,
armed once at ``repro.obs.faults`` import -- and then proves the serving
tier's contract under it: every request either lands with the correct
result (checked against a suppressed-injection oracle) or fails with a
catalogued resilience error. CI runs it with every fault point pinned at
a 10% rate and fixed seeds, so a failure replays bit-identically with
the same spec string.

Exit status: 0 on contract held, 1 otherwise.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> int:
    import numpy as np

    import jax.numpy as jnp

    from repro import obs
    from repro.core import formats as F, matgen, plan as P
    from repro.launch import resilience, server as SV

    faults = obs.faults.get_faults()
    if not faults:
        print("chaos_smoke: SPC5_FAULTS is not set or armed nothing; "
              "this smoke only means something under injection",
              file=sys.stderr)
        return 1
    print(f"chaos_smoke: armed points = {list(faults.points)}")

    csr = matgen.pruned_weight(256, 128, 0.1, (1, 8), seed=0)
    mat = F.csr_to_spc5(csr, 1, 8)
    cache = SV.PlanCache(capacity_bytes=16 << 20, verify_on_admit=True)
    # plan.build / cache.admit chaos: the ladder must still land a plan
    plan = cache.get_or_build(mat, layout="panels", pr=64, xw=16, cb=32,
                              tune=False)

    rng = np.random.default_rng(0)
    xs = [jnp.asarray(rng.standard_normal(mat.shape[1]), jnp.float32)
          for _ in range(8)]
    with faults.suppress():
        refs = [np.asarray(P.execute_spmv(plan, x, use_pallas=False))
                for x in xs]

    ok = failed = 0
    with SV.SPC5Server(plan, cache=cache, window_us=500,
                       max_batch=4) as srv:
        futs = [srv.submit(xs[i % len(xs)]) for i in range(32)]
        for i, f in enumerate(futs):
            try:
                y = np.asarray(f.result(timeout=120))
            except (resilience.ShedError,
                    resilience.DeadlineExceededError,
                    resilience.CircuitOpenError,
                    obs.faults.FaultError):
                failed += 1
                continue
            if not np.allclose(y, refs[i % len(xs)], rtol=1e-5, atol=1e-5):
                print(f"chaos_smoke: request {i} diverged from the oracle",
                      file=sys.stderr)
                return 1
            ok += 1
        st = srv.stats()

    print(f"chaos_smoke: ok={ok} failed={failed} degraded={st['degraded']} "
          f"restarts={st['worker_restarts']} breaker={st['breaker']}")
    for point, ps in faults.stats().items():
        print(f"chaos_smoke:   {point}: checks={ps['checks']} "
              f"fired={ps['fired']} (rate={ps['rate']}, seed={ps['seed']})")
    if ok == 0:
        print("chaos_smoke: no request landed; the ladder never recovered",
              file=sys.stderr)
        return 1
    print("chaos_smoke: contract held (every landed result matched the "
          "oracle)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
