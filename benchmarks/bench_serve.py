"""Serving-tier bench: open-loop Poisson traffic swept to saturation.

Two sections in ``benchmarks.run``:

``spmv_serve`` -- a pruned-weight vocab-projection matrix is served
through ``repro.launch.server``: plan cache (hit demonstrated on the
second warm build), request coalescing (bit-exactness vs per-request
SpMV asserted every run), then an open-loop sweep over offered QPS
recording p50/p99 latency and achieved throughput. Each QPS point prints
a ``gflops=`` CSV line (completed-request FLOP rate), so the section
aggregates under the CI perf-regression gate exactly like the kernel
benches; the saturation line records the peak achieved QPS.

``spmv_serve_overload`` (:func:`overload`) -- the admission-control
story: the same tier driven at 2x its measured saturation QPS with a
bounded pending queue. Each window records the shed rate, the p99
latency of the requests that WERE admitted, and the completed-request
gflops -- the gate metric, so a regression that makes overload sheds
spill into latency (or collapse throughput) fails CI. An overloaded tier
is supposed to shed early and keep the admitted tail flat, not queue
unboundedly.
"""
from __future__ import annotations

from typing import List

import numpy as np

SEED = 0


def run(quick: bool = True) -> List[str]:
    import jax.numpy as jnp

    from repro import obs
    from repro.core import formats as F, matgen, plan as P
    from repro.launch import server as SV

    dim, density = (1024, 0.05) if quick else (4096, 0.02)
    qps_points = [50, 100, 200, 400, 800] if quick else [
        100, 200, 400, 800, 1600, 3200]
    duration_s = 0.4 if quick else 1.0

    csr = matgen.pruned_weight(dim, dim // 2, density, (1, 8), seed=SEED)
    mat = F.csr_to_spc5(csr, 1, 8)
    request = dict(layout="panels", pr=256, xw=64, cb=32, tune=False)

    lines: List[str] = []
    # attach the cache (and so the server) to the GLOBAL registry: the
    # runner's obs snapshot artifact then carries every serving counter,
    # latency histogram, and span this section produced
    cache = SV.PlanCache(capacity_bytes=64 << 20, verify_on_admit=True,
                         registry=obs.get_registry())
    plan = cache.get_or_build(mat, **request)
    cache.get_or_build(mat, **request)      # the warm path: must hit
    st = cache.stats()
    lines.append(f"spmv_serve.plan_cache.{dim},0.0,"
                 f"hits={st['hits']};misses={st['misses']};"
                 f"evictions={st['evictions']};"
                 f"hit_rate={st['hit_rate']:.2f}")
    assert st["hits"] > 0, "plan cache never hit on the warm build"

    srv = SV.SPC5Server(plan, cache=cache, window_us=2000, max_batch=64)
    rng = np.random.default_rng(SEED)
    xs = [jnp.asarray(rng.standard_normal(mat.shape[1]), jnp.float32)
          for _ in range(16)]
    with srv:
        # coalescing parity: concurrent submits vs lone per-request SpMV
        futs = [srv.submit(x) for x in xs]
        ys = [f.result(timeout=60) for f in futs]
        bit = all(np.array_equal(np.asarray(y),
                                 np.asarray(P.execute_spmv(plan, x)))
                  for y, x in zip(ys, xs))
        assert bit, "coalesced SpMM diverged from per-request SpMV"
        lines.append(f"spmv_serve.coalesce_parity.{dim},0.0,"
                     f"bitexact={int(bit)};"
                     f"widest_batch={srv.widest_batch}")

        peak = None
        for qps in qps_points:
            res = SV.open_loop(srv, xs, qps, duration_s=duration_s,
                               seed=SEED)
            gf = 2.0 * csr.nnz * res["completed"] / res["elapsed_s"] / 1e9
            lines.append(
                f"spmv_serve.openloop.{dim}.qps{qps},"
                f"{res['p50_us']:.1f},gflops={gf:.4f};"
                f"p99={res['p99_us']:.1f};"
                f"achieved={res['qps_achieved']:.1f}")
            if peak is None or res["qps_achieved"] > peak["qps_achieved"]:
                peak = res
        gf = 2.0 * csr.nnz * peak["qps_achieved"] / 1e9
        lines.append(f"spmv_serve.saturation.{dim},"
                     f"{peak['p50_us']:.1f},gflops={gf:.4f};"
                     f"peak_qps={peak['qps_achieved']:.1f};"
                     f"p99={peak['p99_us']:.1f}")
        st = srv.stats()
        lines.append(f"spmv_serve.coalescing.{dim},0.0,"
                     f"batches={st['batches']};"
                     f"mean_batch={st['mean_batch']:.2f};"
                     f"widest_batch={st['widest_batch']}")
    return lines


def overload(quick: bool = True) -> List[str]:
    """Drive the tier at 2x saturation with a bounded pending queue.

    Probes the saturation QPS with a short doubling sweep, then runs
    ``windows`` independent open-loop windows at 2x that rate against a
    server with ``max_pending`` admission control. Per-window lines carry
    shed_rate and admitted-p99 alongside the ``gflops=`` gate metric.
    """
    import jax.numpy as jnp

    from repro import obs
    from repro.core import formats as F, matgen
    from repro.launch import server as SV

    dim, density = (1024, 0.05) if quick else (4096, 0.02)
    probe_s = 0.2 if quick else 0.5
    window_s = 0.3 if quick else 1.0
    windows = 5
    max_pending = 8

    csr = matgen.pruned_weight(dim, dim // 2, density, (1, 8), seed=SEED)
    mat = F.csr_to_spc5(csr, 1, 8)
    request = dict(layout="panels", pr=256, xw=64, cb=32, tune=False)

    cache = SV.PlanCache(capacity_bytes=64 << 20, verify_on_admit=True,
                         registry=obs.get_registry())
    plan = cache.get_or_build(mat, **request)
    rng = np.random.default_rng(SEED)
    xs = [jnp.asarray(rng.standard_normal(mat.shape[1]), jnp.float32)
          for _ in range(16)]

    lines: List[str] = []
    # probe saturation on an UNBOUNDED server with the same coalescing
    # config as the overload windows: double offered QPS until achieved
    # stops improving (the plateau IS the capacity); warm the exec paths
    # first so the first probe window does not eat compilation
    with SV.SPC5Server(plan, cache=cache, window_us=2000,
                       max_batch=4) as srv:
        [f.result(timeout=60)
         for f in [srv.submit(x) for x in xs[:2]]]
        sat, qps = 1.0, 100.0
        for _ in range(8):
            ach = SV.open_loop(srv, xs, qps, duration_s=probe_s,
                               seed=SEED)["qps_achieved"]
            grew = ach > 1.15 * sat
            sat = max(sat, ach)
            if not grew:
                break
            qps *= 2.0
    offered = 2.0 * sat
    lines.append(f"spmv_serve_overload.saturation.{dim},0.0,"
                 f"sat_qps={sat:.1f};offered_qps={offered:.1f}")

    # overload server: same tier, but a TIGHT pending bound so the 2x
    # windows exercise admission control instead of queueing unboundedly
    srv = SV.SPC5Server(plan, cache=cache, window_us=2000, max_batch=4,
                        max_pending=max_pending)
    with srv:
        for i in range(windows):
            res = SV.open_loop(srv, xs, offered, duration_s=window_s,
                               seed=SEED + i)
            shed_rate = res["shed"] / max(res["submitted"], 1)
            gf = 2.0 * csr.nnz * res["completed"] / res["elapsed_s"] / 1e9
            lines.append(
                f"spmv_serve_overload.window.{dim}.w{i},"
                f"{res['p99_us']:.1f},gflops={gf:.4f};"
                f"shed_rate={shed_rate:.3f};"
                f"achieved={res['qps_achieved']:.1f};"
                f"errors={res['errors']}")
        st = srv.stats()
        lines.append(f"spmv_serve_overload.admission.{dim},0.0,"
                     f"shed={st['shed']};expired={st['expired']};"
                     f"max_pending={st['max_pending']};"
                     f"breaker={st['breaker']}")
    return lines
