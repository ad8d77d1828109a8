"""Batched serving launcher (TP-sharded weights, greedy decode).

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --batch 4 \
        --tokens 32 [--mesh 1x4] [--kv-dtype int8]

Every knob is a :class:`repro.launch.server.ServeConfig` field -- the
argparse flags below are GENERATED from the dataclass
(``server.add_config_args``), so the CLI and the programmatic
``server.start(config)`` path share one configuration surface (the
``serve-config-knobs`` lint rule enforces it).

SPC5 integration: ``--records`` points at a benchmark record store
(JSON/JSONL file or directory, e.g. the CI ``benchmarks/records/``
artifact) and installs it as the selector's default store, so any sparse
layer built in-process gets an auto-tuned (layout, pr, xw, cb).
``--vocab-spmv DENSITY`` additionally benches a magnitude-pruned
SparseLinear vocab projection at decode shape (batch 1-vector SpMV) using
the tuned configuration; ``--panel pr,xw,cb`` is the explicit escape hatch
that overrides the tuner for that bench, ``--reorder STRATEGY``
(sigma / rcm / colwindow / auto) permutes the pruned weight through the
reordering subsystem (repro.core.reorder) before the layout is built --
the layer's call signature is unchanged, the permutation is internal.
Every layout runs the bit-mask decode; the layout alone picks the kernel.
``--vdtype f32|bf16|int8|auto`` picks the stored value dtype
(quantised stores halve/quarter the value bytes and accumulate in f32).
Adding ``--qps RATE`` routes the vocab bench through the
persistent serving tier instead: plan cache, request coalescing, and an
open-loop Poisson traffic run (``repro.launch.server``).
"""
from __future__ import annotations

import argparse
import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.launch import compile_cache
from repro.launch import server as SV


def main(argv=None):
    ap = argparse.ArgumentParser()
    SV.add_config_args(ap)
    args = ap.parse_args(argv)
    config = SV.config_from_args(args)
    compile_cache.enable()
    # with --metrics the whole run is profiled: the obs spans are mirrored
    # into the trace, beside the device's ops, on one clock
    with (jax.profiler.trace(config.trace_path) if config.metrics
          else contextlib.nullcontext()):
        _serve(config)
    if config.metrics:
        # one scrape covers the whole launcher: decode span, serving-tier
        # counters/histograms, plan passes -- all on the global registry
        obs.export.dump_prometheus(obs.get_registry(), config.metrics_path)
        print(f"metrics: {config.metrics_path} (Prometheus), "
              f"{config.trace_path} (profiler trace)")


def _serve(config: SV.ServeConfig) -> None:
    from repro.core import selector as S
    if config.records:
        store = S.load_records(config.records)
        if config.verify:
            from repro.analysis.verify import verify_records
            print(verify_records(store).summary())
        S.set_default_store(store)

    from jax.sharding import Mesh
    from repro.configs import get_smoke_config
    from repro.models import model as MD
    from repro.sharding.rules import make_rules
    from repro.train.step import make_serve_step

    devs = jax.devices()
    rules = None
    if config.mesh:
        d, m = (int(x) for x in config.mesh.split("x"))
        mesh = Mesh(np.asarray(devs[:d * m]).reshape(d, m),
                    ("data", "model"))
        rules = make_rules(mesh, fsdp=False, seq_shard=False)

    import dataclasses
    cfg = dataclasses.replace(get_smoke_config(config.arch), dtype="float32")
    if cfg.is_encdec:
        raise SystemExit("enc-dec serving path: see tests/test_models.py")
    params = MD.init_params(cfg, jax.random.PRNGKey(0))
    cache = MD.init_cache(cfg, config.batch, config.tokens,
                          kv_dtype=config.kv_dtype)
    if rules is not None:
        params = jax.device_put(params, rules.param_shardings(params))
        cache = jax.device_put(cache, rules.cache_shardings(cache))
    step = jax.jit(make_serve_step(cfg, rules), donate_argnums=(1,))

    tok = jnp.zeros((config.batch, 1), jnp.int32)
    outs = []
    with obs.span("serve.decode", arch=config.arch, batch=config.batch,
                  tokens=config.tokens) as sp:
        for t in range(config.tokens - 1):
            tok, cache = step(params, cache, tok, jnp.asarray(t))
            outs.append(np.asarray(tok))
        jax.block_until_ready(tok)
    dt = sp.duration_s
    print(f"{config.arch}: {config.batch}x{config.tokens} tokens, "
          f"{config.batch * (config.tokens - 1) / dt:.1f} tok/s "
          f"(kv={config.kv_dtype}, mesh={config.mesh or '1 device'})")

    if config.vocab_spmv > 0 and config.qps > 0:
        _serve_vocab(config, cfg)
    elif config.vocab_spmv > 0:
        _bench_vocab(config, cfg)


def _serve_vocab(config: SV.ServeConfig, cfg) -> None:
    """The persistent-tier path: plan cache + coalescing + open-loop
    Poisson traffic at ``--qps`` (records already installed above)."""
    srv = SV.start(config, install_records=False)
    rng = np.random.default_rng(0)
    xs = [jnp.asarray(rng.standard_normal(cfg.d_model), jnp.float32)
          for _ in range(8)]
    with srv:
        res = SV.open_loop(srv, xs, config.qps,
                           duration_s=config.duration_s)
        st = srv.stats()
    c = st["cache"]
    print(f"vocab_serve[{cfg.vocab}x{cfg.d_model}@{config.vocab_spmv}]: "
          f"offered={res['qps_offered']:.0f}qps "
          f"achieved={res['qps_achieved']:.0f}qps "
          f"p50={res['p50_us']:.0f}us p99={res['p99_us']:.0f}us "
          f"shed={res['shed']} expired={res['expired']} "
          f"errors={res['errors']} "
          f"(batches={st['batches']}, mean_batch={st['mean_batch']:.1f}, "
          f"degraded={st['degraded']}, restarts={st['worker_restarts']}, "
          f"cache {c['hits']}h/{c['misses']}m/{c['evictions']}e)")
    fr = obs.faults.get_faults()
    if fr:
        print("faults: " + ", ".join(
            f"{name}@{s['rate']:g} {s['fired']}/{s['checks']}"
            for name, s in fr.stats().items()))


def _bench_vocab(config: SV.ServeConfig, cfg) -> None:
    """The original closed-loop microbench (``--qps`` left at 0)."""
    from repro.core.sparse_linear import SparseLinear
    kw = {}
    if config.panel:
        pr, xw, cb = (int(v) for v in config.panel.split(","))
        kw = dict(layout="panels", pr=pr, xw=xw, cb=cb)
    if config.reorder:
        kw["reorder"] = config.reorder
    kw["vdtype"] = config.vdtype
    rng = np.random.default_rng(0)
    w = rng.standard_normal((cfg.vocab, cfg.d_model)).astype(np.float32)
    dtype = np.float32 if config.vdtype == "auto" else None
    lin = SparseLinear.from_dense(w, density=config.vocab_spmv,
                                  dtype=dtype, nvec=1, **kw)
    x = jnp.asarray(rng.standard_normal(cfg.d_model), jnp.float32)
    h = lin.handle
    if config.verify:
        # plan-cache admission gate: prove the plan's invariants before
        # the first request touches it (raises on any violation)
        from repro.analysis.verify import verify_plan
        report = verify_plan(h, nvec=1).raise_if_failed()
        print(f"verify: plan ok ({len(report.checked)} rules checked)")
    lin(x).block_until_ready()
    iters = 16
    with obs.span("serve.vocab_bench", iters=iters) as sp:
        for _ in range(iters):
            y = lin(x)
        y.block_until_ready()
    us = sp.duration_s / iters * 1e6
    # the plan is self-describing: layout key + geometry from its static
    # meta, reordering from its pass trace -- no layout branching here
    if h.is_reordered:
        reo_str = (f", reorder={h.strategy}"
                   f"[fused_rows={int(h.rows_fused)}]")
    elif config.reorder:
        reo_str = f", reorder={config.reorder}[declined]"
    else:
        reo_str = ""
    cfg_str = ",".join(f"{k}={v}" for k, v in h.meta
                       if k in ("pr", "xw", "cb", "vdtype") and
                       v != "")
    src = ("explicit --panel" if config.panel
           else ("tuned" if config.records else "defaults"))
    print(f"vocab_spmv[{cfg.vocab}x{cfg.d_model}@{config.vocab_spmv}]: "
          f"{us:.1f} us/call ({h.layout}, {cfg_str}, config={src}"
          f"{reo_str})")


if __name__ == "__main__":
    main()
