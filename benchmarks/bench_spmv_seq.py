"""Paper Fig. 3: sequential SpMV throughput per kernel per matrix.

MKL-CSR / CSR5 are unavailable offline; the baseline is a jnp CSR
(segment-sum) SpMV on the same data. Absolute GFlop/s on this CPU container
are NOT Skylake numbers -- the deliverable is the RELATIVE format comparison
and the records that feed the paper's selector (bench_selector.py) and the
(layout, pr, xw, cb) auto-tuner (``selector.tune``).

Three record-producing modes:

  * the main loop benches every kernel at the fixed default configs and
    tags records with the full config + matrix features, including the
    panel layout's locality stats (total real chunks = DMA windows, which
    land in the records' ``nchunks`` field);
  * ``sweep_matrix`` (the candidate-sweep mode, ``run(sweep=True)``)
    additionally measures a grid of candidate configurations per kernel so
    the tuner has per-config training data across the feature space;
  * ``bench_reorder`` measures every (reordering strategy x panel geometry)
    combination through the plan pipeline against the unreordered baseline
    on matrices where ordering matters (a scrambled banded matrix -- the
    classic RCM case -- and a genuinely scattered one), reporting pre/post
    bandwidth and chunk totals so BENCH artifacts show whether reordering
    shrank DMA traffic; every combination lands in the store with
    ``PanelConfig.reorder`` + the post features, so ``selector.tune``'s
    reorder signal covers the geometry grid, not just one default config.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import formats as F
from repro.core import matgen
from repro.core import selector as S
from repro.core.selector import PanelConfig, RecordStore
from repro.kernels import ops

from .timing import time_fn

KERNELS = [(1, 8), (2, 4), (2, 8), (4, 4), (4, 8), (8, 4)]

# Row-panel heights for the panel-tiled layout sweep (pr=0 rows, i.e. the
# whole-vector layout, is benched implicitly by the main loop). Records are
# tagged with pr so the selector can distinguish the layouts.
PANEL_PRS = (512, 2048)
PANEL_XW = 2048

# Candidate configurations for the sweep mode: the auto-tuner's training
# grid. Whole-vector chunk sizes bracket the default; panel configs span
# short/tall panels and narrow/wide x windows.
SWEEP_CONFIGS: Tuple[PanelConfig, ...] = (
    PanelConfig("whole_vector", 0, 0, 256),
    PanelConfig("whole_vector", 0, 0, 512),
    PanelConfig("panels", 256, 512, 64),
    PanelConfig("panels", 512, 2048, 64),
    PanelConfig("panels", 2048, 2048, 64),
    PanelConfig("panels", 512, 512, 32),
    # quantised value stores (v4 records): the tuner learns per-matrix
    # whether halving/quartering the value bytes pays on each layout
    PanelConfig("whole_vector", 0, 0, 512, vdtype="bf16"),
    PanelConfig("panels", 512, 2048, 64, vdtype="int8"),
)
SWEEP_KERNELS = ((1, 8), (4, 4))
# Sweep-mode matrix subset: one per structural class keeps the quick run
# minutes-scale while covering the feature space.
SWEEP_MATRICES = ("atmosmodd", "bone010", "ns3Da")

# Reorder bench: (strategy x geometry) x matrices, at geometries where
# per-panel x windows (not the cb cap) bound the chunking, so ordering
# actually moves the chunk count. "scrambled-band" is a banded matrix under
# a random symmetric permutation (reordering should win big); "ns3Da" is
# uniform random (strategies should decline rather than regress). Every
# combination goes through the plan pipeline and emits a record, so the
# tuner's reorder signal covers the geometry grid.
REORDER_STRATEGIES = ("none", "sigma", "rcm", "colwindow")
REORDER_MATRICES = {
    "scrambled-band": lambda: matgen.scrambled_banded(12_000, 8, 1.0,
                                                      seed=42),
    "ns3Da": matgen.SET_A["ns3Da"],
}
REORDER_RC = (1, 8)
REORDER_GEOMS: Tuple[PanelConfig, ...] = (
    PanelConfig("panels", 256, 512, 64),
    PanelConfig("panels", 512, 1024, 64),
    PanelConfig("panels", 256, 512, 32),
)


@functools.partial(jax.jit, static_argnames=("nrows",))
def csr_spmv(rowlen_rows, colidx, values, x, *, nrows):
    """Baseline CSR SpMV: gather + segment-sum (row ids precomputed)."""
    prod = values * x[colidx]
    return jax.ops.segment_sum(prod, rowlen_rows, num_segments=nrows)


def bench_matrix(name: str, csr, store: Optional[RecordStore] = None,
                 workers: int = 1) -> List[str]:
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(csr.shape[1]), jnp.float32)
    flops = 2.0 * csr.nnz
    lines = []
    # CSR baseline
    row_ids = jnp.asarray(np.repeat(np.arange(csr.nrows),
                                    np.diff(csr.rowptr)).astype(np.int32))
    colidx = jnp.asarray(csr.colidx)
    values = jnp.asarray(csr.values.astype(np.float32))
    t = time_fn(lambda: csr_spmv(row_ids, colidx, values, x,
                                 nrows=csr.nrows))
    gf_csr = flops / t / 1e9
    lines.append(f"spmv_seq.{name}.csr,{t*1e6:.1f},gflops={gf_csr:.3f}"
                 f";vdtype=f32")
    # same-dtype CSR baseline for the quantised kernels: bf16 values,
    # f32 accumulate (the gathered product promotes) -- so the _bf16/_int8
    # speedup lines compare against a baseline moving the same value bytes,
    # not the f32 one
    values_bf16 = values.astype(jnp.bfloat16)
    t = time_fn(lambda: csr_spmv(row_ids, colidx, values_bf16, x,
                                 nrows=csr.nrows))
    gf_csr_bf16 = flops / t / 1e9
    lines.append(f"spmv_seq.{name}.csr_bf16,{t*1e6:.1f},"
                 f"gflops={gf_csr_bf16:.3f};vdtype=bf16")
    for rc in KERNELS:
        mat = F.csr_to_spc5(csr, *rc)
        feats = S.spc5_features(mat)
        h = ops.prepare(mat, cb=512, dtype=np.float32, layout="whole_vector")
        t = time_fn(lambda: ops.spmv(h, x, use_pallas=False))
        gf = flops / t / 1e9
        kname = f"{rc[0]}x{rc[1]}"
        lines.append(f"spmv_seq.{name}.{kname},{t*1e6:.1f},"
                     f"gflops={gf:.3f};speedup_vs_csr={gf/gf_csr:.2f}"
                     f";vdtype=f32")
        if store is not None:
            store.add_measurement(kname, feats,
                                  PanelConfig("whole_vector", 0, 0, 512),
                                  workers, gf, matrix=name)
        if rc in ((1, 8), (2, 4)):
            # quantised value stores at the same geometry: speedups are
            # against the SAME-dtype CSR baseline (csr_bf16 above), with
            # the f32 ratio alongside so the bytes-saved win is visible
            for vd in ("bf16", "int8"):
                hq = ops.prepare(mat, cb=512, vdtype=vd,
                                 layout="whole_vector")
                tq = time_fn(lambda: ops.spmv(hq, x, use_pallas=False))
                gfq = flops / tq / 1e9
                lines.append(
                    f"spmv_seq.{name}.{kname}_{vd},{tq*1e6:.1f},"
                    f"gflops={gfq:.3f}"
                    f";speedup_vs_csr_bf16={gfq/gf_csr_bf16:.2f}"
                    f";vs_f32={gfq/gf:.2f};vdtype={vd}")
                if store is not None:
                    store.add_measurement(
                        kname, feats,
                        PanelConfig("whole_vector", 0, 0, 512, vdtype=vd),
                        workers, gfq, matrix=name)
        # row-panel-tiled layout sweep (bounded-VMEM path). Locality stats
        # ride along: nchunks_total counts the REAL (mask != 0) chunks --
        # the layout's DMA-window total, what reordering tries to shrink --
        # next to the padded grid dims; chunks_per_panel is its mean.
        for pr in PANEL_PRS:
            hp = ops.prepare(mat, layout="panels", pr=pr, cb=64,
                             xw=PANEL_XW, dtype=np.float32, tune=False)
            # real chunks straight off the built layout (mask==0 is padding)
            # -- no second pass-1 planner run
            nch_total = int(np.asarray(
                (hp.dev.chunk_mask != 0).any(axis=-1).sum()))
            tp = time_fn(lambda: ops.spmv(hp, x, use_pallas=False))
            gfp = flops / tp / 1e9
            lines.append(
                f"spmv_seq.{name}.{kname}_pr{pr},{tp*1e6:.1f},"
                f"gflops={gfp:.3f};panels={hp.npanels};chunks={hp.nchunks}"
                f";nchunks_total={nch_total}"
                f";chunks_per_panel={nch_total / max(hp.npanels, 1):.2f}"
                f";bandwidth={feats.bandwidth:.1f};vdtype=f32")
            if store is not None:
                store.add_measurement(
                    kname, feats, PanelConfig("panels", pr, PANEL_XW, 64),
                    workers, gfp, matrix=name, nchunks=nch_total)
        # paper's beta(r,c)_test variants for the small blocks
        if rc in ((1, 8), (2, 4)):
            ht = ops.prepare(mat, layout="test", cb=512, dtype=np.float32)
            tt = time_fn(lambda: ops.spmv_test(ht, x, use_pallas=False))
            gft = flops / tt / 1e9
            lines.append(
                f"spmv_seq.{name}.{kname}_test,{tt*1e6:.1f},"
                f"gflops={gft:.3f};singles={int(ht.n_single)};vdtype=f32")
            if store is not None:
                store.add_measurement(f"{kname}_test", feats,
                                      PanelConfig("whole_vector", 0, 0, 512),
                                      workers, gft, matrix=name)
    return lines


def sweep_matrix(name: str, csr, store: RecordStore,
                 kernels: Sequence[Tuple[int, int]] = SWEEP_KERNELS,
                 configs: Sequence[PanelConfig] = SWEEP_CONFIGS,
                 workers: int = 1, iters: int = 4) -> List[str]:
    """Candidate-sweep mode: measure every (kernel, config) candidate.

    This is the auto-tuner's training loop -- each measurement lands in the
    store with the full configuration and the matrix's features, so
    ``selector.tune`` can interpolate per-config throughput for unseen
    matrices. Configs are clamped to the matrix first (identical geometry
    after clamping is measured once).
    """
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(csr.shape[1]), jnp.float32)
    flops = 2.0 * csr.nnz
    lines = []
    for rc in kernels:
        mat = F.csr_to_spc5(csr, *rc)
        feats = S.spc5_features(mat)
        kname = f"{rc[0]}x{rc[1]}"
        seen = set()
        for cfg in configs:
            cfg = S.clamp_config(cfg, nrows=mat.nrows, ncols=mat.ncols,
                                 r=mat.r, c=mat.c, nblocks=mat.nblocks)
            if cfg in seen:
                continue
            seen.add(cfg)
            quant = cfg.vdtype in ("bf16", "int8")
            h = ops.prepare(mat, layout=cfg.layout, pr=cfg.pr or None,
                            xw=cfg.xw or None, cb=cfg.cb,
                            dtype=None if quant else np.float32,
                            vdtype=cfg.vdtype if quant else "auto",
                            tune=False)
            t = time_fn(lambda: ops.spmv(h, x, use_pallas=False), iters=iters)
            gf = flops / t / 1e9
            tag = (f"pr{cfg.pr}_xw{cfg.xw}_cb{cfg.cb}" if cfg.pr
                   else f"whole_cb{cfg.cb}")
            if quant:
                tag += f"_{cfg.vdtype}"
            lines.append(f"spmv_sweep.{name}.{kname}.{tag},{t*1e6:.1f},"
                         f"gflops={gf:.3f};vdtype={cfg.vdtype}")
            store.add_measurement(kname, feats, cfg, workers, gf, matrix=name)
    return lines


def bench_reorder(name: str, csr, store: Optional[RecordStore] = None,
                  workers: int = 1, iters: int = 4,
                  geoms: Sequence[PanelConfig] = REORDER_GEOMS) -> List[str]:
    """Reordering comparison over a (strategy x geometry) grid.

    One line per combination: throughput plus the pre/post locality metrics
    (mean element bandwidth and total panel chunks = DMA windows) at THAT
    geometry -- whether a permutation pays depends on the window/chunk
    shape, so each geometry gets its own accept/decline decision through
    the plan pipeline. Each result is checked against the unreordered
    baseline product, so a permutation-plumbing regression fails the bench
    rather than emitting wrong-but-fast numbers. Every combination lands in
    the store (``PanelConfig.reorder`` tags the strategy only when it
    actually applied, with the post-reorder features), so ``selector.tune``
    learns when reordering pays across the geometry grid, not just one
    default config.
    """
    from repro.core import structure as ST

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(csr.shape[1]), jnp.float32)
    flops = 2.0 * csr.nnz
    mat = F.csr_to_spc5(csr, *REORDER_RC)
    feats = S.spc5_features(mat)            # PRE-reorder tune coordinates
    kname = f"{REORDER_RC[0]}x{REORDER_RC[1]}"
    lines = []
    y_base = None
    for geo in geoms:
        pre = ST.profile(csr, blocks=(REORDER_RC,), r=mat.r, c=mat.c,
                         pr=geo.pr, xw=geo.xw, cb=geo.cb)
        gtag = f"pr{geo.pr}_xw{geo.xw}_cb{geo.cb}"
        for strat in REORDER_STRATEGIES:
            h = ops.prepare(mat, layout="panels", pr=geo.pr, xw=geo.xw,
                            cb=geo.cb, dtype=np.float32, tune=False,
                            reorder=None if strat == "none" else strat)
            t = time_fn(lambda: ops.spmv(h, x, use_pallas=False),
                        iters=iters)
            gf = flops / t / 1e9
            y = np.asarray(ops.spmv(h, x, use_pallas=False))
            if y_base is None:
                y_base = y
            else:
                np.testing.assert_allclose(y, y_base, atol=1e-3, rtol=1e-4)
            if h.is_reordered:
                st = h.stats
                applied = 1
                bw_post = float(st.get("bw_post", 0.0))
                nch_post = int(st.get("nchunks_post", 0))
            else:
                applied = 0
                bw_post = pre.bandwidth_mean
                nch_post = pre.nchunks_total
            lines.append(
                f"spmv_reorder.{name}.{kname}.{strat}.{gtag},{t*1e6:.1f},"
                f"gflops={gf:.3f};applied={applied}"
                f";bw_pre={pre.bandwidth_mean:.1f};bw_post={bw_post:.1f}"
                f";nchunks_pre={pre.nchunks_total};nchunks_post={nch_post}"
                f";vdtype=f32")
            if store is not None:
                cfg = PanelConfig("panels", geo.pr, geo.xw, geo.cb,
                                  reorder=strat if applied else "")
                store.add_measurement(kname, feats, cfg, workers, gf,
                                      matrix=name, bandwidth_post=bw_post,
                                      nchunks=nch_post)
    return lines


def run(quick: bool = False, store: Optional[RecordStore] = None,
        sweep: bool = False, sweep_store: Optional[RecordStore] = None):
    """``sweep_store`` receives the candidate-sweep records; it defaults to
    ``store`` but callers that later fit the paper's per-kernel predictors
    on ``store`` (bench_selector) should pass a separate one -- those
    predictors key only on (kernel, workers, pr) and would otherwise mix
    the sweep's alternative chunk sizes into one curve."""
    names = list(matgen.SET_A)
    if quick:
        names = ["atmosmodd", "bone010", "kron_g500-logn21", "pdb1HYS",
                 "Dense-800", "ns3Da"]
    lines = []
    for name in names:
        csr = matgen.SET_A[name]()
        lines.extend(bench_matrix(name, csr, store=store))
        if sweep and store is not None and name in SWEEP_MATRICES:
            lines.extend(sweep_matrix(name, csr, sweep_store or store))
    for name, make in REORDER_MATRICES.items():
        lines.extend(bench_reorder(name, make(), store=store))
    return lines


if __name__ == "__main__":
    for line in run(quick=True):
        print(line)
