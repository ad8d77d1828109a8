"""Roofline analysis over the dry-run JSON records (assignment §Roofline),
plus the SpMV kernel-lowering bytes-per-nnz model (mask decode vs
build-time descriptors -- :func:`spmv_lowering_rows`; the descriptor
tables' extra index bytes are accounted so both lowerings' arithmetic
intensity is honest).

Three terms per (arch x shape x mesh), all PER-DEVICE (the SPMD module's
shapes are per-device):

    compute    = HLO_FLOPs / peak_FLOPs            (197 TF/s bf16, v5e)
    memory     = HLO_bytes / HBM_bw                (819 GB/s)
    collective = modeled_link_bytes / link_bw      (~50 GB/s per ICI link)

HLO_FLOPs/bytes come from the loop-aware HLO parser (repro.analysis.hlo) --
XLA's own cost_analysis counts while bodies once and is reported alongside
for reference. MODEL_FLOPS = 6*N*D (train; 6*N_active*D for MoE), 2*N*D
(prefill), per-token forward + cache reads (decode).

The reported score per cell:
    step_bound        = max(compute, memory, collective)  [perfect overlap]
    roofline_fraction = model_flops_per_device / peak / step_bound
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

from repro.analysis.peaks import V5E

PEAK_FLOPS = V5E["bf16_flops"]          # bf16 / chip
HBM_BW = V5E["hbm_bytes_per_s"]         # bytes/s
LINK_BW = 50e9            # bytes/s per ICI link (modelled, one direction)

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                          "dryrun")

# Avg-NNZ/block sample points for the SpMV lowering model: from near-empty
# blocks (the descriptor lowering's best case -- decode work dominates) to
# full fill (its worst -- the r*c-fold index tables dominate the bytes).
SPMV_AVG_POINTS = (1.5, 4.0, 8.0, 16.0, 32.0)
SPMV_BLOCKS = ((1, 8), (2, 4), (4, 4), (4, 8))
# Value-dtype axis for the lowering model: the bytes-per-nnz (and so the
# memory-bound gflops ceiling) shifts as the value store narrows while the
# index/mask bytes stay fixed -- the model quantifies how much of each
# lowering's stream quantisation actually removes.
SPMV_VDTYPES = ("f32", "bf16", "int8")


def spmv_lowering_rows(s_float: Optional[int] = None,
                       vdtype: str = "f32") -> List[Dict]:
    """Bytes-per-nnz + memory-bound ceilings of the SpMV kernels, per
    lowering (the descriptor tables' bytes are accounted, so these numbers
    stay honest for both variants -- same model the plan registry's
    lowering arbitration uses, ``formats.spmv_bytes_per_nnz``).

    ``vdtype`` sets the value itemsize ("f32" | "bf16" | "int8"); an
    explicit ``s_float`` overrides it (the legacy call shape)."""
    from repro.core import formats as F

    if s_float is None:
        s_float = F.value_itemsize(vdtype)
    rows = []
    for (r, c) in SPMV_BLOCKS:
        for avg in SPMV_AVG_POINTS:
            if avg > r * c:
                continue
            b_mask = F.spmv_bytes_per_nnz(r, c, avg, "mask", s_float=s_float)
            b_desc = F.spmv_bytes_per_nnz(r, c, avg, "descriptor",
                                          s_float=s_float)
            rows.append({
                "block": f"{r}x{c}", "avg": avg, "vdtype": vdtype,
                "bytes_nnz_mask": b_mask, "bytes_nnz_desc": b_desc,
                # 2 flops/nnz (mul+add) against the HBM stream: the
                # memory-bound gflops ceiling per lowering
                "gflops_mem_mask": 2.0 / b_mask * HBM_BW / 1e9,
                "gflops_mem_desc": 2.0 / b_desc * HBM_BW / 1e9,
            })
    return rows


def spmv_lowering_lines(s_float: Optional[int] = None,
                        vdtypes=SPMV_VDTYPES) -> List[str]:
    """CSV lines of :func:`spmv_lowering_rows` for the bench harness.

    f32 keeps the historical line names (the gate's priors); the quantised
    dtypes append a ``.bf16`` / ``.int8`` segment so they land as fresh
    sections, and every line carries a ``;vdtype=`` field."""
    lines = []
    for vd in vdtypes:
        for r in spmv_lowering_rows(s_float, vdtype=vd):
            suffix = "" if vd == "f32" else f".{vd}"
            lines.append(
                f"roofline.spmv_lowering.{r['block']}.avg{r['avg']:g}"
                f"{suffix},0,"
                f"bytes_mask={r['bytes_nnz_mask']:.2f};"
                f"bytes_desc={r['bytes_nnz_desc']:.2f};"
                f"gflops_mem_mask={r['gflops_mem_mask']:.1f};"
                f"gflops_mem_desc={r['gflops_mem_desc']:.1f};"
                f"vdtype={vd}")
    return lines


def load_cells(dryrun_dir: str = DRYRUN_DIR, tag: str = "") -> List[Dict]:
    cells = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("tag", "") != tag:
            continue
        cells.append(rec)
    return cells


def analyze_cell(rec: Dict) -> Optional[Dict]:
    if "skipped" in rec:
        return {"arch": rec["arch"], "shape": rec["shape"],
                "mesh": rec["mesh"], "skipped": rec["skipped"]}
    h = rec["hlo"]
    ndev = rec["n_devices"]
    compute = h["flops_per_device"] / PEAK_FLOPS
    memory = h["hbm_bytes_per_device"] / HBM_BW
    collective = h["coll_bytes_per_device"] / LINK_BW
    bound = max(compute, memory, collective)
    dominant = ("compute" if bound == compute
                else "memory" if bound == memory else "collective")
    model_flops_dev = rec["model_flops"] / ndev
    useful_ratio = model_flops_dev / max(h["flops_per_device"], 1.0)
    frac = model_flops_dev / PEAK_FLOPS / max(bound, 1e-12)
    fixes = {
        "compute": ("reduce recompute (remat policy / causal-block skipping) "
                    "to close the useful-FLOP gap"),
        "memory": ("fuse elementwise chains / drop f32 intermediates; a "
                   "Pallas fusion of the dominant block would cut HBM trips"),
        "collective": ("shrink TP degree or switch strategy (DP-only/ZeRO), "
                       "overlap collectives with compute"),
    }
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "kind": rec["kind"],
        "compute_s": compute, "memory_s": memory, "collective_s": collective,
        "bound_s": bound, "dominant": dominant,
        "model_flops": rec["model_flops"],
        "hlo_flops_per_device": h["flops_per_device"],
        "useful_flop_ratio": useful_ratio,
        "roofline_fraction": frac,
        "peak_gib": rec["memory"]["peak_bytes_per_device"] / 2**30,
        "coll_by_kind": h.get("coll_by_kind", {}),
        "fix": fixes[dominant],
        "knobs": {k: rec.get(k) for k in
                  ("remat", "kv_dtype", "fsdp", "seq_shard", "accum",
                   "tp_enabled")},
    }


def markdown_table(rows: List[Dict]) -> str:
    out = ["| arch | shape | mesh | compute s | memory s | coll s | bound | "
           "dominant | useful | roofline |",
           "|---|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if "skipped" in r:
            out.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                       f"SKIP | | | | | | |")
            continue
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['compute_s']:.3f} | {r['memory_s']:.3f} "
            f"| {r['collective_s']:.3f} | {r['bound_s']:.3f} "
            f"| {r['dominant']} | {r['useful_flop_ratio']:.2f} "
            f"| {r['roofline_fraction']*100:.1f}% |")
    return "\n".join(out)


def main(dryrun_dir: str = DRYRUN_DIR, tag: str = "", csv: bool = True):
    if csv:
        for line in spmv_lowering_lines():
            print(line)
    rows = [analyze_cell(rec) for rec in load_cells(dryrun_dir, tag)]
    rows = [r for r in rows if r is not None]
    order = {"pod16x16": 0, "pod2x16x16": 1}
    rows.sort(key=lambda r: (r["arch"], r["shape"], order.get(r["mesh"], 2)))
    if rows:        # nothing to report (and maybe no experiments/ dir) -> skip
        md = markdown_table(rows)
        out_path = os.path.join(dryrun_dir, "..", f"roofline{tag}.md")
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            f.write(md + "\n")
    if csv:
        for r in rows:
            if "skipped" in r:
                print(f"roofline.{r['arch']}.{r['shape']}.{r['mesh']},skip,0")
            else:
                print(f"roofline.{r['arch']}.{r['shape']}.{r['mesh']},"
                      f"{r['bound_s']*1e6:.1f},"
                      f"{r['roofline_fraction']*100:.2f}")
    return rows


if __name__ == "__main__":
    main()
