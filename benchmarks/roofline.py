"""Roofline analysis over the dry-run JSON records (assignment §Roofline).

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
