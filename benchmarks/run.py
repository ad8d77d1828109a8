"""Benchmark harness: one section per paper table/figure + the LM substrate.

Prints ``name,us_per_call,derived`` CSV lines (one per measurement).
``--quick`` runs a representative subset (a few minutes on CPU);
``--full`` runs every Set-A/Set-B matrix.
Roofline rows appear when experiments/dryrun/*.json exists (run
``python -m repro.launch.dryrun`` first; see EXPERIMENTS.md).

Artifacts (both written by default, disable with ``--no-artifacts``):

  * ``BENCH_spmv.json`` (``--out``): every section's CSV lines plus the
    full record list -- the per-PR perf trace CI uploads;
  * a versioned JSONL record store under ``benchmarks/records/``
    (``--records-dir``): the auto-tuner's training data.
    ``selector.load_records`` merges the directory across runs, so
    accumulated CI artifacts keep refining ``selector.tune``'s fits;
  * ``BENCH_obs.json`` (``--obs-out``): the global ``repro.obs`` registry
    snapshot -- plan-pass spans, serving-tier counters and latency
    histograms accumulated across every section of the run.

Everything runs in CPU-interpret mode (use_pallas=False / interpret=True
under the hood) with fixed seeds, so record identities -- matrix set,
kernels, configurations, features -- are deterministic run-to-run; only the
measured gflops values vary with machine load. Timing is warmup-discard +
median-of-repeats (``benchmarks.timing.time_fn``) so the per-section
aggregates are stable enough for the CI perf-regression gate
(``benchmarks.regression_gate``) to compare against the prior run's
artifact.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import traceback


def write_artifacts(sections_out, store, out_path: str, records_dir: str,
                    mode: str) -> None:
    """Write BENCH_spmv.json + the JSONL record store for this run."""
    from repro.core.selector import RECORDS_VERSION

    if records_dir:
        os.makedirs(records_dir, exist_ok=True)
        store.save_jsonl(os.path.join(records_dir, f"spmv_{mode}.jsonl"))
    if out_path:
        payload = {
            "version": RECORDS_VERSION,
            "mode": mode,
            "sections": sections_out,
            "n_records": len(store.records),
            "records": [dataclasses.asdict(r) for r in store.records],
        }
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1)
        os.replace(tmp, out_path)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--full", action="store_true",
                      help="all matrices (slower); default is --quick subset")
    mode.add_argument("--quick", action="store_true",
                      help="representative subset (the default)")
    ap.add_argument("--out", default="BENCH_spmv.json",
                    help="benchmark-record JSON artifact path")
    ap.add_argument("--obs-out", default="BENCH_obs.json",
                    help="obs registry snapshot artifact path")
    ap.add_argument("--records-dir",
                    default=os.path.join(os.path.dirname(__file__), "records"),
                    help="directory for the JSONL record store")
    ap.add_argument("--no-artifacts", action="store_true",
                    help="print CSV lines only, write nothing")
    args = ap.parse_args(argv)
    quick = not args.full
    from repro.launch import compile_cache
    compile_cache.enable()

    from repro.core.selector import RecordStore
    store = RecordStore()
    # sweep records live apart until artifact time: bench_selector fits the
    # paper's per-kernel predictors on `store`, and those key only on
    # (kernel, workers, pr) -- mixing the sweep's alternative chunk sizes in
    # would bend the fitted curves
    sweep_store = RecordStore()

    sections = []

    from . import bench_formats
    sections.append(("formats", lambda: bench_formats.run(quick=quick)))

    from . import bench_spmv_seq
    sections.append(("spmv_seq",
                     lambda: bench_spmv_seq.run(quick=quick, store=store,
                                                sweep=True,
                                                sweep_store=sweep_store)))

    from . import bench_spmv_par
    sections.append(("spmv_par",
                     lambda: bench_spmv_par.run(quick=quick, store=store)))

    from . import bench_selector
    sections.append(("selector",
                     lambda: bench_selector.run(quick=quick, store=store)))

    from . import bench_lm_step
    sections.append(("lm", lambda: bench_lm_step.run(quick=quick)))

    from . import bench_serve
    sections.append(("spmv_serve", lambda: bench_serve.run(quick=quick)))
    sections.append(("spmv_serve_overload",
                     lambda: bench_serve.overload(quick=quick)))

    from . import roofline
    def _roofline():
        rows = roofline.main(csv=False)
        out = []
        for r in rows:
            if "skipped" in r:
                out.append(
                    f"roofline.{r['arch']}.{r['shape']}.{r['mesh']},skip,0")
            else:
                out.append(
                    f"roofline.{r['arch']}.{r['shape']}.{r['mesh']},"
                    f"{r['bound_s']*1e6:.1f},"
                    f"frac={r['roofline_fraction']*100:.2f}pct;"
                    f"dom={r['dominant']}")
        return out
    sections.append(("roofline", _roofline))

    failed = 0
    sections_out = {}
    for name, fn in sections:
        print(f"# --- {name} ---")
        try:
            lines = list(fn())
            sections_out[name] = lines
            for line in lines:
                print(line)
        except Exception as e:  # noqa: BLE001 -- keep the harness running
            failed += 1
            sections_out[name] = [f"{name}.ERROR,0,{e!r}"]
            print(f"{name}.ERROR,0,{e!r}", file=sys.stderr)
            traceback.print_exc()
    if not args.no_artifacts:
        write_artifacts(sections_out, store.extend(sweep_store), args.out,
                        args.records_dir, mode="quick" if quick else "full")
        if args.obs_out:
            from repro import obs
            obs.export.dump_json(obs.get_registry(), args.obs_out)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
