#!/usr/bin/env python3
"""Readings that set a cell's limits: sound runs and control runs, in one
process (set-up is long, so one process reads many seeds).

    python3 bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3 --control-seeds 4,5,6

Each seed is a whole run of the cell (set-up, window, check) as
``run.py`` makes it; a control seed puts ``bench.control``'s bfloat16
reference in the program's place. One JSON line per seed. The benchmark's
own runs never run this.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT


def main(argv=None) -> int:
    from bench import control, harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.import_program()
    devices = harness.require_chips(cell.chips)[:cell.chips]
    harness.enable_compile_cache()
    plan = [(int(s), "sound") for s in args.seeds.split(",") if s] + \
        [(int(s), "control") for s in args.control_seeds.split(",") if s]
    for seed, kind in plan:
        wrap = control.bf16_product if kind == "control" else None
        t0 = time.perf_counter()
        try:
            res = harness.run_cell(cell, seed, args.seconds, False, t0,
                                   devices, wrap_product=wrap)
            line = {"seed": seed, "kind": kind, "checks": res["checks"],
                    "correct": res["correct"],
                    "attempted": res["attempted"], "failed": res["failed"],
                    "metrics": res["metrics"], "notes": res["notes"]}
        except Exception as e:          # noqa: BLE001 -- a crash is a reading
            line = {"seed": seed, "kind": kind,
                    "error": f"{type(e).__name__}: {e}"}
        print(json.dumps(line), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
