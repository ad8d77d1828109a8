#!/usr/bin/env python3
"""Find the highest rate an open-loop cell's server sustains (its knee).

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 4,6,8,10

One process sets the cell up once, then offers each rate in turn for
``--seconds``, and stops after the first rate at which the achieved rate
falls under 0.9 of the offered one or the backlog (requests due and not
answered) grew by more than a second's worth of arrivals between the
window's middle and its last submit. One JSON line per rate: offered
and achieved rate, p50 and p95 (ms), backlog, mean batch width, generator
lateness, and the check's numbers. The rate a cell's traffic file carries
comes from such a sweep; the benchmark's own runs never run this.
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
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.import_program()
    devices = harness.require_chips(cell.chips)[:cell.chips]
    harness.enable_compile_cache()
    loop = harness.load_plugin("loops", cell.traffic["loop"])
    run = harness.Run(cell, args.seed, args.seconds, devices)
    t0 = time.perf_counter()
    state = loop.setup(run)
    print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            run.traffic["rate_per_s"] = rate
            loop.requests(run, state)
            out = loop.window(run, state)
            checks = loop.check(run, state)
            notes = out["notes"]
            print(json.dumps({"offered_per_s": rate, **out["metrics"],
                              **notes, "failed": out["failed"],
                              "checks": checks}), flush=True)
            state.pop("answers", None)
            gc.collect()
            if (notes["achieved_per_s"] < 0.9 * rate
                    or notes["backlog_at_last_submit"]
                    - notes["backlog_mid"] > rate):
                break
    finally:
        loop.release(state)
    return 0


if __name__ == "__main__":
    sys.exit(main())
