#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are listed in
``BENCHMARK.json`` at the checkout's root; see ``bench/harness.py`` for how
a cell's files are found. The run sets up the cell (counted in
``setup_s``), measures for ``--seconds``, then checks what the window
produced against the plain reference. With ``--trace 0`` the result holds
the cell's end-to-end metrics; with ``--trace 1`` the window is traced and
the result holds its per-layer metrics.

The last line of standard output is the JSON result; the numbers compared
for ``correct`` are the last lines of standard error. Without a TPU, or
with fewer chips than the cell asks for, the run prints no result and
exits non-zero.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT


def main(argv=None) -> int:
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the raw trace here (default: a temporary "
                         "directory, removed after it is read)")
    args = ap.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
        harness.import_program()
        devices = harness.require_chips(cell.chips)[:cell.chips]
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    harness.enable_compile_cache()
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              T0, devices, trace_dir=args.trace_dir)
    notes = result.pop("notes")
    print(f"notes: {json.dumps(notes, sort_keys=True)}", flush=True)
    print(f"window programs built: {result['window_programs_built']}",
          flush=True)
    for name, c in result["checks"].items():
        ok = c["value"] <= c["limit"]
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if ok else 'FAIL'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
