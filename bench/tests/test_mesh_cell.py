"""The four-chip cell ``hpcg208x208x104.cg`` on the CPU rehearsal, and the
readers of its per-layer metrics.

The rehearsal runs in a child process over four virtual CPU devices (this
process keeps its one): the sound run must read correct, and the bfloat16
control and two planted faults, which reach the sharded path through
``plan.execute_spmv`` as on the one-chip cell, must read incorrect."""
import json
import os
import subprocess
import sys
import types

import pytest

from bench import flops, harness, peaks

CELL = "hpcg208x208x104.cg"
RUNS = ("sound", "bf16_control", "altered_answer", "state_unchanged")

CHILD = """
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}, {tests!r}]
import jax
from bench import control, harness
from test_faults import altered_answer, state_unchanged
cell = harness.load_cell({cell!r})
wraps = dict(sound=None, bf16_control=control.bf16_product,
             altered_answer=altered_answer, state_unchanged=state_unchanged)
out = {{}}
for name in {runs!r}:
    res = harness.run_cell(cell, 2 ** 31 + 29, 2.0, False,
                           time.perf_counter(), jax.devices()[:cell.chips],
                           rehearsal=True, wrap_product=wraps[name])
    out[name] = dict(correct=res["correct"], checks=res["checks"],
                     attempted=res["attempted"], metrics=res["metrics"],
                     devices=res["device"]["count"],
                     plan=res["notes"]["plan"])
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def rehearsals():
    code = CHILD.format(root=harness.ROOT,
                        src=os.path.join(harness.ROOT, "src"),
                        tests=os.path.dirname(os.path.abspath(__file__)),
                        cell=CELL, runs=RUNS)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    lines = [ln for ln in res.stdout.splitlines() if ln.startswith("RESULT ")]
    assert res.returncode == 0 and lines, res.stderr[-4000:]
    return json.loads(lines[-1][len("RESULT "):])


def test_the_cell_asks_for_four_chips_and_reports_gflops():
    cell = harness.load_cell(CELL)
    assert cell.chips == 4
    assert {m["name"] for m in cell.end_to_end} == {"gflops", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "panel_kernel_roofline.mesh", "panel_step_us", "wrapper_ms",
        "idle_pct", "convert_s", "plan_build_s", "exec_dispatch_ms"}


def test_rehearsal_runs_the_sharded_panel_kernel_and_reads_correct(
        rehearsals):
    res = rehearsals["sound"]
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["metrics"] == {}
    assert res["devices"] == 4
    assert res["plan"]["ndev"] == 4
    assert (res["plan"]["layout"], res["plan"]["lowering"]) == ("panels",
                                                                "mask")


@pytest.mark.parametrize("name", RUNS[1:])
def test_control_and_faults_read_incorrect(rehearsals, name):
    res = rehearsals[name]
    assert not res["correct"], (name, res["checks"])
    gap = res["checks"]["rel_gap"]
    assert gap["value"] > 10 * gap["limit"]


def _run(**layer):
    red = types.SimpleNamespace(kernel_s=2.0, kernel_calls=8, chips=4)
    return types.SimpleNamespace(reduction=red, layer=layer,
                                 peaks=peaks.chip_peaks("TPU v5 lite"))


def test_roofline_averages_the_shards_least_times():
    shards = [dict(nnz=1000 * (k + 1), nrows=100, ncols=120 + k)
              for k in range(4)]
    run = _run(products=[1, 1, 1], shards=shards, value_bytes=4)
    least = [flops.spmv_least_seconds(s["nnz"], s["nrows"], s["ncols"], 4,
                                      1, run.peaks) for s in shards]
    want = 100.0 * 3 * (sum(least) / 4) / 2.0
    got = harness.load_plugin("metrics", "panel_kernel_roofline.mesh").read(
        run)
    assert got == pytest.approx(want, rel=1e-12)
    assert harness.load_plugin("metrics", "panel_kernel_roofline.mesh").read(
        _run(products=[1])) is None


def test_roofline_without_a_kernel_event_is_an_error():
    run = _run(products=[1], value_bytes=4,
               shards=[dict(nnz=10, nrows=4, ncols=4)])
    run.reduction.kernel_calls = 0
    with pytest.raises(harness.BenchError, match="no Mosaic"):
        harness.load_plugin("metrics", "panel_kernel_roofline.mesh").read(run)


def test_plan_build_s_sums_the_shard_passes():
    trace = [{"pass": p, "duration_s": d} for p, d in
             (("tune", 0.5), ("reorder", 0.25), ("lowering", 0.125),
              ("partition", 1.0), ("shard", 8.0))]
    reader = harness.load_plugin("metrics", "plan_build_s")
    assert reader.read(_run(plan_trace=trace)) == pytest.approx(9.875)
    assert reader.read(_run()) is None
