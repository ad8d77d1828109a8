"""The one-chip cell ``hpcg104_rhs8.bcg`` (block CG with 8 right-hand sides
through the SpMM path) on the CPU rehearsal, and the readers of its own
per-layer metrics on a synthetic run."""
import time
import types

import jax
import pytest

from bench import control, harness
from repro import obs
from repro.obs import spans as SP

CELL = "hpcg104_rhs8.bcg"
CHUNKS = harness.load_plugin("metrics", "panel_chunk_us.spmm")
DISPATCH = harness.load_plugin("metrics", "exec_dispatch_ms.spmm")


def rehearse(wrap=None, seed=2 ** 31 + 41):
    return harness.run_cell(harness.load_cell(CELL), seed, 2.0, False,
                            time.perf_counter(), jax.devices(),
                            rehearsal=True, wrap_product=wrap)


def test_the_cell_asks_for_one_chip_and_reports_gflops():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1
    assert cell.config["nrhs"] == 8
    assert {m["name"] for m in cell.end_to_end} == {"gflops", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "convert_s", "plan_build_s", "panel_kernel_roofline", "wrapper_ms",
        "idle_pct", "panel_chunk_us.spmm", "exec_dispatch_ms.spmm"}
    for m in cell.per_layer:
        assert callable(harness.load_plugin("metrics", m["name"]).read)


def test_rehearsal_runs_block_cg_through_the_spmm_path_and_reads_correct():
    before = len(obs.get_registry().spans())
    res = rehearse()
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"] == {} and res["device"]["platform"] == "cpu"
    assert res["notes"]["plan"]["layout"] == "panels"
    spans = [e for e in obs.get_registry().spans()[before:]
             if e.name.startswith("exec.")]
    # the warm-up product and one a window iteration, all 8 vectors wide
    assert len(spans) == res["attempted"] + 1
    assert {e.name for e in spans} == {"exec.spmm"}
    assert {e.attrs["nvec"] for e in spans} == {8}


def test_control_fails_the_check():
    """The bfloat16 reference in the program's place must read incorrect."""
    res = rehearse(wrap=control.bf16_product)
    assert not res["correct"]
    gap = res["checks"]["rel_gap"]
    assert gap["value"] > 10 * gap["limit"]


@pytest.fixture
def registry(monkeypatch):
    """A fresh global registry on a clock the test moves by hand."""
    clock = types.SimpleNamespace(now=0.0)
    monkeypatch.setattr(SP, "monotonic", lambda: clock.now)
    reg = obs.Registry()
    prev = obs.set_registry(reg)
    try:
        yield reg, clock
    finally:
        obs.set_registry(prev)


def _run(products, kernel_s=2.0):
    return types.SimpleNamespace(
        reduction=types.SimpleNamespace(kernel_s=kernel_s),
        layer={"products": [8] * products})


def test_readers_take_only_the_window_spmm_spans(registry):
    reg, clock = registry
    with reg.span("exec.spmm", nvec=8, grid_steps=1, chunk_steps=999):
        clock.now += 30.0                 # warm-up: compile
    for _ in range(3):
        with reg.span("exec.spmm", layout="panels", nvec=8, grid_steps=2197,
                      chunk_steps=79092, chunks_per_step=36,
                      vectors_per_step=8):
            clock.now += 0.004
        with reg.span("exec.spmv", grid_steps=5, chunk_steps=5):
            clock.now += 1.0              # never read by these readers
    run = _run(3, kernel_s=0.9)
    assert CHUNKS.read(run) == pytest.approx(0.9 / (3 * 79092) * 1e6)
    assert DISPATCH.read(run) == pytest.approx(4.0)


def test_readers_read_nothing_without_enough_spmm_spans(registry):
    reg, clock = registry
    for _ in range(2):
        with reg.span("exec.spmv", grid_steps=5, chunk_steps=5):
            clock.now += 0.001
    with reg.span("exec.spmm", nvec=8, grid_steps=5):
        clock.now += 0.001
    assert CHUNKS.read(_run(1)) is None   # no chunk_steps on the span
    assert DISPATCH.read(_run(1)) == pytest.approx(1.0)
    for run in (_run(2), _run(0)):
        assert CHUNKS.read(run) is None and DISPATCH.read(run) is None
    run = _run(1)
    run.reduction = None
    assert CHUNKS.read(run) is None
