"""``panel_chunk_us`` on a synthetic run: a stub reduction, and a registry
holding a warm-up span before the window's (the layout of
``test_step_metrics.py``)."""
import types

import pytest

from bench.harness import load_plugin
from repro import obs
from repro.obs import spans as SP

CHUNKS = load_plugin("metrics", "panel_chunk_us")
STEPS = load_plugin("metrics", "panel_step_us")


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
        layer={"products": [1] * products})


def test_chunk_reader_divides_by_the_window_chunks(registry):
    reg, clock = registry
    with reg.span("exec.spmv", grid_steps=999, chunk_steps=999):
        clock.now += 30.0                 # warm-up: compile, other steps
    for _ in range(4):
        with reg.span("exec.spmv", layout="panels", lowering="mask", nvec=1,
                      grid_steps=1000, chunk_steps=36000,
                      chunks_per_step=36):
            clock.now += 0.002
    run = _run(4, kernel_s=0.048)
    assert CHUNKS.read(run) == pytest.approx(0.048 / (4 * 36000) * 1e6)
    assert STEPS.read(run) == pytest.approx(0.048 / 4000 * 1e6)


def test_chunk_reader_reads_nothing_without_chunk_steps(registry):
    """A program whose spans carry ``grid_steps`` alone has no chunk
    count to read; nor has a run without a reduction or enough spans."""
    reg, clock = registry
    for _ in range(3):
        with reg.span("exec.spmv", layout="panels", lowering="mask", nvec=1,
                      grid_steps=10):
            clock.now += 0.001
    run = _run(3)
    assert STEPS.read(run) is not None
    assert CHUNKS.read(run) is None
    assert CHUNKS.read(_run(4)) is None
    run.reduction = None
    assert CHUNKS.read(run) is None
