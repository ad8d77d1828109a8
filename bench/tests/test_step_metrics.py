"""``panel_step_us`` and ``exec_dispatch_ms`` on a synthetic run: a stub
reduction, and a registry holding warm-up spans before the window's."""
import types

import pytest

from bench.harness import load_plugin
from repro import obs
from repro.obs import spans as SP

STEPS = load_plugin("metrics", "panel_step_us")
DISPATCH = load_plugin("metrics", "exec_dispatch_ms")


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


def _exec(reg, clock, seconds, steps):
    with reg.span("exec.spmv", layout="panels", lowering="mask", nvec=1,
                  grid_steps=steps):
        clock.now += seconds


def _run(products, kernel_s=2.0):
    return types.SimpleNamespace(
        reduction=types.SimpleNamespace(kernel_s=kernel_s),
        layer={"products": [1] * products})


def test_readers_take_only_the_window_spans(registry):
    reg, clock = registry
    _exec(reg, clock, 30.0, 999)          # warm-up: compile, other steps
    with reg.span("plan.build"):
        clock.now += 1.0
    for _ in range(4):
        _exec(reg, clock, 0.002, 1000)
    with reg.span("bench.other"):         # spans after the window's
        clock.now += 5.0
    run = _run(4, kernel_s=0.048)
    assert STEPS.read(run) == pytest.approx(0.048 / 4000 * 1e6)
    assert DISPATCH.read(run) == pytest.approx(2.0)


@pytest.mark.parametrize("products,spans", [(0, 3), (3, 0), (3, 2)])
def test_readers_read_nothing_without_enough_spans(registry, products,
                                                   spans):
    reg, clock = registry
    for _ in range(spans):
        _exec(reg, clock, 0.001, 10)
    run = _run(products)
    assert STEPS.read(run) is None
    assert DISPATCH.read(run) is None


def test_step_reader_reads_nothing_without_a_reduction(registry):
    reg, clock = registry
    _exec(reg, clock, 0.001, 10)
    run = _run(1)
    run.reduction = None
    assert STEPS.read(run) is None
    assert DISPATCH.read(run) == pytest.approx(1.0)
