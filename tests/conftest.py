import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 300):
    """Run a python snippet in a subprocess with N fake CPU devices.

    Keeps the main pytest process at 1 device (per the assignment: only the
    dry-run and explicitly-distributed tests may see many devices).
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    if res.returncode != 0:
        raise AssertionError(
            f"subprocess failed\nstdout:\n{res.stdout}\nstderr:\n{res.stderr}")
    return res.stdout


@pytest.fixture
def devices8():
    return lambda code, **kw: run_with_devices(code, 8, **kw)


@pytest.fixture(scope="module")
def devices4():
    return lambda code, **kw: run_with_devices(code, 4, **kw)


def assert_rowwise_close(y, dense, x, rel, ref=None):
    """``|y - ref| <= rel * (|A| |x|)`` in every row and column of ``y``,
    ``ref`` defaulting to the float64 product A x: the benchmark's
    ``rel_gap`` form, which admits a float32 reassociation of a row's adds
    but not a lost, doubled or stray term."""
    import numpy as np
    a = np.asarray(dense, np.float64)
    xs = np.asarray(x, np.float64)
    ref = a @ xs if ref is None else np.asarray(ref, np.float64)
    gap = np.abs(np.asarray(y, np.float64) - ref)
    bound = rel * (np.abs(a) @ np.abs(xs))
    worst = np.unravel_index(np.argmax(gap - bound), gap.shape)
    assert np.all(gap <= bound), (
        f"row {worst}: |y - ref| = {gap[worst]:.3e} over "
        f"{rel} * |A||x| = {bound[worst]:.3e}")


@pytest.fixture
def rowwise_close():
    return assert_rowwise_close
