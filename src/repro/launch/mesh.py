"""Production mesh construction (a FUNCTION: importing never touches jax
device state)."""
from __future__ import annotations

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (one v5e pod's 256 chips) or 2x16x16 (two pods, 512 chips).

    Uses the first prod(shape) devices so a 512-placeholder-device process
    can build both meshes.
    """
    import jax
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)}; "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "(launch/dryrun.py sets this automatically)")
    # Auto axes: the models shard through sharding rules, not explicit-axis
    # types (jax.make_mesh's default since 0.7)
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh over however many devices the test process has."""
    import jax
    from jax.sharding import Mesh
    n = int(np.prod(shape))
    return Mesh(np.asarray(jax.devices()[:n]).reshape(shape), axes)
