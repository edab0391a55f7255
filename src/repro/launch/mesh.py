"""Production mesh construction.

A FUNCTION, not a module constant — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import; smoke tests
see the real single device).
"""
from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) ('data', 'model') = 256 chips.
    Multi-pod:  (2, 16, 16) ('pod', 'data', 'model') = 512 chips.
    `pod` acts as an outer data-parallel axis (batch sharded over
    ('pod', 'data')); params/optimizer replicate across pods.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh on the real host device (smoke tests)."""
    return _auto_mesh((1, 1), ("data", "model"))


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes, like ``jax.sharding.Mesh``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, which refuse layouts
    this repo leaves to the compiler (the replay ring's sharded
    ``dynamic_update_slice`` among them)."""
    auto = (jax.sharding.AxisType.Auto,) * len(shape)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_scaleout_mesh(data: int = 0, model: int = 1):
    """('data', 'model') mesh over the first ``data*model`` visible devices.

    Unlike ``jax.make_mesh`` this accepts a SUBSET of the device pool, which
    is what scaling curves need: the same process measures 1-, 2-, 4- and
    8-device meshes out of 8 emulated host devices without re-launching.
    ``data=0`` means "all devices on the data axis" — the default production
    scale-out for fused scoring, where rows shard over ``data`` and the
    committee replicates (see docs/scaling.md).
    """
    devs = jax.devices()
    if data <= 0:
        if len(devs) % model:
            raise ValueError(
                f"make_scaleout_mesh: {len(devs)} devices not divisible by "
                f"model={model}")
        data = len(devs) // model
    need = data * model
    if need > len(devs):
        raise ValueError(
            f"make_scaleout_mesh: need {data}x{model}={need} devices, have "
            f"{len(devs)}")
    grid = np.array(devs[:need]).reshape(data, model)
    return jax.sharding.Mesh(grid, ("data", "model"))
