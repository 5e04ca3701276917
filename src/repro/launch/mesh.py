"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (device count is locked at first backend init — dryrun.py sets
``--xla_force_host_platform_device_count=512`` before importing us).

Production topology (assignment): one pod = 16 x 16 = 256 chips
(``data`` x ``model``); multi-pod = 2 pods = 512 chips with a leading
``pod`` axis that crosses DCN (pure data parallel + optional FSDP).
"""

from __future__ import annotations

import jax


def _mk(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int | None = None):
    """Small mesh for CPU tests (requires forced host device count)."""
    if pod:
        return _mk((pod, data, model), ("pod", "data", "model"))
    return _mk((data, model), ("data", "model"))
