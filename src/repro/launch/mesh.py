"""Production mesh construction.

Defined as functions (not module-level constants) so importing this module
never touches jax device state. The dry-run entry point sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* importing jax.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """jax.make_mesh with Auto axis types on every axis."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = n // model
    return make_mesh((data, model), ("data", "model"))


def make_cv_mesh(data: int | None = None):
    """Data-only mesh for the CV serving fan-out (`serve/shard_dispatch`).

    The CV batch path is pure data parallelism — every image is
    independent, so the mesh has a single "data" axis over the host's
    devices (capped at `data` when given).  Single-device hosts get a
    1-device mesh: `CvEngine` then serves exactly as before (the
    dispatcher only engages past one data-axis device)."""
    n = len(jax.devices())
    data = n if data is None else max(1, min(int(data), n))
    return make_mesh((data,), ("data",))
