"""Production mesh construction.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benchmarks see the real single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """Mesh whose axes are all ``Auto``: the model code shards through
    ``with_sharding_constraint`` hints, which Explicit axes (the
    ``jax.make_mesh`` default) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips ("data", "model").
    Multi-pod: 2x16x16 = 512 chips ("pod", "data", "model") — the "pod" axis
    is the HSDP replica axis (paper §3.1 Table 5)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """A trivial 1-device mesh for CPU smoke runs through the same API."""
    return _auto_mesh((1, 1), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
