"""Production mesh definitions.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module does not touch jax device state.  The dry-run driver
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to get placeholder devices; everything else (smoke tests, benches)
sees the real single CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod meshes: single pod 16x16 = 256 chips; 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_bench_mesh(n_devices: int, model: int = 1):
    """Small mesh over host devices for CPU multi-device tests/benches."""
    data = n_devices // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
