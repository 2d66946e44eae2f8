"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  The single-pod mesh is 16x16 = 256 chips
("data", "model"); the multi-pod mesh is 2x16x16 = 512 chips
("pod", "data", "model").
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(n_devices: int = 1):
    """Small local mesh for tests: (n, 1) ("data", "model")."""
    import numpy as np
    devs = jax.devices()[:n_devices]
    return jax.sharding.Mesh(
        np.asarray(devs).reshape(len(devs), 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
