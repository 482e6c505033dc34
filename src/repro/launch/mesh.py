"""Production mesh builders.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax
init, smoke tests must keep seeing 1 device.
"""

from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    return jax.make_mesh(shape, axes, devices=jax.devices()[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """Arbitrary mesh (elastic re-meshing path: same axes, new shape or
    device permutation after a spare-host swap)."""
    if devices is None:
        n = 1
        for s in shape:
            n *= s
        devices = jax.devices()[:n]
    return jax.make_mesh(shape, axes, devices=list(devices),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over however many local devices exist (tests)."""
    devs = jax.devices()[: n_data * n_model]
    arr = np.asarray(devs).reshape(n_data, n_model)
    return jax.sharding.Mesh(arr, ("data", "model"))
