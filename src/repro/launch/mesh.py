"""Production meshes.  A function, not a constant: importing this module
never touches jax device state."""
from __future__ import annotations

from typing import Sequence

import jax


def _make_mesh(shape, axes, devices=None):
    """jax.make_mesh with Auto on every axis, the behaviour the sharded
    paths assume."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) ("data","model") single pod = 256 chips;
    multi_pod -> (2,16,16) ("pod","data","model") = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Tiny mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = n // model_axis
    return _make_mesh((data, model_axis), ("data", "model"))


def make_serving_mesh(n_devices: int | None = None, *,
                      devices: Sequence[jax.Device] | None = None):
    """Pure data-parallel serving mesh: all (or the first `n_devices`)
    local devices on one "data" axis — the vision engine's batch DP mesh.
    `devices` pins the mesh to an explicit device list instead (a replica
    on its own chip is `make_serving_mesh(devices=[d])`).  Works degenerate
    on 1 CPU device and scales to a full host of chips."""
    devs = list(jax.devices() if devices is None else devices)
    if n_devices is not None:
        devs = devs[:n_devices]
    return _make_mesh((len(devs),), ("data",), devices=devs)
