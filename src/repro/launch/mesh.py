"""Production mesh builders.

Functions (not module-level constants) so importing this module never
touches jax device state; the 512-device dry-run sets XLA_FLAGS before any
jax import (see dryrun.py).
"""

from __future__ import annotations

import jax
import numpy as np


def mesh_kwargs(n_axes: int, **extra) -> dict:
    """``jax.make_mesh`` kwargs: every axis ``Auto`` (``make_mesh`` defaults
    to ``Explicit``; the solver places its collectives by hand)."""
    return dict(extra, axis_types=(jax.sharding.AxisType.Auto,) * n_axes)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi_pod adds a leading 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **mesh_kwargs(len(axes)))


def make_host_mesh(shape=None, axes=("data", "model")):
    """Mesh over whatever devices exist (tests / examples on CPU)."""
    n = len(jax.devices())
    if shape is None:
        shape = (n, 1)
    return jax.make_mesh(shape, axes, **mesh_kwargs(len(axes)))


def make_fleet_mesh(fleet: int, *, layout: str = "fleet", devices=None):
    """Mesh with a leading ``fleet`` axis for fleet-sharded ``solve_many``.

    ``fleet`` devices shard the instance dim; the remaining ``n // fleet``
    devices shard states within each fleet slice (``layout="fleet"``), or
    states x actions (``layout="fleet2d"``: the trailing axis of size 2 —
    or 1 when indivisible — shards actions).
    """
    devs = list(devices) if devices is not None else jax.devices()
    n = len(devs)
    if fleet < 1 or n % fleet:
        raise ValueError(f"fleet-axis size {fleet} must divide the device "
                         f"count {n}")
    rest = n // fleet
    if layout == "fleet":
        shape, names = (fleet, rest), ("fleet", "data")
    elif layout == "fleet2d":
        am = 2 if rest % 2 == 0 and rest >= 2 else 1
        shape, names = (fleet, rest // am, am), ("fleet", "data", "model")
    else:
        raise ValueError(f"make_fleet_mesh serves the fleet layouts, "
                         f"got {layout!r}")
    extra = {} if devices is None else dict(devices=np.asarray(devs))
    return jax.make_mesh(shape, names, **mesh_kwargs(len(names), **extra))
