"""Production meshes.

Every mesh in the repo is built by :func:`make_mesh`, which gives each axis
the ``Auto`` type: ``jax.make_mesh`` defaults to ``Explicit`` axes, and the
activation constraints (``parallel/sharding.PartitionConstraints``) go
through ``with_sharding_constraint``, which accepts ``Auto`` axes only.

``make_production_mesh`` is a *function* (never a module-level constant) so
importing this module never touches jax device state — smoke tests must
keep seeing 1 device.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-partitioned)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading "pod"
    axis (DP spans pod x data; TP spans model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for(devices: Optional[int] = None, *, model: int = 0):
    """Elastic mesh for whatever devices this process actually has.

    Picks the largest power-of-two TP ("model") axis <= requested (or 1/4 of
    the device count) and puts the rest on "data" — the restart path after a
    node failure builds its mesh through here.
    """
    n = devices if devices is not None else len(jax.devices())
    if model <= 0:
        model = 1
        while model * model * 4 <= n:
            model *= 2
    while n % model != 0:
        model //= 2
    return make_mesh((n // model, model), ("data", "model"))
