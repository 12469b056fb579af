"""Mesh construction.

FUNCTIONS, not module-level constants: importing this module never
touches jax device state.  Every mesh here has Auto axes, so GSPMD
propagates shardings (jax's default for ``jax.make_mesh`` is Explicit).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with every axis Auto (over ``devices`` if given)."""
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False):
    """The dry-run's production layout: 16x16 (one pod) or 2x16x16."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_mesh(devices: Optional[Sequence] = None, *, pod: bool = False, model: int = 1):
    """A mesh over exactly ``devices`` (default: ``jax.devices()``).

    * ``pod=False``: ``(data=n/model, model=model)`` -- GSPMD data/tensor
      parallel; one chip gives ``(data=1, model=1)``.
    * ``pod=True``: ``(pod=n/model, data=1, model=model)`` -- the Hoplite
      chain syncs gradients over ``pod``; four chips give
      ``(pod=4, data=1, model=1)``.

    Raises ValueError when the devices do not fill the layout: no padding,
    no dropped devices, no pod axis of one device (its sync would be a no-op).
    """
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    if model < 1 or n % model:
        raise ValueError(f"{n} devices do not split into model={model} groups")
    outer = n // model
    if pod:
        if outer < 2:
            raise ValueError(
                f"a pod axis needs at least 2 groups of model={model} devices; got {n} devices"
            )
        return auto_mesh((outer, 1, model), ("pod", "data", "model"), devices)
    return auto_mesh((outer, model), ("data", "model"), devices)
