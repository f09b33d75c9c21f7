"""Mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device
state.

Every mesh is built here with Auto axis types: the train step places its
tensors with ``with_sharding_constraint``, which accepts only Auto axes.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[Sequence[Any]] = None) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_host_mesh(devices: Optional[Sequence[Any]] = None) -> Mesh:
    """``devices`` (default: every device of this host) on the ``model``
    axis."""
    devices = list(devices) if devices is not None else jax.devices()
    return make_mesh((1, len(devices)), ("data", "model"), devices)
