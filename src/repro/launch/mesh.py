"""Production mesh construction.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benchmarks see the real single CPU device.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """``jax.make_mesh`` with ``Auto`` axes.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which every
    sharding must be spelled out at each op (a vocab-sharded embedding
    gather then raises); the model stack leaves those choices to GSPMD
    and pins only what it names through ``with_sharding_constraint``.
    """
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes), **kw)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes(mesh) -> Tuple[str, ...]:
    """The data-parallel (gradient-sync) axes of a mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def all_axes(mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def model_size(mesh) -> int:
    return mesh.shape["model"]


def dp_size(mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n
