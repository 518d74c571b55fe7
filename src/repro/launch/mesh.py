"""Production mesh construction (function — importing this module never
touches jax device state)."""
from __future__ import annotations

from repro.core.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 v5e chips) or 2x16x16 two-pod (512) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
