"""Production meshes.

``make_production_mesh`` is a function (not a module constant) so importing
this module never touches jax device state; the dry-run sets
``--xla_force_host_platform_device_count=512`` before any jax import.

  single pod: (16, 16)    over ("data", "model")        — 256 chips (v5e)
  multi pod:  (2, 16, 16) over ("pod", "data", "model") — 512 chips

RPS (the unreliable exchange) runs over ("data",) / ("pod", "data") for
rps_model archs and over ("pod",) for rps_grad archs; "model" is the
reliable ICI tensor-parallel direction (DESIGN.md §4).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax


def _axis_types(n: int) -> dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_types(len(axes)))


def make_sim_mesh(n_workers: int, model: int = 1):
    """Small host-device mesh for multi-device tests/demos."""
    axes: Tuple[str, ...]
    if model > 1:
        return jax.make_mesh((n_workers, model), ("data", "model"),
                             **_axis_types(2))
    return jax.make_mesh((n_workers,), ("data",), **_axis_types(1))


def rps_axes_for(rps_mode: str, mesh) -> Tuple[str, ...]:
    names = mesh.axis_names
    if rps_mode == "rps_grad":
        return ("pod",) if "pod" in names else ()
    return tuple(a for a in ("pod", "data") if a in names)
