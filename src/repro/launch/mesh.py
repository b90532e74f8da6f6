"""Mesh construction. Importing this module never touches jax device state;

meshes are built inside functions only (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """The production target: one v5e pod slice (16x16 = 256 chips) or two

    pods (2x16x16 = 512 chips) with a leading pure-DP "pod" axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Mesh over prod(shape) devices laid out along the physical topology
    (``jax.make_mesh``), with GSPMD-propagated (``Auto``) axes. A host
    with more devices uses the first prod(shape) (the dry-run host
    exposes 512 placeholder devices; the single-pod mesh uses 256)."""
    from jax.sharding import AxisType
    n = 1
    for s in shape:
        n *= s
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(f"need {n} devices, have {len(devs)}")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(shape),
                         devices=devs[:n])


def dp_axes(mesh) -> Tuple[str, ...]:
    """The data-parallel submesh axes (pod + data when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def tp_axis(mesh) -> Optional[str]:
    return "model" if "model" in mesh.axis_names else None


def axis_size(mesh, name) -> int:
    if name is None:
        return 1
    names = list(mesh.axis_names)
    if name not in names:
        return 1
    return mesh.devices.shape[names.index(name)]


def dp_size(mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= axis_size(mesh, a)
    return n
