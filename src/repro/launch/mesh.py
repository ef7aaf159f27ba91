"""Mesh construction.  ``make_production_mesh`` is the deliverable entry
point; everything is a function (importing this module never touches jax
device state).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

from repro.configs.base import MeshConfig, MULTI_POD, SINGLE_POD


def make_production_mesh(*, multi_pod: bool = False):
    """Production mesh: 16x16 (one 256-chip v5e pod) or 2x16x16 (two pods).

    The dry-run launcher sets XLA_FLAGS=--xla_force_host_platform_device_count=512
    *before* any jax import so this can build on CPU."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def build_mesh(cfg: MeshConfig):
    """Mesh from an arbitrary MeshConfig (tests use small shapes)."""
    return auto_mesh(tuple(cfg.shape), tuple(cfg.axis_names))


def auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with every axis Auto: the model code places arrays
    through GSPMD sharding constraints, which Explicit axes (the default
    since jax 0.7) reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def mesh_config_for(mesh) -> MeshConfig:
    return MeshConfig(shape=tuple(mesh.devices.shape),
                      axis_names=tuple(mesh.axis_names))


def dp_size(mesh_cfg: MeshConfig) -> int:
    n = 1
    for s, a in zip(mesh_cfg.shape, mesh_cfg.axis_names):
        if a in ("pod", "data"):
            n *= s
    return n


def model_size(mesh_cfg: MeshConfig) -> int:
    n = 1
    for s, a in zip(mesh_cfg.shape, mesh_cfg.axis_names):
        if a == "model":
            n *= s
    return n
