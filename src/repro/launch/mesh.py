"""Mesh construction.

FUNCTIONS, not module-level constants — importing this module never
touches jax device state (the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests see the real single device).

Every mesh is built with ``AxisType.Auto`` axes: the sharding rules in
``repro.sharding`` are GSPMD-style constraints, while ``jax.make_mesh``
defaults to explicit axes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.configs.base import MULTI_POD, SINGLE_POD, MeshConfig


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """``jax.make_mesh`` with Auto axis types over ``devices`` (default:
    all local devices)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    cfg = mesh_config(multi_pod=multi_pod)
    return make_mesh(cfg.shape, cfg.axes)


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    return MULTI_POD if multi_pod else SINGLE_POD


def make_mesh_from_config(cfg: MeshConfig):
    return make_mesh(cfg.shape, cfg.axes)


def make_local_mesh(axes: tuple[str, ...] = ("data", "model")):
    """A 1x1 (or 1x1x1) mesh over the first local device — used by smoke
    tests and examples so the same pjit code paths run on one device."""
    return make_mesh((1,) * len(axes), axes, devices=jax.devices()[:1])
