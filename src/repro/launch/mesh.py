"""Production mesh factory.

A function (not module-level constant) so importing never touches jax
device state. Single pod: 16x16 = 256 chips (data, model). Multi-pod:
2 x 16 x 16 = 512 chips with a leading 'pod' axis (pure DP across the
slower inter-pod links — DCN-friendly).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the partitioner places
    shardings, and ``with_sharding_constraint`` may name the axes (jax ≥
    0.9 defaults new meshes to ``Explicit`` axes, which refuse both)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU integration tests (requires host-device override)."""
    return auto_mesh(shape, axes)


def tp_degree(mesh) -> int:
    return mesh.shape["model"]
