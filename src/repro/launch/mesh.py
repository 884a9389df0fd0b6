"""Mesh factory: every mesh in the repo is built here.

Target: TPU v5e pods, 256 chips each.
  single-pod : (data=16, model=16)            = 256 chips
  multi-pod  : (pod=2, data=16, model=16)     = 512 chips

Meshes carry Auto axis types: the engines place arrays with
``with_sharding_constraint`` and ``shard_map``, which the Explicit axes
that ``jax.make_mesh`` defaults to would refuse.

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(shape=(1, 1), axes=("data", "model")):
    """1-device mesh for CPU tests."""
    return make_mesh(shape, axes)
