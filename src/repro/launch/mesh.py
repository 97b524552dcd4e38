"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state. ``dryrun.py`` sets XLA_FLAGS for 512 placeholder devices
*before* any jax import; everything else sees the real device count.

Meshes use Auto axes (``jax.make_mesh`` defaults to Explicit): every
sharding in this codebase is an explicit NamedSharding or a
``with_sharding_constraint``, and the partitioner propagates the rest.
Callers enter a mesh with ``jax.set_mesh``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.configs.base import MeshConfig


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]
              ) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_from_config(mc: MeshConfig) -> jax.sharding.Mesh:
    return make_mesh(mc.shape, mc.axes)


def make_host_mesh(data: Optional[int] = None, model: int = 1
                   ) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (CPU tests, examples)."""
    n = len(jax.devices())
    if data is None:
        data = n // model
    return make_mesh((data, model), ("data", "model"))
