"""Mesh-aware activation sharding helpers usable from model code.

``MeshInfo`` is threaded through the model; ``shard(x, spec)`` applies a
``with_sharding_constraint`` resolving logical names (batch/fsdp/tp) to mesh
axes, and is a no-op when no mesh is active (CPU smoke tests).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.params import resolve_spec


@dataclass(frozen=True)
class MeshInfo:
    mesh: Optional[jax.sharding.Mesh] = None

    @property
    def active(self) -> bool:
        return self.mesh is not None

    @property
    def multi_pod(self) -> bool:
        return self.active and "pod" in self.mesh.axis_names

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return self.mesh.axis_names if self.active else ()

    def size(self, axis: str) -> int:
        return self.mesh.shape[axis] if self.active else 1

    @property
    def batch_axes(self):
        return ("pod", "data") if self.multi_pod else ("data",)

    @property
    def all_axes(self):
        return self.mesh.axis_names if self.active else ()


NO_MESH = MeshInfo(None)


def shard(x: jax.Array, mi: MeshInfo, spec: P) -> jax.Array:
    if not mi.active:
        return x
    resolved = resolve_spec(spec, x.shape, mi.mesh)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mi.mesh, resolved))
