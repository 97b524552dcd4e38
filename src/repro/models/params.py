"""Parameter-spec trees: one definition drives init, abstract eval and sharding.

A model's parameter structure is a pytree whose leaves are ``ParamSpec``:
shape + dtype + PartitionSpec + initializer. From that single tree we derive

  * ``abstract(tree)``      -> ShapeDtypeStruct tree (dry-run, no allocation)
  * ``pspecs(tree)``        -> PartitionSpec tree (pjit in_shardings)
  * ``materialize(tree, k)`` -> real arrays (smoke tests / real training)

Sharding conventions (see DESIGN.md §5): weight matrices are sharded
FSDP-style on their d_model-sized dimension over the ``data`` axis and
tensor-parallel on their hidden/head/vocab dimension over the ``model`` axis.
Optimizer state inherits parameter shardings, which is what makes the ZeRO
memory behavior fall out of pure annotations.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# Logical axis names used in specs; resolved to mesh axes by `logical_to_mesh`.
# "fsdp"  -> the data axis (param sharding over data; batch also uses data)
# "tp"    -> the model axis
LOGICAL_RULES_SINGLE = {"fsdp": "data", "tp": "model", "batch": ("data",)}


def logical_to_mesh(spec: P, mesh_axes: Tuple[str, ...]) -> P:
    """Resolve logical names to the mesh's axes.

    On the multi-pod mesh the batch shards over (pod, data) and fsdp stays on
    data (pods replicate params; pure DP across the DCN-connected pod axis).
    """
    multi_pod = "pod" in mesh_axes
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif entry == "batch":
            out.append(("pod", "data") if multi_pod else "data")
        elif entry == "fsdp":
            out.append("data")
        elif entry == "tp":
            out.append("model")
        else:
            out.append(entry)
    return P(*out)


def resolve_spec(spec: P, shape, mesh) -> P:
    """Resolve logical names and drop axes that do not divide the dim.

    E.g. GQA with n_kv_heads=8 on a model=16 axis falls back to replicating
    the KV-head dimension (the standard TP>kv_heads behavior).
    """
    resolved = logical_to_mesh(spec, mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    for i, dim in enumerate(shape):
        entry = resolved[i] if i < len(resolved) else None
        if entry is None:
            out.append(None)
            continue
        axes = list(entry) if isinstance(entry, tuple) else [entry]
        while axes:
            total = 1
            for a in axes:
                total *= sizes[a]
            if dim % total == 0:
                break
            axes.pop()              # drop innermost axis and retry
        out.append(tuple(axes) if len(axes) > 1 else (axes[0] if axes else None))
    return P(*out)


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    dtype: Any = jnp.bfloat16
    pspec: P = P()
    init: str = "normal"        # normal | zeros | ones | uniform_pm (+- scale)
    scale: Optional[float] = None   # None -> 1/sqrt(fan_in)

    def fan_in(self) -> int:
        if len(self.shape) <= 1:
            return max(self.shape[-1] if self.shape else 1, 1)
        return self.shape[-2]


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def tree_map_specs(f: Callable[[ParamSpec], Any], tree):
    return jax.tree_util.tree_map(f, tree, is_leaf=is_spec)


def abstract(tree, mesh=None):
    """ShapeDtypeStruct tree; attaches NamedSharding when a mesh is given."""
    def mk(s: ParamSpec):
        if mesh is not None:
            sh = jax.sharding.NamedSharding(
                mesh, resolve_spec(s.pspec, s.shape, mesh))
            return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
        return jax.ShapeDtypeStruct(s.shape, s.dtype)
    return tree_map_specs(mk, tree)


def pspecs(tree, mesh_axes: Tuple[str, ...] = ("data", "model")):
    return tree_map_specs(lambda s: logical_to_mesh(s.pspec, mesh_axes), tree)


def shardings(tree, mesh):
    return tree_map_specs(
        lambda s: jax.sharding.NamedSharding(
            mesh, resolve_spec(s.pspec, s.shape, mesh)), tree)


@functools.partial(jax.jit, static_argnums=0)
def _init_leaf(s: ParamSpec, k: jax.Array) -> jax.Array:
    """One leaf, built on the default device in its own dtype.

    A narrower leaf fuses its float32 draw into the cast, so no float32 copy
    of it is ever held; fusion reorders the draw's scaling, which moves about
    six in a million bfloat16 values by one ulp against an unfused draw. A
    float32 leaf has no temporary to save, and a barrier keeps it
    bit-identical to the unfused draw for the same key."""
    if s.init == "zeros":
        return jnp.zeros(s.shape, s.dtype)
    if s.init == "ones":
        return jnp.ones(s.shape, s.dtype)
    scale = s.scale if s.scale is not None else s.fan_in() ** -0.5
    if s.init == "uniform_pm":
        return jax.random.uniform(k, s.shape, jnp.float32, -scale, scale).astype(s.dtype)
    x = jax.random.normal(k, s.shape, jnp.float32)
    if jnp.dtype(s.dtype) == jnp.float32:
        x = jax.lax.optimization_barrier(x)
    return (x * scale).astype(s.dtype)


def materialize(tree, key: jax.Array):
    """Allocate real parameters (used for smoke tests and real training)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(
        treedef, [_init_leaf(s, k) for s, k in zip(leaves, keys)])


def count_params(tree) -> int:
    import numpy as np
    leaves = jax.tree_util.tree_leaves(tree, is_leaf=is_spec)
    return int(sum(int(np.prod(l.shape)) for l in leaves))


def param_bytes(tree) -> int:
    import numpy as np
    leaves = jax.tree_util.tree_leaves(tree, is_leaf=is_spec)
    return int(sum(int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
                   for l in leaves))
