"""heat3d 7-point stencil (paper kernel #3), z-slab tiled with halos.

Each grid step DMAs a (bz+2, Y, X) slab (one-plane halo on each side, via an
element-indexed z dim over a pre-padded volume) into VMEM and computes the
interior update — the 3-D input tiling + double buffering of §III-B.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _kernel(u_ref, o_ref, *, c0: float, c1: float):
    u = u_ref[...]                           # (bz+2, Y+2, X+2)
    center = u[1:-1, 1:-1, 1:-1]
    neigh = (u[:-2, 1:-1, 1:-1] + u[2:, 1:-1, 1:-1]
             + u[1:-1, :-2, 1:-1] + u[1:-1, 2:, 1:-1]
             + u[1:-1, 1:-1, :-2] + u[1:-1, 1:-1, 2:])
    o_ref[...] = (c0 * center + c1 * neigh).astype(o_ref.dtype)


def heat3d_step(u: jax.Array, *, c0: float = 0.4, c1: float = 0.1,
                bz: int = 8, interpret: bool | None = None) -> jax.Array:
    """One timestep over (Z, Y, X); boundary kept fixed (Dirichlet)."""
    Z, Y, X = u.shape
    bz = min(bz, Z - 2)
    while (Z - 2) % bz:
        bz -= 1
    inner = pl.pallas_call(
        functools.partial(_kernel, c0=c0, c1=c1),
        grid=((Z - 2) // bz,),
        # Element-indexed z dim: the index map returns the slab's first
        # plane, so consecutive slabs OVERLAP by the one-plane halo — the
        # stencil's redundant-fetch pattern.
        in_specs=[pl.BlockSpec((pl.Element(bz + 2), Y, X),
                               lambda i: (i * bz, 0, 0))],
        out_specs=pl.BlockSpec((bz, Y - 2, X - 2), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Z - 2, Y - 2, X - 2), u.dtype),
        interpret=resolve_interpret(interpret),
    )(u)
    return u.at[1:-1, 1:-1, 1:-1].set(inner)
