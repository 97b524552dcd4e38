"""Sort (paper kernel #5) — TPU adaptation of the cluster's parallel merge
sort.

HARDWARE ADAPTATION (DESIGN.md §8): the Snitch implementation merges with
scalar cores; TPUs have no scalar sort units, so the TPU-native equivalent is
a BITONIC sorting network — data-independent compare-exchange stages that
vectorize on the VPU. The Pallas kernel sorts VMEM-resident blocks with a
fully unrolled bitonic network; the global stages (cross-block, bandwidth-
bound) run as jnp reshape/min/max passes in ops.py, playing the role of the
DMA-engine merge passes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _bitonic_block(x: jax.Array) -> jax.Array:
    """Fully-unrolled bitonic sort of a (rows, n) block along axis 1 (asc)."""
    rows, n = x.shape
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            idx = jnp.arange(n)
            partner = idx ^ j
            xp = x[:, partner]
            up = (idx & k) == 0                 # ascending region
            first = idx < partner
            keep_min = jnp.where(up, first, ~first)
            lo = jnp.minimum(x, xp)
            hi = jnp.maximum(x, xp)
            x = jnp.where(keep_min[None, :], lo, hi)
            j //= 2
        k *= 2
    return x


def _kernel(x_ref, o_ref):
    o_ref[...] = _bitonic_block(x_ref[...])


def block_sort(x: jax.Array, *, block: int = 1024,
               interpret: bool | None = None) -> jax.Array:
    """Sort contiguous blocks of a (n,) array (n, block powers of two)."""
    n = x.shape[0]
    block = min(block, n)
    assert n % block == 0 and (block & (block - 1)) == 0
    xb = x.reshape(n // block, block)
    out = pl.pallas_call(
        _kernel,
        grid=(n // block,),
        in_specs=[pl.BlockSpec((1, block), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xb.shape, x.dtype),
        interpret=resolve_interpret(interpret),
    )(xb)
    return out.reshape(n)
