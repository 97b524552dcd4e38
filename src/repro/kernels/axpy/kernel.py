"""Streaming axpy (paper kernel #4): y = a*x + y, tiled through VMEM."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _kernel(a_ref, x_ref, y_ref, o_ref):
    o_ref[...] = a_ref[0] * x_ref[...] + y_ref[...]


def axpy(a, x, y, *, block: int = 8192, interpret: bool | None = None):
    n = x.shape[0]
    block = min(block, n)
    while n % block:
        block -= 1
    return pl.pallas_call(
        _kernel,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), x.dtype),
        interpret=resolve_interpret(interpret),
    )(jnp.reshape(a, (1,)).astype(x.dtype), x, y)
