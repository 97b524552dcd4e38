"""Fused gesummv (paper kernel #2): y = alpha*A@x + beta*B@x.

Row-blocked: each grid step streams a (bm, K) stripe of BOTH matrices into
VMEM (one pass over memory — the fusion the paper's cluster implementation
exploits) against a VMEM-resident x.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _kernel(ab_ref, a_ref, b_ref, x_ref, o_ref):
    alpha, beta = ab_ref[0], ab_ref[1]
    x = x_ref[...]
    ya = jax.lax.dot_general(a_ref[...], x, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    yb = jax.lax.dot_general(b_ref[...], x, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    o_ref[...] = (alpha * ya + beta * yb).astype(o_ref.dtype)


def gesummv(alpha, beta, a, b, x, *, bm: int = 128, interpret: bool | None = None):
    N, K = a.shape
    bm = min(bm, N)
    while N % bm:
        bm -= 1
    ab = jnp.stack([jnp.asarray(alpha, jnp.float32),
                    jnp.asarray(beta, jnp.float32)])
    return pl.pallas_call(
        _kernel,
        grid=(N // bm,),
        in_specs=[
            pl.BlockSpec((2,), lambda i: (0,)),
            pl.BlockSpec((bm, K), lambda i: (i, 0)),
            pl.BlockSpec((bm, K), lambda i: (i, 0)),
            pl.BlockSpec((K,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bm,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((N,), x.dtype),
        interpret=resolve_interpret(interpret),
    )(ab, a, b, x)
