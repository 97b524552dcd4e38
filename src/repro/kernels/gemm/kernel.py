"""Tiled GEMM with double-buffered HBM->VMEM pipelining (paper kernel #1).

The Snitch cluster's DMA double buffering maps to the Pallas grid pipeline:
grid (M/bm, N/bn, K/bk) with a VMEM fp32 accumulator revisited across the K
axis; the next K-tile's DMA overlaps the current tile's MXU work. Tile sizes
default to MXU-aligned 128 multiples (TPU target); interpret mode validates
on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def gemm(a: jax.Array, b: jax.Array, *, bm: int = 128, bn: int = 128,
         bk: int = 128, interpret: bool | None = None) -> jax.Array:
    """a: (M, K) @ b: (K, N) -> (M, N)."""
    M, K = a.shape
    _, N = b.shape
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    n_k = K // bk
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        interpret=resolve_interpret(interpret),
    )(a, b)
