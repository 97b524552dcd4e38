"""Full sort = Pallas block-local bitonic sort + global bitonic merge stages.

The global stages are data-independent compare-exchanges at stride >= block,
expressed as reshape/min/max — bandwidth-bound, like the paper's DMA merge
passes.
"""
import functools

import jax
import jax.numpy as jnp

from repro.kernels.mergesort.kernel import block_sort
from repro.kernels.mergesort.ref import sort_ref


def _global_stage(x, j):
    """One all-ascending compare-exchange ladder step at stride j."""
    n = x.shape[0]
    idx = jnp.arange(n)
    partner = idx ^ j
    xp = x[partner]
    keep_min = idx < partner
    return jnp.where(keep_min, jnp.minimum(x, xp), jnp.maximum(x, xp))


@functools.partial(jax.jit, static_argnames=("block", "interpret",
                                             "use_pallas"))
def mergesort(x, *, block: int = 1024, interpret=None,
              use_pallas: bool = True):
    """Ascending sort of a power-of-two length array."""
    if not use_pallas:
        return sort_ref(x)
    n = x.shape[0]
    assert (n & (n - 1)) == 0, "power-of-two length"
    block = min(block, n)
    # bitonic structure requires alternating block directions; simplest
    # correct composition: local sort produces ascending blocks, then global
    # bitonic stages re-establish order per merge level k > block using full
    # compare-exchange ladders (j from k/2 down to 1).
    x = block_sort(x, block=block, interpret=interpret)
    k = block * 2
    while k <= n:
        # direction pattern for this level needs bitonic inputs: flip odd blocks
        nb = n // (k // 2)
        xb = x.reshape(nb, k // 2)
        flip = (jnp.arange(nb) % 2) == 1
        xb = jnp.where(flip[:, None], xb[:, ::-1], xb)
        x = xb.reshape(n)
        j = k // 2
        while j >= 1:
            x = _global_stage(x, j)
            j //= 2
        k *= 2
    return x
