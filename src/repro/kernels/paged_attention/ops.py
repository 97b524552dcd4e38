"""Jitted public wrapper for the paged-attention kernels.

Dispatches on pool rank: (B, n_pages, page, Hkv, D) is the per-slot layout,
(total_pages, page, Hkv, D) the serving engine's shared global pool, read by
the ragged kernel (block tables may then point several slots at the SAME
physical page — prefix sharing resolves inside the SMEM-driven page DMAs).
``table_residency`` selects the per-slot kernel's translation baseline.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.paged_attention.kernel import (paged_attention,
                                                  ragged_paged_decode)
from repro.kernels.paged_attention.ref import (paged_attention_global_ref,
                                               paged_attention_ref)


@functools.partial(jax.jit, static_argnames=("softcap", "table_residency",
                                             "interpret", "use_pallas"))
def paged_decode(q, k_pool, v_pool, block_table, lengths, *, softcap=None,
                 table_residency: str = "smem", interpret=None,
                 use_pallas: bool = True):
    is_global = k_pool.ndim == 4
    if not use_pallas:
        ref = paged_attention_global_ref if is_global else paged_attention_ref
        return ref(q, k_pool, v_pool, block_table, lengths, softcap=softcap)
    if is_global:
        return ragged_paged_decode(q, k_pool, v_pool, block_table, lengths,
                                   softcap=softcap, interpret=interpret)
    return paged_attention(q, k_pool, v_pool, block_table, lengths,
                           softcap=softcap, table_residency=table_residency,
                           interpret=interpret)
