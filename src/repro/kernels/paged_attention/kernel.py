"""Paged decode attention — the paper's technique as a TPU kernel.

The block table is passed as a SCALAR-PREFETCH operand: it lives in SMEM and
drives the BlockSpec index maps, so every KV page's HBM->VMEM DMA is issued
*through the translation* with zero per-access walk cost. This is the TPU
realization of the paper's LLC-resident page-table walk (translations in
fast memory next to the walker), while the bulk KV pages stream around it
(the DMA-bypasses-LLC path). ``table_residency="hbm"`` instead loads
translations from HBM inside the kernel — the paper's LLC-off baseline.

Layout (per sequence-batch):
  q:        (B, Hq, D)
  k_pool:   (B, n_pages, page, Hkv, D)  physical pages
  v_pool:   (B, n_pages, page, Hkv, D)
  table:    (B, n_pages) int32          logical -> physical
  lengths:  (B,) int32                  valid tokens per sequence
  out:      (B, Hq, D)

Grid: (B, n_pages) — online softmax accumulates across the page axis in VMEM
scratch, exactly the Snitch double-buffered DMA pattern (pages are fetched
one grid step ahead by the Pallas pipeline while the previous page computes).

``paged_attention_global`` is the same kernel over the serving engine's
GLOBAL layout: ONE physical pool shared by every slot —
  k_pool / v_pool: (total_pages, page, Hkv, D)
  table:           (B, max_pages) int32 into the global pool; entries
                   >= total_pages are the NULL page marking unallocated
                   slots (they only appear at logical positions >= length,
                   so the length mask already excludes them; the index map
                   just clamps them to a safe page for the DMA).
Because the per-sequence translation happens in the SMEM index map, two
slots whose tables point at the same physical page (copy-on-write prefix
sharing) stream it from the same HBM address — the kernel IS the map-don't-
copy path at decode granularity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _kernel(table_ref, len_ref,        # scalar-prefetch (SMEM)
            q_ref, k_ref, v_ref,       # VMEM blocks
            o_ref,                     # output block
            m_ref, l_ref, acc_ref,     # VMEM scratch carried across pages
            *, page: int, n_pages: int, softcap):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _, Hkv, G, D = q_ref.shape                 # q block: (1, Hkv, G, D)
    # KV block: (1, 1, page, Hkv, D) per-slot, (1, page, Hkv, D) global —
    # the same page once the leading singleton block dims are indexed away.
    lead = (0,) * (k_ref.ndim - 3)
    dt = jnp.promote_types(q_ref.dtype, k_ref.dtype)
    length = len_ref[b]
    pos = p * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    valid = pos < length

    # KV head h serves the G query rows q_ref[0, h]: every contraction is one
    # 2-D dot per KV head (a batched dot over heads does not lower on the
    # TPU). q.k of bf16 operands is exact in float32 accumulation; the
    # probabilities are float32, so PV runs at HIGHEST.
    for h in range(Hkv):
        kv = lead + (slice(None), h, slice(None))
        s = jax.lax.dot_general(q_ref[0, h].astype(dt), k_ref[kv].astype(dt),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (D ** -0.5)                    # (G, page)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        # mask before the max: stale KV in a recycled page must not move m
        s = jnp.where(valid, s, -jnp.inf)

        m_prev, l_prev = m_ref[h], l_ref[h]    # (G, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p_ = jnp.exp(s - m_safe)
        p_ = jnp.where(valid, p_, 0.0)
        corr = jnp.where(jnp.isfinite(m_prev),
                         jnp.exp(m_prev - m_safe), 0.0)
        l_ref[h] = l_prev * corr + jnp.sum(p_, axis=-1, keepdims=True)
        pv = jnp.dot(p_, v_ref[kv].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
        acc_ref[h] = acc_ref[h] * corr + pv   # (G, D)
        m_ref[h] = m_new

    @pl.when(p == n_pages - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _decode_call(q, k_pool, v_pool, block_table, lengths, kv_spec, *,
                 n_pages: int, softcap, interpret):
    """One pallas_call over the grid (B, n_pages); ``kv_spec`` maps a grid
    step to its KV page through the SMEM-resident table. q is viewed as
    (B, Hkv, G, D): q head h attends KV head h // G."""
    B, Hq, D = q.shape
    page, Hkv = k_pool.shape[-3], k_pool.shape[-2]
    G = Hq // Hkv
    q_spec = pl.BlockSpec((1, Hkv, G, D), lambda b, p, tbl, ln: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, page=page, n_pages=n_pages,
                               softcap=softcap)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=resolve_interpret(interpret),
    )(block_table, lengths, q.reshape(B, Hkv, G, D), k_pool, v_pool)
    return out.reshape(B, Hq, D)


def paged_attention(q, k_pool, v_pool, block_table, lengths, *,
                    softcap=None, table_residency: str = "smem",
                    interpret: bool | None = None):
    """See module docstring. Returns (B, Hq, D)."""
    B, Hq, D = q.shape
    _, n_pages, page, Hkv, _ = k_pool.shape

    if table_residency == "hbm":
        # LLC-off baseline: translations are NOT prefetched; resolve them
        # with an explicit gather pass (pays the full-table data movement),
        # then run the kernel on an identity table.
        k_pool = jnp.take_along_axis(
            k_pool, block_table[:, :, None, None, None], axis=1)
        v_pool = jnp.take_along_axis(
            v_pool, block_table[:, :, None, None, None], axis=1)
        block_table = jnp.broadcast_to(
            jnp.arange(n_pages, dtype=jnp.int32), block_table.shape)

    # THE TECHNIQUE: the KV page DMA source address goes through the
    # SMEM-resident block table (IOVA -> PA translation at zero cost)
    kv_spec = pl.BlockSpec((1, 1, page, Hkv, D),
                           lambda b, p, tbl, ln: (b, tbl[b, p], 0, 0, 0))
    return _decode_call(q, k_pool, v_pool, block_table, lengths, kv_spec,
                        n_pages=n_pages, softcap=softcap,
                        interpret=interpret)


def paged_attention_global(q, k_pool, v_pool, block_table, lengths, *,
                           softcap=None, table_residency: str = "smem",
                           interpret: bool | None = None):
    """Decode attention over the GLOBAL (shared-pool) layout — see module
    docstring. q: (B, Hq, D); pools: (total, page, Hkv, D); table: (B, P)
    int32 with NULL (>= total) marking unallocated entries. Returns
    (B, Hq, D)."""
    B, Hq, D = q.shape
    total, page, Hkv, _ = k_pool.shape
    P = block_table.shape[1]

    if table_residency == "hbm":
        # LLC-off baseline: gather each sequence's pages out of the shared
        # pool into a private per-slot pool (pays the full data movement),
        # then run the per-slot kernel on an identity table.
        null = (block_table >= total)[:, :, None, None, None]
        safe = jnp.where(block_table >= total, 0, block_table)
        kg = jnp.where(null, 0, k_pool[safe]).astype(k_pool.dtype)
        vg = jnp.where(null, 0, v_pool[safe]).astype(v_pool.dtype)
        ident = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32), (B, P))
        return paged_attention(q, kg, vg, ident, lengths, softcap=softcap,
                               interpret=interpret)

    def kv_index(b, p, tbl, ln):
        # THE TECHNIQUE, shared-pool form: the DMA source page is the
        # SMEM-resident translation. NULL entries are clamped to page 0 for
        # a safe (dead) fetch — their logical positions are >= length, so
        # the kernel's validity mask already zeroes their contribution.
        t = tbl[b, p]
        return (jnp.where(t >= total, 0, t), 0, 0, 0)

    return _decode_call(q, k_pool, v_pool, block_table, lengths,
                        pl.BlockSpec((1, page, Hkv, D), kv_index),
                        n_pages=P, softcap=softcap, interpret=interpret)
