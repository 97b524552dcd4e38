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

``ragged_paged_decode`` serves the engine's GLOBAL layout: ONE physical pool
shared by every slot, read where it lies in HBM —
  k_pool / v_pool: (total_pages, page, Hkv, D), or the layer loop's stacked
                   (n_blocks, total_pages, page, Hkv, D) with ``layer``
  table:           (B, max_pages) int32 into the global pool; entries
                   >= total_pages are the NULL page marking unallocated
                   slots
  layer:           int32 scalar, the stacked pool's layer (None for 4-D)
Grid: (B,). Row b reads pages 0 .. ceil(len_b / page) - 1 and no other, a
block of pages per compute step: one async copy a page, issued through the
SMEM-resident table, double-buffered so that the next block's copies (the
next live row's first block after a row's last) run while the current
block computes. A row with no KV (length 0, or a NULL first entry) issues
no copy and writes zeros. Two slots whose tables point at the same physical
page (copy-on-write prefix sharing) stream it from the same HBM address —
the kernel IS the map-don't-copy path at decode granularity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
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
    dt = jnp.promote_types(q_ref.dtype, k_ref.dtype)
    length = len_ref[b]
    pos = p * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    valid = pos < length

    # KV head h serves the G query rows q_ref[0, h]: every contraction is one
    # 2-D dot per KV head (a batched dot over heads does not lower on the
    # TPU). q.k of bf16 operands is exact in float32 accumulation; the
    # probabilities are float32, so PV runs at HIGHEST.
    for h in range(Hkv):
        kv = (0, 0, slice(None), h, slice(None))   # (1, 1, page, Hkv, D)
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
    # q is viewed as (B, Hkv, G, D): q head h attends KV head h // G
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


# ------------------------------------------------------------------ ragged

# K bytes one compute block aims for: 32 pages of 16 KiB (qwen2-7b's 4 KV
# heads x 128 x 16 tokens in bf16), 4 of 128 KiB (32 KV heads).
_BLOCK_BYTES = 512 * 1024


def _head_rows(blk):
    """Yield (h, rows): KV head h of a (ppb, page, Hkv, D) VMEM block as
    (ppb * page, D). The block is read as (tokens x heads, D) rows and each
    head's rows are a strided load; in bfloat16 two heads share a 32-bit
    word (the even head in its low half), so one load serves both."""
    ppb, page, hkv, d = blk.shape
    t = ppb * page
    rows = blk.reshape(t * hkv, d)
    if hkv == 1:
        yield 0, rows[...]
        return
    if blk.dtype == jnp.float32:
        for h in range(hkv):
            yield h, rows[pl.ds(h, t, stride=hkv), :]
        return
    if blk.dtype != jnp.bfloat16 or hkv % 2:
        raise NotImplementedError(
            f"ragged paged decode reads float32 pools, or bfloat16 pools "
            f"with one or an even number of KV heads; got {blk.dtype} with "
            f"{hkv} heads")
    words = rows.bitcast(jnp.uint32)
    for w in range(hkv // 2):
        x = words[pl.ds(w, t, stride=hkv // 2), :]
        lo = pltpu.bitcast(x << 16, jnp.float32)
        hi = pltpu.bitcast(x & jnp.uint32(0xFFFF0000), jnp.float32)
        yield 2 * w, lo.astype(jnp.bfloat16)
        yield 2 * w + 1, hi.astype(jnp.bfloat16)


def _pv(p, v):
    """P.V in float32: float32 probabilities against float32 values."""
    return jnp.dot(p, v.astype(jnp.float32), precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _attend(q_ref, k_blk, v_blk, m_ref, l_ref, acc_ref, first, length,
            softcap):
    """Online-softmax update of every KV head by one block of tokens
    [first, first + ppb * page), of which those before ``length`` count."""
    ppb, page, _, d = k_blk.shape
    valid = first + lax.broadcasted_iota(jnp.int32, (1, ppb * page), 1) \
        < length
    dt = jnp.promote_types(q_ref.dtype, k_blk.dtype)
    # KV head h serves the G query rows q_ref[0, h]: one 2-D dot per head
    for (h, k), (_, v) in zip(_head_rows(k_blk), _head_rows(v_blk)):
        s = lax.dot_general(q_ref[0, h].astype(dt), k.astype(dt),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = s * (d ** -0.5)                            # (G, ppb * page)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        # mask before the max: stale KV in a recycled page must not move m.
        # Every block computed holds a valid token, so m stays finite.
        s = jnp.where(valid, s, -jnp.inf)
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * corr + _pv(p, v)
        m_ref[h] = m_new


def _ragged_kernel(layer_ref, tbl_ref, len_ref,        # scalar prefetch
                   q_ref, k_hbm, v_hbm,                # q block; pools (HBM)
                   o_ref,
                   k_buf, v_buf, sems, slot_ref, m_ref, l_ref, acc_ref,
                   *, softcap):
    b = pl.program_id(0)
    n_rows, n_table = tbl_ref.shape
    _, ppb, page, _, _ = k_buf.shape
    total = k_hbm.shape[-4]
    stacked = k_hbm.ndim == 5

    def live_pages(row):
        """Pages of ``row`` below its length; none where its first entry is
        NULL (a masked row: the append through its NULL table was dropped,
        so it holds no KV)."""
        length = jnp.minimum(len_ref[row], n_table * page)
        return jnp.where(tbl_ref[row, 0] < total, (length + page - 1) // page,
                         0)

    def each_copy(row, blk, slot, act):
        """``act`` on the copies of block ``blk`` of ``row`` into buffer
        ``slot``: one of K and one of V for each of its pages below the
        row's length."""
        first = blk * ppb

        def page(j, carry):
            t = tbl_ref[row, first + j]
            t = jnp.where(t < total, t, 0)   # a live position maps no NULL
            src = (layer_ref[0], t) if stacked else (t,)
            act(pltpu.make_async_copy(k_hbm.at[src], k_buf.at[slot, j],
                                      sems.at[0, slot]))
            act(pltpu.make_async_copy(v_hbm.at[src], v_buf.at[slot, j],
                                      sems.at[1, slot]))
            return carry
        lax.fori_loop(0, jnp.clip(live_pages(row) - first, 0, ppb), page, 0)

    def start(row, blk, slot):
        each_copy(row, blk, slot, lambda c: c.start())

    def wait(row, blk, slot):
        each_copy(row, blk, slot, lambda c: c.wait())

    def next_live(row):
        """The first row after ``row`` that holds KV, or n_rows."""
        def body(i, nxt):
            r = n_rows - 1 - i
            return jnp.where((r > row) & (live_pages(r) > 0), r, nxt)
        return lax.fori_loop(0, n_rows, body, jnp.int32(n_rows))

    @pl.when(b == 0)
    def _first():
        # a page past a row's length is never copied: its buffer rows keep
        # what an earlier block left, finite once zeroed here, so the zero
        # probabilities the mask gives them leave P.V exact
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        row = next_live(-1)

        @pl.when(row < n_rows)
        def _():
            start(row, 0, 0)

    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    length = jnp.minimum(len_ref[b], n_table * page)
    n_blk = (live_pages(b) + ppb - 1) // ppb

    def block(i, slot):
        nxt = 1 - slot

        @pl.when(i + 1 < n_blk)
        def _():
            start(b, i + 1, nxt)

        @pl.when(i + 1 == n_blk)
        def _():
            row = next_live(b)

            @pl.when(row < n_rows)
            def _():
                start(row, 0, nxt)

        wait(b, i, slot)
        _attend(q_ref, k_buf.at[slot], v_buf.at[slot], m_ref, l_ref, acc_ref,
                i * ppb * page, length, softcap)
        return nxt

    slot_ref[0] = lax.fori_loop(0, n_blk, block, slot_ref[0])
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)) \
        .astype(o_ref.dtype)


def ragged_paged_decode(q, k_pool, v_pool, block_table, lengths, layer=None,
                        *, softcap=None, interpret: bool | None = None):
    """Decode attention over the GLOBAL (shared-pool) layout — see module
    docstring. q: (B, Hq, D); pools (total, page, Hkv, D), or stacked
    (n_blocks, total, page, Hkv, D) read at ``layer``; table (B, P) int32
    with NULL (>= total) marking unallocated entries; lengths (B,) or one
    for every row. Returns (B, Hq, D)."""
    B, Hq, D = q.shape
    page, Hkv = k_pool.shape[-3], k_pool.shape[-2]
    if block_table.ndim != 2:
        raise ValueError(
            "ragged paged decode serves one layer's (B, P) block table; got "
            f"a table of shape {block_table.shape} (a leading stacked-blocks "
            "axis belongs inside the layer scan)")
    if (k_pool.ndim == 5) != (layer is not None):
        raise ValueError("a stacked pool is read through a layer index, and "
                         "only a stacked pool")
    G = Hq // Hkv
    P = block_table.shape[1]
    page_bytes = page * Hkv * D * k_pool.dtype.itemsize
    ppb = max(1, min(P, _BLOCK_BYTES // page_bytes))
    layer = jnp.reshape(jnp.asarray(0 if layer is None else layer, jnp.int32),
                        (1,))
    q_spec = pl.BlockSpec((1, Hkv, G, D), lambda b, *_: (b, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((2, ppb, page, Hkv, D), k_pool.dtype)
    # The most the kernel reads: K and V of every table entry. What it
    # reads in a call, the live pages, is known only on the device.
    tokens = B * P * page
    cost = pl.CostEstimate(
        flops=4 * tokens * Hq * D, transcendentals=tokens * Hq,
        bytes_accessed=2 * B * P * page_bytes + 2 * q.size * q.dtype.itemsize)
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, softcap=softcap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[q_spec, hbm, hbm],
            out_specs=q_spec,
            scratch_shapes=[
                buf, buf,
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((Hkv, G, 1), jnp.float32),
                pltpu.VMEM((Hkv, G, 1), jnp.float32),
                pltpu.VMEM((Hkv, G, D), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        # rows run in order: a row prefetches the next one's first block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=cost,
        interpret=resolve_interpret(interpret),
    )(layer, block_table.astype(jnp.int32),
      jnp.broadcast_to(lengths, (B,)).astype(jnp.int32),
      q.reshape(B, Hkv, G, D), k_pool, v_pool)
    return out.reshape(B, Hq, D)
