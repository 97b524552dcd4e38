"""Attention: GQA projections, chunked flash attention, paged-KV decode.

Three execution paths in pure JAX (Pallas kernels in repro.kernels are the
TPU-target equivalents: the decode kernel serves on a TPU, the others are
selected via ``cfg.use_pallas``):

  * ``flash_attention`` — memory-efficient chunked online-softmax attention
    (train / prefill). Scans over KV chunks carrying (m, l, acc).
  * ``paged_decode_attention`` — single-token decode over a *paged* KV pool
    with block-table indirection: the paper's technique. On a TPU the
    ragged Pallas kernel (kernels/paged_attention) reads the shared pool
    through the block table scalar-prefetched to SMEM, the analogue of the
    paper's PTW-in-LLC; elsewhere the gather through the table is the IOVA
    translation.
  * ``sp_decode_attention`` — sequence-parallel decode (long_500k): KV pages
    sharded over the data axis, flash-decoding-style (m, l, acc) merge via
    psum — page placement is sequence-affine (shard i owns logical pages
    [i*P/n, (i+1)*P/n)), so translation stays shard-local, mirroring the
    paper's requirement that DMA bursts never cross the translation cache.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import resolve_interpret
from repro.kernels.paged_attention.kernel import ragged_paged_decode
from repro.models.params import ParamSpec


# ------------------------------------------------------------ projections

def attention_specs(cfg, cross: bool = False) -> dict:
    d, dh = cfg.d_model, cfg.d_head
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    dt = jnp.dtype(cfg.param_dtype)
    out = {
        "wq": ParamSpec((d, hq, dh), dt, P("fsdp", "tp", None)),
        "wk": ParamSpec((d, hkv, dh), dt, P("fsdp", "tp", None)),
        "wv": ParamSpec((d, hkv, dh), dt, P("fsdp", "tp", None)),
        "wo": ParamSpec((hq, dh, d), dt, P("tp", None, "fsdp")),
    }
    if cfg.qkv_bias and not cross:
        out["bq"] = ParamSpec((hq, dh), dt, P("tp", None), init="zeros")
        out["bk"] = ParamSpec((hkv, dh), dt, P("tp", None), init="zeros")
        out["bv"] = ParamSpec((hkv, dh), dt, P("tp", None), init="zeros")
    if cross:
        out["gate"] = ParamSpec((), dt, P(), init="zeros")  # llama-vision gated x-attn
    return out


def qkv_proj(p: dict, x: jax.Array, kv_x: Optional[jax.Array] = None):
    kv_x = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", kv_x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return q, k, v


def out_proj(p: dict, o: jax.Array) -> jax.Array:
    y = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    if "gate" in p:
        y = jnp.tanh(p["gate"].astype(jnp.float32)).astype(y.dtype) * y
    return y


# ------------------------------------------------------------ flash attention

def _softcap(s: jax.Array, cap: Optional[float]) -> jax.Array:
    return cap * jnp.tanh(s / cap) if cap is not None else s


def _chunk_of(total: int, want: int) -> int:
    """Largest divisor of ``total`` that is <= want."""
    c = min(want, total)
    while total % c != 0:
        c -= 1
    return c


def _block_mask(q_pos, kv_pos, causal, window):
    mask = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), bool)
    if causal:
        mask &= kv_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= q_pos[:, None] - kv_pos[None, :] < window
    return mask


@functools.lru_cache(maxsize=64)
def _flash_core(causal: bool, window, softcap, C: int, QC: int, unroll: bool):
    """custom_vjp flash attention core for fixed static config.

    Saves only (q, k, v, out, lse) — the backward recomputes score blocks
    (FlashAttention-style), so live memory is one (QC, C) block per step
    instead of every block's residuals.
    """
    scale_of = lambda D: D ** -0.5

    def fwd_blocks(q, k, v, q_offset):
        B, Sq, H, D = q.shape
        Skv = k.shape[1]
        nq, nkv = Sq // QC, Skv // C
        scale = scale_of(D)
        kc = k.reshape(B, nkv, C, H, D).swapaxes(0, 1)
        vc = v.reshape(B, nkv, C, H, D).swapaxes(0, 1)
        qc = q.reshape(B, nq, QC, H, D).swapaxes(0, 1)

        def q_step(_, inp):
            qi, i = inp
            q_pos = i * QC + jnp.arange(QC) + q_offset

            def kv_step(carry, kv_inp):
                m, l, acc = carry
                kj, vj, j = kv_inp
                kv_pos = j * C + jnp.arange(C)
                s = jnp.einsum("bqhd,bchd->bqhc", qi, kj,
                               preferred_element_type=jnp.float32) * scale
                s = _softcap(s, softcap)
                mask = _block_mask(q_pos, kv_pos, causal, window)
                s = jnp.where(mask[None, :, None, :], s, -jnp.inf)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                p_ = jnp.exp(s - m_safe[..., None])
                corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
                l_new = l * corr + jnp.sum(p_, axis=-1)
                acc_new = acc * corr[..., None] + jnp.einsum(
                    "bqhc,bchd->bqhd", p_.astype(vj.dtype), vj,
                    preferred_element_type=jnp.float32)
                return (m_new, l_new, acc_new), None

            m0 = jnp.full((B, QC, H), -jnp.inf, jnp.float32)
            l0 = jnp.zeros((B, QC, H), jnp.float32)
            a0 = jnp.zeros((B, QC, H, D), jnp.float32)
            (m, l, acc), _ = jax.lax.scan(
                kv_step, (m0, l0, a0), (kc, vc, jnp.arange(nkv)),
                unroll=unroll)
            out = (acc / jnp.maximum(l, 1e-20)[..., None]).astype(q.dtype)
            m_s = jnp.where(jnp.isfinite(m), m, 0.0)
            lse = m_s + jnp.log(jnp.maximum(l, 1e-20))      # (B,QC,H)
            return 0, (out, lse)

        _, (outs, lses) = jax.lax.scan(q_step, 0, (qc, jnp.arange(nq)),
                                       unroll=unroll)
        out = outs.swapaxes(0, 1).reshape(B, Sq, H, D)
        lse = lses.swapaxes(0, 1).reshape(B, Sq, H)
        return out, lse

    @jax.custom_vjp
    def core(q, k, v, q_offset):
        out, _ = fwd_blocks(q, k, v, q_offset)
        return out

    def core_fwd(q, k, v, q_offset):
        out, lse = fwd_blocks(q, k, v, q_offset)
        return out, (q, k, v, out, lse, q_offset)

    def core_bwd(res, do):
        q, k, v, out, lse, q_offset = res
        B, Sq, H, D = q.shape
        Skv = k.shape[1]
        nq, nkv = Sq // QC, Skv // C
        scale = scale_of(D)
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)                             # (B,Sq,H)
        qc = q.reshape(B, nq, QC, H, D).swapaxes(0, 1)
        doc = do.reshape(B, nq, QC, H, D).swapaxes(0, 1)
        lsec = lse.reshape(B, nq, QC, H).swapaxes(0, 1)
        dc = delta.reshape(B, nq, QC, H).swapaxes(0, 1)
        kc = k.reshape(B, nkv, C, H, D).swapaxes(0, 1)
        vc = v.reshape(B, nkv, C, H, D).swapaxes(0, 1)

        def q_step(carry, inp):
            dk, dv = carry                                   # fp32 (nkv,B,C,H,D)
            qi, doi, lsei, di, i = inp
            q_pos = i * QC + jnp.arange(QC) + q_offset

            def kv_step(dkv, kv_inp):
                dkj, dvj, kj, vj, j = kv_inp
                kv_pos = j * C + jnp.arange(C)
                s_raw = jnp.einsum("bqhd,bchd->bqhc", qi, kj,
                                   preferred_element_type=jnp.float32) * scale
                s = _softcap(s_raw, softcap)
                mask = _block_mask(q_pos, kv_pos, causal, window)
                s = jnp.where(mask[None, :, None, :], s, -jnp.inf)
                p_ = jnp.exp(s - lsei[..., None])            # (B,QC,H,C)
                dp = jnp.einsum("bqhd,bchd->bqhc", doi.astype(jnp.float32),
                                vj.astype(jnp.float32))
                ds = p_ * (dp - di[..., None])
                if softcap is not None:
                    ds = ds * (1.0 - jnp.square(jnp.tanh(s_raw / softcap)))
                ds = jnp.where(mask[None, :, None, :], ds, 0.0) * scale
                dq_i = jnp.einsum("bqhc,bchd->bqhd", ds,
                                  kj.astype(jnp.float32))
                dk_j = dkj + jnp.einsum("bqhc,bqhd->bchd", ds,
                                        qi.astype(jnp.float32))
                dv_j = dvj + jnp.einsum("bqhc,bqhd->bchd", p_,
                                        doi.astype(jnp.float32))
                return dq_i, (dk_j, dv_j)

            def kv_scan(dq_acc, kv_inp):
                dq_i, dkv_j = kv_step(None, kv_inp)
                return dq_acc + dq_i, dkv_j

            dq0 = jnp.zeros((B, QC, H, D), jnp.float32)
            dq_i, (dk, dv) = jax.lax.scan(
                kv_scan, dq0, (dk, dv, kc, vc, jnp.arange(nkv)),
                unroll=unroll)
            return (dk, dv), dq_i

        dk0 = jnp.zeros((nkv, B, C, H, D), jnp.float32)
        dv0 = jnp.zeros((nkv, B, C, H, D), jnp.float32)
        (dk, dv), dqs = jax.lax.scan(
            q_step, (dk0, dv0), (qc, doc, lsec, dc, jnp.arange(nq)),
            unroll=unroll)
        dq = dqs.swapaxes(0, 1).reshape(B, Sq, H, D).astype(q.dtype)
        dk = dk.swapaxes(0, 1).reshape(B, Skv, H, D).astype(k.dtype)
        dv = dv.swapaxes(0, 1).reshape(B, Skv, H, D).astype(v.dtype)
        return dq, dk, dv, None

    core.defvjp(core_fwd, core_bwd)
    return core


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    q_offset: int | jax.Array = 0,
                    kv_chunk: int = 1024,
                    q_chunk: int = 512,
                    unroll: bool = False) -> jax.Array:
    """Double-chunked flash attention with GQA and a flash backward.

    q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D). Returns (B, Sq, Hq, D).
    Outer scan over q chunks, inner over kv chunks: live score block is
    (B, q_chunk, Hq, kv_chunk) fp32. K/V are repeated to the full Hq so head
    sharding propagates under TP (GQA head counts rarely divide the mesh);
    jnp.repeat's transpose sums group gradients back to the KV heads.
    The backward is a custom VJP saving only (q, k, v, out, lse).

    NOTE (roofline): causal masking is applied but masked blocks are still
    computed — the pure-JAX path pays ~2x attention FLOPs on causal shapes;
    the Pallas flash kernel (kernels/flash_attention) skips them on TPU.
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    C = _chunk_of(Skv, kv_chunk)
    QC = _chunk_of(Sq, q_chunk)
    core = _flash_core(bool(causal), window, softcap, C, QC, bool(unroll))
    q_offset = jnp.asarray(q_offset, jnp.int32)
    return core(q, k, v, q_offset)


# ------------------------------------------------------------ paged KV cache

class PagedKV(NamedTuple):
    """Paged KV pool + block table (the SVA structures). Two layouts:

    per-slot (dry-run / staging-copy baseline):
      k_pool / v_pool: (B, n_pages, page, Hkv, D) — each batch slot owns a
                       private row of physical pages.
      block_table:     (B, n_pages) int32, a permutation of [0, n_pages).

    global (zero-copy serving): ONE physical pool shared by all slots —
      k_pool / v_pool: (total_pages, page, Hkv, D)
      block_table:     (B, max_pages) int32 into the global pool; entries
                       >= total_pages are the NULL page (writes dropped,
                       reads zero) marking unallocated table slots.

    length: () or (B,) int32 — tokens currently valid per sequence.
    The layouts are statically distinguishable by rank (see
    ``is_global_layout``), so one jitted step handles either.

    layer: None, or an int32 scalar when this is one layer's view of a
    STACKED global pool inside the layer loop (``models/model._run_blocks``):
    k_pool / v_pool are then (n_blocks, total_pages, page, Hkv, D) — the
    loop carry — while block_table / length are the layer's own. The
    global-layout reads and writes index ``[layer, page, slot]`` (see
    ``pool_index``), so no layer slice is taken and none is restacked.
    """
    k_pool: jax.Array
    v_pool: jax.Array
    block_table: jax.Array
    length: jax.Array
    layer: Optional[jax.Array] = None

    def pool_index(self, *idx):
        """Index into k_pool / v_pool for this layer: ``idx`` behind the
        stacked pool's layer index when the view has one."""
        return idx if self.layer is None else (self.layer, *idx)

    @property
    def page_size(self) -> int:
        return self.k_pool.shape[-3]

    @property
    def capacity(self) -> int:
        """Per-sequence token capacity."""
        if is_global_layout(self):
            return self.block_table.shape[-1] * self.page_size
        return self.k_pool.shape[1] * self.k_pool.shape[2]


def is_global_layout(kv: PagedKV) -> bool:
    """True for the shared-global-pool layout (see PagedKV docstring).

    Rank-based and therefore robust to a leading stacked-blocks axis:
    per-slot pools carry (pages, page, H, D) behind the table's (B, P) dims
    (+3 ranks), a global pool carries (total, page, H, D) beside a (B, P)
    table (+2 ranks); a layer's view of the stacked global pool (``layer``
    set) adds the blocks axis to the pool alone (+3 ranks).
    """
    return kv.k_pool.ndim == kv.block_table.ndim + 2 + (kv.layer is not None)


def paged_kv_specs(cfg, batch: int, max_len: int, page_size: int,
                   n_kv_layers: int, stack: Optional[int] = None):
    """ShapeDtypeStruct-compatible ParamSpecs for a paged cache.

    ``stack``: leading (n_blocks,) axis when layers are scanned.
    """
    n_pages = -(-max_len // page_size)
    lead = (stack,) if stack else ()
    hkv, dh = cfg.n_kv_heads, cfg.d_head
    dt = jnp.dtype(cfg.activation_dtype)
    pool = lambda: ParamSpec(lead + (n_kv_layers, batch, n_pages, page_size, hkv, dh),
                             dt, P(*([None] * len(lead)), None, "batch", None, None, "tp", None))
    return PagedKV(
        k_pool=pool(), v_pool=pool(),
        block_table=ParamSpec(lead + (n_kv_layers, batch, n_pages), jnp.int32,
                              P(*([None] * len(lead)), None, "batch", None)),
        length=ParamSpec((), jnp.int32, P()),
    )


def gather_global_pages(kv: PagedKV):
    """Logical (B, P, page, Hkv, D) view of a GLOBAL shared pool through the
    per-slot block table — the IOVA translation for the shared-pool layout.
    NULL entries (>= total pages: unallocated table slots) read as exact
    zeros, matching a freshly zero-initialized per-slot pool bit-for-bit.
    A layer view of the stacked pool is read through ``[layer, page]`` in
    the same gather."""
    total = kv.k_pool.shape[-4]
    tbl = kv.block_table
    null = (tbl >= total)[..., None, None, None]
    safe = kv.pool_index(jnp.where(tbl >= total, 0, tbl))
    k = jnp.where(null, 0, kv.k_pool[safe]).astype(kv.k_pool.dtype)
    v = jnp.where(null, 0, kv.v_pool[safe]).astype(kv.v_pool.dtype)
    return k, v


def gather_pages(pool: jax.Array, table: jax.Array) -> jax.Array:
    """(B, n_pages, page, H, D) gathered through (B, n_pages) -> (B, S, H, D).

    This gather is the IOVA translation step of the paper: every access to the
    paged pool goes through the block table.
    """
    B, n_pages, page, H, D = pool.shape
    g = jnp.take_along_axis(pool, table[:, :, None, None, None], axis=1)
    return g.reshape(B, n_pages * page, H, D)


def ragged_decode_serves(kv: PagedKV) -> bool:
    """The one choice of decode-attention path: the ragged Pallas kernel
    (``kernels/paged_attention.ragged_paged_decode``) serves a global-layout
    pool wherever it runs compiled, i.e. on a TPU
    (``repro.kernels.resolve_interpret``). Elsewhere, and for the per-slot
    layout, the pure-JAX paths below serve."""
    return is_global_layout(kv) and not resolve_interpret()


def paged_decode_attention(q: jax.Array, kv: PagedKV, *,
                           softcap: Optional[float] = None) -> jax.Array:
    """One-token decode over the paged pool. q: (B, 1, Hq, D).

    On a global-layout pool, where ``ragged_decode_serves`` says so, the
    ragged Pallas kernel reads each row's live pages out of the pool where
    they lie: the block table lives in SMEM and drives the KV page DMAs —
    the paper's PTW-in-LLC realized on the serving hot path. A layer's view
    of the stacked pool is read through its layer index, so no layer slice
    is taken; NULL entries and masked rows cost nothing.

    Sliding-window layers use a pool whose capacity equals the window; the
    rolling write in ``paged_append`` makes every slot valid once
    length >= capacity (attention is permutation-invariant over the KV set,
    and RoPE is applied at write time, so slot order does not matter).

    SHARDING (perf iteration 1): the (pages, page) dims are NEVER merged —
    a reshape merging an unsharded-major with a sharded-minor dim cannot
    keep the sharding and forced XLA to all-gather the whole pool (measured
    ~1 GiB/link per block on decode_32k). All einsums/reductions run on the
    2-D page layout; the softmax reduction psums across the sharded dim.

    ZERO-COPY (perf iteration 2): attention is permutation-invariant over
    the KV set, so we attend over the pool in PHYSICAL order and translate
    only the METADATA — per-page logical positions through the inverse
    block table (B x n_pages ints) — instead of gathering the pool data
    itself. This removes a full pool copy per layer per step (the paper's
    map-don't-copy insight applied to the kernel's own data movement).
    This holds for the per-slot layout. The global layout's pure-JAX path
    gathers every table entry; the kernel reads only the live pages.
    """
    B, _, Hq, D = q.shape
    if ragged_decode_serves(kv):
        out = ragged_paged_decode(q[:, 0], kv.k_pool, kv.v_pool,
                                  kv.block_table, kv.length, kv.layer,
                                  softcap=softcap)
        return out[:, None]
    if is_global_layout(kv):
        # GLOBAL POOL: each sequence sees its pages in LOGICAL order through
        # its table row — the gather IS the IOVA translation. NULL entries
        # (unallocated) read as exact zeros, matching a freshly
        # zero-initialized per-slot pool bit-for-bit.
        T = kv.page_size
        P_ = kv.block_table.shape[1]
        k, v = gather_global_pages(kv)
        pos = (jnp.arange(P_)[:, None] * T
               + jnp.arange(T)[None, :])[None]             # logical (1,P,T)
        pos = jnp.broadcast_to(pos, (B, P_, T))
    else:
        k, v = kv.k_pool, kv.v_pool                        # physical order
        P_, T = k.shape[1], k.shape[2]
        inv = jnp.argsort(kv.block_table, axis=1)          # phys -> logical
        pos = inv[:, :, None] * T + jnp.arange(T)[None, None, :]   # (B,P,T)
    Hkv = k.shape[3]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bpthd->bhgpt", qg, k,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    s = _softcap(s, softcap)
    valid = pos < jnp.minimum(
        jnp.broadcast_to(kv.length, (B,))[:, None, None], kv.capacity)
    # Mask BEFORE the softmax max (like the Pallas kernel and
    # prefix_context_attention): an invalid slot must not contribute to
    # ``m``, or stale KV in a recycled page perturbs every valid
    # probability at the ULP level — outputs would depend on what a
    # page's PREVIOUS owner wrote (preempt/release recycling breaks
    # bit-identity even though the masked sum is mathematically the same).
    s = jnp.where(valid[:, None, None], s, -jnp.inf)
    m = jnp.max(s, axis=(-2, -1), keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    p_ = jnp.exp(s - m)
    p_ = jnp.where(valid[:, None, None], p_, 0.0)
    l = jnp.sum(p_, axis=(-2, -1), keepdims=True)
    o = jnp.einsum("bhgpt,bpthd->bhgd", p_.astype(jnp.float32),
                   v.astype(jnp.float32))
    o = o / jnp.maximum(l[..., 0], 1e-20)
    return o.reshape(B, 1, Hq, D).astype(q.dtype)


def prefix_context_attention(q: jax.Array, k_suf: jax.Array, v_suf: jax.Array,
                             kv: PagedKV, prefix_lens: jax.Array,
                             suffix_lens: jax.Array, *,
                             softcap: Optional[float] = None) -> jax.Array:
    """Suffix-only prefill attention with a CACHED prefix (CoW prefix
    sharing): row b's queries are the ``suffix_lens[b]`` right-padded tokens
    at logical positions ``prefix_lens[b] + s``; keys/values are the union
    of (a) the prefix KV already resident in the GLOBAL paged pool — read
    through the row's block table, exactly the pages ``admit`` mapped via
    refcount++ — and (b) the suffix K/V computed by this very call.

    q/k_suf/v_suf: (B, S, H*, D); kv: global-layout PagedKV whose pool holds
    the shared prefix pages. Returns (B, S, Hq, D). Dense (one (S, P*T+S)
    score block in fp32): admission-path work where S is a padded suffix —
    tokens the prefix cache just SAVED from this matmul dwarf its cost.
    """
    assert is_global_layout(kv), "prefix continuation needs the global pool"
    B, S, Hq, D = q.shape
    T = kv.page_size
    P_ = kv.block_table.shape[1]
    k_pre, v_pre = gather_global_pages(kv)
    k_pre = k_pre.reshape(B, P_ * T, -1, D)
    v_pre = v_pre.reshape(B, P_ * T, -1, D)
    Hkv = k_pre.shape[2]
    G = Hq // Hkv
    if G > 1:
        k_pre = jnp.repeat(k_pre, G, axis=2)
        v_pre = jnp.repeat(v_pre, G, axis=2)
        k_suf = jnp.repeat(k_suf, G, axis=2)
        v_suf = jnp.repeat(v_suf, G, axis=2)
    k = jnp.concatenate([k_pre, k_suf.astype(k_pre.dtype)], axis=1)
    v = jnp.concatenate([v_pre, v_suf.astype(v_pre.dtype)], axis=1)
    # kv-position mask: prefix slot j is valid iff j < prefix_len (every
    # valid prefix position precedes every query); suffix slot s at
    # position prefix+s obeys the causal triangle and the real-token mask.
    pre_valid = jnp.arange(P_ * T)[None] < prefix_lens[:, None]   # (B, P*T)
    sidx = jnp.arange(S)
    suf_valid = (sidx[None, :] < suffix_lens[:, None])            # (B, S)
    causal = sidx[None, :] <= sidx[:, None]                       # (S, S)
    mask = jnp.concatenate(
        [jnp.broadcast_to(pre_valid[:, None], (B, S, P_ * T)),
         suf_valid[:, None] & causal[None]], axis=-1)             # (B,S,P*T+S)
    s = jnp.einsum("bshd,bthd->bsht", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (D ** -0.5)
    s = _softcap(s, softcap)
    s = jnp.where(mask[:, :, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    p_ = jnp.exp(s - m)
    p_ = jnp.where(mask[:, :, None, :], p_, 0.0)
    l = jnp.sum(p_, axis=-1, keepdims=True)
    o = jnp.einsum("bsht,bthd->bshd", p_, v.astype(jnp.float32))
    o = o / jnp.maximum(l, 1e-20)
    return o.astype(q.dtype)


def paged_append(kv: PagedKV, k_new: jax.Array, v_new: jax.Array) -> PagedKV:
    """Write one token's K/V at position ``length`` through the block table.

    ``length`` may be scalar (dry-run: uniform) or (B,) (serving engine).
    Writes are rolling modulo pool capacity (sliding-window layers use a
    pool whose capacity equals the window).

    SHARDING (perf iteration 1, EXPERIMENTS.md §Perf): the dynamic write
    index only touches the page axis (axis 1, unsharded) — gather the target
    page, masked-update the slot lane, scatter the page back. A direct
    dynamic_update_slice on the (possibly 'model'-sharded) within-page dim
    made XLA all-gather the whole pool every layer (~1 GiB/link/block on
    decode_32k).
    """
    B = k_new.shape[0]
    page = kv.page_size
    length_b = jnp.broadcast_to(kv.length, (B,)) % kv.capacity
    logical_page = length_b // page
    slot = length_b % page
    phys = jnp.take_along_axis(kv.block_table, logical_page[:, None],
                               axis=1)[:, 0]
    if is_global_layout(kv):
        # One scatter of B tokens into the shared pool; writes through NULL
        # table entries (inactive slots) are out-of-bounds and dropped.
        idx = kv.pool_index(phys, slot)

        def write_g(pool, new):
            return pool.at[idx].set(new[:, 0].astype(pool.dtype),
                                    mode="drop")
        return kv._replace(k_pool=write_g(kv.k_pool, k_new),
                           v_pool=write_g(kv.v_pool, v_new),
                           length=kv.length + 1)
    slot_mask = (jnp.arange(page)[None, :] ==
                 slot[:, None])[:, None, :, None, None]    # (B,1,page,1,1)

    def write(pool, new):
        # pool: (B, n_pages, page, H, D); new: (B, 1, H, D).
        # Dynamic index ONLY on the (unsharded) page axis; the sharded
        # within-page dim is covered in full with a static 0 start, so the
        # slice/update partitions without collectives.
        H, D = new.shape[-2], new.shape[-1]
        cur = jax.vmap(lambda pb, pg: jax.lax.dynamic_slice(
            pb, (pg, 0, 0, 0), (1, page, H, D)))(pool, phys)
        upd = jnp.where(slot_mask, new[:, :, None].astype(pool.dtype), cur)
        return jax.vmap(lambda pb, pg, u: jax.lax.dynamic_update_slice(
            pb, u, (pg, 0, 0, 0)))(pool, phys, upd)

    return kv._replace(k_pool=write(kv.k_pool, k_new),
                       v_pool=write(kv.v_pool, v_new),
                       length=kv.length + 1)


# ------------------------------------------------------------ SP decode

def sp_paged_decode(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                    kv: PagedKV, mesh, *,
                    softcap: Optional[float] = None,
                    seq_axis: str = "data"):
    """Sequence-parallel paged decode (long_500k): pages sharded on ``data``.

    Appends the new token's K/V on the owner shard, then each shard attends
    over its local pages (shard-local block table) and partial (m, l, acc)
    are merged with psum — flash-decoding on a pod. Page placement is
    sequence-affine: shard i owns logical pages [i*P/n, (i+1)*P/n), so the
    block-table walk never crosses a shard (paper: bursts never cross the
    translation cache).

    Returns (out (B,1,Hq,D), updated PagedKV).
    """
    B, _, Hq, D = q.shape
    n_shards = mesh.shape[seq_axis]
    n_pages_g = kv.k_pool.shape[1]
    page = kv.page_size
    local_pages = n_pages_g // n_shards
    local_tokens = local_pages * page

    def local_fn(q, kn, vn, kp, vp, tbl, length):
        shard = jax.lax.axis_index(seq_axis)
        # ---- append on the owner shard (rolling modulo pool capacity) ----
        wpos = length % (n_shards * local_tokens)
        owner = (wpos // local_tokens) == shard
        local_pos = wpos % local_tokens
        lpage, slot = local_pos // page, local_pos % page
        phys = jnp.take_along_axis(
            tbl, jnp.broadcast_to(lpage, (B,))[:, None], axis=1)[:, 0] % local_pages

        def write(pool, new):
            upd = jax.vmap(lambda pb, pg, nb: jax.lax.dynamic_update_slice(
                pb, nb[None, None], (pg, slot, 0, 0)))(pool, phys, new[:, 0])
            return jnp.where(owner, upd, pool)
        kp, vp = write(kp, kn), write(vp, vn)
        # ---- local partial attention ----
        k = gather_pages(kp, tbl % local_pages)            # shard-local translation
        v = gather_pages(vp, tbl % local_pages)
        Hkv = k.shape[2]
        G = q.shape[2] // Hkv
        qg = q.reshape(B, Hkv, G, D)
        s = jnp.einsum("bhgd,bshd->bhgs", qg, k,
                       preferred_element_type=jnp.float32) * (D ** -0.5)
        s = _softcap(s, softcap)
        pos = shard * local_tokens + jnp.arange(k.shape[1])
        s = jnp.where((pos <= length)[None, None, None, :], s, -jnp.inf)
        m = jnp.max(s, axis=-1)
        m_safe = jnp.where(jnp.isfinite(m), m, -1e30)
        p_ = jnp.exp(s - m_safe[..., None])
        p_ = jnp.where(jnp.isfinite(s), p_, 0.0)
        l = jnp.sum(p_, axis=-1)
        acc = jnp.einsum("bhgs,bshd->bhgd", p_.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        # ---- flash-decoding merge across shards ----
        m_g = jax.lax.pmax(m_safe, seq_axis)
        corr = jnp.exp(m_safe - m_g)
        l_g = jax.lax.psum(l * corr, seq_axis)
        acc_g = jax.lax.psum(acc * corr[..., None], seq_axis)
        out = acc_g / jnp.maximum(l_g, 1e-20)[..., None]
        return out.reshape(B, 1, Hq, D).astype(q.dtype), kp, vp

    # Heads are replicated across 'model' inside this shard_map: decode-step
    # attention at B=1 is tiny compute, while the pools (the memory hog)
    # shard over 'data'. GQA head counts rarely divide the model axis.
    pool_spec = P(None, seq_axis, None, None, None)
    head_spec = P(None, None, None, None)
    out, kp, vp = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(head_spec, head_spec, head_spec, pool_spec, pool_spec,
                  P(None, seq_axis), P()),
        out_specs=(head_spec, pool_spec, pool_spec),
    )(q, k_new, v_new, kv.k_pool, kv.v_pool, kv.block_table, kv.length)
    return out, kv._replace(k_pool=kp, v_pool=vp, length=kv.length + 1)
