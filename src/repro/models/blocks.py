"""Per-layer-kind parameter specs and forward application.

A *block* is one repetition of ``cfg.block_pattern``; blocks are scanned.
``apply_block`` handles the three execution modes:

  train    full sequence, no cache I/O (SSM/RWKV states start at zero)
  prefill  full sequence, writes caches (paged KV pools via the block table)
  decode   one token, reads + updates caches (the paper's paged-SVA path)
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mamba as mam
from repro.models import rwkv6 as rwkv
from repro.models.dist import MeshInfo, shard
from repro.models.layers import (glu_mlp, glu_mlp_specs, mlp2, mlp2_specs,
                                 rmsnorm, rmsnorm_spec, rope)
from repro.models.moe import moe_ffn, moe_specs
from repro.models.params import ParamSpec, tree_map_specs

ATTN_KINDS = {"attn_mlp", "attn_mlp_local", "attn_moe", "cross_mlp", "attn"}
MLP_KINDS = {"attn_mlp", "attn_mlp_local", "xattn_mlp", "cross_mlp", "mamba", "attn"}
MOE_KINDS = {"attn_moe", "mamba_moe"}
MAMBA_KINDS = {"mamba", "mamba_moe"}
# Layer kinds whose only serving state is paged KV in the global pool.
GLOBAL_POOL_KINDS = {"attn_mlp", "attn_moe", "attn"}


def kv_in_global_pool(cfg: ModelConfig) -> bool:
    """True when every piece of a sequence's serving state lives in the
    shared global page pool: no sliding-window ring, recurrent state,
    cross-KV or image tokens kept per slot. Only such a model can be
    prefilled in chunks and share prefix pages."""
    return (not cfg.is_encdec and not cfg.n_image_tokens
            and all(k in GLOBAL_POOL_KINDS for k in cfg.layer_kinds()))


@dataclass(frozen=True)
class FwdCtx:
    cfg: ModelConfig
    mi: MeshInfo
    mode: str                   # train | prefill | decode
    causal: bool = True
    q_offset: Any = 0           # rope/mask offset of token 0 (decode: cache len)
    cross_x: Optional[jax.Array] = None   # image / encoder embeddings (train, prefill)
    sp: bool = False            # sequence-parallel decode (long_500k)
    seq_lengths: Optional[jax.Array] = None  # (B,) real prompt lengths when a
                                # batched prefill carries right-padded rows
    # CoW prefix sharing (suffix-only prefill): tokens already resident in
    # the shared pool per row, and the scatter tables whose shared entries
    # are NULLed so the prefill write never touches a shared page.
    kv_prefix_lens: Optional[jax.Array] = None   # (B,) int32
    write_tables: Optional[jax.Array] = None     # (B, max_pages) int32


def _mlp_specs(cfg: ModelConfig):
    if cfg.act == "relu":            # seamless-style 2-layer MLP
        return mlp2_specs(cfg.d_model, cfg.d_ff, jnp.dtype(cfg.param_dtype))
    return glu_mlp_specs(cfg.d_model, cfg.d_ff, jnp.dtype(cfg.param_dtype))


def _apply_mlp(p, x, cfg):
    with jax.named_scope("mlp"):
        return mlp2(p, x, cfg.act) if "w1" in p else glu_mlp(p, x, cfg.act)


def layer_specs(cfg: ModelConfig, kind: str) -> Dict[str, Any]:
    dt = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    s: Dict[str, Any] = {"ln1": rmsnorm_spec(d, dt)}
    if kind in ATTN_KINDS:
        s["attn"] = attn.attention_specs(cfg)
    if kind == "xattn_mlp":
        s["xattn"] = attn.attention_specs(cfg, cross=True)
    if kind == "cross_mlp":
        s["lnx"] = rmsnorm_spec(d, dt)
        s["xattn"] = attn.attention_specs(cfg, cross=True)
    if kind in MAMBA_KINDS:
        s["mamba"] = mam.mamba_specs(cfg)
    if kind == "rwkv":
        s["tm"] = rwkv.rwkv_time_mix_specs(cfg)
        s["ln2"] = rmsnorm_spec(d, dt)
        s["cm"] = rwkv.rwkv_channel_mix_specs(cfg)
        return s
    s["ln2"] = rmsnorm_spec(d, dt)
    if kind in MOE_KINDS:
        s["moe"] = moe_specs(cfg)
    elif kind in MLP_KINDS:
        s["mlp"] = _mlp_specs(cfg)
    return s


def block_specs(cfg: ModelConfig) -> Dict[str, Any]:
    return {str(i): layer_specs(cfg, kind)
            for i, kind in enumerate(cfg.block_pattern)}


def stack_specs(tree, n: int):
    def st(s: ParamSpec) -> ParamSpec:
        return ParamSpec((n,) + tuple(s.shape), s.dtype,
                         P(*((None,) + tuple(s.pspec))), s.init, s.scale)
    return tree_map_specs(st, tree)


# --------------------------------------------------------------- caches

def layer_cache_specs(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                      page_size: int, src_len: int, stack=None,
                      per_seq: bool = False,
                      global_pages: "int | None" = None):
    """Cache spec pytree for one layer of ``kind`` (None if stateless).

    ``global_pages``: when set, full-attention layers use the shared global
    pool layout (one physical pool of that many pages per KV layer, per-slot
    tables into it — the zero-copy serving layout). Sliding-window layers
    keep the per-slot ring layout: their KV is a fixed-size rolling buffer,
    which needs no dynamic paging.
    """
    lead = (stack,) if stack else ()
    ld = (None,) * len(lead)
    dt = jnp.dtype(cfg.activation_dtype)
    hkv, dh = cfg.n_kv_heads, cfg.d_head
    out: Dict[str, Any] = {}
    if kind in ATTN_KINDS:
        eff_len = max_len
        if kind == "attn_mlp_local" and cfg.sliding_window:
            eff_len = min(max_len, cfg.sliding_window)
        n_pages = -(-eff_len // page_size)
        if (global_pages is not None and eff_len == max_len
                and not _sp_mode(cfg, batch, max_len)):
            pool_spec = (P(*ld, None, None, "tp", None)
                         if cfg.n_kv_heads >= 16
                         else P(*ld, None, "tp", None, None))
            pool = lambda: ParamSpec(
                lead + (global_pages, page_size, hkv, dh), dt, pool_spec,
                init="zeros")
            out["kv"] = attn.PagedKV(
                k_pool=pool(), v_pool=pool(),
                block_table=ParamSpec(lead + (batch, n_pages), jnp.int32,
                                      P(*ld, "batch", None), init="zeros"),
                length=ParamSpec(lead + (batch,), jnp.int32,
                                 P(*ld, "batch"), init="zeros"))
        else:
            if _sp_mode(cfg, batch, max_len):
                # long-context decode: pages shard over 'data' (shard_map SP)
                pool_spec = P(*ld, None, "data", None, None, None)
                table_spec = P(*ld, None, "data")
            elif cfg.n_kv_heads >= 16:
                # KV heads divide the model axis: plain head TP
                pool_spec = P(*ld, "batch", None, None, "tp", None)
                table_spec = P(*ld, "batch", None)
            else:
                # GQA heads < model axis: shard the within-page token dim over
                # 'model' instead — block-table gathers stay shard-local and
                # the decode softmax merges partials over 'model'
                # (flash-decoding).
                pool_spec = P(*ld, "batch", None, "tp", None, None)
                table_spec = P(*ld, "batch", None)
            pool = lambda: ParamSpec(
                lead + (batch, n_pages, page_size, hkv, dh),
                dt, pool_spec, init="zeros")
            out["kv"] = attn.PagedKV(
                k_pool=pool(), v_pool=pool(),
                block_table=ParamSpec(lead + (batch, n_pages), jnp.int32,
                                      table_spec, init="zeros"),
                length=ParamSpec(lead + ((batch,) if per_seq else ()),
                                 jnp.int32,
                                 P(*ld, *(("batch",) if per_seq else ())),
                                 init="zeros"))
    if kind in ("xattn_mlp", "cross_mlp"):
        ck = lambda: ParamSpec(lead + (batch, src_len, hkv, dh), dt,
                               P(*ld, "batch", "tp", None, None), init="zeros")
        out["xkv"] = {"k": ck(), "v": ck()}
    if kind in MAMBA_KINDS:
        st = mam.mamba_state_specs(cfg, batch)
        out["ssm"] = tree_map_specs(
            lambda s: ParamSpec(lead + tuple(s.shape), s.dtype,
                                P(*((None,) * len(lead) + tuple(s.pspec))),
                                s.init, s.scale), st)
    if kind == "rwkv":
        st = rwkv.rwkv_state_specs(cfg, batch)
        out["rwkv"] = tree_map_specs(
            lambda s: ParamSpec(lead + tuple(s.shape), s.dtype,
                                P(*((None,) * len(lead) + tuple(s.pspec))),
                                s.init, s.scale), st)
    return out or None


def _sp_mode(cfg: ModelConfig, batch: int, max_len: int) -> bool:
    """Sequence-parallel cache layout when batch can't cover the data axis."""
    return batch == 1 and max_len >= 262144


# --------------------------------------------------------------- forward

def _self_attention(p, x, ctx: FwdCtx, cache, window):
    cfg, mi = ctx.cfg, ctx.mi
    B, S, _ = x.shape
    q, k, v = attn.qkv_proj(p, x)
    # explicit head sharding (q heads over 'model'); without this XLA's SPMD
    # falls back to replicated heads (measured: 4x activation memory).
    q = shard(q, mi, P("batch", None, "tp", None))
    if ctx.mode == "decode":
        pos = jnp.asarray(ctx.q_offset)         # scalar or (B,) lengths
        pos_b = (jnp.full((B,), pos) if pos.ndim == 0 else pos)[:, None]
        q = rope(q, pos_b, cfg.rope_theta)
        k = rope(k, pos_b, cfg.rope_theta)
        kv: attn.PagedKV = cache["kv"]
        if ctx.sp:
            o, kv_new = attn.sp_paged_decode(q, k, v, kv, mi.mesh,
                                             softcap=cfg.attn_softcap)
        else:
            with jax.named_scope("kv_write"):
                kv_new = attn.paged_append(kv, k, v)
            with jax.named_scope("attention"):
                o = attn.paged_decode_attention(q, kv_new,
                                                softcap=cfg.attn_softcap)
        return attn.out_proj(p, o), {**cache, "kv": kv_new}
    if (ctx.kv_prefix_lens is not None and ctx.mode == "prefill"
            and cache is not None and "kv" in cache
            and attn.is_global_layout(cache["kv"])):
        # Suffix-only prefill (CoW prefix sharing): row b's token 0 sits at
        # logical position kv_prefix_lens[b]; the skipped prefix's KV is
        # read back from the shared pool pages instead of being recomputed.
        positions = jnp.arange(S)[None] + ctx.kv_prefix_lens[:, None]
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        kv: attn.PagedKV = cache["kv"]
        with jax.named_scope("attention"):
            o = attn.prefix_context_attention(
                q, k, v, kv, ctx.kv_prefix_lens,
                jnp.broadcast_to(ctx.seq_lengths, (B,)),
                softcap=cfg.attn_softcap)
        o = shard(o, mi, P("batch", None, "tp", None))
        y = attn.out_proj(p, o)
        with jax.named_scope("kv_write"):
            kv = _prefill_write_global(kv, k, v, ctx, S)
        return y, {**cache, "kv": kv}
    positions = jnp.arange(S)[None] + ctx.q_offset
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    kr, vr = k, v
    if cfg.n_q_per_kv > 1:          # pre-repeat so head TP sharding applies
        kr = jnp.repeat(k, cfg.n_q_per_kv, axis=2)
        vr = jnp.repeat(v, cfg.n_q_per_kv, axis=2)
    kr = shard(kr, mi, P("batch", None, "tp", None))
    vr = shard(vr, mi, P("batch", None, "tp", None))
    o = attn.flash_attention(q, kr, vr, causal=ctx.causal, window=window,
                             softcap=cfg.attn_softcap,
                             q_chunk=cfg.flash_q_chunk,
                             kv_chunk=cfg.flash_kv_chunk,
                             unroll=cfg.unroll_scans)
    o = shard(o, mi, P("batch", None, "tp", None))
    y = attn.out_proj(p, o)
    if ctx.mode == "prefill" and cache is not None and "kv" in cache:
        kv: attn.PagedKV = cache["kv"]
        if attn.is_global_layout(kv):
            with jax.named_scope("kv_write"):
                kv = _prefill_write_global(kv, k, v, ctx, S)
            return y, {**cache, "kv": kv}
        n_pages, page = kv.k_pool.shape[1], kv.k_pool.shape[2]
        eff = n_pages * page

        def write(pool, kv_seq):
            # Ring alignment: token t lives at slot t % eff, so the decode
            # append (which writes position ``length % eff``) overwrites the
            # OLDEST resident token. Storing the tail at slot 0 instead
            # would desync the ring whenever prompt_len % eff != 0: the
            # append clobbers an in-window token while an out-of-window one
            # stays resident.
            if ctx.seq_lengths is not None:
                # Right-padded batched prefill: per row, keep each
                # sequence's LAST min(len, eff) REAL tokens ring-aligned
                # and zero the rest — slicing the padded tail would store
                # pad-token KV and drop real in-window tokens.
                lens = ctx.seq_lengths
                start = jnp.maximum(lens - eff, 0)[:, None]       # (B, 1)
                i = jnp.arange(eff)[None, :]
                idx = start + (i - start) % eff                   # (B, eff)
                valid = idx < lens[:, None]
                idx = jnp.minimum(idx, max(S - 1, 0))
                seg = jnp.take_along_axis(kv_seq, idx[:, :, None, None],
                                          axis=1)
                seg = jnp.where(valid[:, :, None, None], seg, 0)
            elif eff < S:                     # sliding-window pool: keep tail
                seg = jnp.roll(kv_seq[:, -eff:], (S - eff) % eff, axis=1)
            elif eff > S:                     # pool capacity > prompt: pad
                pad = jnp.zeros((B, eff - S, *kv_seq.shape[2:]), kv_seq.dtype)
                seg = jnp.concatenate([kv_seq, pad], axis=1)
            else:
                seg = kv_seq
            pages = seg.reshape(B, n_pages, page, *seg.shape[2:])
            inv = jnp.argsort(kv.block_table, axis=1)
            return jnp.take_along_axis(pages, inv[:, :, None, None, None], axis=1)
        kv = kv._replace(k_pool=write(kv.k_pool, k), v_pool=write(kv.v_pool, v),
                         length=jnp.full_like(kv.length, min(S, eff)))
        cache = {**cache, "kv": kv}
    return y, cache


def _prefill_write_global(kv: attn.PagedKV, k, v, ctx: FwdCtx, S: int
                          ) -> attn.PagedKV:
    """Scatter a batched prefill's KV through per-sequence block tables into
    the SHARED global pool — the zero-copy admission path: no staging cache,
    no post-hoc slot copy.

    Right-padded positions (>= seq_lengths) are zeroed before the scatter
    and EVERY table entry of each row is written (real KV first, then zero
    pages), so recycled physical pages are scrubbed and a sequence's mapped
    region is bit-identical to a freshly zero-initialized cache. Writes
    through NULL entries are out-of-bounds and dropped.

    Prefix continuation (``ctx.kv_prefix_lens`` set): row b's token s lives
    at logical position ``prefix[b] + s``, and the scatter goes through
    ``ctx.write_tables`` — the row tables with SHARED entries NULLed — so a
    shared page is never written (neither with recomputed KV nor with the
    zero scrub; its content is already exactly right, and other sequences
    still map it). Fresh pages keep the zero-scrub hygiene.
    """
    lens = ctx.seq_lengths
    assert lens is not None, \
        "global-layout prefill requires batch['lengths'] (per-seq prompt lengths)"
    B = k.shape[0]
    page = kv.page_size
    P_ = kv.block_table.shape[-1]
    prefix = ctx.kv_prefix_lens
    if prefix is None:
        scatter_tbl = kv.block_table
        total_len = lens
    else:
        scatter_tbl = jnp.broadcast_to(ctx.write_tables,
                                       kv.block_table.shape).astype(jnp.int32)
        total_len = lens + prefix
    keep = (jnp.arange(S)[None, :] < lens[:, None])[:, :, None, None]

    def write(pool, kv_seq):
        kw = jnp.where(keep, kv_seq, 0).astype(pool.dtype)
        feat = kv_seq.shape[2:]
        if prefix is None:
            pad = P_ * page - S
            if pad > 0:
                kw = jnp.concatenate(
                    [kw, jnp.zeros((B, pad, *feat), pool.dtype)], axis=1)
            elif pad < 0:
                kw = kw[:, :P_ * page]
        else:
            # Re-align each row so token s lands at logical slot
            # prefix[b] + s: gather with a shifted index (out-of-suffix
            # slots -> 0 = the scrub value). Slots belonging to shared
            # pages also read 0 here, but their writes are dropped by the
            # NULLed scatter table.
            sidx = jnp.arange(P_ * page)[None, :] - prefix[:, None]  # (B,P*T)
            valid = (sidx >= 0) & (sidx < lens[:, None])
            gidx = jnp.clip(sidx, 0, max(S - 1, 0))
            kw = jnp.take_along_axis(kw, gidx[:, :, None, None], axis=1)
            kw = jnp.where(valid[:, :, None, None], kw, 0)
        # Pages scatter as (page x Hkv, D) rows: a (page, Hkv, D) window
        # makes the TPU compiler re-tile the pool, a copy of the whole
        # stacked pool into and out of the layer loop; this view of the
        # pool is a bitcast of its default layout.
        rows = pool.reshape(*pool.shape[:-3], page * feat[0], feat[1])
        return rows.at[kv.pool_index(scatter_tbl.reshape(-1))].set(
            kw.reshape(B * P_, page * feat[0], feat[1]),
            mode="drop").reshape(pool.shape)

    return kv._replace(k_pool=write(kv.k_pool, k),
                       v_pool=write(kv.v_pool, v),
                       length=jnp.broadcast_to(total_len, kv.length.shape)
                       .astype(kv.length.dtype))


def _cross_attention(p, x, ctx: FwdCtx, cache):
    cfg = ctx.cfg
    if ctx.mode == "decode":
        xkv = cache["xkv"]
        k, v = xkv["k"], xkv["v"]
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        o = attn.flash_attention(q, k, v, causal=False,
                                 q_chunk=cfg.flash_q_chunk,
                                 kv_chunk=cfg.flash_kv_chunk,
                                 unroll=cfg.unroll_scans)
        return attn.out_proj(p, o), cache
    q, k, v = attn.qkv_proj(p, x, kv_x=ctx.cross_x.astype(x.dtype))
    o = attn.flash_attention(q, k, v, causal=False,
                             q_chunk=cfg.flash_q_chunk,
                             kv_chunk=cfg.flash_kv_chunk,
                             unroll=cfg.unroll_scans)
    y = attn.out_proj(p, o)
    if ctx.mode == "prefill" and cache is not None and "xkv" in cache:
        cache = {**cache, "xkv": {"k": k, "v": v}}
    return y, cache


def apply_layer(kind: str, p, x, ctx: FwdCtx, cache):
    cfg, mi = ctx.cfg, ctx.mi
    cache = cache if cache is not None else {}
    out_cache = dict(cache)
    window = cfg.sliding_window if kind == "attn_mlp_local" else None

    if kind == "rwkv":
        st: rwkv.RWKVState = cache["rwkv"] if "rwkv" in cache else \
            _zero_rwkv(cfg, x)
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        h_prev, new_tm = rwkv.token_shift(h, st.shift_tm)
        if ctx.mode == "decode":
            y, wkv = rwkv.rwkv_time_mix_step(p["tm"], h, h_prev, cfg, st.wkv)
        else:
            y, wkv = rwkv.rwkv_time_mix(p["tm"], h, h_prev, cfg, mi, st.wkv)
        x = x + y
        h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        h_prev, new_cm = rwkv.token_shift(h, st.shift_cm)
        x = x + rwkv.rwkv_channel_mix(p["cm"], h, h_prev)
        if ctx.mode != "train":
            out_cache["rwkv"] = rwkv.RWKVState(wkv, new_tm, new_cm)
        return x, (out_cache or None)

    if kind in MAMBA_KINDS:
        st: mam.MambaState = cache["ssm"] if "ssm" in cache else \
            _zero_mamba(cfg, x)
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        if ctx.mode == "decode":
            y, st_new = mam.mamba_mix_step(p["mamba"], h, cfg, st)
        else:
            y, st_new = mam.mamba_mix(p["mamba"], h, cfg, mi, st)
        x = x + y
        if ctx.mode != "train":
            out_cache["ssm"] = st_new
    elif kind == "xattn_mlp":
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        y, out_cache = _cross_attention(p["xattn"], h, ctx, out_cache)
        x = x + y
    else:
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        y, out_cache = _self_attention(p["attn"], h, ctx, out_cache, window)
        x = x + y
        if kind == "cross_mlp":
            h = rmsnorm(x, p["lnx"], cfg.norm_eps)
            y, out_cache = _cross_attention(p["xattn"], h, ctx, out_cache)
            x = x + y

    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    if kind in MOE_KINDS:
        x = x + moe_ffn(p["moe"], h, cfg, mi)
    else:
        x = x + _apply_mlp(p["mlp"], h, cfg)
    return x, (out_cache or None)


def _zero_rwkv(cfg, x):
    B = x.shape[0]
    return rwkv.RWKVState(
        wkv=jnp.zeros((B, cfg.n_heads, cfg.d_head, cfg.d_head), jnp.float32),
        shift_tm=jnp.zeros((B, cfg.d_model), x.dtype),
        shift_cm=jnp.zeros((B, cfg.d_model), x.dtype))


def _zero_mamba(cfg, x):
    B = x.shape[0]
    d_in = cfg.ssm.expand * cfg.d_model
    return mam.MambaState(
        conv=jnp.zeros((B, cfg.ssm.d_conv - 1, d_in), x.dtype),
        ssm=jnp.zeros((B, d_in, cfg.ssm.d_state), jnp.float32))


def apply_block(p_blk, x, ctx: FwdCtx, cache_blk, pattern=None):
    """One repetition of a block pattern. cache_blk: dict pos->cache|None."""
    out_caches = {}
    pattern = pattern if pattern is not None else ctx.cfg.block_pattern
    for i, kind in enumerate(pattern):
        c_in = None if cache_blk is None else cache_blk.get(str(i))
        x, c_out = apply_layer(kind, p_blk[str(i)], x, ctx, c_in)
        if c_out is not None and cache_blk is not None:
            out_caches[str(i)] = c_out
    # Block-boundary activations shard d_model over 'model' as well: these are
    # the remat-saved tensors, so this is ZeRO-R-style activation partitioning
    # (16x smaller saved stack for one small all-gather per block).
    x = shard(x, ctx.mi, P("batch", None, "tp"))
    return x, (out_caches if cache_blk is not None else None)
