"""Model assembly: embedding -> (first dense layers) -> scanned blocks -> head.

Public API:
  model_specs(cfg)                         ParamSpec tree
  cache_specs(cfg, batch, max_len, ...)    cache ParamSpec tree (serve modes)
  init_cache(cfg, batch, max_len, ...)     zero-filled runtime cache
  forward_train(cfg, params, batch, mi)    -> (loss, aux)
  forward_prefill(cfg, params, batch, cache, mi) -> (last_logits, cache)
  forward_decode(cfg, params, token, pos, cache, mi) -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models.blocks import (FwdCtx, apply_block, block_specs,
                                 layer_cache_specs, stack_specs, _sp_mode)
from repro.models.dist import NO_MESH, MeshInfo, shard
from repro.models.layers import (chunked_xent, embed, embedding_specs,
                                 logits_fn, rmsnorm, rmsnorm_spec)
from repro.models.params import ParamSpec, materialize, tree_map_specs

DEFAULT_PAGE_SIZE = 64
ENCDEC_SRC_LEN = 3072          # stubbed audio-frame count for serve shapes


# --------------------------------------------------------------- specs

def _residual_init_damping(specs: Dict[str, Any], cfg: ModelConfig):
    """GPT-2-style init: residual-writing projections scaled by 1/sqrt(2L)
    so the backward signal into the embedding stays O(1) at init (measured:
    embedding grad-norm 2.2e6 -> O(1e2) on a 12L/768 from-scratch run)."""
    import math
    damp = 1.0 / math.sqrt(2.0 * max(cfg.n_layers, 1))
    res_keys = {"wo", "w_down", "w2", "out_proj"}

    def walk(tree, name=""):
        if isinstance(tree, dict):
            return {k: walk(v, k) for k, v in tree.items()}
        if isinstance(tree, ParamSpec) and name in res_keys \
                and tree.init == "normal" and len(tree.shape) >= 2:
            base = tree.scale if tree.scale is not None else tree.fan_in() ** -0.5
            return ParamSpec(tree.shape, tree.dtype, tree.pspec, tree.init,
                             base * damp)
        if isinstance(tree, tuple) and hasattr(tree, "_fields"):
            return type(tree)(*(walk(v) for v in tree))
        return tree
    return walk(specs)


def model_specs(cfg: ModelConfig) -> Dict[str, Any]:
    dt = jnp.dtype(cfg.param_dtype)
    specs: Dict[str, Any] = {
        "embed": embedding_specs(cfg.vocab_size, cfg.d_model, dt,
                                 cfg.tie_embeddings),
        "final_ln": rmsnorm_spec(cfg.d_model, dt),
        "blocks": stack_specs(block_specs(cfg), cfg.n_blocks),
    }
    if cfg.first_k_dense:
        from repro.models.blocks import layer_specs
        specs["first"] = {str(i): layer_specs(cfg, "attn_mlp")
                          for i in range(cfg.first_k_dense)}
    if cfg.is_encdec:
        specs["encoder"] = {
            "blocks": stack_specs(
                {str(i): _enc_layer_specs(cfg, k)
                 for i, k in enumerate(cfg.enc_block_pattern)},
                cfg.n_enc_layers // len(cfg.enc_block_pattern)),
            "final_ln": rmsnorm_spec(cfg.d_model, dt),
        }
    return _residual_init_damping(specs, cfg)


def _enc_layer_specs(cfg, kind):
    from repro.models.blocks import layer_specs
    return layer_specs(cfg, kind)


def cache_specs(cfg: ModelConfig, batch: int, max_len: int,
                page_size: int = DEFAULT_PAGE_SIZE,
                src_len: int = ENCDEC_SRC_LEN,
                per_seq: bool = False,
                global_pages: Optional[int] = None) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    blk = {}
    for i, kind in enumerate(cfg.block_pattern):
        c = layer_cache_specs(cfg, kind, batch, max_len, page_size, src_len,
                              stack=cfg.n_blocks, per_seq=per_seq,
                              global_pages=global_pages)
        if c is not None:
            blk[str(i)] = c
    out["blocks"] = blk
    if cfg.first_k_dense:
        out["first"] = {
            str(i): layer_cache_specs(cfg, "attn_mlp", batch, max_len,
                                      page_size, src_len, per_seq=per_seq,
                                      global_pages=global_pages)
            for i in range(cfg.first_k_dense)}
    if cfg.is_encdec:
        # encoder output embeddings, needed by decode steps
        out["enc_out"] = ParamSpec((batch, src_len, cfg.d_model),
                                   jnp.dtype(cfg.activation_dtype),
                                   P("batch", "tp", None), init="zeros")
    return out


def _identity_tables(cache):
    """Fill block tables with the identity mapping (dry-run/smoke default;
    the serving engine supplies real page allocations). Global-layout leaves
    instead start with every entry NULL (== total pages): nothing is mapped
    until the engine uploads real rows."""
    def walk(tree):
        if isinstance(tree, attn.PagedKV):
            bt = tree.block_table
            if attn.is_global_layout(tree):
                return tree._replace(
                    block_table=jnp.full_like(bt, tree.k_pool.shape[-4]))
            n_pages = bt.shape[-1]
            iota = jnp.broadcast_to(
                jnp.arange(n_pages, dtype=jnp.int32), bt.shape)
            return tree._replace(block_table=iota)
        if isinstance(tree, dict):
            return {k: walk(v) for k, v in tree.items()}
        if isinstance(tree, tuple) and hasattr(tree, "_fields"):
            return tree
        return tree
    return walk(cache)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               page_size: int = DEFAULT_PAGE_SIZE,
               src_len: int = ENCDEC_SRC_LEN,
               length: int = 0, per_seq: bool = False,
               global_pages: Optional[int] = None):
    specs = cache_specs(cfg, batch, max_len, page_size, src_len,
                        per_seq=per_seq, global_pages=global_pages)
    cache = materialize(specs, jax.random.key(0))
    cache = _identity_tables(cache)
    if length:
        cache = set_cache_length(cache, length)
    return cache


def set_cache_length(cache, length):
    """``length``: scalar, or (B,) per-sequence lengths (batched prefill)."""
    length = jnp.asarray(length)
    def walk(tree):
        if isinstance(tree, attn.PagedKV):
            return tree._replace(
                length=jnp.broadcast_to(length, tree.length.shape)
                .astype(tree.length.dtype))
        if isinstance(tree, dict):
            return {k: walk(v) for k, v in tree.items()}
        return tree
    return walk(cache)


# --------------------------------------------------------------- forward

def _maybe_remat(fn, cfg: ModelConfig, mode: str):
    if mode != "train" or cfg.remat == "none":
        return fn
    policy = {
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "full": None,
    }[cfg.remat]
    return jax.checkpoint(fn, policy=policy)


def _with_pools(blk_cache, pools, layer):
    """``blk_cache`` with the KV at each pattern position in ``pools`` given
    those (k_pool, v_pool) and ``layer`` as its ``PagedKV.layer``."""
    out = dict(blk_cache)
    for i, (k, v) in pools.items():
        out[i] = {**out[i], "kv": out[i]["kv"]._replace(
            k_pool=k, v_pool=v, layer=layer)}
    return out


def _run_blocks(cfg, params, x, ctx: FwdCtx, cache):
    """Apply first_k_dense layers then the scanned block stack.

    The block cache's leaves carry a leading (n_blocks,) axis. Global-layout
    KV pools (the zero-copy serving pool, ``attn.is_global_layout``) ride
    the loop CARRY with a layer counter: each layer reads and writes the
    stacked pool at ``[layer, page, slot]`` (``PagedKV.layer``), so the
    loop updates it in place and a donated input pool is the output pool
    (as ``xs``/``ys`` each layer's pool would be sliced out of the stack
    and restacked: four whole-pool copies a call). Every other leaf (block
    tables and lengths, per-slot pools, window rings, recurrent states,
    cross-KV) rides ``xs``/``ys``.
    """
    from repro.models.blocks import apply_layer

    new_cache: Dict[str, Any] = {} if cache is not None else None
    if cfg.first_k_dense:
        fc_out = {}
        for i in range(cfg.first_k_dense):
            c_in = cache.get("first", {}).get(str(i)) if cache else None
            x, c_out = apply_layer("attn_mlp", params["first"][str(i)], x,
                                   ctx, c_in)
            if cache is not None:
                fc_out[str(i)] = c_out
        if cache is not None:
            new_cache["first"] = fc_out

    blk_cache = cache.get("blocks") if cache else None
    pools = {i: (c["kv"].k_pool, c["kv"].v_pool)
             for i, c in (blk_cache or {}).items()
             if "kv" in c and attn.is_global_layout(c["kv"])}
    no_pools = dict.fromkeys(pools, (None, None))
    if pools:
        blk_cache = _with_pools(blk_cache, no_pools, None)

    def body(carry, xs):
        x, layer, pools = carry
        p_blk, c_blk = xs
        if pools:
            c_blk = _with_pools(c_blk, pools, layer)
        x, c_out = apply_block(p_blk, x, ctx, c_blk)
        if pools:
            pools = {i: (c_out[i]["kv"].k_pool, c_out[i]["kv"].v_pool)
                     for i in pools}
            c_out = _with_pools(c_out, no_pools, None)
        return (x, layer + 1, pools), c_out

    body = _maybe_remat(body, cfg, ctx.mode)

    carry = (x, jnp.int32(0), pools)
    if cfg.scan_blocks and cfg.n_blocks > 1:
        carry, c_stack = jax.lax.scan(body, carry,
                                      (params["blocks"], blk_cache))
    else:
        c_list = []
        for b in range(cfg.n_blocks):
            take = lambda t: jax.tree.map(lambda a: a[b], t)
            carry, c_out = body(carry, (take(params["blocks"]),
                                        take(blk_cache)
                                        if blk_cache is not None else None))
            c_list.append(c_out)
        c_stack = (jax.tree.map(lambda *xs: jnp.stack(xs), *c_list)
                   if cache is not None and c_list and c_list[0] is not None
                   else None)
    x, _, pools = carry
    if cache is not None:
        new_cache["blocks"] = (_with_pools(c_stack, pools, None) if pools
                               else c_stack)
    return x, new_cache


def _run_encoder(cfg, params, enc_x, mi: MeshInfo):
    ctx = FwdCtx(cfg=cfg, mi=mi, mode="train", causal=False)
    x = enc_x.astype(jnp.dtype(cfg.activation_dtype))
    enc = params["encoder"]

    def body(x, p_blk):
        x, _ = apply_block(p_blk, x, ctx, None, pattern=cfg.enc_block_pattern)
        return x, None

    if cfg.scan_blocks:
        x, _ = jax.lax.scan(body, x, enc["blocks"])
    else:
        n = cfg.n_enc_layers // len(cfg.enc_block_pattern)
        for b in range(n):
            x, _ = body(x, jax.tree.map(lambda a: a[b], enc["blocks"]))
    return rmsnorm(x, enc["final_ln"], cfg.norm_eps)


def _embed_in(cfg, params, tokens, mi):
    x = embed(params["embed"], tokens).astype(jnp.dtype(cfg.activation_dtype))
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return shard(x, mi, P("batch", None, None))


def forward_train(cfg: ModelConfig, params, batch: Dict[str, jax.Array],
                  mi: MeshInfo = NO_MESH):
    """batch: tokens (B,S), labels (B,S); + enc_x / img_x per family."""
    tokens, labels = batch["tokens"], batch["labels"]
    cross_x = None
    if cfg.is_encdec:
        cross_x = _run_encoder(cfg, params, batch["enc_x"], mi)
    elif cfg.n_image_tokens:
        cross_x = batch["img_x"].astype(jnp.dtype(cfg.activation_dtype))
    ctx = FwdCtx(cfg=cfg, mi=mi, mode="train", cross_x=cross_x)
    x = _embed_in(cfg, params, tokens, mi)
    x, _ = _run_blocks(cfg, params, x, ctx, None)
    x = rmsnorm(x, params["final_ln"], cfg.norm_eps)
    loss = chunked_xent(params["embed"], x, labels, cfg.logit_softcap,
                        unroll=cfg.unroll_scans)
    return loss


def forward_prefill(cfg: ModelConfig, params, batch, cache,
                    mi: MeshInfo = NO_MESH):
    """``batch`` may carry ``lengths`` (B,) int32 — real per-sequence prompt
    lengths when rows are right-padded (batched/bucketed serving prefill):
    logits are then taken at each row's last REAL token and cache lengths
    are set per sequence.

    CoW prefix sharing adds ``prefix_lens`` (B,) int32 — tokens already
    resident in the shared pool, so ``tokens`` holds only each prompt's
    SUFFIX (``lengths`` = suffix lengths) — and ``write_tables`` (B, P)
    int32, the scatter tables with shared entries NULLed (see
    core/serving/engine.py)."""
    tokens = batch["tokens"]
    lengths = batch.get("lengths")
    prefix_lens = batch.get("prefix_lens")
    cross_x = None
    if cfg.is_encdec:
        cross_x = _run_encoder(cfg, params, batch["enc_x"], mi)
    elif cfg.n_image_tokens:
        cross_x = batch["img_x"].astype(jnp.dtype(cfg.activation_dtype))
    ctx = FwdCtx(cfg=cfg, mi=mi, mode="prefill", cross_x=cross_x,
                 seq_lengths=lengths, kv_prefix_lens=prefix_lens,
                 write_tables=batch.get("write_tables"))
    x = _embed_in(cfg, params, tokens, mi)
    x, cache = _run_blocks(cfg, params, x, ctx, cache)
    if cfg.is_encdec:
        cache["enc_out"] = cross_x
    if lengths is None:
        x = x[:, -1:]
    else:
        idx = jnp.maximum(lengths.astype(jnp.int32) - 1, 0)[:, None, None]
        x = jnp.take_along_axis(x, idx, axis=1)
    with jax.named_scope("lm_head"):
        x = rmsnorm(x, params["final_ln"], cfg.norm_eps)
        logits = logits_fn(params["embed"], x, cfg.logit_softcap)
    total = tokens.shape[1] if lengths is None else lengths
    if prefix_lens is not None:
        total = total + prefix_lens        # cache holds prefix + suffix
    cache = set_cache_length(cache, total)
    return logits, cache


def forward_decode(cfg: ModelConfig, params, token, pos, cache,
                   mi: MeshInfo = NO_MESH, sp: Optional[bool] = None):
    """token: (B,1) int32; pos: scalar int32 (current cache length)."""
    if sp is None:
        sp = _decode_is_sp(cfg, cache)
    cross_x = cache.get("enc_out") if cfg.is_encdec else None
    ctx = FwdCtx(cfg=cfg, mi=mi, mode="decode", q_offset=pos,
                 cross_x=cross_x, sp=sp)
    x = _embed_in(cfg, params, token, mi)
    x, cache_out = _run_blocks(cfg, params, x, ctx, cache)
    if cfg.is_encdec:
        cache_out["enc_out"] = cache["enc_out"]
    with jax.named_scope("lm_head"):
        x = rmsnorm(x, params["final_ln"], cfg.norm_eps)
        logits = logits_fn(params["embed"], x, cfg.logit_softcap)
    return logits, cache_out


def _decode_is_sp(cfg, cache) -> bool:
    def find_kv(tree):
        if isinstance(tree, attn.PagedKV):
            return tree
        if isinstance(tree, dict):
            for v in tree.values():
                r = find_kv(v)
                if r is not None:
                    return r
        return None
    kv = find_kv(cache)
    if kv is None:
        return False
    if attn.is_global_layout(kv):
        return False            # global serving layout is never SP-sharded
    batch = kv.k_pool.shape[-5 + 0] if kv.k_pool.ndim == 5 else kv.k_pool.shape[1]
    n_pages = kv.k_pool.shape[-4]
    page = kv.k_pool.shape[-3]
    return _sp_mode(cfg, batch, n_pages * page)


def init_params(cfg: ModelConfig, key: jax.Array):
    return materialize(model_specs(cfg), key)
