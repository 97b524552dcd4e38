"""Flash attention forward (TPU target for train/prefill attention).

Grid (B, H, nq, nkv), online softmax carried in VMEM scratch across the
innermost kv axis. Causal block-skipping: blocks strictly above the diagonal
are skipped with pl.when (the FLOPs the pure-JAX path wastes — see
models/attention.py note). GQA is handled by the ops wrapper (KV repeat).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bkv: int, n_kv: int, causal: bool, softcap,
            scale: float):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)

    @pl.when(ikv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0, 0]                         # (bq, D)
        k = k_ref[0, 0]                         # (bkv, D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        if causal:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            kpos = ikv * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
            s = jnp.where(kpos <= qpos, s, -jnp.inf)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe)
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # skip blocks strictly above the diagonal (the causal-FLOPs saving
        # the pure-JAX path does not get)
        pl.when(ikv * bkv <= iq * bq + bq - 1)(_compute)
    else:
        _compute()

    @pl.when(ikv == n_kv - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-20)).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, softcap=None,
                        bq: int = 128, bkv: int = 128,
                        interpret: bool | None = None):
    """q, k, v: (B, H, S, D) with H == Hkv (pre-repeated). -> (B, H, S, D)."""
    B, H, S, D = q.shape
    Skv = k.shape[2]
    bq, bkv = min(bq, S), min(bkv, Skv)
    while S % bq:
        bq -= 1
    while Skv % bkv:
        bkv -= 1
    grid = (B, H, S // bq, Skv // bkv)
    kernel = functools.partial(_kernel, bq=bq, bkv=bkv, n_kv=Skv // bkv,
                               causal=causal, softcap=softcap,
                               scale=D ** -0.5)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
