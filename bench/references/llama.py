"""Plain float32 reference of a LLaMA-family decoder (Qwen2, DeepSeek-LLM).

Follows the published description: token embedding; per layer RMSNorm,
attention with rotate-half RoPE (q/k/v bias where the architecture has it),
causal softmax over all earlier positions with K/V heads shared by groups
of query heads, output projection, residual, RMSNorm, SiLU-gated MLP,
residual; final RMSNorm and an untied LM head. Everything runs in float32
at the highest matmul precision, one layer at a time, over one whole
sequence: no cache, no paging, no batching, no kernels.

The weights are a plain dict of arrays in the layout the benchmark made
them in (``bench/model.py``): ``embed.table`` (V, d), ``embed.head`` (d, V),
``final_ln`` (d,), and per stacked layer ``ln1``/``ln2`` (d,),
``attn.wq`` (d, Hq, D), ``attn.wk``/``attn.wv`` (d, Hkv, D), ``attn.wo``
(Hq, D, d), optional ``attn.bq``/``bk``/``bv`` (H, D), ``mlp.w_gate``/
``w_up`` (d, F), ``mlp.w_down`` (F, d).

``lowp`` swaps every matmul's operands for their float8 (e4m3, scaled per
tensor) values: the control one precision below the served bfloat16.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
BUCKET = 512          # sequences are padded to a multiple of this (causal:
                      # padding after the last real position changes nothing)


def _q8(x):
    """float8 e4m3 value of ``x`` with a per-tensor scale, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, lowp):
    if lowp:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (S, H, D), rotate-half convention, positions 0..S-1."""
    S, _, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs      # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "lowp"))
def _layer(x, p, *, eps, theta, lowp):
    f = lambda a: a.astype(jnp.float32)
    S = x.shape[0]
    h = _rms(x, f(p["ln1"]), eps)
    q = _mm("sd,dhk->shk", h, f(p["attn"]["wq"]), lowp)
    k = _mm("sd,dhk->shk", h, f(p["attn"]["wk"]), lowp)
    v = _mm("sd,dhk->shk", h, f(p["attn"]["wv"]), lowp)
    if "bq" in p["attn"]:
        q = q + f(p["attn"]["bq"])
        k = k + f(p["attn"]["bk"])
        v = v + f(p["attn"]["bv"])
    q, k = _rope(q, theta), _rope(k, theta)
    hq, hkv, D = q.shape[1], k.shape[1], q.shape[2]
    qg = q.reshape(S, hkv, hq // hkv, D)
    s = _mm("qhgd,khd->hgqk", qg, k, lowp) * (D ** -0.5)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = _mm("hgqk,khd->qhgd", a, v, lowp).reshape(S, hq, D)
    x = x + _mm("shk,hkd->sd", o, f(p["attn"]["wo"]), lowp)
    h = _rms(x, f(p["ln2"]), eps)
    g = _mm("sd,df->sf", h, f(p["mlp"]["w_gate"]), lowp)
    u = _mm("sd,df->sf", h, f(p["mlp"]["w_up"]), lowp)
    return x + _mm("sf,fd->sd", jax.nn.silu(g) * u,
                   f(p["mlp"]["w_down"]), lowp)


@functools.partial(jax.jit, static_argnames=("eps", "lowp"))
def _head(x, ln, head, *, eps, lowp):
    h = _rms(x, ln.astype(jnp.float32), eps)
    return _mm("sd,dv->sv", h, head.astype(jnp.float32), lowp)


def logits(params: Dict, cfg: dict, tokens: Sequence[int], rows: slice,
           lowp: bool = False) -> np.ndarray:
    """float32 logits at positions ``rows`` of one sequence ``tokens``."""
    S = len(tokens)
    pad = -(-S // BUCKET) * BUCKET
    ids = np.zeros((pad,), np.int32)
    ids[:S] = tokens
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    x = jnp.take(params["embed"]["table"], jnp.asarray(ids), axis=0) \
        .astype(jnp.float32)
    blocks = params["blocks"]["0"]
    for i in range(cfg["num_hidden_layers"]):
        p = jax.tree_util.tree_map(lambda a: a[i], blocks)
        x = _layer(x, p, eps=eps, theta=theta, lowp=lowp)
    lo, hi = rows.start, rows.stop
    n = hi - lo
    npad = -(-n // BUCKET) * BUCKET
    sel = jnp.asarray(np.minimum(np.arange(lo, lo + npad), pad - 1))
    out = _head(jnp.take(x, sel, axis=0), params["final_ln"],
                params["embed"]["head"], eps=eps, lowp=lowp)
    return np.asarray(out[:n])


def served_gaps(params: Dict, cfg: dict, prompt: List[int],
                served: List[int], lowp: bool = False):
    """For each served token, the reference's best logit minus the logit of
    the token the program served there; with ``lowp``, minus the logit of
    the token the float8 control puts first instead.

    Returns (gaps, argmax agreements) as numpy arrays over the served
    tokens. The sequence fed is the prompt and the served tokens but the
    last, so position len(prompt)-1+j predicts served[j]."""
    seq = list(prompt) + list(served[:-1])
    rows = slice(len(prompt) - 1, len(seq))
    ref = logits(params, cfg, seq, rows)
    best = ref.max(-1)
    if lowp:
        low = logits(params, cfg, seq, rows, lowp=True)
        pick = low.argmax(-1)
    else:
        pick = np.asarray(served)
    got = ref[np.arange(len(pick)), pick]
    return best - got, pick == ref.argmax(-1)
