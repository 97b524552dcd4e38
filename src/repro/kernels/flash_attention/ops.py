import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "softcap", "bq", "bkv",
                                             "interpret", "use_pallas"))
def flash_attention_op(q, k, v, *, causal=True, softcap=None, bq=128,
                       bkv=128, interpret=None, use_pallas=True):
    """q: (B,S,Hq,D); k,v: (B,S,Hkv,D) — BSHD layout like models/attention."""
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    if Hq != Hkv:
        k = jnp.repeat(k, Hq // Hkv, axis=2)
        v = jnp.repeat(v, Hq // Hkv, axis=2)
    qt, kt, vt = (x.swapaxes(1, 2) for x in (q, k, v))
    if not use_pallas:
        return attention_ref(qt, kt, vt, causal=causal,
                             softcap=softcap).swapaxes(1, 2)
    o = flash_attention_fwd(qt, kt, vt, causal=causal, softcap=softcap,
                            bq=bq, bkv=bkv, interpret=interpret)
    return o.swapaxes(1, 2)
