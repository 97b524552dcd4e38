import functools

import jax

from repro.kernels.gesummv.kernel import gesummv
from repro.kernels.gesummv.ref import gesummv_ref


@functools.partial(jax.jit, static_argnames=("bm", "interpret", "use_pallas"))
def gesummv_op(alpha, beta, a, b, x, *, bm=128, interpret=None,
               use_pallas=True):
    if not use_pallas:
        return gesummv_ref(alpha, beta, a, b, x)
    return gesummv(alpha, beta, a, b, x, bm=bm, interpret=interpret)
