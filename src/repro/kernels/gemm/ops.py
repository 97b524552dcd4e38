import functools

import jax

from repro.kernels.gemm.kernel import gemm
from repro.kernels.gemm.ref import gemm_ref


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "use_pallas"))
def matmul(a, b, *, bm=128, bn=128, bk=128, interpret=None, use_pallas=True):
    if not use_pallas:
        return gemm_ref(a, b)
    return gemm(a, b, bm=bm, bn=bn, bk=bk, interpret=interpret)
