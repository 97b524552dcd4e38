import functools

import jax

from repro.kernels.axpy.kernel import axpy
from repro.kernels.axpy.ref import axpy_ref


@functools.partial(jax.jit, static_argnames=("block", "interpret",
                                             "use_pallas"))
def axpy_op(a, x, y, *, block=8192, interpret=None, use_pallas=True):
    if not use_pallas:
        return axpy_ref(a, x, y)
    return axpy(a, x, y, block=block, interpret=interpret)
