import functools

import jax

from repro.kernels.heat3d.kernel import heat3d_step
from repro.kernels.heat3d.ref import heat3d_step_ref


@functools.partial(jax.jit, static_argnames=("steps", "bz", "interpret",
                                             "use_pallas"))
def heat3d(u, *, steps: int = 1, bz: int = 8, interpret=None,
           use_pallas=True):
    for _ in range(steps):
        u = (heat3d_step(u, bz=bz, interpret=interpret) if use_pallas
             else heat3d_step_ref(u))
    return u
