"""Pallas TPU kernels for the compute hot-spots the paper optimizes.

Every kernel takes ``interpret=None`` by default and resolves it through
:func:`resolve_interpret`: compiled on a TPU, interpreted everywhere else.
No kernel decides this on its own, so nothing on the TPU path can silently
run the interpreter.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """A kernel's ``interpret`` argument: an explicit bool wins; None means
    interpret mode everywhere but on a TPU (JAX's default backend)."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
