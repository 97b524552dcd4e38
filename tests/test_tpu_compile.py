"""Compiles for a described TPU v5e chip: the main path's Pallas kernels at
qwen2-7b widths, through the chip's own compiler, without a chip attached.

A compile that passes here is not a chip run; it proves the kernel lowers
(no interpret mode) and that the compiled program holds it. The topology is
described only inside a fixture, so importing this file never loads the TPU
library."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.kernel import (paged_attention,
                                                  paged_attention_global)

HQ, HKV, D = 28, 4, 128          # qwen2-7b attention heads
PAGE, B, P = 16, 8, 128          # 8 slots x 2048 tokens in 16-token pages
POOL_PAGES = 4096                # a 64k-token global pool


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    # the TPU compiler otherwise writes its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described-chip compile cannot be read back without the chip: keep
    these compiles out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


@pytest.mark.parametrize("q_dtype", [jnp.bfloat16, jnp.float32])
def test_paged_attention_global_compiles_for_v5e(one_chip, no_compile_cache,
                                                 q_dtype):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = S((POOL_PAGES, PAGE, HKV, D), jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v, t, n: paged_attention_global(q, k, v, t, n,
                                                     interpret=False),
        S((B, HQ, D), q_dtype), pool, pool, S((B, P), jnp.int32),
        S((B,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_attention_per_slot_compiles_for_v5e(one_chip,
                                                   no_compile_cache):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = S((B, 32, PAGE, HKV, D), jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v, t, n: paged_attention(q, k, v, t, n,
                                              interpret=False),
        S((B, HQ, D), jnp.bfloat16), pool, pool, S((B, 32), jnp.int32),
        S((B,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()
