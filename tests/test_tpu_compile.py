"""Compiles for a described TPU v5e chip: the main path's Pallas kernels at
qwen2-7b widths, through the chip's own compiler, without a chip attached.

A compile that passes here is not a chip run; it proves the kernel lowers
(no interpret mode) and that the compiled program holds it. The topology is
described only inside a fixture, so importing this file never loads the TPU
library."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.kernel import (paged_attention,
                                                  ragged_paged_decode)

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
    """The ragged kernel over qwen2-7b's served pool: 14 stacked layers of
    5,376 pages, read at a layer index, 24 slots x 224 table entries."""
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = S((14, 5376, PAGE, HKV, D), jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v, t, n, layer: ragged_paged_decode(
            q, k, v, t, n, layer, interpret=False),
        S((24, HQ, D), q_dtype), pool, pool, S((24, 224), jnp.int32),
        S((24,), jnp.int32), S((), jnp.int32))
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


NB, SLOTS, TABLE, CARRY_POOL = 3, 8, 128, 4096   # 3 blocks, 8 x 2048 tokens


def _engine_program(one_chip, program, heads=(HQ, HKV), d_model=3584):
    """The engine's decode or chunked-prefill program over the stacked
    global pool, for 3 scanned blocks, compiled for the described chip."""
    import functools
    import types

    from repro.configs.base import ModelConfig
    from repro.core.serving.engine import ServingEngine
    from repro.models import model_specs
    from repro.models.dist import NO_MESH
    from repro.models.model import cache_specs
    from repro.models.params import abstract

    cfg = ModelConfig(name="attn-widths", family="dense", n_layers=NB,
                      d_model=d_model, n_heads=heads[0], n_kv_heads=heads[1],
                      d_head=D, d_ff=512, vocab_size=1024,
                      block_pattern=("attn_mlp",), qkv_bias=True, act="silu",
                      param_dtype="bfloat16", activation_dtype="bfloat16")
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    on_chip = lambda specs: jax.tree.map(lambda a: S(a.shape, a.dtype),
                                         abstract(specs))
    i32 = lambda *shape: S(shape, jnp.int32)
    params = on_chip(model_specs(cfg))
    cache = on_chip(cache_specs(cfg, SLOTS, TABLE * PAGE, PAGE, per_seq=True,
                                global_pages=CARRY_POOL))
    eng = types.SimpleNamespace(cfg=cfg, mi=NO_MESH, src_len=16,
                                null_page=CARRY_POOL)
    if program == "decode":
        fn = jax.jit(functools.partial(ServingEngine._decode_masked, eng),
                     donate_argnums=(5,))
        args = (params, i32(SLOTS, 1), i32(SLOTS), i32(SLOTS, TABLE),
                S((SLOTS,), jnp.bool_), cache)
    else:
        rows = 2
        batch = {"tokens": i32(rows, 64), "lengths": i32(rows),
                 "tables": i32(rows, TABLE), "slots": i32(rows),
                 "prefix_lens": i32(rows), "write_tables": i32(rows, TABLE)}
        fn = jax.jit(functools.partial(ServingEngine._prefill_zero_copy, eng),
                     donate_argnums=(2,))
        args = (params, batch, cache)
    return fn.lower(*args).compile()


def _on_the_chip(monkeypatch):
    """Make the program take the path it takes on a TPU: the one choice
    (``attention.ragged_decode_serves``) follows the platform JAX reports,
    which a described chip does not change."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("program", ["decode", "prefill", "decode-kernel"])
def test_zero_copy_programs_hold_no_second_pool(one_chip, no_compile_cache,
                                                monkeypatch, program):
    """The engine's decode and chunked-prefill programs over the stacked
    global pool, for 3 scanned blocks at qwen2-7b attention widths: the
    pool rides the layer loop's carry and is updated in place, so the
    programs' temporaries stay below ONE layer's K pool. Sliced out of
    the stack and restacked each layer, they held more than the whole
    stacked pool. ``decode`` is the pure-JAX path, ``decode-kernel`` the
    ragged kernel a TPU runs."""
    if program == "decode-kernel":
        _on_the_chip(monkeypatch)
    compiled = _engine_program(one_chip, program.split("-")[0])
    layer_k_pool = CARRY_POOL * PAGE * HKV * D * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_k_pool


@pytest.mark.parametrize("heads,d_model",
                         [((HQ, HKV), 3584), ((32, 32), 4096)],
                         ids=["qwen2-7b", "hkv32"])
def test_decode_program_reads_pool_through_kernel(one_chip, no_compile_cache,
                                                  monkeypatch, heads,
                                                  d_model):
    """On a TPU the engine's decode program reads the stacked pool through
    the ragged kernel: the program holds a ``tpu_custom_call`` under the
    ``attention`` scope (``decode.attn_device_ms_per_step`` reads that
    scope), and no gather reads the pool, whose every table entry the
    pure-JAX path gathers."""
    _on_the_chip(monkeypatch)
    hlo = _engine_program(one_chip, "decode", heads, d_model).as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert calls
    for ln in calls:
        assert "/attention/" in re.search(r'op_name="([^"]*)"', ln).group(1)
    assert not [op for op in _gather_operands(hlo)
                if op.endswith(f"{CARRY_POOL},{PAGE},{heads[1]},{D}]")]


def _gather_operands(hlo: str):
    """The shape of each gather's first operand in an HLO module's text
    (an instruction line names its operands, their definitions give the
    shapes)."""
    shapes = dict(re.findall(r"(%[\w.-]+) = (\w+\[[\d,]*\])", hlo))
    return [shapes[name] for name in
            re.findall(r" gather\((%[\w.-]+),", hlo)]
