"""The served path's chip bring-up pieces, checked on the CPU: chip_smoke's
serving and kernel-check phases at smoke size, its refusal to run off a TPU,
the compile-cache placement and the interpret-mode decision."""
import os
import pathlib
import subprocess
import sys

import jax
import pytest

import chip_smoke
from repro.kernels import resolve_interpret
from repro.launch import compile_cache
from repro.launch.serve import build_config, build_engine
from repro.models import attention as attn
from repro.models import init_params

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    cfg = build_config(chip_smoke.ARCH, smoke=True)
    return cfg, init_params(cfg, jax.random.key(0))


def test_chip_smoke_phases_at_smoke_size(smoke, monkeypatch):
    """Serve through the continuous engine with the ragged decode kernel
    (interpret mode here, forced through the one choice) over a pool
    larger than the slots' worst case, then check the kernel on the live
    pool against the float32 reference."""
    cfg, params = smoke
    monkeypatch.setattr(attn, "ragged_decode_serves", attn.is_global_layout)
    eng = build_engine(cfg, params, slots=4, max_len=64, page_size=8,
                       pool_pages=40)
    assert eng.scheduler_mode == "continuous"
    assert eng.null_page == 40
    prompts = chip_smoke.make_prompts(4, cfg.vocab_size, (8, 40), seed=0)
    outs, snap = chip_smoke.serve(eng, prompts, 5, snapshot=True)
    chip_smoke.check_lengths(outs, 5)
    s = eng.stats()
    assert s["tokens"] > 0 and s["staging_copies"] == 0
    assert snap["k_pool"].shape[0] == 40
    assert "HloModule" in eng.decode_step_hlo()
    kc = chip_smoke.kernel_check(snap, cfg.n_heads, seed=0)
    assert kc["rows"] >= 2
    assert kc["err"] <= chip_smoke.KERNEL_TOL * (1 + kc["scale"])
    assert kc["jax_err"] <= chip_smoke.KERNEL_TOL * (1 + kc["scale"])


def test_chip_smoke_backends_agree_at_smoke_size(smoke, monkeypatch):
    """At float32 smoke widths the ragged kernel and the pure-JAX decode
    path serve the same tokens through chip_smoke's serve phase, each
    chosen the way chip_smoke chooses it."""
    cfg, params = smoke
    prompts = chip_smoke.make_prompts(3, cfg.vocab_size, (8, 30), seed=1)
    outs = []
    with chip_smoke.jax_decode_path():
        eng = build_engine(cfg, params, slots=3, max_len=64, page_size=8)
        outs.append(chip_smoke.serve(eng, prompts, 4)[0])
    # off a TPU the kernel path is forced, and interpreted
    monkeypatch.setattr(attn, "ragged_decode_serves", attn.is_global_layout)
    eng = build_engine(cfg, params, slots=3, max_len=64, page_size=8)
    outs.append(chip_smoke.serve(eng, prompts, 4)[0])
    assert outs[0] == outs[1]


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300,
                       cwd=ROOT, env=env)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "not a TPU" in r.stderr


def test_compile_cache_honors_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_inside_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert compile_cache.enable_compile_cache() == got   # fixed path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_pallas_backend_refuses_stacked_table(monkeypatch):
    """The ragged kernel never takes the pure-JAX path in silence: a table
    with a leading stacked-blocks axis raises."""
    import jax.numpy as jnp
    monkeypatch.setattr(attn, "ragged_decode_serves", attn.is_global_layout)
    pool = jnp.zeros((2, 6, 8, 2, 16))               # (blocks, pages, ...)
    kv = attn.PagedKV(pool, pool, jnp.zeros((2, 3, 4), jnp.int32),
                      jnp.zeros((2, 3), jnp.int32))
    with pytest.raises(ValueError, match="ragged paged decode"):
        attn.paged_decode_attention(jnp.zeros((3, 1, 4, 16)), kv)


@pytest.mark.parametrize("platform,interpret", [
    ("tpu", False), ("cpu", True), ("gpu", True)])
def test_interpret_mode_by_platform(monkeypatch, platform, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert resolve_interpret() is interpret


def test_resolve_interpret_follows_platform():
    assert resolve_interpret(None) is (jax.default_backend() != "tpu")
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True
