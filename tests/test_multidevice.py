"""Multi-device tests (shard_map SP decode, pipeline parallelism, compressed
psum, sharded train step). Run in subprocesses so conftest keeps 1 device."""
import subprocess
import sys
import textwrap

import pytest


def _run(script: str, devices: int = 4):
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import sys
        sys.path.insert(0, "src")
    """) + textwrap.dedent(script)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=540, cwd=".")
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


def test_pipeline_parallel_fwd_grad():
    _run("""
        import jax, jax.numpy as jnp
        from repro.launch.pipeline import pipeline_apply
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("stage",))
        W = jax.random.normal(jax.random.key(0), (8, 16, 16)) * 0.3
        x = jax.random.normal(jax.random.key(1), (6, 2, 4, 16))
        def apply_stage(w_loc, x):
            def body(x, w): return jnp.tanh(x @ w), None
            return jax.lax.scan(body, x, w_loc)[0]
        def ref_fn(Wp):
            def one(xx):
                return jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None),
                                    xx, Wp)[0]
            return jnp.sum(jnp.sin(jax.vmap(one)(x)))
        with jax.set_mesh(mesh):
            out = jax.jit(lambda W, x: pipeline_apply(
                W, x, apply_stage, mesh))(W, x)
            g1 = jax.jit(jax.grad(lambda Wp: jnp.sum(jnp.sin(
                pipeline_apply(Wp, x, apply_stage, mesh)))))(W)
        def one(xx):
            return jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), xx, W)[0]
        ref = jax.vmap(one)(x)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5
        g2 = jax.grad(ref_fn)(W)
        assert float(jnp.max(jnp.abs(g1 - g2))) < 1e-4
        print("PP OK")
    """)


def test_compressed_psum_close_to_exact():
    _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.optim.compression import compressed_psum
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))
        x = jax.random.normal(jax.random.key(0), (4, 256))
        def f(x):
            return compressed_psum(x, "data"), jax.lax.psum(x, "data")
        with jax.set_mesh(mesh):
            got, exact = jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P("data"),
                out_specs=(P("data"), P("data"))))(x)
        rel = float(jnp.max(jnp.abs(got - exact))) / float(jnp.max(jnp.abs(exact)))
        assert rel < 0.05, rel
        print("compressed psum OK", rel)
    """)


def test_sharded_train_step_matches_single_device():
    _run("""
        import jax, jax.numpy as jnp
        from repro.configs import TrainConfig, get_config, reduce_for_smoke
        from repro.launch.mesh import make_host_mesh
        from repro.launch.steps import make_train_step
        from repro.models import MeshInfo, NO_MESH, init_params
        from repro.optim import init_opt_state
        cfg = reduce_for_smoke(get_config("llama3.2-1b"))
        tc = TrainConfig(lr=1e-3, warmup_steps=1, total_steps=10)
        params = init_params(cfg, jax.random.key(0))
        batch = {"tokens": jax.random.randint(jax.random.key(1), (4, 32), 0,
                                              cfg.vocab_size),
                 "labels": jax.random.randint(jax.random.key(2), (4, 32), 0,
                                              cfg.vocab_size)}
        # single device
        s1 = make_train_step(cfg, tc, NO_MESH)
        p1, o1, m1 = s1(params, init_opt_state(params), batch)
        # 2x2 mesh
        mesh = make_host_mesh(data=2, model=2)
        s2 = make_train_step(cfg, tc, MeshInfo(mesh))
        with jax.set_mesh(mesh):
            p2, o2, m2 = jax.jit(s2)(params, init_opt_state(params), batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
        d = max(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
        assert d < 1e-4, d
        print("sharded train OK", d)
    """)


def test_sp_decode_long_context():
    _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.models.attention import PagedKV, sp_paged_decode
        from repro.models.attention import paged_decode_attention, paged_append
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 1), ("data", "model"))
        B, Hq, Hkv, P_, T, D = 1, 4, 2, 8, 4, 16
        ks = jax.random.split(jax.random.key(0), 5)
        q = jax.random.normal(ks[0], (B, 1, Hq, D))
        kn = jax.random.normal(ks[3], (B, 1, Hkv, D))
        vn = jax.random.normal(ks[4], (B, 1, Hkv, D))
        kp = jax.random.normal(ks[1], (B, P_, T, Hkv, D))
        vp = jax.random.normal(ks[2], (B, P_, T, Hkv, D))
        tbl = jnp.broadcast_to(jnp.arange(P_, dtype=jnp.int32), (B, P_))
        ln = jnp.int32(P_ * T - 3)
        kv = PagedKV(kp, vp, tbl, ln)
        # reference on one device: append + dense paged attention
        kv_ref = paged_append(kv, kn, vn)
        ref = paged_decode_attention(q, kv_ref)
        with jax.set_mesh(mesh):
            out, kv2 = jax.jit(lambda q, kn, vn, kv: sp_paged_decode(
                q, kn, vn, kv, mesh))(q, kn, vn, kv)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 1e-5, err
        assert float(jnp.max(jnp.abs(kv2.k_pool - kv_ref.k_pool))) < 1e-6
        print("SP decode OK", err)
    """)
