"""Per-kernel interpret-mode validation: shape/dtype sweeps vs pure-jnp
oracles (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.axpy.kernel import axpy
from repro.kernels.axpy.ref import axpy_ref
from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.gemm.kernel import gemm
from repro.kernels.gemm.ref import gemm_ref
from repro.kernels.gesummv.kernel import gesummv
from repro.kernels.gesummv.ref import gesummv_ref
from repro.kernels.heat3d.kernel import heat3d_step
from repro.kernels.heat3d.ref import heat3d_step_ref
from repro.kernels.mergesort.ops import mergesort
from repro.kernels.paged_attention.kernel import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref


@pytest.mark.parametrize("m,n,k,bm,bn,bk", [
    (128, 128, 128, 128, 128, 128),
    (256, 512, 384, 64, 128, 128),
    (64, 64, 256, 32, 32, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm(m, n, k, bm, bn, bk, dtype, key):
    a = jax.random.normal(key, (m, k), dtype)
    b = jax.random.normal(jax.random.key(1), (k, n), dtype)
    out = gemm(a, b, bm=bm, bn=bn, bk=bk)
    ref = gemm_ref(a, b)
    tol = 2e-3 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol * np.sqrt(k), rtol=tol)


@pytest.mark.parametrize("n,block", [(1024, 1024), (32768, 4096), (4096, 512)])
def test_axpy(n, block, key):
    x = jax.random.normal(key, (n,))
    y = jax.random.normal(jax.random.key(2), (n,))
    out = axpy(jnp.float32(2.5), x, y, block=block)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(axpy_ref(2.5, x, y)),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("n,k,bm", [(512, 512, 128), (256, 384, 64)])
def test_gesummv(n, k, bm, key):
    a = jax.random.normal(key, (n, k))
    b = jax.random.normal(jax.random.key(3), (n, k))
    x = jax.random.normal(jax.random.key(4), (k,))
    out = gesummv(1.5, -0.5, a, b, x, bm=bm)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(gesummv_ref(1.5, -0.5, a, b, x)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shape,bz", [((34, 34, 34), 8), ((18, 10, 12), 4)])
def test_heat3d(shape, bz, key):
    u = jax.random.normal(key, shape)
    out = heat3d_step(u, bz=bz)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(heat3d_step_ref(u)), atol=1e-5)


@pytest.mark.parametrize("n,block", [(4096, 256), (65536, 1024), (1024, 64)])
def test_mergesort(n, block, key):
    x = jax.random.normal(key, (n,))
    assert bool(jnp.all(mergesort(x, block=block) == jnp.sort(x)))
    xi = jax.random.randint(jax.random.key(5), (n,), 0, 37).astype(jnp.float32)
    assert bool(jnp.all(mergesort(xi, block=block) == jnp.sort(xi)))


@pytest.mark.parametrize("B,Hq,Hkv,n_pages,page,cap", [
    (3, 8, 2, 4, 16, None),
    (2, 4, 4, 8, 8, None),
    (1, 16, 4, 4, 32, 30.0),
])
@pytest.mark.parametrize("residency", ["smem", "hbm"])
def test_paged_attention(B, Hq, Hkv, n_pages, page, cap, residency, key):
    D = 64
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, Hq, D))
    kp = jax.random.normal(ks[1], (B, n_pages, page, Hkv, D))
    vp = jax.random.normal(ks[2], (B, n_pages, page, Hkv, D))
    tbl = jnp.stack([jax.random.permutation(kk, n_pages)
                     for kk in jax.random.split(ks[3], B)]).astype(jnp.int32)
    lens = jnp.asarray(
        np.random.default_rng(0).integers(1, n_pages * page, B), jnp.int32)
    out = paged_attention(q, kp, vp, tbl, lens, softcap=cap,
                          table_residency=residency)
    ref = paged_attention_ref(q, kp, vp, tbl, lens, softcap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("G,Hkv,dtype,stacked,cap", [
    (1, 2, jnp.float32, True, None),
    (7, 2, jnp.float32, True, 30.0),
    (1, 2, jnp.float32, False, None),
    (7, 2, jnp.float32, False, None),
    (7, 4, jnp.bfloat16, True, None),       # two heads to a 32-bit word
    (1, 4, jnp.bfloat16, False, 30.0),
    (7, 1, jnp.bfloat16, True, None),
])
def test_paged_attention_global_layout(G, Hkv, dtype, stacked, cap, key,
                                       monkeypatch):
    """The ragged kernel over the shared global pool, against the float32
    oracle. Rows of length 0, 1, page - 1, page, page + 1 and the full
    table; two rows map the SAME physical pages (CoW prefix sharing); a
    masked row (length 1 through an all-NULL table, as the engine's masked
    decode leaves it); entries past a row's pages hold NULL or a page of
    NaNs, which the kernel must never read. Blocks of 3 pages do not divide
    the 8-page table. A stacked pool is read at a layer index."""
    from repro.kernels.paged_attention import kernel as K
    from repro.kernels.paged_attention.ref import paged_attention_global_ref
    page, P, D, L, layer = 4, 8, 64, 3, 1
    Hq = G * Hkv
    lens = [0, 1, page - 1, page, page + 1, P * page, 2 * page + 3,
            2 * page + 1, 1]
    B = len(lens)
    monkeypatch.setattr(K, "_BLOCK_BYTES",
                        3 * page * Hkv * D * jnp.dtype(dtype).itemsize)
    total = sum(-(-n // page) for n in lens) + 1       # + one NaN page
    nan_page = total - 1
    tbl = np.full((B, P), total, np.int32)             # NULL
    nxt = 0
    for b, n in enumerate(lens[:-1]):
        k = -(-n // page)
        tbl[b, :k] = np.arange(nxt, nxt + k)
        nxt += k
    tbl[7, :2] = tbl[6, :2]            # row 7 shares row 6's first pages
    tbl[2, 1:] = nan_page              # past its pages: a page of NaNs
    tbl[4, 2] = nan_page
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hq, D))
    kp = jax.random.normal(ks[1], (L, total, page, Hkv, D)).astype(dtype)
    vp = jax.random.normal(ks[2], (L, total, page, Hkv, D)).astype(dtype)
    kp = kp.at[:, nan_page].set(jnp.nan)
    vp = vp.at[:, nan_page].set(jnp.nan)
    tbl, lengths = jnp.asarray(tbl), jnp.asarray(lens, jnp.int32)
    if stacked:
        out = K.ragged_paged_decode(q, kp, vp, tbl, lengths, jnp.int32(layer),
                                    softcap=cap)
    else:
        out = K.ragged_paged_decode(q, kp[layer], vp[layer], tbl, lengths,
                                    softcap=cap)
    clean = lambda pool: jnp.nan_to_num(pool[layer].astype(jnp.float32))
    ref = paged_attention_global_ref(q, clean(kp), clean(vp), tbl, lengths,
                                     softcap=cap)
    live = np.asarray([n > 0 for n in lens[:-1]] + [False])
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live],
                               atol=1e-5, rtol=1e-5)
    assert np.all(np.asarray(out)[~live] == 0)    # no KV: zeros, no reads


@pytest.mark.parametrize("S,Hq,Hkv,causal", [
    (128, 4, 2, True), (64, 2, 2, False), (256, 8, 2, True)])
def test_flash_kernel(S, Hq, Hkv, causal, key):
    B, D = 2, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    out = flash_attention_op(q, k, v, causal=causal, bq=64, bkv=64)
    kr = jnp.repeat(k, Hq // Hkv, axis=2)
    vr = jnp.repeat(v, Hq // Hkv, axis=2)
    ref = attention_ref(q.swapaxes(1, 2), kr.swapaxes(1, 2),
                        vr.swapaxes(1, 2), causal=causal).swapaxes(1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
