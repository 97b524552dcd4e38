"""Cross-engine conformance matrix over the shared harness
(tests/conformance.py): every engine kind (continuous-ample, continuous,
disagg-share, disagg-copy) x tenancy mode (off, single-tenant deployment,
two tenants) must reproduce the copy-mode reference engine's outputs
token-for-token on the canonical pressure workload.

The single-tenant column is the PR's compatibility acceptance: a
deployment description with one tenant and partitioning OFF routes every
request through the TenantDomain/quota/isolation machinery and still
matches today's engines bit-for-bit. The two-tenant column adds ASID
isolation across interleaved tenants with quotas and partitions off —
isolation bookkeeping alone never changes tokens."""
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.configs.deployment import DeploymentConfig, TenantSpec
from repro.models import init_params
from tests.conformance import (ARRIVAL_CASES, ENGINE_KINDS, POOL,
                               REFERENCE, assert_bit_identical, make_engine,
                               pressure_workload, serve)

TENANCIES = ("off", "single", "two")


@pytest.fixture(scope="module")
def setup():
    import jax
    cfg = reduce_for_smoke(get_config("llama3.2-1b"))
    return cfg, init_params(cfg, jax.random.key(0))


@pytest.fixture(scope="module")
def ref(setup):
    """Ground truth, computed once: the copy-mode reference engine,
    untenanted (copy mode refuses tenants)."""
    cfg, params = setup
    outs, _, _ = serve(cfg, params, REFERENCE,
                       pressure_workload(cfg.vocab_size))
    return outs


def _tenancy(cfg, kind, tenancy, n_req=6):
    """(compiled cfg, engine tenants dict, per-request tenant names,
    pool_pages) for one matrix cell. Quotas equal the whole pool and
    partitioning stays off, so tenancy adds bookkeeping, never behavior."""
    pool = POOL if kind != "continuous-ample" else None
    if tenancy == "off":
        return cfg, None, None, pool
    if tenancy == "single":
        dep = DeploymentConfig((TenantSpec("t0", pool_share=1.0),))
        quota_base = pool if pool is not None else 32   # 4 slots x 8 pages
        return (dep.compile(cfg), dep.tenant_dict(quota_base),
                ("t0",) * n_req, pool)
    # two tenants, interleaved per request, no quotas/partitions
    return (cfg, {"a": {}, "b": {}},
            tuple("ab"[i % 2] for i in range(n_req)), pool)


@pytest.mark.parametrize("tenancy", TENANCIES)
@pytest.mark.parametrize("kind", ENGINE_KINDS)
def test_cross_engine_matrix(setup, ref, kind, tenancy):
    cfg, params = setup
    ecfg, tenants, names, pool = _tenancy(cfg, kind, tenancy)
    wl = pressure_workload(cfg.vocab_size, tenants=names)
    outs, eng, _ = serve(ecfg, params, kind, wl, tenants=tenants,
                         pool_pages=pool)
    assert outs == ref
    if tenants is not None:
        # every tenant served and the isolation gate saw no denials
        s = eng.stats()["tenant"]
        assert sorted(s) == sorted(tenants)
        assert all(b["denials"] == 0 for b in s.values())
        assert sum(b["seqs"] for b in s.values()) == 0   # all released


@pytest.mark.parametrize("arrivals", ARRIVAL_CASES)
def test_two_tenant_interleavings_bit_identical(setup, ref, arrivals):
    """Tenancy under every arrival interleaving: staggered cross-tenant
    admission still reproduces the untenanted outputs."""
    cfg, params = setup
    wl = pressure_workload(cfg.vocab_size, arrivals=arrivals,
                           tenants=tuple("ab"[i % 2] for i in range(6)))
    outs, _, _ = serve(cfg, params, "continuous", wl,
                       tenants={"a": {}, "b": {}}, pool_pages=POOL)
    assert outs == ref


def test_assert_bit_identical_entrypoint(setup):
    """The harness's own assertion helper: two fresh engines of different
    kinds, one workload."""
    cfg, params = setup
    wl = pressure_workload(cfg.vocab_size)
    assert_bit_identical(make_engine(cfg, params, REFERENCE),
                         make_engine(cfg, params, "continuous",
                                     pool_pages=POOL),
                         wl)


def test_assert_bit_identical_detects_divergence(setup):
    """The helper actually fails on divergent engines (different request
    mix via truncated max_tokens)."""
    cfg, params = setup
    wl = pressure_workload(cfg.vocab_size)
    short = pressure_workload(cfg.vocab_size)
    short = type(short)(short.prompts, tuple(m - 1 for m in short.maxtoks))

    class Clipped:
        """Engine proxy that serves the clipped workload instead."""

        def __init__(self, eng):
            self._eng = eng
            self._i = 0

        def submit(self, prompt, max_tokens=16, tenant=None):
            m = short.maxtoks[self._i % len(short.maxtoks)]
            self._i += 1
            return self._eng.submit(prompt, max_tokens=m, tenant=tenant)

        def __getattr__(self, name):
            return getattr(self._eng, name)

    with pytest.raises(AssertionError):
        assert_bit_identical(make_engine(cfg, params, REFERENCE),
                             Clipped(make_engine(cfg, params, REFERENCE)),
                             wl)
