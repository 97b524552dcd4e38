"""Multi-tenant serving domains: ASID isolation, way-partitioned IOTLB,
tenant quotas, deployment descriptions, and scenario determinism.

The core property (hypothesis-randomized when hypothesis is installed,
fixed cases always): NO interleaving of admit / append / CoW / migrate /
release across two tenants ever translates a foreign page — the IOMMU's
isolation gate refuses cross-tenant and anonymous access to owned ASIDs,
and the translation sanitizer's independent shadow check
(cross-tenant-translate) watches the whole run. Manager-level tests are
jax-free; CI runs this file under ``REPRO_SVASAN=1`` (the manager tests
force ``sanitize=True`` regardless, so the property holds outside CI
too)."""
import dataclasses

import numpy as np
import pytest

from benchmarks.scenarios import (SCENARIO_KINDS, generate,
                                  trace_fingerprint)
from repro.configs import get_config, reduce_for_smoke
from repro.configs.deployment import (DeploymentConfig, TenantSpec,
                                      two_tenant_demo)
from repro.core.sva.iommu import (IOMMU, CountingWalk, IsolationError,
                                  TLBConfig)
from repro.core.sva.kv_manager import CapacityError, PagedKVManager
from repro.models import init_params
from tests.conformance import REFERENCE, Workload, pressure_workload, serve

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


@pytest.fixture(scope="module")
def setup():
    import jax
    cfg = reduce_for_smoke(get_config("llama3.2-1b"))
    return cfg, init_params(cfg, jax.random.key(0))


def _mgr(**kw):
    kw.setdefault("tenants", {"a": {}, "b": {}})
    return PagedKVManager(n_slots=4, max_pages_per_slot=4, page_size=8,
                          kv_bytes_per_token=256, sanitize=True, **kw)


# ------------------------------------------------------- isolation basics

def test_isolation_error_is_structured():
    mgr = _mgr()
    st_a = mgr.admit(1, prompt_len=10, max_tokens=4,
                     tokens=list(range(10)), lazy=True, tenant="a")
    with pytest.raises(IsolationError) as ei:
        mgr.iommu.translate(st_a.slot, 0, tenant="b")
    e = ei.value
    assert (e.tenant, e.owner, e.asid, e.page) == ("b", "a", st_a.slot, 0)
    assert isinstance(e, PermissionError)
    # anonymous access to an owned ASID is refused too
    with pytest.raises(IsolationError) as ei:
        mgr.iommu.translate(st_a.slot, 0)
    assert ei.value.tenant is None and ei.value.owner == "a"
    # denials are charged to the REQUESTING domain
    assert mgr.iommu._tenants["b"].denials == 1
    assert mgr.iommu._tenants["a"].denials == 0


def test_attach_unknown_tenant_rejected():
    mgr = _mgr()
    with pytest.raises(ValueError):
        mgr.admit(1, prompt_len=8, max_tokens=2, tokens=list(range(8)),
                  lazy=True, tenant="zeta")
    iommu = IOMMU(walk_model=CountingWalk())
    iommu.register_tenant("a")
    with pytest.raises(ValueError):
        iommu.attach(0, tenant="nope")


def test_quota_ensure_fits_rejects_unservable():
    """A request needing more pages than the tenant's quota can NEVER run
    — rejected at submit, not queued forever."""
    mgr = _mgr(tenants={"a": {"quota_pages": 2}, "b": {}})
    with pytest.raises(CapacityError):
        mgr.ensure_fits(prompt_len=20, max_tokens=8, tenant="a")  # 4 pages
    mgr.ensure_fits(prompt_len=20, max_tokens=8, tenant="b")      # no quota
    mgr.ensure_fits(prompt_len=8, max_tokens=4, tenant="a")       # 2 pages


def test_total_refs_reconciles_with_seq_pages():
    """pool.total_refs() is the gauge quotas meter against: with prefix
    sharing off it equals the sum of live sequences' page mappings, and
    returns to zero after release."""
    mgr = _mgr(prefix_sharing=False)
    mgr.admit(1, prompt_len=16, max_tokens=2, tokens=list(range(16)),
              lazy=True, tenant="a")
    mgr.admit(2, prompt_len=8, max_tokens=2, tokens=list(range(8)),
              lazy=True, tenant="b")
    assert mgr.pool.total_refs() == sum(len(s.pages)
                                        for s in mgr.seqs.values()) == 3
    assert mgr.tenant_pages_used("a") == 2
    assert mgr.tenant_pages_used("b") == 1
    mgr.release(1)
    mgr.release(2)
    assert mgr.pool.total_refs() == 0


# ----------------------------------------- the isolation property machine

def _run_tenant_ops(ops):
    """Interpret a list of (op, k) codes as a two-tenant admit / append /
    CoW(shared-prefix admit) / migrate / release interleaving; after every
    op, every live mapping must translate ONLY under its owner and refuse
    the other tenant — sanitizer watching throughout."""
    from repro.core.sva.page_pool import OutOfPages
    mgr = _mgr()
    next_id, live = 1, []
    common = list(range(12))                     # shared-prefix bait (CoW)
    for op, k in ops:
        try:
            if op == 0 and len(live) < 3:        # admit (alternating tenant)
                t = "ab"[next_id % 2]
                tokens = common + [100 + next_id] if k % 2 else \
                    list(range(20 + next_id, 30 + next_id))
                s = mgr.admit(next_id, prompt_len=len(tokens), max_tokens=4,
                              tokens=tokens, lazy=True, tenant=t)
                if s is not None:
                    live.append(next_id)
                next_id += 1
            elif op == 1 and live:               # append (CoW on shared)
                mgr.append_token(live[k % len(live)], 7)
            elif op == 2 and live:               # migrate to a free slot
                sid = live[k % len(live)]
                used = {s.slot for s in mgr.seqs.values()}
                free = [s for s in range(4) if s not in used]
                if free:
                    mgr.reserve_slots([free[0]])
                    mgr.migrate(sid, free[0],
                                mode="share" if k % 2 else "copy")
                    mgr.pending_cow.clear()      # engine-side copy queue
            elif op == 3 and live:               # release
                mgr.release(live.pop(k % len(live)))
        except OutOfPages:
            pass                                 # transient; invariants hold
        # invariant: every live mapping translates under its owner only
        mgr.translate_step()
        for sid in live:
            s = mgr.seqs[sid]
            owner = mgr.iommu._asid_tenant.get(s.slot)
            assert owner == s.tenant
            if s.pages:
                other = "b" if s.tenant == "a" else "a"
                phys, _, _ = mgr.iommu.translate(s.slot, 0,
                                                 tenant=s.tenant)
                assert phys == s.pages[0]
                with pytest.raises(IsolationError):
                    mgr.iommu.translate(s.slot, 0, tenant=other)
    assert mgr.sanitizer.stats()["reports"] == 0
    assert mgr.sanitizer.stats()["checks"] > 0


FIXED_OP_CASES = [
    [(0, 1), (0, 1), (1, 0), (1, 1), (3, 0), (3, 0)],          # CoW pair
    [(0, 0), (0, 1), (2, 0), (1, 0), (2, 1), (3, 1), (3, 0)],  # migrations
    [(0, 1), (1, 0), (0, 1), (1, 1), (2, 0), (3, 0), (0, 0), (3, 0)],
]


@pytest.mark.parametrize("ops", FIXED_OP_CASES)
def test_isolation_interleavings_fixed(ops):
    _run_tenant_ops(ops)


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 3)),
                    min_size=1, max_size=12))
    def test_isolation_interleaving_property(ops):
        """NO admit/append/CoW/migrate/release interleaving across two
        tenants ever translates a foreign page."""
        _run_tenant_ops(ops)


# ------------------------------------------------- way-partition bounds

def test_partition_occupancy_bounds():
    """A partitioned tenant's resident entries never exceed its way
    budget in any set, no matter how hard it thrashes — and the victim
    tenant's working set survives the noisy neighbor."""
    tlb = TLBConfig(8, "lru", ways=4, partitions={"a": 2, "b": 1})
    iommu = IOMMU(walk_model=CountingWalk(), tlb=tlb)
    iommu.register_tenant("a")
    iommu.register_tenant("b")
    iommu.attach(0, tenant="a")
    iommu.attach(1, tenant="b")
    for lp in range(2):                          # b's tiny working set
        iommu.translate(1, lp, phys=lp, tenant="b")
    for lp in range(64):                         # a thrashes
        iommu.translate(0, lp, phys=lp, tenant="a")
    occ = iommu.tlb.partition_occupancy()
    for si in range(iommu.tlb.n_sets):
        assert occ["a"][si] <= 2
        assert occ["b"][si] <= 1
        assert occ[None][si] <= 4 - 2 - 1        # leftover shared ways
    # b's most-recent entry outlived a's 64-page sweep
    _, _, hit = iommu.translate(1, 1, phys=1, tenant="b")
    assert hit
    ts = iommu.tlb.tenant_stats["a"].as_dict()
    assert ts["conflict_misses"] > 0             # budget-bound, not capacity


def test_partition_validation():
    with pytest.raises(ValueError):              # reserves more than ways
        TLBConfig(8, "lru", ways=4, partitions={"a": 3, "b": 2})
    with pytest.raises(ValueError):              # needs set-associativity
        PagedKVManager(n_slots=2, max_pages_per_slot=4, page_size=8,
                       tenants={"a": {"tlb_ways": 2}})
    cfgs = [TLBConfig(8, "lru", ways=4, partitions={"a": 2}),
            TLBConfig(8, "lru", ways=4, partitions=(("a", 2),))]
    assert cfgs[0] == cfgs[1] and hash(cfgs[0]) == hash(cfgs[1])


# --------------------------------------------- quota-pressure preemption

def test_quota_preemption_bit_identical(setup):
    """Pool is AMPLE but tenant a's quota is tight: decode growth pushes a
    over quota, the scheduler sheds a's newest sequence (sparing the
    oldest — no thrash), and outputs still match the untenanted copy-mode
    reference engine token-for-token."""
    cfg, params = setup
    base = pressure_workload(cfg.vocab_size)
    prompts, maxtoks = base.prompts[:4], (10, 10, 8, 8)
    ref, _, _ = serve(cfg, params, REFERENCE, Workload(prompts, maxtoks))
    outs, eng, _ = serve(cfg, params, "continuous",
                         Workload(prompts, maxtoks,
                                  tenants=("a", "a", "b", "b")),
                         tenants={"a": {"quota_pages": 5}, "b": {}})
    s = eng.stats()
    assert outs == ref
    assert s["sched"]["preemptions"] >= 1
    assert s["sched"]["resumes"] >= 1
    assert s["tenant"]["a"]["quota_pages"] == 5
    assert s["tenant"]["a"]["denials"] == 0      # pressure, not isolation


# ------------------------------------------------ deployment descriptions

def test_deployment_validation_errors():
    with pytest.raises(ValueError, match="non-empty string"):
        TenantSpec("")
    with pytest.raises(ValueError, match="pool_share"):
        TenantSpec("a", pool_share=1.5)
    with pytest.raises(ValueError, match="tlb_ways"):
        TenantSpec("a", tlb_ways=-1)
    with pytest.raises(ValueError, match="at least one tenant"):
        DeploymentConfig(())
    with pytest.raises(ValueError, match="duplicate tenant names"):
        DeploymentConfig((TenantSpec("a"), TenantSpec("a")))
    with pytest.raises(ValueError, match="over-committed"):
        DeploymentConfig((TenantSpec("a", pool_share=0.7),
                          TenantSpec("b", pool_share=0.7)))
    with pytest.raises(ValueError, match="prefix_shares"):
        DeploymentConfig((TenantSpec("a", prefix_share=0.8),
                          TenantSpec("b", prefix_share=0.8)))
    with pytest.raises(ValueError, match="reserve 3 ways"):
        DeploymentConfig((TenantSpec("a", tlb_ways=2),
                          TenantSpec("b", tlb_ways=1)), tlb_ways=2)
    with pytest.raises(ValueError, match="mutually exclusive"):
        DeploymentConfig((TenantSpec("a", tlb_ways=1),),
                         autotune_interval=64)


def test_deployment_compile_and_quotas():
    base = reduce_for_smoke(get_config("llama3.2-1b"))
    dep = two_tenant_demo(partitioned=True, ways=4)
    cfg = dep.compile(base)
    assert cfg.serve_tlb_ways == 4
    td = dep.tenant_dict(16)
    assert td == {"a": {"quota_pages": 8, "tlb_ways": 2},
                  "b": {"quota_pages": 4, "tlb_ways": 1}}
    assert dep.names == ("a", "b")
    # a nonzero share always grants at least one page
    tiny = DeploymentConfig((TenantSpec("a", pool_share=0.01),))
    assert tiny.tenant_dict(8)["a"]["quota_pages"] == 1
    with pytest.raises(ValueError, match="pool_pages"):
        dep.tenant_dict(0)
    # compile-time errors need the resolved geometry
    with pytest.raises(ValueError, match="set-associative"):
        DeploymentConfig((TenantSpec("a", tlb_ways=2),)).compile(base)
    auto = dataclasses.replace(base, serve_tlb_ways=4,
                               serve_tlb_autotune=64)
    with pytest.raises(ValueError, match="mutually exclusive"):
        DeploymentConfig((TenantSpec("a", tlb_ways=2),)).compile(auto)


# --------------------------------------------------- scenario determinism

GOLDEN_FINGERPRINTS = {
    "bursty_tenants": "5262511097938705",
    "conversation_trees": "4c4a9606a15e2e88",
    "adversarial_prefix_collisions": "b26344952cfe8d65",
}


@pytest.mark.parametrize("kind", SCENARIO_KINDS)
def test_scenario_seed_determinism(kind):
    """Same (kind, tenants, vocab, n_req, seed) -> byte-identical trace:
    the A/B arms of paged_serving --tenants replay the exact workload, and
    these goldens pin the generator against silent drift."""
    a = generate(kind, ("a", "b"), vocab=256, n_req=12, seed=0)
    b = generate(kind, ("a", "b"), vocab=256, n_req=12, seed=0)
    assert a == b
    assert trace_fingerprint(a) == GOLDEN_FINGERPRINTS[kind]
    assert trace_fingerprint(
        generate(kind, ("a", "b"), vocab=256, n_req=12, seed=1)) \
        != GOLDEN_FINGERPRINTS[kind]
    assert all(r.tenant in ("a", "b") for r in a)
    assert sorted(set(r.tenant for r in a)) == ["a", "b"]
    arrivals = [r.arrival for r in a]
    assert arrivals == sorted(arrivals)          # merged by arrival tick


def test_scenario_generator_validation():
    with pytest.raises(ValueError, match="unknown scenario kind"):
        generate("flash_crowd", ("a",), vocab=64)
    with pytest.raises(ValueError, match="at least one tenant"):
        generate("bursty_tenants", (), vocab=64)


def test_collision_scenario_is_adversarial():
    """The adversarial trace really does submit byte-identical prompts
    under different tenants — the cross-tenant prefix-sharing bait."""
    reqs = generate("adversarial_prefix_collisions", ("a", "b"),
                    vocab=256, n_req=9, seed=7)
    by_prompt = {}
    for r in reqs:
        by_prompt.setdefault(r.prompt, set()).add(r.tenant)
    assert any(len(ts) > 1 for ts in by_prompt.values())
