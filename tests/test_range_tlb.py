"""Contiguity-aware allocation + range-coalesced IOTLB entries (PR 9).

Covers the allocation->translation spine end to end: the PagePool's
address-ordered free list and ``alloc_run`` (the regression the refactor
pins down: alloc/free/alloc round-trips preserve run availability), the
TranslationCache/IOMMU range entries (map-time and demand-miss coalescing,
range-granular invalidation that SPLITS a range when a subset of its pages
is unmapped — with a pre-fix-failing shape: the split test fails against
any implementation that only drops exact keys), the svasan ``stale-range``
detector, a hypothesis property asserting no range entry ever translates a
page its sequence no longer owns, replay-side miss reduction, and the
serving bit-identity contract (range-on vs range-off outputs identical in
both continuous and disaggregated modes — ranges change translation
accounting only, never data movement)."""
import dataclasses

import numpy as np
import pytest

from benchmarks.trace_replay import (replay_trace, runs_in,
                                     trace_fragmentation)
from repro.configs import get_config, reduce_for_smoke
from repro.configs.paper_soc import PaperSoCConfig
from repro.core.sva.iommu import (IOMMU, CountingWalk, Sv39Walk, TLBConfig,
                                  WalkCacheConfig)
from repro.core.sva.kv_manager import PagedKVManager
from repro.core.sva.page_pool import OutOfPages, PagePool
from repro.core.sva.sanitizer import SanitizerError, SVASanitizer


def mk_iommu(entries=16, ranges=8, walk=None, sanitize=False):
    iommu = IOMMU(walk_model=walk or CountingWalk(),
                  tlb=TLBConfig(entries, "lru", ranges=ranges))
    if sanitize:
        iommu.sanitizer = SVASanitizer()
    return iommu


def range_keys(iommu):
    return sorted(k for k in iommu.tlb.keys() if len(k) == 3)


# ------------------------------------------------------------- PagePool

def test_free_list_is_address_ordered_and_deterministic():
    """The documented policy: free pages are handed out lowest-first, and
    ``free`` re-inserts in address order — not LIFO."""
    pool = PagePool(8, page_size=64)
    assert pool.alloc(3) == [0, 1, 2]
    assert pool.alloc(2) == [3, 4]
    pool.free([0, 1, 2])
    # LIFO would hand back 2 (or the reversed run); address order gives 0.
    assert pool.alloc(1) == [0]
    pool.check_invariants()


def test_alloc_free_alloc_round_trip_preserves_runs():
    """The satellite regression: freeing a contiguous run re-forms it in
    the free list, so the next run allocation finds it again."""
    pool = PagePool(12, page_size=64)
    a = pool.alloc_run(4)
    assert a == [0, 1, 2, 3]
    b = pool.alloc_run(4)
    assert b == [4, 5, 6, 7]
    pool.free(a)
    pool.check_invariants()
    # the freed run is whole again — and is the first fit
    assert pool.alloc_run(4) == a
    pool.free(b)
    pool.free(a)
    # interior round-trip: free a middle run while neighbours stay live
    c, d, e = pool.alloc_run(3), pool.alloc_run(3), pool.alloc_run(3)
    pool.free(d)
    assert pool.alloc_run(3) == d
    pool.free(c), pool.free(d), pool.free(e)
    pool.check_invariants()
    assert pool.n_free == 12
    assert pool.stats.run_allocs >= 6
    assert pool.stats.run_fallbacks == 0


def test_alloc_run_falls_back_when_fragmented():
    pool = PagePool(6, page_size=64)
    held = pool.alloc(6)
    # free a non-contiguous subset: {0, 2, 4}
    pool.free([held[0], held[2], held[4]])
    got = pool.alloc_run(3)
    assert got == [0, 2, 4]                      # discontiguous fallback
    assert pool.stats.run_fallbacks == 1
    # first-fit skips leading fragments to find a real run
    pool.free(got)
    pool.free([held[1], held[3]])                # free list now 0..4
    assert pool.alloc_run(2) == [0, 1]
    with pytest.raises(OutOfPages):
        pool.alloc_run(4)
    pool.check_invariants()


def test_free_runs_reports_maximal_runs():
    pool = PagePool(8, page_size=64)
    pages = pool.alloc(8)
    pool.free([pages[0], pages[1], pages[3], pages[6], pages[7]])
    assert pool.free_runs() == [(0, 2), (3, 1), (6, 2)]


def test_shared_run_refcounting_preserves_runs():
    """share/free keep run availability: a shared run only returns to the
    free list when the LAST owner drops it — and returns whole."""
    pool = PagePool(8, page_size=64)
    run = pool.alloc_run(4)
    pool.share(run)
    pool.free(run)                               # first owner
    assert pool.n_free == 4                      # still live via sharer
    pool.free(run)                               # last owner
    assert pool.alloc_run(4) == run
    pool.check_invariants()


# ------------------------------------------------- TLB/IOMMU range entries

def test_map_time_coalescing_installs_range_entries():
    iommu = mk_iommu()
    sp = iommu.attach(1)
    sp.map([10, 11, 12, 13])
    assert range_keys(iommu) == [(1, 0, 4)]
    assert iommu.tlb.range_covering(1, 2) == (0, 4)
    for lp in range(4):
        pp, cost, hit = sp.translate(lp)
        assert (pp, hit) == (10 + lp, True)
    s = iommu.stats()["range"]
    assert s["fills"] == 1 and s["coalesced_pages"] == 4
    assert s["hits"] == 4 and s["n_ranges"] == 1


def test_map_time_coalescing_caps_at_range_max():
    iommu = mk_iommu(ranges=2)
    sp = iommu.attach(1)
    sp.map(list(range(20, 25)))                  # 5 contiguous pages, cap 2
    assert all(n <= 2 for _, _, n in range_keys(iommu))
    assert sum(n for _, _, n in range_keys(iommu)) == 4   # 2+2, singleton 4


def test_discontiguous_map_warms_per_page():
    iommu = mk_iommu()
    sp = iommu.attach(1)
    sp.map([10, 12, 14])
    assert range_keys(iommu) == []
    assert sp.translate(1) == (12, 0.0, True)


def test_demand_miss_coalesces_whole_run_from_one_walk():
    iommu = mk_iommu()
    sp = iommu.attach(1)
    sp.map([30, 31, 32, 33], warm=False)         # cold TLB, table installed
    pp, _, hit = sp.translate(0)
    assert (pp, hit) == (30, False)
    assert iommu.walk_model.stats.walks == 1
    # neighbours ride the range entry the single walk installed
    for lp in (1, 2, 3):
        assert sp.translate(lp) == (30 + lp, 0.0, True)
    assert iommu.walk_model.stats.walks == 1
    s = iommu.stats()["range"]
    assert s["fills"] == 1 and s["coalesced_pages"] == 4


def test_range_aware_is_constructor_opt_in():
    """ranges=0 keeps the per-page behaviour bit-identical — no range keys
    ever appear (the walk cache also uses 3-tuple keys internally, so
    range decoding must never be inferred from key arity)."""
    iommu = mk_iommu(ranges=0,
                     walk=Sv39Walk(llc=False,
                                   walk_cache=WalkCacheConfig(8)))
    sp = iommu.attach(1)
    sp.map([10, 11, 12, 13])
    assert range_keys(iommu) == []
    assert "range" not in iommu.stats()
    assert sp.translate(2)[0] == 12


def test_ranges_coexist_with_walk_cache():
    iommu = mk_iommu(walk=Sv39Walk(llc=False,
                                   walk_cache=WalkCacheConfig(8)))
    sp = iommu.attach(1)
    sp.map([10, 11, 12, 13], warm=False)
    for lp in range(4):
        assert sp.translate(lp)[0] == 10 + lp
    assert iommu.stats()["range"]["hits"] == 3


def test_tlb_config_rejects_degenerate_ranges():
    with pytest.raises(ValueError):
        TLBConfig(4, "lru", ranges=1)
    with pytest.raises(ValueError):
        TLBConfig(4, "lru", ranges=-2)


# --------------------------------------- range-granular invalidation/split

def test_partial_unmap_splits_range():
    """THE pre-fix-failing shape: unmapping a subset of a range's pages
    must split the entry into its surviving segments. An implementation
    that only drops exact ``(asid, lp)`` keys leaves the range translating
    the dead page and fails every assertion below."""
    iommu = mk_iommu()
    sp = iommu.attach(1)
    sp.map([40, 41, 42, 43])
    assert range_keys(iommu) == [(1, 0, 4)]
    sp.unmap([1])
    # no surviving entry covers the dead page
    assert iommu.tlb.range_covering(1, 1) is None
    assert (1, 1) not in iommu.tlb
    # survivors still translate, WITHOUT a new walk (re-filled on split)
    assert sp.translate(0) == (40, 0.0, True)
    assert sp.translate(2) == (42, 0.0, True)
    assert sp.translate(3) == (43, 0.0, True)
    assert iommu.walk_model.stats.walks == 0
    # split into exact (0) + range (2,2)
    assert range_keys(iommu) == [(1, 2, 2)]
    assert iommu.stats()["range"]["splits"] == 1
    # translating the dead page is a caller error on an attached space
    with pytest.raises(KeyError):
        sp.translate(1)


def test_unmap_edge_pages_narrows_range():
    iommu = mk_iommu()
    sp = iommu.attach(1)
    sp.map([50, 51, 52, 53])
    sp.unmap([0, 3])
    assert range_keys(iommu) == [(1, 1, 2)]
    assert sp.translate(1) == (51, 0.0, True)
    assert sp.translate(2) == (52, 0.0, True)


def test_unmap_all_pages_of_range_leaves_nothing():
    iommu = mk_iommu()
    sp = iommu.attach(1)
    sp.map([50, 51])
    sp.unmap([0, 1])
    assert range_keys(iommu) == []
    assert iommu.stats()["range"]["splits"] == 0   # no survivors: a drop,
    assert iommu.tlb.n_ranges == 0                 # not a split


def test_cow_remap_splits_shared_run():
    """A CoW divergence remaps ONE logical page of a shared run: the range
    must split around it and the fresh translation must win."""
    iommu = mk_iommu()
    sp = iommu.attach(1)
    sp.map([60, 61, 62, 63])
    sp.remap(2, 99)                               # CoW: lp 2 diverges
    assert iommu.tlb.range_covering(1, 2) is None
    assert sp.translate(2) == (99, 0.0, True)
    assert sp.translate(1)[0] == 61
    assert sp.translate(3)[0] == 63
    assert iommu.stats()["range"]["splits"] == 1


def test_asid_invalidation_drops_ranges():
    iommu = mk_iommu()
    sp1, sp2 = iommu.attach(1), iommu.attach(2)
    sp1.map([10, 11, 12])
    sp2.map([20, 21, 22])
    sp1.unmap()
    assert [k[0] for k in range_keys(iommu)] == [2]
    assert iommu.tlb.n_ranges == 1
    assert sp2.translate(1)[0] == 21


def test_range_entry_eviction_cleans_index():
    """An evicted range key must leave the range index too — a stale index
    entry would 'hit' a translation the set no longer holds."""
    iommu = mk_iommu(entries=2, ranges=4)
    sp = iommu.attach(1)
    sp.map([10, 11], warm=False)
    sp.map([20, 21], start=2, warm=False)
    assert sp.translate(0)[0] == 10               # range (0,2) fills
    assert sp.translate(2)[0] == 20               # range (2,2) fills
    # thrash the 2-entry TLB with exact fills until ranges evict
    sp.map([30, 31, 32, 33], start=4, warm=False)
    for lp in (4, 5, 6, 7):
        iommu.tlb.fill((1, lp), sp.table[lp])
    assert iommu.tlb.n_ranges == len(range_keys(iommu))
    assert iommu.tlb.n_ranges <= 2


# ----------------------------------------------------- svasan stale-range

def test_stale_range_detected_by_sanitizer():
    """Injected bug: drop a table entry WITHOUT invalidating — the range
    still covers the dead page and check_unmapped must flag it."""
    iommu = mk_iommu(sanitize=True)
    sp = iommu.attach(1)
    sp.map([10, 11, 12, 13])
    assert range_keys(iommu) == [(1, 0, 4)]
    sp.table.pop(1)                               # the bug: no invalidation
    with pytest.raises(SanitizerError) as ei:
        iommu.sanitizer.check_unmapped(iommu, 1, [1])
    assert ei.value.report.detector == "stale-range"
    assert ei.value.report.key == (1, 0, 4)


def test_clean_unmap_passes_sanitizer():
    iommu = mk_iommu(sanitize=True)
    sp = iommu.attach(1)
    sp.map([10, 11, 12, 13])
    sp.unmap([1])                                 # proper split path
    sp.unmap()                                    # full teardown
    assert iommu.sanitizer.reports == []


# ------------------------------------------------- manager + property test

def mk_manager(**kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_pages_per_slot", 8)
    kw.setdefault("page_size", 4)
    kw.setdefault("sanitize", True)
    kw.setdefault("tlb_ranges", 4)
    return PagedKVManager(**kw)


def assert_no_range_outlives_ownership(mgr):
    """The tentpole's correctness surface: every resident range entry must
    translate ONLY pages the owning sequence still holds, and agree with
    the live table."""
    iommu = mgr.iommu
    for key in list(iommu.tlb.keys()):
        if len(key) != 3:
            continue
        asid, base, n = key
        sp = iommu.space(asid)
        assert sp is not None, f"range {key} for a detached ASID"
        base_ppn = iommu.tlb.peek(key)
        for off in range(n):
            assert sp.table.get(base + off) == base_ppn + off, \
                f"range {key} disagrees with the table at lp {base + off}"
            assert mgr.pool.refcount(base_ppn + off) >= 1, \
                f"range {key} translates freed page {base_ppn + off}"


def test_admit_uses_contiguity_hint():
    mgr = mk_manager()
    st = mgr.admit(0, prompt_len=12, max_tokens=4,
                   tokens=list(range(12)))
    assert st is not None
    assert runs_in(st.pages) == 1                 # fresh admit: one run
    assert mgr.stats()["pool_run_allocs"] >= 1
    mgr.release(0)


def test_cow_write_splits_run_in_manager():
    """Two sequences share a prefix run; the sharer's first divergent
    append CoW-remaps a page — no range entry may keep translating the
    pre-CoW page for the writer."""
    mgr = mk_manager()
    toks = [1, 2, 3, 4, 5, 6, 7, 8]
    a = mgr.admit(0, prompt_len=8, max_tokens=4, tokens=toks)
    b = mgr.admit(1, prompt_len=8, max_tokens=4, tokens=list(toks))
    assert a is not None and b is not None
    assert b.shared_pages >= 1
    mgr.append_token(1, 42)                       # diverge: CoW fires
    mgr.drain_cow_copies()
    assert_no_range_outlives_ownership(mgr)
    mgr.append_token(0, 43)
    assert_no_range_outlives_ownership(mgr)
    mgr.release(0)
    mgr.release(1)
    assert_no_range_outlives_ownership(mgr)
    assert mgr.sanitizer.reports == []


def test_property_no_range_translates_unowned_page():
    """Hypothesis property (the CI tier-1 job runs this file under
    REPRO_SVASAN=1): random admit/append/release interleavings over a
    shared token alphabet — prefix sharing, CoW and eviction arise
    organically — never leave a range entry translating a page its
    sequence no longer owns, and never trip a detector."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st_

    ops = st_.lists(
        st_.tuples(st_.sampled_from(["admit", "append", "release"]),
                   st_.integers(0, 3),           # seq id
                   st_.integers(0, 2)),          # token alphabet
        min_size=1, max_size=60)

    @settings(max_examples=40, deadline=None)
    @given(ops=ops)
    def prop(ops):
        m = mk_manager(n_slots=3, max_pages_per_slot=6)
        live = set()
        for op, sid, tok in ops:
            if op == "admit" and sid not in live:
                got = m.admit(sid, 4, 6, tokens=[tok, tok, 7, 8])
                if got is not None:
                    live.add(sid)
            elif op == "append" and sid in live:
                if not m.seqs[sid].done:
                    m.append_token(sid, tok)
                    m.drain_cow_copies()
            elif op == "release" and sid in live:
                m.release(sid)
                live.discard(sid)
            assert_no_range_outlives_ownership(m)
        for sid in list(live):
            m.release(sid)
            assert_no_range_outlives_ownership(m)
        assert m.sanitizer.reports == []

    prop()


# ------------------------------------------------------------ trace replay

def _synthetic_trace(n_pages=8, base_pp=100):
    row = list(range(base_pp, base_pp + n_pages))
    accesses = [(0, lp, row[lp]) for lp in range(n_pages)]
    return [("map", list(row), 0, list(row)),
            ("step", accesses, n_pages),
            ("step", accesses, n_pages),
            ("unmap", 0, n_pages)]


def _replay_misses(trace, ranges):
    iommu = IOMMU(walk_model=CountingWalk(),
                  tlb=TLBConfig(4, "lru", ranges=ranges))
    replay_trace(trace, iommu, kv_bytes_per_token=64,
                 compute_per_token=1.0, soc=PaperSoCConfig(),
                 dram_latency=200)
    return iommu


def test_replay_range_reduces_demand_misses_at_equal_entries():
    """The acceptance shape: a contiguous 8-page mapping through a 4-entry
    IOTLB thrashes per-page but fits in ONE range entry."""
    trace = _synthetic_trace()
    per_page = _replay_misses(trace, ranges=0)
    ranged = _replay_misses(trace, ranges=8)
    assert ranged.tlb.stats.misses < per_page.tlb.stats.misses
    assert ranged.walk_model.stats.walks < per_page.walk_model.stats.walks
    assert ranged.stats()["range"]["coalesced_pages"] >= 8


def test_trace_fragmentation_summary():
    contiguous = _synthetic_trace()
    assert runs_in([5, 6, 7]) == 1 and runs_in([5, 7, 9]) == 3
    assert runs_in([]) == 0
    frag = trace_fragmentation(contiguous)
    assert frag["sequences"] == 1 and frag["runs_per_seq"] == 1.0
    scattered = [("map", [3, 5, 9], 1, [3, 5, 9])]
    assert trace_fragmentation(scattered)["runs_per_seq"] == 3.0
    assert trace_fragmentation([])["runs_per_seq"] == 0.0


# ------------------------------------------------- serving bit-identity

@pytest.fixture(scope="module")
def setup():
    import jax
    cfg = reduce_for_smoke(get_config("llama3.2-1b"))
    from repro.models import init_params
    return cfg, init_params(cfg, jax.random.key(0))


def _serve_continuous(cfg, params, ranges):
    # shared-system-prompt workload + driver from tests/conformance.py
    from tests.conformance import prefix_workload, serve
    outs, eng, _ = serve(dataclasses.replace(cfg, serve_tlb_ranges=ranges),
                         params, "continuous",
                         prefix_workload(cfg.vocab_size), pool_pages=8,
                         translation_stats=True)
    return outs, eng


def test_continuous_serving_bit_identical_with_ranges(setup):
    cfg, params = setup
    off, _ = _serve_continuous(cfg, params, 0)
    on, eng = _serve_continuous(cfg, params, 8)
    assert on == off
    s = eng.stats()
    assert s["iommu"]["range"]["coalesced_pages"] > 0
    assert "range" not in _serve_continuous(cfg, params, 0)[1] \
        .stats()["iommu"]


@pytest.mark.parametrize("mode", ["share", "copy"])
def test_disagg_serving_bit_identical_with_ranges(setup, mode):
    from tests.conformance import prefix_workload, serve
    cfg, params = setup
    wl = prefix_workload(cfg.vocab_size, n=4)

    def serve_ranges(ranges):
        outs, eng, _ = serve(dataclasses.replace(cfg,
                                                 serve_tlb_ranges=ranges),
                             params, f"disagg-{mode}", wl,
                             translation_stats=True)
        return outs, eng

    off, _ = serve_ranges(0)
    on, eng = serve_ranges(8)
    assert on == off
    assert eng.stats()["disagg"]["transfers"] >= 1
