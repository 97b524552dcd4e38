"""svasan (core/sva/sanitizer.py) — one injected-bug test per detector
(each deliberately breaks the discipline the detector watches and asserts
the precise report; disable the detector and the test fails), plus the
clean-path guarantees: a sanitized run of the real stack produces zero
reports and identical stats, and the env/constructor knobs resolve the
documented way."""
import numpy as np
import pytest

from repro.core.sva.iommu import (IOMMU, CountingWalk, IsolationError,
                                  PrefetchConfig, TLBConfig)
from repro.core.sva.kv_manager import PagedKVManager
from repro.core.sva.page_pool import PagePool
from repro.core.sva.sanitizer import (FREE, OWNED, SHARED, SanitizerError,
                                      SVASanitizer, resolve)


def mk_manager(**kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_pages_per_slot", 8)
    kw.setdefault("page_size", 4)
    kw.setdefault("sanitize", True)
    return PagedKVManager(**kw)


def sanitized_pool(n_pages=16):
    pool = PagePool(n_pages, page_size=4096)
    san = SVASanitizer()
    san.attach_pool(pool)
    return pool, san


# ----------------------------------------------------------- state model

def test_shadow_state_machine():
    pool, san = sanitized_pool()
    (pg,) = pool.alloc(1)
    assert san.state(pool, pg) == OWNED
    pool.share([pg])
    assert san.state(pool, pg) == SHARED
    pool.free([pg])
    assert san.state(pool, pg) == OWNED
    pool.free([pg])
    assert san.state(pool, pg) == FREE
    assert san.reports == []


# ------------------------------------------------- detector: double-free

def test_double_free_detected():
    pool, san = sanitized_pool()
    pages = pool.alloc(2)
    pool.free(pages)
    with pytest.raises(SanitizerError) as ei:
        pool.free(pages)
    assert ei.value.report.detector == "double-free"
    assert ei.value.report.state == FREE


def test_share_of_free_page_detected():
    pool, san = sanitized_pool()
    (pg,) = pool.alloc(1)
    pool.free([pg])
    with pytest.raises(SanitizerError) as ei:
        pool.share([pg])
    assert ei.value.report.detector == "double-free"


# --------------------------------------- detector: translate-after-unmap

def test_tlb_hit_after_stealth_unmap_detected():
    iommu = IOMMU(walk_model=CountingWalk(), tlb=TLBConfig(16, "lru"))
    iommu.sanitizer = SVASanitizer()
    sp = iommu.attach(1)
    sp.map([10, 20, 30])              # warm=True: TLB holds all three
    # the bug: drop the mapping WITHOUT invalidating (table and TLB now
    # disagree) — the next hit is a use-after-free translation
    sp.table.pop(1)
    with pytest.raises(SanitizerError) as ei:
        sp.translate(1)
    rep = ei.value.report
    assert rep.detector == "translate-after-unmap"
    assert rep.key == (1, 1)


def test_tlb_hit_disagreeing_with_remap_detected():
    iommu = IOMMU(walk_model=CountingWalk(), tlb=TLBConfig(16, "lru"))
    iommu.sanitizer = SVASanitizer()
    sp = iommu.attach(1)
    sp.map([10, 20])
    # the bug: CoW retargets the table but skips the invalidation (a
    # correct remap goes through IOAddressSpace.remap)
    sp.table[0] = 99
    with pytest.raises(SanitizerError) as ei:
        sp.translate(0)
    assert ei.value.report.detector == "translate-after-unmap"


def test_tlb_entry_surviving_unmap_detected(monkeypatch):
    iommu = IOMMU(walk_model=CountingWalk(), tlb=TLBConfig(16, "lru"))
    iommu.sanitizer = SVASanitizer()
    sp = iommu.attach(1)
    sp.map([10, 20])
    # the bug: unmap "forgets" to invalidate — entries outlive the space
    monkeypatch.setattr(iommu, "invalidate", lambda *a, **k: None)
    with pytest.raises(SanitizerError) as ei:
        sp.unmap()
    assert ei.value.report.detector == "translate-after-unmap"


# --------------------------------------------- detector: stale-prefetch

def test_inflight_prefetch_surviving_unmap_detected(monkeypatch):
    iommu = IOMMU(walk_model=CountingWalk(), tlb=TLBConfig(16, "lru"),
                  prefetch=PrefetchConfig("next_page", degree=1))
    iommu.sanitizer = SVASanitizer()
    sp = iommu.attach(1)
    sp.map([10, 20, 30], warm=False)
    sp.translate(0)                   # demand miss -> prefetch of lp 1
    assert (1, 1) in iommu._pending   # fill is in flight
    # the bug: the partial unmap skips invalidation, so the in-flight fill
    # survives and would install a dead translation later
    monkeypatch.setattr(iommu, "invalidate", lambda *a, **k: None)
    with pytest.raises(SanitizerError) as ei:
        sp.unmap([1, 2])
    rep = ei.value.report
    assert rep.detector == "stale-prefetch"
    assert rep.key == (1, 1)


def test_prefetch_fill_for_unmapped_page_detected():
    iommu = IOMMU(walk_model=CountingWalk(), tlb=TLBConfig(16, "lru"),
                  prefetch=PrefetchConfig("next_page", degree=1))
    iommu.sanitizer = SVASanitizer()
    sp = iommu.attach(1)
    sp.map([10, 20, 30], warm=False)
    sp.translate(0)                   # prefetch of lp 1 now in flight
    # the bug: the mapping dies behind the IOMMU's back while the fill is
    # in flight; the install must be caught red-handed
    sp.table.pop(1)
    with pytest.raises(SanitizerError) as ei:
        sp.translate(2)               # next demand installs pending fills
    assert ei.value.report.detector == "stale-prefetch"


# -------------------------------------------- detector: cow-bypass-write

def test_cow_bypass_write_detected(monkeypatch):
    m = mk_manager()
    m.admit(1, 4, 8, tokens=[1, 2, 3, 4])
    st = m.seqs[1]
    write_pg = st.pages[1]            # the next append writes page index 1
    m.pool.share([write_pg])          # another mapping still references it
    # the bug: the CoW-before-write pass is skipped
    monkeypatch.setattr(m, "_cow_before_write", lambda st: None)
    with pytest.raises(SanitizerError) as ei:
        m.append_token(1, 5)
    rep = ei.value.report
    assert rep.detector == "cow-bypass-write"
    assert rep.page == write_pg
    assert rep.state == SHARED


def test_cow_before_write_keeps_shared_page_safe():
    """Control for the bypass test: with the real CoW pass in place the
    same scenario is sanitizer-clean (the write page is duplicated)."""
    m = mk_manager()
    m.admit(1, 4, 8, tokens=[1, 2, 3, 4])
    st = m.seqs[1]
    shared_pg = st.pages[1]
    m.pool.share([shared_pg])
    m.append_token(1, 5)              # CoW duplicates before the write
    assert st.pages[1] != shared_pg
    assert m.sanitizer.reports == []
    m.pool.free([shared_pg])          # drop the artificial reference


# --------------------------------------------- detector: leak-at-release

def test_page_leak_at_release_detected(monkeypatch):
    m = mk_manager(prefix_sharing=False)
    m.admit(1, 8, 4, tokens=list(range(8)))
    orig_free = m.pool.free
    # the bug: release drops all but one of the sequence's references
    monkeypatch.setattr(m.pool, "free",
                        lambda pages: orig_free(list(pages)[:-1]))
    with pytest.raises(SanitizerError) as ei:
        m.release(1)
    rep = ei.value.report
    assert rep.detector == "leak-at-release"
    assert rep.page is not None
    assert "leaked" in rep.message


# ------------------------------------- detector: cross-tenant-translate

def test_cross_tenant_translate_detected(monkeypatch):
    """The injected bug: the IOMMU's isolation gate is patched out
    entirely. The sanitizer re-derives ASID ownership from the registry
    INSIDE translate, so the foreign translation is still refused —
    a buggy or bypassed ``_check_tenant`` cannot leak a page silently."""
    m = mk_manager(tenants={"a": {}, "b": {}})
    m.admit(1, 8, 4, tokens=list(range(8)), tenant="a")
    slot = m.seqs[1].slot
    monkeypatch.setattr(m.iommu, "_check_tenant", lambda *a, **k: None)
    with pytest.raises(SanitizerError) as ei:
        m.iommu.translate(slot, 0, tenant="b")
    rep = ei.value.report
    assert rep.detector == "cross-tenant-translate"
    assert "bypassed" in rep.message
    # with the gate intact the same access raises IsolationError BEFORE
    # the sanitizer ever sees it (gate first, shadow check second)
    m2 = mk_manager(tenants={"a": {}, "b": {}})
    m2.admit(1, 8, 4, tokens=list(range(8)), tenant="a")
    with pytest.raises(IsolationError):
        m2.iommu.translate(m2.seqs[1].slot, 0, tenant="b")
    assert m2.sanitizer.stats()["reports"] == 0


# ------------------------------------------------------------ clean path

def _workload(m):
    m.admit(1, 8, 6, tokens=[1, 2, 3, 4, 5, 6, 7, 8])
    m.admit(2, 8, 6, tokens=[1, 2, 3, 4, 5, 6, 9, 10])
    for t in range(4):
        m.append_token(1, 100 + t)
        m.append_token(2, 200 + t)
    m.release(1)
    m.admit(3, 8, 6, tokens=[1, 2, 3, 4, 5, 6, 7, 8])
    for t in range(3):
        m.append_token(2, 300 + t)
        m.append_token(3, 400 + t)
    m.release(2)
    m.release(3)
    return m.stats()


def test_clean_run_zero_reports():
    st = _workload(mk_manager())
    assert st["svasan"]["reports"] == 0
    assert st["svasan"]["checks"] > 0


def test_sanitizer_observes_without_changing_behavior():
    """On vs off: identical stats (svasan only observes)."""
    on = _workload(mk_manager(sanitize=True))
    off = _workload(mk_manager(sanitize=False))
    assert "svasan" not in off
    on.pop("svasan")
    assert on == off


def test_env_knob_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_SVASAN", raising=False)
    assert resolve(None) is False
    assert resolve(True) is True
    monkeypatch.setenv("REPRO_SVASAN", "1")
    assert resolve(None) is True
    assert resolve(False) is False   # explicit off beats the env
    monkeypatch.setenv("REPRO_SVASAN", "0")
    assert resolve(None) is False
    # and the manager picks the env default up through sanitize=None
    monkeypatch.setenv("REPRO_SVASAN", "1")
    assert mk_manager(sanitize=None).sanitizer is not None


def test_collect_mode_gathers_multiple_reports():
    pool = PagePool(8, page_size=4096)
    san = SVASanitizer(raise_on_report=False)
    san.attach_pool(pool)
    pages = pool.alloc(2)
    pool.free(pages)
    san.on_free(pool, pages)          # shadow-only double free, twice
    assert len(san.reports) == 2
    assert all(r.detector == "double-free" for r in san.reports)


# ----------------------------------------------------- property (fuzzing)

def test_random_interleavings_run_sanitizer_clean():
    """Random admit/append/release interleavings over a shared token
    alphabet (prefix sharing and CoW arise organically) never trip any
    detector."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st_

    ops = st_.lists(
        st_.tuples(st_.sampled_from(["admit", "append", "release"]),
                   st_.integers(0, 3),          # seq id
                   st_.integers(0, 2)),         # token alphabet
        min_size=1, max_size=60)

    @settings(max_examples=40, deadline=None)
    @given(ops=ops)
    def prop(ops):
        m = mk_manager(n_slots=3, max_pages_per_slot=6)
        live = set()
        for op, sid, tok in ops:
            if op == "admit" and sid not in live:
                # shared alphabet -> admissions share prompt prefixes
                got = m.admit(sid, 4, 6, tokens=[tok, tok, 7, 8])
                if got is not None:
                    live.add(sid)
            elif op == "append" and sid in live:
                if not m.seqs[sid].done:
                    m.append_token(sid, tok)
            elif op == "release" and sid in live:
                m.release(sid)
                live.discard(sid)
        for sid in list(live):
            m.release(sid)
        assert m.sanitizer.reports == []

    prop()
