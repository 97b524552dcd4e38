"""SVA layer + continuous-batching engine correctness."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.configs.base import with_blocks
from repro.core.sva.kv_manager import PagedKVManager
from repro.core.sva.mapping import SVASpace
from repro.core.sva.page_pool import PagePool
from repro.core.serving.engine import ServingEngine
from repro.models import (forward_decode, forward_prefill, init_cache,
                          init_params)


def test_mapping_zero_copy_vs_copy_costs():
    space = SVASpace(PagePool(128, 4096))
    m = space.map(16 * 4096)
    assert space.stats.bytes_copied == 0
    assert space.stats.table_entries_written == 16
    space.unmap(m)
    m2 = space.stage(16 * 4096)
    assert space.stats.bytes_copied == 16 * 4096   # the staging copy


def test_mapping_prefix_sharing():
    space = SVASpace(PagePool(64, 4096))
    a = space.map(8 * 4096)
    b = space.map(8 * 4096, share_prefix_from=a, prefix_pages=4)
    assert b.pages[:4] == a.pages[:4]
    assert space.pool.n_used == 12                 # 8 + 4 fresh
    space.unmap(a)
    assert space.pool.refcount(b.pages[0]) == 1    # prefix survives
    space.unmap(b)
    assert space.pool.n_used == 0


def test_kv_manager_global_pool_shared_across_slots():
    """Default layout: ONE pool shared by every slot; unallocated table
    entries hold the NULL sentinel."""
    mgr = PagedKVManager(n_slots=2, max_pages_per_slot=8, page_size=4)
    assert mgr.layout == "global" and mgr.pool.n_pages == 16
    a = mgr.admit(0, prompt_len=10, max_tokens=6)       # 4 pages
    b = mgr.admit(1, prompt_len=10, max_tokens=6)
    assert a is not None and b is not None
    rows = mgr.tables
    used = rows[a.slot][:4].tolist() + rows[b.slot][:4].tolist()
    assert sorted(used) == sorted(set(used)), "slots share one page space"
    assert all(p < 16 for p in used)
    assert (rows[a.slot][4:] == mgr.null_page).all()    # unmapped == NULL
    assert mgr.pool.n_used == 8
    mgr.release(0)
    assert (mgr.tables[a.slot] == mgr.null_page).all()
    assert mgr.pool.n_used == 4
    mgr.release(1)
    assert mgr.pool.n_used == 0 and len(mgr.free_slots) == 2


def test_kv_manager_per_slot_tables_are_permutations():
    """Copy-baseline layout keeps the per-slot permutation invariant."""
    mgr = PagedKVManager(n_slots=2, max_pages_per_slot=8, page_size=4,
                         offload_mode="copy")
    assert mgr.layout == "per_slot"
    st = mgr.admit(0, prompt_len=10, max_tokens=6)
    assert st is not None
    assert sorted(mgr.tables[st.slot].tolist()) == list(range(8))
    for i in range(6):
        mgr.append_token(0, i)
    assert sorted(mgr.tables[st.slot].tolist()) == list(range(8))
    mgr.release(0)
    assert mgr.free_slots and mgr.pools[st.slot].n_free == 8


def test_kv_manager_delta_rows_and_epoch():
    mgr = PagedKVManager(n_slots=4, max_pages_per_slot=4, page_size=4)
    mgr.delta_rows()                                    # drain initial dirt
    assert mgr.delta_rows() == []
    st = mgr.admit(0, prompt_len=4, max_tokens=4)
    assert mgr.delta_rows() == [st.slot]                # only the new row
    assert mgr.delta_rows() == []                       # nothing changed
    epoch = mgr.epoch
    mgr.invalidate_epoch()
    assert mgr.epoch == epoch + 1
    assert mgr.delta_rows() == [0, 1, 2, 3]             # full re-upload due


def _engine_outputs(mode, cfg, params, prompts, n=6):
    eng = ServingEngine(cfg, params, n_slots=3, max_len=64, page_size=8,
                        offload_mode=mode)
    rids = [eng.submit(p, max_tokens=n) for p in prompts]
    done = eng.run()
    return [done[r].out_tokens for r in rids], eng.stats()


def _manual_tokens(cfg, params, prompt, n=6):
    """Greedy tokens of one prompt through a per-slot cache of its own:
    prefill, then ``n - 1`` decode steps."""
    cache = init_cache(cfg, 1, max_len=64, page_size=8, per_seq=True)
    lg, cache = forward_prefill(
        cfg, params, {"tokens": jnp.asarray(prompt, jnp.int32)[None]}, cache)
    toks = [int(jnp.argmax(lg[0, -1]))]
    pos = len(prompt)
    for _ in range(n - 1):
        lg, cache = forward_decode(
            cfg, params, jnp.asarray([[toks[-1]]], jnp.int32),
            jnp.asarray([pos], jnp.int32), cache)
        toks.append(int(jnp.argmax(lg[0, 0])))
        pos += 1
    return toks


def test_engine_matches_manual_loop(key):
    cfg = reduce_for_smoke(get_config("llama3.2-1b"))
    params = init_params(cfg, key)
    prompts = [[5, 9, 2, 14], [100, 7], [3, 3, 3, 8, 1, 30], [42]]
    expected = [_manual_tokens(cfg, params, p) for p in prompts]
    got, stats = _engine_outputs("zero_copy", cfg, params, prompts)
    assert got == expected
    assert stats["sva"]["bytes_copied"] == 0


@pytest.mark.parametrize("scan_blocks", [True, False])
def test_continuous_engine_pool_in_layer_carry(key, scan_blocks):
    """Three blocks: the continuous engine's global pool rides the layer
    loop's carry and is read and written at [layer, page, slot], scanned or
    unrolled. Its tokens match the per-slot loop without a scan, whose
    pools ride the loop's inputs and outputs (chunked prefill over 8-token
    chunks, so later chunks read earlier ones back from the pool)."""
    base = with_blocks(reduce_for_smoke(get_config("llama3.2-1b")), 3)
    params = init_params(base, key)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, base.vocab_size, size=k).tolist()
               for k in (21, 6, 13, 9)]
    ref_cfg = replace(base, scan_blocks=False)
    expected = [_manual_tokens(ref_cfg, params, p) for p in prompts]
    cfg = replace(base, scan_blocks=scan_blocks, sched_prefill_chunk=8)
    eng = ServingEngine(cfg, params, n_slots=3, max_len=64, page_size=8)
    assert eng.scheduler_mode == "continuous"
    rids = [eng.submit(p, max_tokens=6) for p in prompts]
    done = eng.run()
    assert [done[r].out_tokens for r in rids] == expected


@pytest.mark.parametrize("arch,engine,offload,loop", [
    ("llama3.2-1b", "colocated", "zero_copy", "continuous"),
    ("llama3.2-1b", "colocated", "copy", "fixed"),
    ("gemma2-2b", "colocated", "zero_copy", "fixed"),
    ("gemma2-2b", "disagg", "zero_copy", None),
])
def test_engine_picks_step_loop(key, arch, engine, offload, loop):
    """The step loop follows from what the engine can observe: continuous
    when the KV lives in the global zero-copy pool, fixed-wave otherwise
    (the copy baseline; gemma2's sliding-window rings are per slot). The
    disaggregated engine exists only on the continuous loop."""
    from repro.core.serving.disagg import DisaggEngine
    cfg = reduce_for_smoke(get_config(arch))
    params = init_params(cfg, key)
    if engine == "disagg":
        with pytest.raises(NotImplementedError, match="global pool"):
            DisaggEngine(cfg, params, n_prefill_slots=1, n_decode_slots=1,
                         max_len=32, page_size=8)
        return
    eng = ServingEngine(cfg, params, n_slots=2, max_len=32, page_size=8,
                        offload_mode=offload)
    assert eng.scheduler_mode == loop
    assert (eng.sched is not None) == (loop == "continuous")


def test_engine_copy_mode_same_tokens_more_copies(key):
    """copy-based admission produces identical TOKENS but pays staging."""
    cfg = reduce_for_smoke(get_config("llama3.2-1b"))
    params = init_params(cfg, key)
    prompts = [[11, 4, 9], [87, 23, 1, 5]]
    zc, zc_stats = _engine_outputs("zero_copy", cfg, params, prompts)
    cp, cp_stats = _engine_outputs("copy", cfg, params, prompts)
    assert zc == cp
    assert cp_stats["staging_copies"] > 0
    assert cp_stats["sva"]["bytes_copied"] > 0
    assert zc_stats["staging_copies"] == 0


def test_engine_queueing_more_requests_than_slots(key):
    cfg = reduce_for_smoke(get_config("llama3.2-1b"))
    params = init_params(cfg, key)
    prompts = [[i + 1, i + 2] for i in range(7)]   # 7 requests, 3 slots
    got, stats = _engine_outputs("zero_copy", cfg, params, prompts, n=4)
    assert len(got) == 7
    assert all(len(t) == 4 for t in got)
    assert stats["sva"]["unmap_calls"] == 7        # every seq released


def test_engine_zero_copy_no_admission_materialization(key):
    """Acceptance: zero_copy admission moves table entries (int32 per page),
    never KV bytes — no staging copies, no per-request cache; decode uses
    delta table uploads with a full upload only for the initial epoch."""
    cfg = reduce_for_smoke(get_config("llama3.2-1b"))
    params = init_params(cfg, key)
    prompts = [[5, 9, 2, 14], [100, 7], [3, 3, 3, 8, 1, 30], [42]]
    _, s = _engine_outputs("zero_copy", cfg, params, prompts)
    assert s["staging_copies"] == 0
    assert s["sva"]["bytes_copied"] == 0
    assert s["table_uploads_full"] == 1            # initial epoch sync only
    assert s["table_uploads_delta"] >= 1
    # admission bytes: int32 table entries, not KV. Compare against what the
    # copy baseline would have staged for the same prompts.
    kv_bytes_staged = sum(len(p) for p in prompts) * 2 * cfg.n_kv_heads \
        * cfg.d_head * cfg.n_layers
    assert s["admit_table_bytes"] < kv_bytes_staged
    assert s["sva"]["table_entries_written"] == 6  # ceil((len+6)/8) per seq


def test_engine_epoch_invalidation_forces_full_upload(key):
    cfg = reduce_for_smoke(get_config("llama3.2-1b"))
    params = init_params(cfg, key)
    eng = ServingEngine(cfg, params, n_slots=2, max_len=64, page_size=8,
                        offload_mode="zero_copy")
    eng.submit([1, 2, 3], max_tokens=4)
    eng.run()
    assert eng.stats()["table_uploads_full"] == 1
    eng.invalidate_epoch()                         # paper Listing 1 flush
    eng.submit([4, 5], max_tokens=4)
    eng.run()
    assert eng.stats()["table_uploads_full"] == 2
    assert eng.stats()["tlb"]["invalidations"] >= 1


def test_submit_rejects_over_capacity(key):
    """Regression: prompt+max_tokens beyond slot capacity must be rejected,
    not silently truncated (the old ``min(need, max_pages)``) — truncation
    later wrapped page indices into other sequences' KV."""
    from repro.core.sva.kv_manager import CapacityError
    cfg = reduce_for_smoke(get_config("llama3.2-1b"))
    params = init_params(cfg, key)
    eng = ServingEngine(cfg, params, n_slots=2, max_len=32, page_size=8,
                        offload_mode="zero_copy")
    with pytest.raises(CapacityError):
        eng.submit(list(range(30)), max_tokens=16)  # 46 > 32 tokens
    mgr = PagedKVManager(n_slots=1, max_pages_per_slot=4, page_size=8)
    with pytest.raises(CapacityError):
        mgr.admit(0, prompt_len=30, max_tokens=16)
    # boundary: exactly at capacity is fine
    assert mgr.admit(1, prompt_len=16, max_tokens=16) is not None


def test_engine_sliding_window_bucketed_prefill_matches_manual(key):
    """Regression: bucket-padding a prompt past the sliding window (12
    tokens -> bucket 16 > window 8) must not store pad-token KV in the
    window ring — each row keeps its own last min(len, window) REAL
    tokens."""
    cfg = reduce_for_smoke(get_config("gemma2-2b"))
    assert cfg.sliding_window and cfg.sliding_window < 16
    params = init_params(cfg, key)
    prompts = [[5, 9, 2, 14, 8, 1, 7, 3, 11, 13, 4, 6], [100, 7, 42]]

    def manual(prompt, n=4):
        cache = init_cache(cfg, 1, max_len=64, page_size=8, per_seq=True)
        lg, cache = forward_prefill(
            cfg, params, {"tokens": jnp.asarray(prompt, jnp.int32)[None]},
            cache)
        toks = [int(jnp.argmax(lg[0, -1]))]
        pos = len(prompt)
        for _ in range(n - 1):
            lg, cache = forward_decode(
                cfg, params, jnp.asarray([[toks[-1]]], jnp.int32),
                jnp.asarray([pos], jnp.int32), cache)
            toks.append(int(jnp.argmax(lg[0, 0])))
            pos += 1
        return toks

    expected = [manual(p) for p in prompts]
    eng = ServingEngine(cfg, params, n_slots=2, max_len=64, page_size=8,
                        offload_mode="zero_copy")
    rids = [eng.submit(p, max_tokens=4) for p in prompts]
    done = eng.run()
    assert [done[r].out_tokens for r in rids] == expected
    # copy mode can't map rows onto the smaller window leaves: fail fast
    with pytest.raises(NotImplementedError):
        ServingEngine(cfg, params, n_slots=2, max_len=64, page_size=8,
                      offload_mode="copy")


# --------------------------------------------------------- mapping.py fixes

def test_stage_keeps_zero_copy_stats_clean():
    """Regression: the copy baseline must NOT inflate the zero-copy
    counters (stage() used to call map() internally, so every copy-mode
    admission also bumped map_calls/table_entries_written/bytes_mapped,
    corrupting any Fig.2-style A/B)."""
    space = SVASpace(PagePool(128, 4096))
    space.stage(16 * 4096)
    assert space.stats.stage_calls == 1
    assert space.stats.bytes_copied == 16 * 4096
    assert space.stats.map_calls == 0
    assert space.stats.table_entries_written == 0
    assert space.stats.bytes_mapped == 0
    space.map(4 * 4096)
    assert space.stats.map_calls == 1 and space.stats.stage_calls == 1


def test_extend_updates_mapping_and_stats():
    """Regression: extend() used to grow m.pages but leave Mapping.n_bytes
    and stats.bytes_mapped stale — decode-driven growth was invisible to
    the memory-pressure stats."""
    space = SVASpace(PagePool(64, 4096))
    m = space.map(2 * 4096)
    assert m.n_bytes == 2 * 4096
    space.extend(m, n_new_pages=3)
    assert len(m.pages) == 5
    assert m.n_bytes == 5 * 4096
    assert space.stats.bytes_mapped == 5 * 4096
    assert space.stats.table_entries_written == 5


def test_unmap_invalidates_only_own_translations():
    """Regression: unmap() used to epoch-flush the WHOLE TLB, forcing a
    full re-walk for every other live mapping per completed request; it
    must drop only the unmapped pages' entries."""
    space = SVASpace(PagePool(64, 4096))
    a = space.map(4 * 4096)
    b = space.map(4 * 4096)
    assert len(space.tlb) == 8                   # map warms per-page entries
    space.unmap(a)
    assert space.tlb.stats.invalidations == 0    # no epoch flush
    for lp in range(4):
        assert space.tlb.lookup((b.handle, lp))[1], "b's translations died"
        assert not space.tlb.lookup((a.handle, lp))[1]
    space.invalidate_epoch()                     # Listing-1 flush is explicit
    assert space.tlb.stats.invalidations == 1
    assert len(space.tlb) == 0


# ---------------------------------------------------- CoW prefix sharing

SYS = list(range(200, 216))                      # 2 full pages @ page_size 8


def _share_engine_outputs(cfg, params, prompts, share, n=6):
    """Serve ``prompts[0]`` alone for one token, then the rest together.
    The first request's KV is resident and indexed before the others are
    admitted, and since its only token is never written, its partial tail
    page stays in the warm cache too."""
    eng = ServingEngine(cfg, params, n_slots=4, max_len=64, page_size=8,
                        prefix_sharing=share)
    rids = [eng.submit(prompts[0], max_tokens=1)]
    done = eng.run()
    rids += [eng.submit(p, max_tokens=n) for p in prompts[1:]]
    done.update(eng.run())
    return [done[r].out_tokens for r in rids], eng.stats()


def test_prefix_sharing_bit_identical_to_unshared(key):
    """Acceptance: shared-prefix admissions prefill only the non-shared
    suffix (prefill_tokens_saved > 0, pages shared > 0) and decode outputs
    are bit-identical to unshared serving. The two copies of the first
    prompt both map its cached partial tail page, so the first of them to
    write there duplicates it (CoW)."""
    cfg = reduce_for_smoke(get_config("llama3.2-1b"))
    params = init_params(cfg, key)
    dup = SYS + [5, 9, 2]
    prompts = [dup, SYS + [7, 7], dup, dup, [42, 43]]
    got_s, ss = _share_engine_outputs(cfg, params, prompts, True)
    got_u, su = _share_engine_outputs(cfg, params, prompts, False)
    assert got_s == got_u                        # placement never changes tokens
    assert ss["prefill_tokens_saved"] > 0
    assert ss["prefix"]["pages_shared"] > 0
    assert ss["shared_admissions"] == 3          # the three SYS prompts
    assert ss["cow_page_copies"] > 0             # identical prompt diverged
    assert su["prefill_tokens_saved"] == 0 and "prefix" not in su
    assert ss["sva"]["bytes_copied"] == 0        # still zero-copy admission


def test_prefix_cache_warm_across_completions(key):
    """release() leaves prompt pages behind as a warm prefix cache: a later
    request with the same system prompt maps them via refcount++."""
    cfg = reduce_for_smoke(get_config("llama3.2-1b"))
    params = init_params(cfg, key)
    eng = ServingEngine(cfg, params, n_slots=2, max_len=64, page_size=8)
    eng.submit(SYS + [1, 2, 3], max_tokens=4)
    eng.run()
    assert eng.stats()["pool_used"] > 0          # cache retains pages
    eng.submit(SYS + [9, 8, 7], max_tokens=4)
    done = eng.run()
    s = eng.stats()
    assert s["prefix"]["hits"] == 1
    assert s["prefill_tokens_saved"] >= len(SYS)
    assert all(len(r.out_tokens) == 4 for r in done.values())


def test_cow_never_mutates_shared_page():
    """A CoW duplication must leave the original page untouched and still
    referenced by the other sharers; only the writer's table changes."""
    mgr = PagedKVManager(n_slots=3, max_pages_per_slot=8, page_size=4)
    prompt = list(range(40, 52))                 # 12 tokens: 3 full pages
    a = mgr.admit(0, 12, 6, tokens=prompt)
    b = mgr.admit(1, 12, 6, tokens=prompt)
    assert b.shared_pages == 3 and b.pages[:3] == a.pages[:3]
    shared_page = a.pages[2]
    # both write into their own FRESH page 3 first (position 12): no CoW
    mgr.append_token(0, 1)
    mgr.append_token(1, 1)
    assert mgr.pending_cow == []
    # force a divergence inside the shared region: identical 10-token
    # prompt c shares a's PARTIAL page; c's first append writes into it
    mgr2 = PagedKVManager(n_slots=3, max_pages_per_slot=8, page_size=4)
    p10 = prompt[:10]
    c = mgr2.admit(0, 10, 6, tokens=p10)
    d = mgr2.admit(1, 10, 6, tokens=p10)
    assert d.shared_pages == 3                   # 2 full + partial tail
    part = c.pages[2]
    rc_before = mgr2.pool.refcount(part)
    mgr2.append_token(0, 5)                      # c writes pos 10 -> CoW
    (src, dst), = mgr2.drain_cow_copies()
    assert src == part and dst == mgr2.seqs[0].pages[2] != part
    assert mgr2.seqs[1].pages[2] == part         # sharer untouched
    assert mgr2.pool.refcount(part) == rc_before - 1
    assert mgr2.tables[mgr2.seqs[1].slot][2] == part
    mgr2.pool.check_invariants()


def test_prefix_cache_lru_eviction_under_pressure():
    """OutOfPages pressure evicts warm-cache entries LRU instead of
    rejecting the admission."""
    mgr = PagedKVManager(n_slots=2, max_pages_per_slot=4, page_size=4)
    mgr.admit(0, 8, 8, tokens=list(range(8)))
    mgr.release(0)
    assert mgr.prefix.n_cached_pages == 2        # warm full pages
    assert mgr.admit(1, 8, 8, tokens=list(range(50, 58))) is not None
    # 8 pages total, 4 live + 2 cached: next 4-page admission must evict
    assert mgr.admit(2, 8, 8, tokens=list(range(80, 88))) is not None
    assert mgr.prefix.stats.evictions > 0
    mgr.pool.check_invariants()


def test_engine_pallas_decode_backend_matches_jax(key, monkeypatch):
    """The ragged paged-decode kernel on the hot path (interpret mode on
    CPU, forced through ``attention.ragged_decode_serves``) produces the
    same tokens as the pure-JAX gather path, on the fixed-wave engine
    (gemma2-2b: sliding-window layers keep it there) and the continuous
    engine (llama3.2-1b)."""
    from repro.models import attention as attn
    prompts = [SYS + [5, 9, 2], [11, 4], SYS + [7]]
    serves = attn.ragged_decode_serves

    def run(kernel, cfg, params):
        monkeypatch.setattr(attn, "ragged_decode_serves",
                            attn.is_global_layout if kernel else serves)
        eng = ServingEngine(cfg, params, n_slots=2, max_len=32, page_size=8)
        rids = [eng.submit(p, max_tokens=4) for p in prompts]
        done = eng.run()
        return eng.scheduler_mode, [done[r].out_tokens for r in rids]

    for arch, mode in (("gemma2-2b", "fixed"), ("llama3.2-1b", "continuous")):
        cfg = reduce_for_smoke(get_config(arch))
        params = init_params(cfg, key)
        kernel = run(True, cfg, params)
        assert kernel[0] == mode
        assert kernel == run(False, cfg, params)


def _prefix_policy_scenario(policy):
    """Hot 2-page prompt (matched twice, high frequency) vs a colder 2-page
    one-shot prompt, both RELEASED, competing under a 4-page warm-cache
    cap; a later 1-page admission overflows the cap by one and forces the
    policy to pick a victim. Returns the hot prompt's shared pages on
    re-admission."""
    mgr = PagedKVManager(n_slots=2, max_pages_per_slot=8, page_size=4,
                         prefix_policy=policy, prefix_cap_pages=4)
    hot = list(range(8))                         # exactly 2 full pages
    mgr.admit(0, 8, 8, tokens=hot)
    mgr.release(0)
    mgr.admit(1, 8, 8, tokens=hot)               # match bumps frequency
    mgr.release(1)
    mgr.admit(2, 8, 8, tokens=list(range(100, 108)))   # cold, 2 pages
    mgr.release(2)                               # cached: hot 2 + cold 2
    mgr.admit(3, 4, 4, tokens=list(range(200, 204)))   # +1 page > cap
    mgr.release(3)
    probe = mgr.admit(4, 8, 8, tokens=hot)
    mgr.pool.check_invariants()
    return probe.shared_pages, mgr


def test_prefix_cache_policy_lfu_keeps_hot_prompt():
    """Under a capped warm cache, LFU retains the frequently re-admitted
    prompt intact while LRU (recency) sheds its tail in favor of the newer
    one-shot prompt — the ROADMAP's frequency-aware eviction ask."""
    shared_lfu, mgr_lfu = _prefix_policy_scenario("lfu")
    shared_lru, _ = _prefix_policy_scenario("lru")
    assert shared_lfu == 2                       # hot prefix fully resident
    assert shared_lru < shared_lfu               # recency evicted its tail
    assert mgr_lfu.prefix.stats.evictions > 0
    assert mgr_lfu.stats()["prefix"]["policy"] == "lfu"


def test_prefix_cache_cap_enforced():
    """prefix_cache_pages bounds the warm cache's sole-owned footprint:
    after release, the next admission sheds entries down to the cap."""
    mgr = PagedKVManager(n_slots=2, max_pages_per_slot=8, page_size=4,
                         prefix_cap_pages=2)
    mgr.admit(0, 16, 4, tokens=list(range(300, 316)))    # 4 full pages
    mgr.release(0)
    assert mgr.prefix.n_cached_pages == 4        # live cap waits for release
    mgr.admit(1, 4, 4, tokens=list(range(400, 404)))
    assert mgr.prefix.n_cached_pages <= 2
    assert mgr.prefix.stats.evictions >= 2
    mgr.pool.check_invariants()


def test_engine_wires_prefix_policy_from_config(key):
    import dataclasses
    cfg = dataclasses.replace(reduce_for_smoke(get_config("llama3.2-1b")),
                              prefix_cache_policy="lfu",
                              prefix_cache_pages=8)
    params = init_params(cfg, key)
    eng = ServingEngine(cfg, params, n_slots=2, max_len=64, page_size=8)
    assert eng.mgr.prefix.policy == "lfu"
    assert eng.mgr.prefix.max_pages == 8
    s = eng.stats()
    assert s["prefix"]["policy"] == "lfu" and s["prefix"]["max_pages"] == 8
    assert s["iommu"]["walk"]["model"] == "counting"


def test_map_tables_rejects_wraparound():
    """Regression: installing a table row into a leaf with fewer pages
    (sliding-window) must raise, not wrap entries modulo the pool size."""
    import jax.numpy as jnp
    from repro.core.serving.engine import _map_tables
    from repro.models import attention as attn
    kv = attn.PagedKV(
        k_pool=jnp.zeros((1, 4, 4, 1, 2)), v_pool=jnp.zeros((1, 4, 4, 1, 2)),
        block_table=jnp.zeros((1, 4), jnp.int32),
        length=jnp.zeros((1,), jnp.int32))
    row = np.asarray([[7, 0, 1, 2, 3, 4, 5, 6]], np.int32)   # entry 7 >= 4
    with pytest.raises(ValueError):
        _map_tables({"kv": kv}, row, np.zeros(1, np.int32))
