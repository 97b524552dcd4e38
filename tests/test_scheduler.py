"""Continuous-batching scheduler: token-budget steps, chunked prefill,
preemption/resume under pool pressure — and the core contract: the
continuous engine's outputs are BIT-IDENTICAL to the copy-mode reference
engine's for every workload and arrival interleaving (scheduling policy
never changes tokens), including across preempt/resume round-trips.

Workload constants, the arrival-faithful driver, and the bit-identity
assertion live in tests/conformance.py (shared with test_disagg.py,
test_range_tlb.py, and the cross-engine matrix in test_conformance.py).
The interleaving property runs as fixed parameterized cases always, plus a
hypothesis-randomized version when hypothesis is installed."""
import dataclasses

import numpy as np
import pytest

from benchmarks.trace_replay import replay_trace
from repro.configs import get_config, reduce_for_smoke
from repro.configs.paper_soc import PaperSoCConfig
from repro.core.serving.scheduler import Scheduler
from repro.core.serving.sequence_buffer import SequenceBuffer
from repro.core.sva.iommu import IOMMU, CountingWalk, TLBConfig
from repro.core.sva.kv_manager import PagedKVManager
from repro.models import init_params
from tests.conformance import (ARRIVAL_CASES, POOL, REFERENCE, Workload,
                               pressure_workload, serve)

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


# -------------------------------------------------------------- validation

def test_scheduler_knob_validation():
    mgr = PagedKVManager(n_slots=2, max_pages_per_slot=4, page_size=8)
    buf = SequenceBuffer(2, 32)
    with pytest.raises(ValueError):
        Scheduler(mgr, buf, token_budget=0, prefill_chunk=8)
    with pytest.raises(ValueError):
        Scheduler(mgr, buf, token_budget=8, prefill_chunk=0)
    sched = Scheduler(mgr, buf, token_budget=8, prefill_chunk=8)
    with pytest.raises(ValueError):
        sched.submit(0, [], max_tokens=4)


def test_config_sched_knob_validation():
    cfg = reduce_for_smoke(get_config("llama3.2-1b"))
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, sched_token_budget=0)
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, sched_prefill_chunk=0)
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, prefix_cache_autotune=-1)


def test_pool_pages_validation():
    with pytest.raises(ValueError):
        PagedKVManager(n_slots=2, max_pages_per_slot=4, page_size=8,
                       pool_pages=3)      # < max_pages_per_slot
    # > n_slots * max_pages_per_slot: the spare pages hold the warm prefix
    # cache, and the device sentinel moves past the larger pool
    big = PagedKVManager(n_slots=2, max_pages_per_slot=4, page_size=8,
                         pool_pages=9)
    assert big.pool.n_pages == 9 and big.null_page == 9
    assert (big.tables == 9).all()
    mgr = PagedKVManager(n_slots=2, max_pages_per_slot=4, page_size=8,
                         pool_pages=5)
    assert mgr.null_page == 8             # device arrays keep worst case
    assert mgr.pool.n_pages == 5
    # a request that fits a slot but not the shrunken pool is rejected
    with pytest.raises(Exception):
        mgr.ensure_fits(prompt_len=30, max_tokens=18)


# ------------------------------------------------------------ bit-identity

def test_continuous_matches_fixed_ample_pool(setup):
    """No pool pressure: continuous (chunked prefill + masked decode)
    reproduces the fixed-wave copy-mode reference token-for-token."""
    cfg, params = setup
    wl = pressure_workload(cfg.vocab_size)
    fixed, ref_eng, _ = serve(cfg, params, REFERENCE, wl)
    cont, eng, _ = serve(cfg, params, "continuous", wl)
    assert ref_eng.scheduler_mode == "fixed"
    assert eng.scheduler_mode == "continuous"
    assert cont == fixed
    assert eng.stats()["sched"]["preemptions"] == 0


def test_preempt_resume_bit_identical_under_pressure(setup):
    """Oversubscribed pool: the continuous engine preempts and resumes at
    least once, and STILL produces the unconstrained outputs (the KV
    rebuild after resume is content-addressed, the pending token is
    re-injected, max_tokens is rebased)."""
    cfg, params = setup
    wl = pressure_workload(cfg.vocab_size)
    ref, _, _ = serve(cfg, params, REFERENCE, wl)
    cont, eng, _ = serve(cfg, params, "continuous", wl, pool_pages=POOL)
    s = eng.stats()
    assert s["sched"]["preemptions"] >= 1
    assert s["sched"]["resumes"] >= 1
    assert s["preemptions"] == s["sched"]["preemptions"]   # mgr mirror
    assert cont == ref


def test_preemption_svasan_clean(setup):
    """The preempt path mirrors release exactly under the translation
    sanitizer: no stale-mapping, leak, or double-free reports across
    preempt/resume round-trips."""
    cfg, params = setup
    cfg = dataclasses.replace(cfg, svasan=True)
    wl = pressure_workload(cfg.vocab_size)
    cont, eng, _ = serve(cfg, params, "continuous", wl, pool_pages=POOL)
    s = eng.stats()
    assert s["sched"]["preemptions"] >= 1
    assert s["svasan"]["reports"] == 0
    assert s["svasan"]["checks"] > 0


# ----------------------------------------------------- arrival interleaving

@pytest.mark.parametrize("arrivals", ARRIVAL_CASES)
def test_interleaving_bit_identity(setup, arrivals):
    cfg, params = setup
    ref, _, _ = serve(cfg, params, REFERENCE,
                      pressure_workload(cfg.vocab_size))
    cont, _, _ = serve(cfg, params, "continuous",
                       pressure_workload(cfg.vocab_size, arrivals=arrivals),
                       pool_pages=POOL)
    assert cont == ref


def _check_interleaving(reqs, seed):
    """(prompt_len, max_tokens, arrival_gap) requests: the pool-constrained
    continuous engine is bit-identical to the reference engine on them."""
    import jax
    cfg = reduce_for_smoke(get_config("llama3.2-1b"))
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(seed)
    prompts = tuple(tuple(rng.integers(0, cfg.vocab_size,
                                       size=n).tolist())
                    for n, _, _ in reqs)
    maxtoks = tuple(m for _, m, _ in reqs)
    arrivals = tuple(np.cumsum([g for _, _, g in reqs]).tolist())
    ref, _, _ = serve(cfg, params, REFERENCE, Workload(prompts, maxtoks))
    cont, _, _ = serve(cfg, params, "continuous",
                       Workload(prompts, maxtoks, arrivals=arrivals),
                       pool_pages=POOL)
    assert cont == ref
    assert [len(t) for t in ref] == list(maxtoks)


# Shrunk examples the property once found, pinned: a 1-token prompt with
# max_tokens=1 is finished by its prefill alone.
@pytest.mark.parametrize("reqs,seed", [([(1, 1, 0)], 0)])
def test_interleaving_pinned_cases(reqs, seed):
    _check_interleaving(reqs, seed)


if HAVE_HYPOTHESIS:
    @settings(max_examples=5, deadline=None)
    @given(st.lists(st.tuples(st.integers(1, 14), st.integers(1, 6),
                              st.integers(0, 3)),
                    min_size=1, max_size=4),
           st.integers(0, 2 ** 31 - 1))
    def test_interleaving_property(reqs, seed):
        """Any (prompt_len, max_tokens, arrival_gap) interleaving: the
        pool-constrained continuous engine is bit-identical to the
        reference engine on the same requests."""
        _check_interleaving(reqs, seed)


# --------------------------------------------------- jit-cache boundedness

def test_bounded_jit_cache_across_mixed_burst(setup):
    """Chunked prefill buckets (suffix length, batch rows) to powers of
    two and masked decode always runs at full slot width, so a
    mixed-length burst compiles a BOUNDED set of shapes — retracing per
    request would make continuous batching slower than what it replaces."""
    cfg, params = setup
    _, eng, _ = serve(cfg, params, "continuous",
                      pressure_workload(cfg.vocab_size), pool_pages=POOL)
    assert eng._decode_m._cache_size() == 1       # one masked-decode shape
    n_prefill = eng._prefill._cache_size()
    # power-of-two buckets: suffix lengths up to max_len x row counts up
    # to n_slots
    assert n_prefill <= np.log2(64) * np.log2(4) + 1


# ------------------------------------------------------------ trace replay

def test_preemption_trace_replays_end_to_end(setup):
    """A recorded continuous-scheduler trace carries preempt/resume
    events and replays through the IOMMU cost model without error."""
    cfg, params = setup
    _, eng, _ = serve(cfg, params, "continuous",
                      pressure_workload(cfg.vocab_size), pool_pages=POOL,
                      record_translation_trace=True)
    trace = eng.translation_trace
    kinds = {ev[0] for ev in trace}
    assert {"preempt", "resume", "map", "unmap", "step"} <= kinds
    iommu = IOMMU(walk_model=CountingWalk(), tlb=TLBConfig(8, "lru"))
    per_step = replay_trace(trace, iommu, kv_bytes_per_token=256,
                            compute_per_token=10.0, soc=PaperSoCConfig(),
                            dram_latency=200)
    assert len(per_step) == sum(1 for ev in trace if ev[0] == "step")
