"""Shared serving-conformance harness (NOT a test module).

Every scheduling/disaggregation/tenancy feature in this repo carries the
same contract: it may change WHEN work runs and HOW translation is
accounted, but never the tokens. The suites that pin that contract
(tests/test_scheduler.py, tests/test_disagg.py, tests/test_range_tlb.py,
tests/test_conformance.py, tests/test_multitenant.py) all drive engines
over the same pressure workload and compare outputs bit-for-bit — this
module is the single home for that machinery:

  Workload            prompts x max_tokens x arrival ticks x per-request
                      tenants, as one immutable value
  pressure_workload   the verified oversubscribed mix (mixed lengths,
                      POOL=8 pages forces preempt/resume on continuous
                      engines)
  prefix_workload     the shared-system-prompt mix (CoW + prefix paths)
  make_engine         one constructor for every engine kind:
                      continuous-ample | continuous | disagg-share |
                      disagg-copy, and the REFERENCE: the copy-mode engine
                      (per-slot pools, whole-prompt prefill, staging copy;
                      an implementation independent of the served loop)
  drive               arrival-faithful driver (requests injected between
                      steps at their tick; the engine never sees the
                      future), tenant-aware
  serve               make_engine + drive in one call
  assert_bit_identical  THE conformance assertion: two engines, one
                      workload, outputs must match token-for-token
"""
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import numpy as np

from repro.core.serving.disagg import DisaggEngine
from repro.core.serving.engine import ServingEngine

# The verified pressure workload: mixed lengths, tight pool -> the
# continuous engine preempts and resumes.
LENS = (11, 23, 5, 17, 9, 13)
MAXTOKS = (10, 8, 12, 9, 11, 10)
POOL = 8

# Arrival interleavings every bit-identity suite parameterizes over.
ARRIVAL_CASES = [
    [0, 0, 0, 0, 0, 0],            # one burst
    [0, 0, 0, 5, 5, 5],            # two bursts
    [0, 1, 2, 3, 4, 5],            # steady trickle
    [0, 0, 9, 9, 0, 4],            # stragglers mid-serve
]

ENGINE_KINDS = ("continuous-ample", "continuous", "disagg-share",
                "disagg-copy")
# Ground truth every engine kind must reproduce token-for-token.
REFERENCE = "copy"


@dataclass(frozen=True)
class Workload:
    """One driveable workload. ``arrivals`` (per-request step ticks) are
    injected between steps; None submits everything up front.
    ``tenants`` names each request's TenantDomain (None = untenanted)."""
    prompts: Tuple[tuple, ...]
    maxtoks: Tuple[int, ...]
    arrivals: Optional[Tuple[int, ...]] = None
    tenants: Optional[Tuple[Optional[str], ...]] = None

    def __post_init__(self):
        n = len(self.prompts)
        for field_name in ("maxtoks", "arrivals", "tenants"):
            v = getattr(self, field_name)
            if v is not None and len(v) != n:
                raise ValueError(f"{field_name} has {len(v)} entries for "
                                 f"{n} prompts")

    def tenant_of(self, i: int) -> Optional[str]:
        return self.tenants[i] if self.tenants is not None else None


def pressure_workload(vocab: int, n: int = 6, seed: int = 3,
                      arrivals=None, tenants=None) -> Workload:
    """The canonical oversubscribed mix (LENS/MAXTOKS at POOL pages)."""
    rng = np.random.default_rng(seed)
    prompts = tuple(tuple(rng.integers(0, vocab, size=k).tolist())
                    for k in LENS[:n])
    return Workload(prompts, tuple(MAXTOKS[:n]),
                    arrivals=tuple(arrivals) if arrivals is not None
                    else None,
                    tenants=tuple(tenants) if tenants is not None else None)


def prefix_workload(vocab: int, n: int = 6, max_tokens: int = 6,
                    seed: int = 7) -> Workload:
    """Shared-system-prompt mix: most requests extend one common prefix
    (prefix sharing + CoW divergence), every third is unrelated."""
    rng = np.random.default_rng(seed)
    system = rng.integers(0, vocab, size=24).tolist()
    prompts = []
    for i in range(n):
        if i % 3 == 2:
            prompts.append(tuple(rng.integers(0, vocab, size=10).tolist()))
        else:
            prompts.append(tuple(system
                                 + rng.integers(0, vocab, size=5).tolist()))
    return Workload(tuple(prompts), (max_tokens,) * n)


def make_engine(cfg, params, kind: str, n_slots: int = 4, max_len: int = 64,
                page_size: int = 8, tenants: Optional[Dict[str, dict]] = None,
                **engine_kw):
    """One constructor for every engine kind (see ENGINE_KINDS) and the
    REFERENCE. ``disagg-*`` splits ``n_slots`` evenly into prefill/decode
    workers so every kind serves at equal total slot width; the two
    colocated continuous kinds differ only in the pool their caller
    passes (``continuous-ample``: ``pool_pages=None``)."""
    if kind not in ENGINE_KINDS + (REFERENCE,):
        raise ValueError(f"kind={kind!r} (expected one of {ENGINE_KINDS} "
                         f"or {REFERENCE!r})")
    if kind.startswith("disagg-"):
        return DisaggEngine(cfg, params, n_prefill_slots=n_slots // 2,
                            n_decode_slots=n_slots - n_slots // 2,
                            max_len=max_len, page_size=page_size,
                            disagg_mode=kind.split("-", 1)[1],
                            tenants=tenants, **engine_kw)
    if kind == REFERENCE:
        engine_kw["offload_mode"] = "copy"
    return ServingEngine(cfg, params, n_slots=n_slots, max_len=max_len,
                         page_size=page_size, tenants=tenants, **engine_kw)


def drive(eng, workload: Workload):
    """Run one engine over the workload, arrival-faithfully. Returns
    (per-request output tokens, finished {req_id: Request})."""
    wl = workload
    finished = {}
    if wl.arrivals is None:
        rids = [eng.submit(list(p), max_tokens=m, tenant=wl.tenant_of(j))
                for j, (p, m) in enumerate(zip(wl.prompts, wl.maxtoks))]
        finished = eng.run()
    else:
        rids = [None] * len(wl.prompts)
        order = sorted(range(len(wl.prompts)), key=lambda j: wl.arrivals[j])
        i, clock = 0, 0
        while i < len(order) or eng.has_work:
            while i < len(order) and wl.arrivals[order[i]] <= clock:
                j = order[i]
                rids[j] = eng.submit(list(wl.prompts[j]),
                                     max_tokens=wl.maxtoks[j],
                                     tenant=wl.tenant_of(j))
                i += 1
            if eng.has_work:
                eng.step(finished)
            clock += 1
    return [finished[r].out_tokens for r in rids], finished


def serve(cfg, params, kind: str, workload: Workload, **engine_kw):
    """make_engine + drive. Returns (outputs, engine, finished)."""
    eng = make_engine(cfg, params, kind, **engine_kw)
    outs, finished = drive(eng, workload)
    return outs, eng, finished


def assert_bit_identical(engine_a, engine_b, workload: Workload) -> None:
    """Drive two FRESH engines over the same workload and require
    token-for-token identical outputs — the conformance contract every
    scheduling/tenancy/translation feature must satisfy."""
    outs_a, _ = drive(engine_a, workload)
    outs_b, _ = drive(engine_b, workload)
    assert outs_a == outs_b, (
        f"outputs diverged: {type(engine_a).__name__} vs "
        f"{type(engine_b).__name__} on {len(workload.prompts)} requests "
        f"(first mismatch at request "
        f"{next(i for i, (a, b) in enumerate(zip(outs_a, outs_b)) if a != b)})")
