"""Benchmark E6 — trace-driven translation design-space sweep (Kim et al.),
static grid + adaptive front-end rows.

Records real serving translation traces (``ServingEngine(
record_translation_trace=True)``, all on the engine's continuous loop) for
three deployment profiles — a prefix-heavy mix (shared system prompt, CoW
divergence), an all-unique mix (no cross-request reuse), and the
prefix-heavy mix again over an oversubscribed page pool (its trace bears
preempt/resume events around real ASID teardown/re-mapping) — then replays each trace through the
unified IOMMU front-end across a grid of hardware geometries:

  IOTLB entries x set associativity (ways) x replacement policy
  x walk-cache size (non-leaf Sv39 PTE cache)

The walker is ``Sv39Walk(llc=False)`` — the no-LLC platform where the
paper pays 4.2-17.6% of accelerator runtime for translation, i.e. exactly
the regime where IOTLB/walk-cache geometry decides the design space (with
LLC-resident PTEs the walker is ~free and every geometry ties). Every
replay of the same trace is bit-reproducible: the walker draws no RNG with
the LLC off, the ``random`` policy is seeded, and the prefetcher/tuner are
deterministic.

After the static grid, the ADAPTIVE rows replay the same traces with the
IOTLB *prefetcher* (``stream`` / ``next_page``) and with the online
geometry *auto-tuner* enabled, so static-vs-adaptive is one CSV: the
``adaptive`` column labels the row, ``demand_ptw_cycles`` is the
demand-exposed translation cost (what a prefetcher actually lowers; equal
to ``ptw_cycles`` for static rows), and the ``prefetch_*`` columns carry
the issued/useful/late counters. See ``benchmarks/README.md`` for the
full column contract.

Emits the grid + adaptive rows as CSV (``--out``, default
``tlb_sweep.csv``) and prints summary rows: PTW overhead as a % of modeled
decode-step runtime per geometry axis, the best static geometry per
deployment, and the adaptive rows' win/loss against it.

After the adaptive rows, one RANGE row per deployment prices the same
trace with range-coalesced IOTLB entries (``TLBConfig(ranges=N)``,
``--ranges``) against the per-page 4-entry baseline at EQUAL entry count —
the ``range_entries`` / ``coalesced_pages`` / ``range_splits`` columns
carry the coalescing counters (zero on per-page rows), and the
``tlb_sweep.range.<deployment>`` summary rows print the demand-miss and
demand-PTW-cycle deltas.

``--smoke`` shrinks the grid and the recorded workload (CI smoke path —
wired into ``benchmarks/run.py --only sweep`` and the figure-benchmarks
job).
"""
from __future__ import annotations

import argparse
import csv
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.trace_replay import replay_trace
from repro.configs.paper_soc import PaperSoCConfig
from repro.core.simulator.platform import H2A
from repro.core.sva.iommu import (IOMMU, AutoTuneConfig, PrefetchConfig,
                                  Sv39Walk, TLBAutoTuner, TLBConfig,
                                  WalkCacheConfig)
from repro.core.sva.tlb import POLICIES


@dataclass(frozen=True)
class Geometry:
    """One IOTLB + walk-cache design point of the sweep grid."""
    entries: int
    ways: int                 # 0 = fully associative
    policy: str
    wc_entries: int           # 0 = walk cache off
    ranges: int = 0           # 0 = per-page entries; else max coalesced run

    @property
    def resolved_ways(self) -> int:
        return self.ways or self.entries

    def label(self) -> str:
        w = "full" if self.resolved_ways == self.entries else str(self.ways)
        r = f".r{self.ranges}" if self.ranges else ""
        return (f"e{self.entries}.w{w}.{self.policy}.wc{self.wc_entries}{r}")


def sweep_grid(smoke: bool = False) -> List[Geometry]:
    """entries x ways x policy x walk-cache size; degenerate ways (== entries)
    collapse onto the fully-associative point so no geometry is replayed
    twice."""
    if smoke:
        entries, ways = (4, 16), (1, 0)
        policies, wcs = ("lru", "fifo"), (0, 8)
    else:
        entries, ways = (4, 8, 16, 64), (1, 2, 4, 0)
        policies, wcs = POLICIES, (0, 8, 32)
    out: List[Geometry] = []
    seen = set()
    for e in entries:
        for w in ways:
            if w and (w > e or e % w):
                continue
            rw = w or e
            for p in policies:
                for wc in wcs:
                    key = (e, rw, p, wc)
                    if key in seen:
                        continue
                    seen.add(key)
                    out.append(Geometry(e, 0 if rw == e else w, p, wc))
    return out


# --------------------------------------------------------------- recording

def record_traces(dry_run: bool = False) -> Tuple[Dict[str, list], dict]:
    """Serve three deployment profiles with translation tracing ON. Returns
    ({deployment: trace}, cost model constants for the replay). All three
    are recorded on the engine's continuous loop. The ``continuous``
    profile serves over an oversubscribed page pool, so its trace bears
    ``("preempt", ...)`` / ``("resume", ...)`` annotations around real ASID
    teardown/re-mapping — the replay path is exercised on preemption-bearing
    traces even at ``--smoke`` scale."""
    # Lazy: recording needs jax + the serving engine; replay does not.
    from benchmarks.paged_serving import (_BURST_POOL,  # noqa: PLC0415
                                          _cfg_params,
                                          _prefix_heavy_prompts)
    from repro.core.serving.engine import ServingEngine  # noqa: PLC0415

    n_req, max_tokens = (4, 4) if dry_run else (10, 10)
    cfg, params = _cfg_params()
    soc = PaperSoCConfig()

    def serve(prompts, **engine_kw):
        eng = ServingEngine(cfg, params, n_slots=4, max_len=64, page_size=8,
                            record_translation_trace=True, **engine_kw)
        for p in prompts:
            eng.submit(p, max_tokens=max_tokens)
        eng.run()
        return eng, eng.translation_trace

    eng, prefix_trace = serve(_prefix_heavy_prompts(n_req, cfg.vocab_size))
    rng = np.random.default_rng(11)
    unique = [rng.integers(0, cfg.vocab_size,
                           size=int(rng.integers(8, 30))).tolist()
              for _ in range(n_req)]
    _, unique_trace = serve(unique)
    _, cont_trace = serve(_prefix_heavy_prompts(n_req, cfg.vocab_size),
                          pool_pages=_BURST_POOL)

    n_attn = sum(1 for k in cfg.layer_kinds() if "attn" in k)
    consts = dict(
        kv_bytes_per_token=eng.mgr.kv_bytes_per_token,
        # decode attention: ~4 flops per KV token per head-dim per layer
        compute_per_token=4 * cfg.n_heads * cfg.d_head * n_attn / soc.n_pes)
    return {"prefix_heavy": prefix_trace, "unique": unique_trace,
            "continuous": cont_trace}, consts


# ----------------------------------------------------------------- replay

def replay_geometry(trace, geom: Geometry, kv_bytes_per_token: int,
                    compute_per_token: float, dram_latency: int = 200,
                    soc: PaperSoCConfig = None,
                    prefetch: Optional[PrefetchConfig] = None,
                    autotune: Optional[AutoTuneConfig] = None,
                    adaptive: str = "static") -> dict:
    """Price one recorded serving trace under one hardware geometry —
    optionally with the IOTLB prefetcher and/or the online geometry
    auto-tuner armed (``geom`` is then the STARTING geometry). Returns the
    CSV row: TLB/walk-cache stats + PTW overhead as a % of each modeled
    decode step's accelerator runtime. ``demand_ptw_cycles`` is the
    demand-exposed translation cost (what prefetching lowers);
    ``ptw_cycles`` stays the walk model's total, which for adaptive rows
    also contains the prefetch walks done off the demand path."""
    soc = soc or PaperSoCConfig()
    walker = Sv39Walk(
        levels=soc.ptw_levels,
        dram_access_cycles=dram_latency + soc.dram_base_latency,
        llc=False, to_accel=H2A,
        walk_cache=WalkCacheConfig(geom.wc_entries, policy="lru"))
    iommu = IOMMU(walk_model=walker,
                  tlb=TLBConfig(geom.entries, geom.policy, ways=geom.ways,
                                ranges=geom.ranges),
                  prefetch=prefetch or PrefetchConfig())
    tuner = TLBAutoTuner(iommu, autotune) if autotune is not None else None
    per_step = replay_trace(trace, iommu, kv_bytes_per_token,
                            compute_per_token, soc, dram_latency,
                            tuner=tuner)
    pcts = [100.0 * ptw / max(step, 1e-9) for ptw, step in per_step]
    tlb = iommu.tlb.stats
    wc = walker.walk_cache.stats if walker.walk_cache is not None else None
    row = dict(
        n_entries=geom.entries, ways=geom.resolved_ways, policy=geom.policy,
        wc_entries=geom.wc_entries,
        tlb_hits=tlb.hits, tlb_misses=tlb.misses,
        conflict_misses=tlb.conflict_misses,
        hit_rate=round(tlb.hit_rate, 4),
        walks=walker.stats.walks,
        wc_hits=wc.hits if wc else 0, wc_misses=wc.misses if wc else 0,
        ptw_cycles=round(walker.stats.cycles, 1),
        ptw_pct_mean=round(float(np.mean(pcts)) if pcts else 0.0, 3),
        ptw_pct_max=round(float(np.max(pcts)) if pcts else 0.0, 3),
        adaptive=adaptive,
        prefetch_issued=tlb.prefetch_issued,
        prefetch_useful=tlb.prefetch_useful,
        prefetch_late=tlb.prefetch_late,
        demand_ptw_cycles=round(sum(p for p, _ in per_step), 1),
        # range-coalescing counters (all zero on per-page rows)
        range_entries=iommu.range_fills,
        coalesced_pages=iommu.coalesced_pages,
        range_splits=iommu.range_splits)
    if tuner is not None:
        ts = tuner.stats()
        row["n_entries"] = ts["current"]["n_entries"]   # converged geometry
        row["ways"] = ts["current"]["ways"]
        row["policy"] = ts["current"]["policy"]
        row["_tuner"] = ts                              # not a CSV column
    return row


FIELDS = ("deployment", "n_entries", "ways", "policy", "wc_entries",
          "tlb_hits", "tlb_misses", "conflict_misses", "hit_rate", "walks",
          "wc_hits", "wc_misses", "ptw_cycles", "ptw_pct_mean",
          "ptw_pct_max", "adaptive", "prefetch_issued", "prefetch_useful",
          "prefetch_late", "demand_ptw_cycles", "range_entries",
          "coalesced_pages", "range_splits")


def adaptive_rows(trace, best_geom: Geometry, consts: dict,
                  dram_latency: int, smoke: bool = False) -> List[dict]:
    """Replay one trace with the adaptive front-end armed: stream /
    next_page prefetching on both the paper's 4-entry IOTLB and the best
    static geometry, plus the online auto-tuner walking an entries ladder.
    Returns CSV rows (``adaptive`` column labels each configuration)."""
    out: List[dict] = []
    paper = Geometry(4, 0, "lru", 0)
    # The run-ahead distance must fit the IOTLB: on the paper's 4-entry
    # geometry the stream prefetcher runs 2 ahead (more would evict its own
    # unused fills); on the sweep's best static geometry it can run deep.
    pf_points = [("prefetch:next_page:d2", paper,
                  PrefetchConfig("next_page", degree=2)),
                 ("prefetch:stream:d2", paper,
                  PrefetchConfig("stream", degree=2, distance=2)),
                 ("prefetch:stream:d4+best", best_geom,
                  PrefetchConfig("stream", degree=4, distance=8))]
    for label, geom, pf in pf_points:
        out.append(replay_geometry(trace, geom, dram_latency=dram_latency,
                                   prefetch=pf, adaptive=label, **consts))
    ladder = (4, 16) if smoke else (4, 16, 64)
    cands = tuple(TLBConfig(e, "lru") for e in ladder)
    tune = AutoTuneConfig(interval_steps=1 if smoke else 4,
                          candidates=cands)
    out.append(replay_geometry(trace, Geometry(ladder[0], 0, "lru",
                                               best_geom.wc_entries),
                               dram_latency=dram_latency, autotune=tune,
                               adaptive="autotune", **consts))
    return out


# ------------------------------------------------- multi-tenant replay A/B
_TENANTS = ("a", "b")


def _tenant_of_slot(slot: int) -> str:
    """Round-robin slot -> tenant assignment for replaying an untenanted
    recorded trace under tenant identities (the trace carries no tenant
    labels; any deterministic assignment gives the partitioning A/B a
    well-defined workload split)."""
    return _TENANTS[slot % len(_TENANTS)]


def tenant_ab_rows(traces: Dict[str, list], consts: dict,
                   dram_latency: int) -> List[str]:
    """Replay each recorded deployment trace under two-tenant identities
    on a small partitionable IOTLB: all ways shared vs private ways per
    tenant (``TLBConfig.partitions``). Both arms see the exact same
    demand stream — partitioning moves misses between tenants, never
    changes the trace — so the rows isolate the interference/isolation
    trade: a noisy neighbor can thrash the shared arm's whole TLB but
    only its own ways in the partitioned arm."""
    soc = PaperSoCConfig()
    entries, ways = 8, 4
    rows: List[str] = []
    for dep, trace in traces.items():
        arms = {}
        for label, parts in (("shared", {}),
                             ("partitioned", {"a": 2, "b": 1})):
            walker = Sv39Walk(
                levels=soc.ptw_levels,
                dram_access_cycles=dram_latency + soc.dram_base_latency,
                llc=False, to_accel=H2A)
            iommu = IOMMU(walk_model=walker,
                          tlb=TLBConfig(entries, "lru", ways=ways,
                                        partitions=parts))
            for t in _TENANTS:
                iommu.register_tenant(t)
            per_step = replay_trace(trace, iommu,
                                    consts["kv_bytes_per_token"],
                                    consts["compute_per_token"], soc,
                                    dram_latency,
                                    tenant_of=_tenant_of_slot)
            arms[label] = (iommu, sum(p for p, _ in per_step))
        for label in ("shared", "partitioned"):
            iommu, demand = arms[label]
            ts = iommu.stats().get("tenant", {})
            per_t = " ".join(
                f"{t}:hits={ts[t].get('tlb', {}).get('hits', 0)}"
                f"/misses={ts[t].get('tlb', {}).get('misses', 0)}"
                f"/conflict={ts[t].get('tlb', {}).get('conflict_misses', 0)}"
                for t in sorted(ts))
            cfgstr = ("all ways shared" if label == "shared"
                      else "ways a=2 b=1 (+1 shared)")
            rows.append(
                f"tlb_sweep.tenant.{dep}.{label},{demand:.1f},"
                f"demand PTW cycles @ e{entries}.w{ways} {cfgstr}; "
                f"{per_t}")
    return rows


def run(smoke: bool = False, out: str = "tlb_sweep.csv",
        dram_latency: int = 200, ranges: int = 8) -> List[str]:
    traces, consts = record_traces(dry_run=smoke)
    grid = sweep_grid(smoke)
    rows: List[str] = []
    results: Dict[str, List[dict]] = {}
    adaptive: Dict[str, List[dict]] = {}
    for dep, trace in traces.items():
        n_steps = sum(1 for ev in trace if ev[0] == "step")
        n_pre = sum(1 for ev in trace if ev[0] == "preempt")
        rows.append(f"tlb_sweep.trace.{dep},{n_steps},decode steps recorded "
                    f"({len(trace)} events; preempts={n_pre})")
        results[dep] = []
        for geom in grid:
            r = replay_geometry(trace, geom, dram_latency=dram_latency,
                                **consts)
            r["deployment"] = dep
            results[dep].append(r)
    # ONE best-static pick per deployment, shared by the adaptive rows'
    # baseline and the tlb_sweep.best.* summary (so the two can never
    # silently disagree about what "best" means).
    best_key = lambda r: (r["ptw_pct_mean"], r["n_entries"], r["ways"],
                          r["wc_entries"])
    best = {dep: min(rs, key=best_key) for dep, rs in results.items()}
    for dep, trace in traces.items():
        b = best[dep]
        best_geom = Geometry(b["n_entries"],
                             0 if b["ways"] == b["n_entries"]
                             else b["ways"], b["policy"], b["wc_entries"])
        adaptive[dep] = adaptive_rows(trace, best_geom, consts,
                                      dram_latency, smoke=smoke)
        for r in adaptive[dep]:
            r["deployment"] = dep
    # Range-coalescing A/B at EQUAL ENTRY COUNT: the paper's 4-entry
    # fully-assoc lru IOTLB per-page (the static grid row) vs the same
    # geometry with range entries covering up to ``ranges`` pages each.
    range_ab: Dict[str, dict] = {}
    for dep, trace in traces.items():
        r = replay_geometry(trace, Geometry(4, 0, "lru", 0, ranges=ranges),
                            dram_latency=dram_latency,
                            adaptive=f"range:r{ranges}", **consts)
        r["deployment"] = dep
        range_ab[dep] = r

    with open(out, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=FIELDS, extrasaction="ignore")
        w.writeheader()
        for dep in results:
            w.writerows(results[dep])
        for dep in adaptive:
            w.writerows(adaptive[dep])
        w.writerows(range_ab.values())
    n_rows = sum(len(v) for v in results.values()) \
        + sum(len(v) for v in adaptive.values()) + len(range_ab)
    rows.append(f"tlb_sweep.grid,{len(grid)},geometries x "
                f"{len(results)} deployments + "
                f"{sum(len(v) for v in adaptive.values())} adaptive rows "
                f"-> {n_rows} CSV rows ({out})")

    for dep, rs in results.items():
        # Axis cuts at the paper's 4-entry IOTLB (hold the rest at lru/wc0):
        base = {(r["ways"], r["policy"], r["wc_entries"]): r
                for r in rs if r["n_entries"] == 4}
        fa = base.get((4, "lru", 0))
        dm = base.get((1, "lru", 0))
        if fa and dm:
            rows.append(
                f"tlb_sweep.{dep}.assoc_axis,{dm['ptw_pct_mean']:.2f},"
                f"PTW% direct-mapped 4-entry (fully-assoc: "
                f"{fa['ptw_pct_mean']:.2f}%; conflict_misses="
                f"{dm['conflict_misses']})")
        wc_on = base.get((4, "lru", max(g.wc_entries for g in grid)))
        if fa and wc_on:
            rows.append(
                f"tlb_sweep.{dep}.walk_cache_axis,"
                f"{wc_on['ptw_pct_mean']:.2f},PTW% with a "
                f"{wc_on['wc_entries']}-entry walk cache (off: "
                f"{fa['ptw_pct_mean']:.2f}%; wc_hits={wc_on['wc_hits']})")
        sizes = sorted({r["n_entries"] for r in rs})
        size_cut = [r for r in rs
                    if r["ways"] == r["n_entries"] and r["policy"] == "lru"
                    and r["wc_entries"] == 0]
        span = " ".join(f"{r['n_entries']}e={r['ptw_pct_mean']:.2f}%"
                        for r in sorted(size_cut,
                                        key=lambda r: r["n_entries"]))
        rows.append(f"tlb_sweep.{dep}.size_axis,{len(sizes)},"
                    f"fully-assoc lru PTW% by entries: {span}")
        b = best[dep]
        rows.append(
            f"tlb_sweep.best.{dep},{b['ptw_pct_mean']:.2f},"
            f"PTW% of decode-step runtime @ entries={b['n_entries']} "
            f"ways={b['ways']} policy={b['policy']} "
            f"wc={b['wc_entries']} (hit_rate={b['hit_rate']})")
        # ------------------------- adaptive front-end vs the best static
        for r in adaptive[dep]:
            label = r["adaptive"].replace(":", "_").replace("+", "_")
            extra = ""
            if r["adaptive"] == "autotune":
                ts = r["_tuner"]
                extra = (f" converged=e{r['n_entries']}.w{r['ways']}."
                         f"{r['policy']} switches={ts['switches']} "
                         f"windows={ts['windows']}")
            else:
                extra = (f" issued={r['prefetch_issued']} "
                         f"useful={r['prefetch_useful']} "
                         f"late={r['prefetch_late']}")
            rows.append(
                f"tlb_sweep.adaptive.{dep}.{label},"
                f"{r['demand_ptw_cycles']},demand PTW cycles vs best "
                f"static {b['demand_ptw_cycles']} "
                f"(ptw_pct_mean={r['ptw_pct_mean']:.2f} vs "
                f"{b['ptw_pct_mean']:.2f}){extra}")
        # -------------- range coalescing vs per-page at equal entry count
        pp = next(r for r in rs
                  if r["n_entries"] == 4 and r["ways"] == 4
                  and r["policy"] == "lru" and r["wc_entries"] == 0)
        rr = range_ab[dep]
        rows.append(
            f"tlb_sweep.range.{dep},{rr['demand_ptw_cycles']},"
            f"demand PTW cycles @ ranges={ranges} vs per-page "
            f"{pp['demand_ptw_cycles']} at equal entry count (e4 full lru "
            f"wc0; demand misses {rr['tlb_misses']} vs {pp['tlb_misses']}; "
            f"range_entries={rr['range_entries']} "
            f"coalesced_pages={rr['coalesced_pages']} "
            f"splits={rr['range_splits']})")
    # --------- multi-tenant partitioned-vs-shared A/B (same traces,
    # round-robin slot->tenant identities; see benchmarks/README.md)
    rows += tenant_ab_rows(traces, consts, dram_latency)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small grid + dry-run trace (CI smoke path)")
    ap.add_argument("--out", default="tlb_sweep.csv",
                    help="full-grid CSV output path")
    ap.add_argument("--dram-latency", type=int, default=200,
                    help="AXI delayer setting for the Sv39 walk replay")
    ap.add_argument("--ranges", type=int, default=8,
                    help="max pages per range-coalesced IOTLB entry for the "
                         "range A/B rows (>= 2; the per-page baseline rows "
                         "are unaffected)")
    args = ap.parse_args()
    print("\n".join(run(smoke=args.smoke, out=args.out,
                        dram_latency=args.dram_latency,
                        ranges=args.ranges)))
