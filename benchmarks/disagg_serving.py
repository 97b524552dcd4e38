"""Disaggregated prefill/decode A/B on the bursty Poisson workload:
colocated-continuous vs disagg-copy vs disagg-share at the SAME
(oversubscribed) page pool and the SAME total slot width.

The question is the paper's zero-copy-offload claim at cross-worker
scale: when a finished prefill's KV hands off to the decode worker, what
moves? ``copy`` stages the full KV payload (device-side batched page
copy); ``share`` re-maps the same physical pages under the decode
worker's ASID and moves only int32 table entries. Both price the
hand-off's per-page translations through a transfer IOMMU configured as
the paper's hardware — a 4-entry IOTLB over ``Sv39Walk(llc=False)`` — so
the report carries transfer bytes AND transfer PTW cycles side by side.

Reported rows (``name,value,derived`` CSV):

  disagg_serving.bit_identical          share AND copy outputs vs the
                                        colocated continuous engine
  disagg_serving.<mode>.transfer_bytes  payload + table bytes moved
  disagg_serving.transfer_bytes_ratio   copy / share (the SVA payoff)
  disagg_serving.<mode>.transfer_ptw_cycles
                                        modeled remote-DMA walk cost
  disagg_serving.<mode>.ttfdt           mean steps from submit to first
                                        DECODE-step token (the transfer
                                        queue's latency cost)

Run directly (``--dry-run`` for the CI smoke sizes) or via
``python -m benchmarks.run --only disagg``.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path
from typing import List

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.paged_serving import (_BURST_POOL, _bursty_workload,
                                      _cfg_params)
from repro.configs import get_config, reduce_for_smoke
from repro.configs.paper_soc import PaperSoCConfig
from repro.core.serving.disagg import DisaggEngine
from repro.core.serving.engine import ServingEngine
from repro.core.sva.iommu import IOMMU, Sv39Walk, TLBConfig


def _xfer_iommu(soc: PaperSoCConfig) -> IOMMU:
    """The transfer fabric's translation hardware: the paper's 4-entry
    IOTLB in front of a no-LLC Sv39 page-table walk — the design point
    where translation cost is most exposed, so the remote-DMA pricing is
    a worst case, not a rounding error."""
    return IOMMU(walk_model=Sv39Walk(llc=False),
                 tlb=TLBConfig(soc.iotlb_entries, "lru"))


def _drive(eng, prompts, maxtoks, arrivals):
    """Clock-driven arrival loop (the engine never sees the future).
    Returns (outputs, finished requests in submission order, stats)."""
    finished = {}
    rids = [None] * len(prompts)
    order = sorted(range(len(prompts)), key=lambda j: arrivals[j])
    i, clock = 0, 0
    while i < len(order) or eng.has_work:
        while i < len(order) and arrivals[order[i]] <= clock:
            j = order[i]
            rids[j] = eng.submit(prompts[j], max_tokens=maxtoks[j])
            i += 1
        if eng.has_work:
            eng.step(finished)
        clock += 1
    reqs = [finished[r] for r in rids]
    return [r.out_tokens for r in reqs], reqs, eng.stats()


def _ttfdt(reqs) -> float:
    """Mean steps from submission to the first token a DECODE step
    produced. In the disaggregated engine this spans admission wait +
    chunked prefill + the transfer queue; a request that finished at
    prefill (budget exhausted before any decode) is excluded."""
    deltas = [r.first_decode_step - r.submitted_step for r in reqs
              if r.first_decode_step is not None]
    return float(np.mean(deltas)) if deltas else 0.0


def run(dry_run: bool = False) -> List[str]:
    n_req = 4 if dry_run else 6
    soc = PaperSoCConfig()
    vocab = reduce_for_smoke(get_config("llama3.2-1b")).vocab_size
    prompts, maxtoks, arrivals = _bursty_workload(vocab, n_req)
    cfg, params = _cfg_params()

    # Colocated reference: 4 slots, every slot admits AND decodes.
    ref_eng = ServingEngine(cfg, params, n_slots=4, max_len=64, page_size=8,
                            pool_pages=_BURST_POOL)
    ref_outs, ref_reqs, _ = _drive(ref_eng, prompts, maxtoks, arrivals)

    rows = []
    bytes_moved, identical = {}, True
    for mode in ("copy", "share"):
        eng = DisaggEngine(cfg, params, n_prefill_slots=2, n_decode_slots=2,
                           max_len=64, page_size=8, disagg_mode=mode,
                           pool_pages=_BURST_POOL, xfer_iommu=_xfer_iommu(soc))
        outs, reqs, s = _drive(eng, prompts, maxtoks, arrivals)
        identical = identical and outs == ref_outs
        t = s["transfer"]
        d = s["disagg"]
        bytes_moved[mode] = t["payload_bytes"] + t["table_bytes"]
        rows.append(
            f"disagg_serving.{mode}.transfer_bytes,{bytes_moved[mode]},"
            f"payload={t['payload_bytes']} table={t['table_bytes']} over "
            f"{t['transfers']} transfers "
            f"(pages copied={t['pages_copied']} shared={t['pages_shared']}; "
            f"deferred={d['deferred']} cancelled={d['cancelled']})")
        rows.append(
            f"disagg_serving.{mode}.transfer_ptw_cycles,"
            f"{t['ptw_cycles']:.0f},remote-DMA translation cost under a "
            f"{soc.iotlb_entries}-entry IOTLB + Sv39Walk(llc=False): "
            f"tlb_hits={t['tlb_hits']} tlb_misses={t['tlb_misses']}")
        rows.append(
            f"disagg_serving.{mode}.ttfdt,{_ttfdt(reqs):.1f},"
            f"mean steps submit -> first decode token "
            f"(colocated: {_ttfdt(ref_reqs):.1f}; "
            f"preemptions={s['sched']['preemptions']})")
    rows.append(
        f"disagg_serving.transfer_bytes_ratio,"
        f"{bytes_moved['copy'] / max(bytes_moved['share'], 1):.0f},"
        f"x fewer bytes moved by zero-copy ASID re-attachment vs staging "
        f"the KV (share={bytes_moved['share']} copy={bytes_moved['copy']}; "
        f"paper's table-entries-vs-payload argument at cross-worker scale)")
    rows.append(
        f"disagg_serving.bit_identical,{identical},"
        f"disagg-share AND disagg-copy outputs vs the colocated continuous "
        f"engine at equal total width (migration never changes tokens)")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(
        description="Disaggregated prefill/decode serving A/B: colocated "
                    "vs disagg-copy vs disagg-share on the bursty Poisson "
                    "workload, with IOMMU-priced remote-DMA KV transfer.",
        epilog="Methodology and CSV columns: benchmarks/README.md; design "
               "notes: ARCHITECTURE.md 'Disaggregated serving'.")
    ap.add_argument("--dry-run", action="store_true",
                    help="minimal sizes (CI smoke path)")
    args = ap.parse_args()
    print("\n".join(run(dry_run=args.dry_run)))
