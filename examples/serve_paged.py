"""Paged serving example: the continuous-batching engine with zero-copy
admission, copy-on-write prefix sharing, SVA/TLB statistics, and the
adaptive translation front-end (IOTLB prefetching + online TLB-geometry
auto-tuning).

Most requests open with the same system prompt, so admission maps the
already-resident prefix pages (refcount++) and prefills only each prompt's
suffix; exact-duplicate prompts also share the partial tail page and
CoW-duplicate it on their first divergent token.

  PYTHONPATH=src python examples/serve_paged.py
  PYTHONPATH=src python examples/serve_paged.py --tlb-prefetch stream \
      --tlb-autotune 4

The requests arrive as two bursty waves over an OVERSUBSCRIBED page pool
(``--pool-pages``, 16 by default): admission is lazy (prompt pages only),
prompts prefill in chunks inside mixed decode steps, and pool pressure is
resolved by preempting the newest sequence (its KV goes warm into the
prefix cache; resume re-matches it) — watch the preemptions / resumes /
steps-to-first-token lines.

``--disagg share`` (or ``copy``) splits the same demo across a prefill
worker and a decode worker connected by IOMMU-priced KV transfers:
finished prefills migrate to the decode worker's slots, zero-copy (page
re-attachment under the decode ASID) or staged (device-side batched page
copy) — watch the transfer line for bytes moved and remote-DMA PTW
cycles. Outputs are bit-identical to the colocated engines either way.
"""
import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import get_config, reduce_for_smoke
from repro.core.serving.disagg import DisaggEngine
from repro.core.serving.engine import ServingEngine
from repro.core.sva.iommu import IOMMU, Sv39Walk, TLBConfig
from repro.models import init_params

ap = argparse.ArgumentParser(
    description="Paged serving demo over the SVA/IOMMU stack. The serving "
                "TLB's static geometry comes from ModelConfig.serve_tlb_"
                "{entries,ways,policy}; the flags below arm the ADAPTIVE "
                "front-end on top of it.",
    epilog="Geometry/policy methodology and the static-vs-adaptive "
           "benchmark contract are documented in benchmarks/README.md "
           "(see benchmarks/tlb_sweep.py for the full design-space sweep "
           "and paged_serving.py --translation-report for modeled PTW "
           "overhead).")
ap.add_argument("--tlb-prefetch", default="none",
                choices=("none", "next_page", "stream"),
                help="IOTLB prefetch policy on the decode gather stream "
                     "(ModelConfig.serve_tlb_prefetch_policy)")
ap.add_argument("--tlb-prefetch-degree", type=int, default=2,
                help="prefetch fills issued per trigger")
ap.add_argument("--tlb-prefetch-distance", type=int, default=4,
                help="stream run-ahead distance in pages")
ap.add_argument("--tlb-autotune", type=int, default=0, metavar="STEPS",
                help="auto-tune the serving TLB geometry online with this "
                     "measurement window in decode steps "
                     "(ModelConfig.serve_tlb_autotune; 0 = off)")
ap.add_argument("--tlb-ranges", type=int, default=0, metavar="N",
                help="range-coalesced IOTLB entries: one entry covers a "
                     "physically contiguous run of up to N pages "
                     "(ModelConfig.serve_tlb_ranges; 0 = per-page, else "
                     ">= 2 — watch the range: block in the IOMMU stats)")
ap.add_argument("--disagg", default="off",
                choices=("off", "copy", "share"),
                help="disaggregate into a 2-slot prefill worker + 2-slot "
                     "decode worker; finished prefills hand their KV off "
                     "by IOMMU-priced migration (share = zero-copy page "
                     "re-attachment, copy = staged payload)")
ap.add_argument("--pool-pages", type=int, default=16,
                help="physical KV page pool size (0 = full n_slots*pages "
                     "reservation; the default oversubscribes the pool so "
                     "preemption fires)")
args = ap.parse_args()

cfg = reduce_for_smoke(get_config("qwen2-7b"))
cfg = dataclasses.replace(
    cfg,
    serve_tlb_prefetch_policy=args.tlb_prefetch,
    serve_tlb_prefetch_degree=args.tlb_prefetch_degree,
    serve_tlb_prefetch_distance=args.tlb_prefetch_distance,
    serve_tlb_autotune=args.tlb_autotune,
    serve_tlb_ranges=args.tlb_ranges,
    # Small-TLB demo geometry when auto-tuning, so the ladder has room to
    # differentiate within a short example run.
    serve_tlb_entries=64 if args.tlb_autotune else cfg.serve_tlb_entries)
params = init_params(cfg, jax.random.key(0))
pool_pages = args.pool_pages or None
if args.disagg != "off":
    # Prefill/decode disaggregation at the same total width; the transfer
    # fabric prices each hand-off as the paper's 4-entry IOTLB over a
    # no-LLC Sv39 walk (remote DMA by virtual address).
    eng = DisaggEngine(cfg, params, n_prefill_slots=2, n_decode_slots=2,
                       max_len=128, page_size=8, offload_mode="zero_copy",
                       disagg_mode=args.disagg,
                       xfer_iommu=IOMMU(walk_model=Sv39Walk(llc=False),
                                        tlb=TLBConfig(4, "lru")),
                       pool_pages=pool_pages, translation_stats=True)
else:
    eng = ServingEngine(cfg, params, n_slots=4, max_len=128, page_size=8,
                        offload_mode="zero_copy", pool_pages=pool_pages,
                        translation_stats=True)  # live IOTLB hit/miss counts

rng = np.random.default_rng(0)
system = rng.integers(0, cfg.vocab_size, size=16).tolist()  # shared prefix
prompts = [system + rng.integers(0, cfg.vocab_size,
                                 size=rng.integers(2, 8)).tolist()
           for _ in range(7)]
prompts.append(list(prompts[1]))                 # exact duplicate
prompts += [rng.integers(0, cfg.vocab_size, size=12).tolist()
            for _ in range(2)]                   # unrelated
workers = (f"a 2-slot prefill worker + 2-slot decode worker "
           f"({args.disagg}-mode KV transfer) and " if args.disagg != "off"
           else "4 slots and ")
print(f"two bursty arrival waves of 10 requests (8 share a system prompt, "
      f"2 are exact duplicates) over {workers}a "
      f"{eng.mgr.pool.n_pages}-page pool (lazy admission, chunked prefill, "
      "preempt/resume under pressure)...")
finished = {}
# Decode growth (one page per 8 tokens per sequence) is what
# oversubscribes the pool.
rids = [eng.submit(p, max_tokens=24) for p in prompts[:6]]
for _ in range(3):                               # burst 2 lands mid-serve
    eng.step(finished)
rids += [eng.submit(p, max_tokens=24) for p in prompts[6:]]
done = {**finished, **eng.run()}
for rid in rids[:4]:
    r = done[rid]
    print(f"  req {rid}: ttft {(r.first_token_at-r.submitted_at)*1e3:6.0f}ms "
          f"-> {r.out_tokens}")
s = eng.stats()
print(f"\n{s['tokens']} tokens, {s['decode_steps']} decode steps, "
      f"{s['prefills']} prefills")
print(f"SVA: {s['sva']}")
print(f"TLB: {s['tlb']}")
print(f"IOMMU: {s['iommu']}  (unified front-end; the simulator's 4-entry "
      "IOTLB is the same class)")
if "range" in s["iommu"]:
    rg = s["iommu"]["range"]
    print(f"range entries (<= {rg['max_run']} pages each): "
          f"fills={rg['fills']} hits={rg['hits']} "
          f"coalesced_pages={rg['coalesced_pages']} splits={rg['splits']} "
          f"resident={rg['n_ranges']}; contiguity-hinted allocs: "
          f"run_allocs={s['pool_run_allocs']} "
          f"fallbacks={s['pool_run_fallbacks']}")
if "autotune" in s["iommu"]:
    at = s["iommu"]["autotune"]
    print(f"auto-tuner: phase={at['phase']} switches={at['switches']} "
          f"windows={at['windows']} -> current geometry "
          f"e{s['iommu']['tlb_entries']}.w{s['iommu']['tlb_ways']}."
          f"{s['iommu']['tlb_policy']} (explored: {at['explored']})")
sc = s["sched"]
ttft = [done[r].first_token_step - done[r].submitted_step for r in rids]
print(f"scheduler: preemptions={sc['preemptions']} "
      f"resumes={sc['resumes']}; steps-to-first-token "
      f"mean={np.mean(ttft):.1f} max={max(ttft)}")
if args.disagg != "off":
    t = s["transfer"]
    print(f"transfers: {t['transfers']} ({args.disagg}): "
          f"pages shared={t['pages_shared']} copied={t['pages_copied']}, "
          f"payload {t['payload_bytes']}B + table {t['table_bytes']}B, "
          f"remote-DMA PTW {t['ptw_cycles']:.0f} cycles "
          f"(deferred={s['disagg']['deferred']} "
          f"cancelled={s['disagg']['cancelled']})")
print(f"prefix cache: {s['prefix']}")
print(f"prefill tokens saved: {s['prefill_tokens_saved']} "
      f"(shared admissions: {s['shared_admissions']}); "
      f"CoW page copies: {s['cow_page_copies']}")
print(f"pages used/free: {s['pool_used']}/{s['pool_free']} "
      f"(warm prefix cache retains pages after completion)")
