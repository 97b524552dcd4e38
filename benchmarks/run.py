"""Benchmark driver — one section per paper table/figure + the TPU-level
benches. Prints ``name,value,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only table2,fig5
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: table2,fig2,fig3,fig5,serving,disagg,"
                         "sweep,roofline")
    args = ap.parse_args()
    want = set(args.only.split(",")) if args.only else None

    sections = []
    if want is None or "table2" in want:
        from benchmarks import table2_kernels
        sections.append(("Table II (kernel cycles)", table2_kernels.run))
    if want is None or "fig2" in want:
        from benchmarks import fig2_offload
        sections.append(("Fig. 2 (offload breakdown)", fig2_offload.run))
    if want is None or "fig3" in want:
        from benchmarks import fig3_copy_map
        sections.append(("Fig. 3 (copy/map vs latency)", fig3_copy_map.run))
    if want is None or "fig5" in want:
        from benchmarks import fig5_ptw_llc
        sections.append(("Fig. 5 (PTW +-LLC)", fig5_ptw_llc.run))
    if want is None or "serving" in want:
        from benchmarks import paged_serving
        sections.append(("Paged serving (TPU Fig.2 analogue)",
                         paged_serving.run))
    if want is None or "disagg" in want:
        from benchmarks import disagg_serving
        # smoke sizes inside the driver; full sizes via the standalone CLI
        sections.append(("Disaggregated serving A/B (smoke)",
                         lambda: disagg_serving.run(dry_run=True)))
    if want is None or "sweep" in want:
        from benchmarks import tlb_sweep
        # smoke grid inside the driver; the full grid is the standalone CLI
        sections.append(("TLB/walk-cache design-space sweep (smoke)",
                         lambda: tlb_sweep.run(smoke=True)))
    if want is None or "roofline" in want:
        from benchmarks import roofline
        sections.append(("Roofline (dry-run artifacts)", roofline.run))

    print("name,value,derived")
    failed = []
    for title, fn in sections:
        print(f"# --- {title} ---", flush=True)
        t0 = time.time()
        try:
            for row in fn():
                print(row, flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"{title}.ERROR,0,{e!r}", flush=True)
            failed.append(title)
        print(f"# {title}: {time.time()-t0:.1f}s", flush=True)
    if failed:
        print(f"# {len(failed)} section(s) raised: {', '.join(failed)}",
              file=sys.stderr, flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
