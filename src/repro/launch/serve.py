"""Serving launcher: continuous-batching engine over the paged SVA layer.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --requests 8 --max-tokens 12 --offload-mode zero_copy

``--layers N`` keeps N repetitions of the block pattern at published widths
(the depth cut one chip needs for a model whose weights exceed its memory).
``chip_smoke.py`` builds its engine through :func:`build_engine` below.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Optional

import jax
import numpy as np

from repro.configs import ModelConfig, get_config, reduce_for_smoke, with_blocks
from repro.core.serving.engine import ServingEngine
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params


def build_config(arch: str, *, smoke: bool = False,
                 layers: int = 0) -> ModelConfig:
    cfg = get_config(arch)
    if smoke:
        cfg = reduce_for_smoke(cfg)
    if layers:
        cfg = with_blocks(cfg, layers)
    return cfg


def build_engine(cfg: ModelConfig, params, *, slots: int, max_len: int,
                 page_size: int, offload_mode: str = "zero_copy",
                 pool_pages: Optional[int] = None,
                 translation_stats: bool = False) -> ServingEngine:
    """The served engine. It runs the continuous scheduler over the global
    zero-copy pool when the model keeps all its KV there, and the
    fixed-wave loop otherwise (``offload_mode="copy"`` included)."""
    return ServingEngine(cfg, params, n_slots=slots, max_len=max_len,
                         page_size=page_size, offload_mode=offload_mode,
                         pool_pages=pool_pages,
                         translation_stats=translation_stats)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=0,
                    help="block-pattern repetitions to keep (0 = all)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-tokens", type=int, default=12)
    ap.add_argument("--offload-mode", default="zero_copy",
                    choices=["zero_copy", "copy"])
    ap.add_argument("--translation-stats", action="store_true",
                    help="run decode-step page gathers through the IOMMU "
                         "for live IOTLB hit/miss stats (host-side sweep: "
                         "adds per-step overhead, off by default)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = build_config(args.arch, smoke=args.smoke, layers=args.layers)
    params = init_params(cfg, jax.random.key(args.seed))
    eng = build_engine(cfg, params, slots=args.slots, max_len=args.max_len,
                       page_size=args.page_size,
                       offload_mode=args.offload_mode,
                       translation_stats=args.translation_stats)
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    rids = [eng.submit(rng.integers(0, cfg.vocab_size,
                                    size=args.prompt_len).tolist(),
                       max_tokens=args.max_tokens)
            for _ in range(args.requests)]
    done = eng.run()
    wall = time.time() - t0
    for rid in rids:
        r = done[rid]
        ttft = (r.first_token_at - r.submitted_at) * 1e3
        print(f"req {rid}: ttft={ttft:.0f}ms tokens={r.out_tokens[:8]}...")
    s = eng.stats()
    toks = s["tokens"]
    dev = jax.devices()[0]
    print(f"\n{toks} tokens in {wall:.2f}s ({toks/wall:.1f} tok/s, host "
          f"clock incl. compiles, on {dev.platform}:{dev.device_kind}) "
          f"mode={args.offload_mode} scheduler={eng.scheduler_mode}")
    print(json.dumps({k: v for k, v in s.items()
                      if k in ("prefills", "decode_steps", "staging_copies",
                               "sva", "tlb", "iommu", "svasan")}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
