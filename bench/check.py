"""The output check that decides ``correct``.

Once the window has closed, a sample of the requests the engine finished,
drawn from the seed with the longest among them, is run through the
configuration's plain float32 reference (``bench/references``), once over
each prompt with its served tokens. The number compared is the widest gap
by which a served token's reference logit lies below the reference's best
logit at that position (greedy serving: 0 where the program picked the
reference's argmax). Served outputs must also have the length asked for
(no EOS is set, so every request runs to ``max_tokens``).

The control (``control=True``, not run by the benchmark's own runs) puts
the reference computed in float8, one precision below the served bfloat16,
in the program's place: ``max_logit_gap`` is then the gap of the token the
float8 reference puts first, held to the same limit, so a control run
comes out not correct. The program's own gap is reported beside it as
``program_max_logit_gap``, not compared.
"""
from __future__ import annotations

import importlib
import time
from typing import Dict, List

import numpy as np

from bench.traffic import rng_for


def sample(records, seed: int, tokens: int) -> List:
    """Finished requests: the longest, then others in an order drawn from
    the seed, until ``tokens`` served tokens are covered."""
    done = [r for r in records if r.out is not None and not r.failed]
    if not done:
        return []
    done.sort(key=lambda r: (len(r.req.prompt) + len(r.out), r.req.rid))
    first = done.pop()
    order = rng_for(seed, 99).permutation(len(done))
    out, n = [first], len(first.out)
    for i in order:
        if n >= tokens:
            break
        out.append(done[i])
        n += len(done[i].out)
    return out


def run_check(params, cfgj: dict, records, seed: int,
              control: bool = False) -> Dict[str, dict]:
    """Compared numbers, each ``{"value": v, "limit": l}``, plus readings
    that are not compared (``{"value": v}``). With ``control`` the float8
    control's picks stand in the program's place (module docstring)."""
    ref = importlib.import_module(f"bench.references.{cfgj['reference']}")
    lim = cfgj["check"]
    t0 = time.perf_counter()
    picked = sample(records, seed, lim["sample_tokens"])
    wrong_len = sum(1 for r in records
                    if r.out is not None and len(r.out) != r.req.max_tokens)
    gaps, agree, ctrl = [], [], []
    for r in picked:
        g, a = ref.served_gaps(params, cfgj, r.req.prompt, r.out)
        gaps.append(g)
        agree.append(a)
        if control:
            ctrl.append(ref.served_gaps(params, cfgj, r.req.prompt, r.out,
                                        lowp=True)[0])
    n = int(sum(len(g) for g in gaps))

    def widest(gs):
        return float(max((g.max() for g in gs), default=1e30))
    out = {
        "max_logit_gap": {"value": widest(ctrl if control else gaps),
                          "limit": float(lim["max_logit_gap"])},
        "wrong_lengths": {"value": wrong_len, "limit": 0},
        "sampled_tokens": {"value": n},
        "sampled_requests": {"value": len(picked)},
        "argmax_agree_pct": {"value": 100.0 * float(np.concatenate(agree)
                                                    .mean()) if n else 0.0},
    }
    if control:
        out["program_max_logit_gap"] = {"value": widest(gaps)}
    out["check_s"] = {"value": time.perf_counter() - t0}
    return out


def passed(check: Dict[str, dict]) -> bool:
    return all(v["value"] <= v["limit"] for v in check.values()
               if "limit" in v)
