"""Metric readers, one file per metric, found by the metric's name.

``bench/metrics/<name>.py`` defines ``read(ctx) -> float | None``. A reader
that finds nothing to read returns None, and the harness leaves the metric
out of the result line. ``ctx`` is a :class:`Ctx`.
"""
from __future__ import annotations

import importlib.util
import math
import pathlib
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent


@dataclass
class Ctx:
    window: Any                    # loop.WindowResult
    counters: Dict[str, float]     # engine counters, window delta
    work: Any                      # the architecture's counts(c)
    peaks: Any                     # peaks.Peaks (None off a known chip)
    trace: Any = None              # profile.Trace of the traced window
    extra: Dict[str, Any] = field(default_factory=dict)


def reader(name: str, directory: Optional[pathlib.Path] = None) -> Callable:
    path = (directory or HERE) / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def percentile(values: List[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile (numpy's default), None if empty."""
    v = sorted(values)
    if not v:
        return None
    k = (len(v) - 1) * q / 100.0
    f = math.floor(k)
    c = min(f + 1, len(v) - 1)
    return v[f] + (v[c] - v[f]) * (k - f)


def ttfts_ms(w) -> List[float]:
    """Time to first token of every request due in the window, from its due
    time. A request that failed or had no first token by the end of the
    drain counts as waiting until then."""
    end = w.seconds + w.drain_s
    return [((r.first if r.first is not None and not r.failed else end)
             - r.req.due) * 1e3 for r in w.records]


def gaps_ms(w) -> List[float]:
    """Every gap between consecutive output tokens of one request whose later
    token came inside the window."""
    out = []
    for r in w.records + w.warm:
        t = r.times
        out.extend((b - a) * 1e3 for a, b in zip(t, t[1:])
                   if 0 <= b < w.seconds)
    return out


def lateness_ms(w) -> List[float]:
    return [(r.submit - r.req.due) * 1e3 for r in w.records
            if r.submit is not None]
