"""The traced window: capture with the JAX profiler, and the reduction
from its trace to events and per-program device times.

A trace is reduced to three lists of ``Ev`` (name, start, end in ns on the
trace's clock): the device's operations (``XLA Ops`` of the first TPU
plane), its programs (``XLA Modules``), and the harness's own host spans
(``bench.step#i``, ``bench.submit``, ``bench.wait``). Programs are found by
the name of the jitted function in their XLA module name
(``jit_<function>(<fingerprint>)``).
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SPAN_PREFIX = "bench."


@dataclass(frozen=True)
class Ev:
    name: str
    start: int
    end: int

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclass
class Trace:
    ops: List[Ev]
    modules: List[Ev]
    spans: List[Ev]

    # ------------------------------------------------------------ io
    @classmethod
    def from_json(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls(*([Ev(n, int(s), int(e)) for n, s, e in d[k]]
                     for k in ("ops", "modules", "spans")))

    # ------------------------------------------------------------ window
    def steps(self) -> Dict[int, Ev]:
        """Step spans by step index."""
        out = {}
        for e in self.spans:
            if e.name.startswith(SPAN_PREFIX + "step#"):
                out[int(e.name.rsplit("#", 1)[1])] = e
        return out

    def window(self) -> Tuple[int, int]:
        """From the first harness span's start to the last one's end."""
        return (min(e.start for e in self.spans),
                max(e.end for e in self.spans))

    def program(self, fn: str) -> List[Ev]:
        """Executions of the jitted function ``fn`` inside the window
        (none where no harness span was recorded)."""
        if not self.spans:
            return []
        lo, hi = self.window()
        pat = re.compile(r"^jit_" + re.escape(fn) + r"\(")
        return [e for e in self.modules
                if pat.match(e.name) and e.start >= lo and e.end <= hi]


def from_profile(path: str) -> Trace:
    """Reduce an ``.xplane.pb`` to a :class:`Trace`."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    device_done = False
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name) and not device_done:
            device_done = True
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [Ev(e.name.split(" = ", 1)[0], int(e.start_ns),
                              int(e.end_ns)) for e in line.events]
                elif line.name == "XLA Modules":
                    modules = [Ev(e.name, int(e.start_ns), int(e.end_ns))
                               for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Ev(e.name, int(e.start_ns), int(e.end_ns))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    for lst in (ops, modules, spans):
        lst.sort(key=lambda e: e.start)
    return Trace(ops, modules, spans)


def newest_xplane(directory: str) -> Optional[str]:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


# ------------------------------------------------------------ reductions

def union(intervals: Iterable[Tuple[int, int]], lo: int, hi: int
          ) -> List[Tuple[int, int]]:
    """Merged intervals clipped to [lo, hi]."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: List[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in merged)


def busy_ns(tr: Trace, lo: int, hi: int) -> int:
    """Nanoseconds in [lo, hi] in which some operation ran on the device."""
    return covered(union(((e.start, e.end) for e in tr.ops), lo, hi))


def step_busy(tr: Trace) -> Tuple[int, int]:
    """(device-busy ns inside step spans, total step-span ns)."""
    merged = union(((e.start, e.end) for e in tr.ops), *tr.window())
    busy = total = 0
    for s in tr.steps().values():
        total += s.dur
        busy += covered([(max(a, s.start), min(b, s.end)) for a, b in merged
                         if b > s.start and a < s.end])
    return busy, total


def idle_gaps(tr: Trace, top: int = 10) -> List[Tuple[str, float]]:
    """Device idle time inside the window, split by the harness span the
    host was in (``step``, ``submit``, ``wait``, or ``none`` where no span
    covers it), then the longest single gaps labelled by the span that
    covers most of each, in seconds."""
    lo, hi = tr.window()
    merged = union(((e.start, e.end) for e in tr.ops), lo, hi)
    gaps, prev = [], lo
    for s, e in merged:
        if s > prev:
            gaps.append((prev, s))
        prev = e
    if hi > prev:
        gaps.append((prev, hi))
    spans = sorted(tr.spans, key=lambda e: e.start)
    by: Dict[str, float] = {}
    single = []
    for a, b in gaps:
        parts: Dict[str, int] = {}
        for sp in spans:
            if sp.start >= b:
                break
            ov = min(b, sp.end) - max(a, sp.start)
            if ov > 0:
                lab = sp.name[len(SPAN_PREFIX):].split("#")[0]
                parts[lab] = parts.get(lab, 0) + ov
        rest = (b - a) - sum(parts.values())
        if rest > 0:
            parts["none"] = parts.get("none", 0) + rest
        for lab, ns in parts.items():
            by[lab] = by.get(lab, 0.0) + ns / 1e9
        single.append((f"longest gap, in {max(parts, key=parts.get)}",
                       (b - a) / 1e9))
    out = [(f"idle in {n}", v) for n, v in
           sorted(by.items(), key=lambda x: -x[1])]
    out += sorted(single, key=lambda x: -x[1])[:max(0, top - len(out))]
    return out[:top]


def top_ops(tr: Trace, top: int = 10) -> List[Tuple[str, float]]:
    """Device time in the window by program and operation, in seconds. An
    operation that encloses others (a ``while`` loop around a scanned
    layer's operations) is left out: its time is its children's."""
    lo, hi = tr.window()
    mods = [m for m in tr.modules if m.end > lo and m.start < hi]
    tot: Dict[str, float] = {}
    j = 0
    for k, op in enumerate(tr.ops):
        if op.end <= lo or op.start >= hi:
            continue
        nxt = tr.ops[k + 1] if k + 1 < len(tr.ops) else None
        if nxt is not None and nxt.start < op.end and nxt.end <= op.end:
            continue
        while j < len(mods) and mods[j].end <= op.start:
            j += 1
        prog = "?"
        if j < len(mods) and mods[j].start <= op.start:
            prog = re.sub(r"\(\d+\)$", "", mods[j].name)
        key = f"{prog}/{op.name.lstrip('%')}"
        tot[key] = tot.get(key, 0.0) + op.dur / 1e9
    return sorted(tot.items(), key=lambda x: -x[1])[:top]


def step_of(tr: Trace, ev: Ev) -> Optional[int]:
    """Index of the step span during which ``ev`` started on the device."""
    for i, s in tr.steps().items():
        if s.start <= ev.start < s.end:
            return i
    return None
