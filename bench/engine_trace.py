"""The engine's own spans and the device operations' scopes in a traced
window, and the reductions that read them.

The engine (``core/serving/engine.py``) opens ``engine.*`` host spans
inside each step (``jax.profiler.TraceAnnotation``, with a few integer
arguments), and the model's device code runs under ``jax.named_scope``
(``attention``, ``kv_write``, ``mlp``, ``lm_head``). The spans land in the
profiler's trace on the clock of the device's events. A device operation's
event carries no scope: its scope path is the ``op_name`` metadata of its
instruction in the compiled program, whose HLO the profiler stores in the
recording's ``/host:metadata`` plane (read by :func:`hlo_op_names`).
:func:`from_profile` reduces an ``.xplane.pb`` to an :class:`EngineTrace`:
the :class:`bench.profile.Trace` of the same recording plus the engine
spans, with each device operation's scope path. A program that writes no
engine spans and opens no scopes gives no spans and no scope on a path,
and each reader then finds nothing.

  python3 bench/engine_trace.py [xplane.pb or directory]

prints the reductions of a recording as JSON (by default the newest under
``bench/.traces``, which a ``--trace 1`` run leaves behind).
"""
from __future__ import annotations

import gzip
import json
import pathlib
import sys
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench import profile  # noqa: E402
from bench.profile import SPAN_PREFIX, Ev, Trace, newest_xplane, union  # noqa: E402

ENGINE_PREFIX = "engine."
METADATA_PLANE = "/host:metadata"
SCOPES = ("attention", "kv_write", "mlp", "lm_head")
DECODE = "_decode_masked"


@dataclass(frozen=True)
class Event(Ev):
    scope: str = ""                          # device op: named-scope path
    args: Tuple[Tuple[str, int], ...] = ()   # engine span: its arguments

    def arg(self, name: str, default: int = 0) -> int:
        return dict(self.args).get(name, default)


@dataclass
class EngineTrace(Trace):
    engine: List[Event] = field(default_factory=list)

    KEYS = ("ops", "modules", "spans", "engine")

    @classmethod
    def from_json(cls, path: str) -> "EngineTrace":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        ops = [Event(n, s, e, *sc) for n, s, e, *sc in d["ops"]]
        eng = [Event(n, s, e, args=tuple(sorted(a.items())))
               for n, s, e, a in d["engine"]]
        return cls(ops, *([Ev(n, s, e) for n, s, e in d[k]]
                          for k in ("modules", "spans")), eng)

    def to_json(self, path: str, lo: int, hi: int) -> None:
        """Write the events that lie inside [lo, hi]: a recorded fixture."""
        def inside(evs):
            return [e for e in evs if e.start >= lo and e.end <= hi]
        d = {"ops": [[e.name, e.start, e.end] + ([e.scope] if e.scope else [])
                     for e in inside(self.ops)],
             "modules": _keys(inside(self.modules)),
             "spans": _keys(inside(self.spans)),
             "engine": [[e.name, e.start, e.end, dict(e.args)]
                        for e in inside(self.engine)]}
        with gzip.open(path, "wt") as f:
            json.dump(d, f, separators=(",", ":"))


def from_profile(path: str, base: Optional[Trace] = None) -> EngineTrace:
    """Reduce an ``.xplane.pb``: ``base``, the reduction of the same
    recording by :func:`bench.profile.from_profile` (made here if not
    given), with each device operation's scope path and the engine's spans.
    A ``base`` whose harness spans are not the recording's gets neither."""
    from jax.profiler import ProfileData
    base = base or profile.from_profile(path)
    harness, engine = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    harness.append((e.name, int(e.start_ns), int(e.end_ns)))
                elif e.name.startswith(ENGINE_PREFIX):
                    args = tuple(sorted((k, int(v)) for k, v in e.stats
                                        if isinstance(v, int)))
                    engine.append(Event(e.name, int(e.start_ns),
                                        int(e.end_ns), args=args))
    if sorted(harness, key=lambda x: x[1]) != _keys(base.spans):
        return EngineTrace(base.ops, base.modules, base.spans)
    engine.sort(key=lambda e: e.start)
    names = hlo_op_names(path)
    ops, mods, j = [], base.modules, 0
    for op in base.ops:
        while j < len(mods) and mods[j].end <= op.start:
            j += 1
        mod = mods[j].name if j < len(mods) and mods[j].start <= op.start \
            else ""
        ops.append(Event(op.name, op.start, op.end,
                         names.get(mod, {}).get(op.name.lstrip("%"), "")))
    return EngineTrace(ops, base.modules, base.spans, engine)


def of(ctx, directory: Optional[str] = None) -> Optional[EngineTrace]:
    """The :class:`EngineTrace` of a reader's traced window, or None in an
    untraced run. ``ctx.trace`` is the harness's reduction of the newest
    recording under ``directory`` (``bench/.traces``); that recording is
    read again here for the engine spans and the scopes, once per run."""
    tr = ctx.trace
    if tr is None or isinstance(tr, EngineTrace):
        return tr
    if "engine_trace" not in ctx.extra:
        if directory is None:
            from bench.harness import TRACE_DIR
            directory = str(TRACE_DIR)
        path = newest_xplane(directory)
        ctx.extra["engine_trace"] = from_profile(path, tr) if path else \
            EngineTrace(tr.ops, tr.modules, tr.spans)
    return ctx.extra["engine_trace"]


def _keys(evs: List[Ev]) -> List[Tuple[str, int, int]]:
    return [(e.name, e.start, e.end) for e in evs]


# ------------------------------------------------------------ HLO metadata

def _varint(buf, i: int) -> Tuple[int, int]:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return v, i


def _fields(buf, span: Tuple[int, int]):
    """(field number, value) of the protobuf message in ``buf[span]``: an
    int for a varint, a (start, end) span for a length-delimited field;
    fixed-width fields are skipped."""
    i, hi = span
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _sub(buf, span, number: int):
    return [v for f, v in _fields(buf, span) if f == number]


def hlo_op_names(path: str) -> Dict[str, Dict[str, str]]:
    """Program (as ``XLA Modules`` names it) -> instruction -> ``op_name``
    metadata, from the HLO protos in the recording's ``/host:metadata``
    plane. Field numbers: ``XSpace.planes`` 1; ``XPlane`` name 2,
    event_metadata 4 (entry value 2); ``XEventMetadata`` name 2, stats 5;
    ``XStat`` bytes_value 6; ``HloProto.hlo_module`` 1;
    ``HloModuleProto.computations`` 3; ``HloComputationProto.instructions``
    2; ``HloInstructionProto`` name 1, metadata 7; ``OpMetadata.op_name``
    2."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())

    def text(span):
        return bytes(buf[span[0]:span[1]]).decode()
    out: Dict[str, Dict[str, str]] = {}
    for plane in _sub(buf, (0, len(buf)), 1):
        if [text(s) for s in _sub(buf, plane, 2)] != [METADATA_PLANE]:
            continue
        for entry in _sub(buf, plane, 4):
            for meta in _sub(buf, entry, 2):
                names = out.setdefault(
                    "".join(text(s) for s in _sub(buf, meta, 2)), {})
                for stat in _sub(buf, meta, 5):
                    for proto in _sub(buf, stat, 6):
                        for mod in _sub(buf, proto, 1):
                            for comp in _sub(buf, mod, 3):
                                for ins in _sub(buf, comp, 2):
                                    name = op = ""
                                    for f, v in _fields(buf, ins):
                                        if f == 1:
                                            name = text(v)
                                        elif f == 7:
                                            op = "".join(text(s) for s in
                                                         _sub(buf, v, 2))
                                    if op:
                                        names[name] = op
    return out


# ------------------------------------------------------------ reductions

def innermost(spans: Iterable[Ev], label) -> List[Tuple[int, int, str]]:
    """The time nested spans cover, cut into disjoint pieces, each labelled
    ``label(span)`` of the innermost span over it (of the spans over an
    instant, the one that started last, or of those the one that ends
    first)."""
    spans = sorted(spans, key=lambda e: e.start)
    cuts = sorted({t for e in spans for t in (e.start, e.end)})
    out: List[Tuple[int, int, str]] = []
    active: List[Ev] = []
    j = 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(spans) and spans[j].start <= a:
            active.append(spans[j])
            j += 1
        active = [e for e in active if e.end > a]
        if not active:
            continue
        lab = label(max(active, key=lambda e: (e.start, -e.end)))
        if out and out[-1][1] == a and out[-1][2] == lab:
            out[-1] = (out[-1][0], b, lab)
        else:
            out.append((a, b, lab))
    return out


def host_pieces(tr: EngineTrace) -> List[Tuple[int, int, str]]:
    """What the host was in, as disjoint labelled pieces: the harness span
    (``step``, ``submit``, ``wait``) and, inside a step, the innermost
    engine span (``step/engine.decode.commit``). ``step`` alone is step
    time that no engine span covers."""
    harness = innermost(tr.spans,
                        lambda e: e.name[len(SPAN_PREFIX):].split("#")[0])
    engine = innermost(tr.engine, lambda e: e.name)
    out, j = [], 0
    for a, b, lab in harness:
        if lab != "step":
            out.append((a, b, lab))
            continue
        while j < len(engine) and engine[j][1] <= a:
            j += 1
        t, k = a, j
        while k < len(engine) and engine[k][0] < b:
            s, e = max(engine[k][0], a), min(engine[k][1], b)
            if s > t:
                out.append((t, s, lab))
            out.append((s, e, f"{lab}/{engine[k][2]}"))
            t = e
            k += 1
        if t < b:
            out.append((t, b, lab))
    return out


def idle_gaps(tr: EngineTrace, top: int = 10) -> List[Tuple[str, float]]:
    """Device idle time inside the window, split by :func:`host_pieces`
    (``none`` where no span covers it), then the ``top`` longest single
    gaps labelled by the piece that covers most of each, in seconds. On a
    trace with no engine spans the split is
    :func:`bench.profile.idle_gaps`'s."""
    lo, hi = tr.window()
    merged = union(((e.start, e.end) for e in tr.ops), lo, hi)
    gaps, prev = [], lo
    for s, e in merged:
        if s > prev:
            gaps.append((prev, s))
        prev = e
    if hi > prev:
        gaps.append((prev, hi))
    pieces = host_pieces(tr)
    by: Dict[str, float] = {}
    single = []
    j = 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        parts: Dict[str, int] = {}
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            ov = min(b, pieces[k][1]) - max(a, pieces[k][0])
            if ov > 0:
                parts[pieces[k][2]] = parts.get(pieces[k][2], 0) + ov
            k += 1
        rest = (b - a) - sum(parts.values())
        if rest > 0:
            parts["none"] = parts.get("none", 0) + rest
        for lab, ns in parts.items():
            by[lab] = by.get(lab, 0.0) + ns / 1e9
        single.append((f"longest gap, in {max(parts, key=parts.get)}",
                       (b - a) / 1e9))
    out = [(f"idle in {n}", v) for n, v in
           sorted(by.items(), key=lambda x: -x[1])]
    return out + sorted(single, key=lambda x: -x[1])[:top]


def program_ops(tr: Trace, fn: str) -> List[Ev]:
    """The operations that ran inside the executions of ``fn`` in the
    window, leaving out one that encloses the next (a ``while`` loop around
    a scanned layer's operations: its time is its children's), as
    :func:`bench.profile.top_ops` does."""
    runs = tr.program(fn)
    ops = tr.ops
    out, j = [], 0
    for k, op in enumerate(ops):
        while j < len(runs) and runs[j].end <= op.start:
            j += 1
        if j == len(runs):
            break
        nxt = ops[k + 1] if k + 1 < len(ops) else None
        if nxt is not None and nxt.start < op.end and nxt.end <= op.end:
            continue
        if runs[j].start <= op.start:
            out.append(op)
    return out


def scope_of(op: Ev) -> Optional[str]:
    """The innermost of ``SCOPES`` on the op's scope path, if any."""
    hit = [p for p in getattr(op, "scope", "").split("/") if p in SCOPES]
    return hit[-1] if hit else None


def by_scope(tr: Trace, fn: str = DECODE) -> Dict[str, float]:
    """Device seconds of ``fn``'s operations in the window by model scope,
    with ``unscoped`` for the rest: the compiler's own copies show there."""
    out = {s: 0.0 for s in SCOPES + ("unscoped",)}
    for op in program_ops(tr, fn):
        out[scope_of(op) or "unscoped"] += op.dur / 1e9
    return out


def engine_steps(tr: EngineTrace) -> List[Dict[str, List[Event]]]:
    """For each ``engine.step`` span, the engine spans inside it by name."""
    out: List[Dict[str, List[Event]]] = []
    steps = [e for e in tr.engine if e.name == ENGINE_PREFIX + "step"]
    j = 0
    for st in steps:
        parts: Dict[str, List[Event]] = {}
        while j < len(tr.engine) and tr.engine[j].start < st.start:
            j += 1
        k = j
        while k < len(tr.engine) and tr.engine[k].start < st.end:
            e = tr.engine[k]
            if e is not st and e.end <= st.end:
                parts.setdefault(e.name, []).append(e)
            k += 1
        out.append(parts)
    return out


def summed_ms(steps: List[Dict[str, List[Event]]], names) -> float:
    return sum(e.dur for s in steps for n in names for e in s.get(n, ())) / 1e6


def in_step_idle(gaps: List[Tuple[str, float]]) -> Dict[str, float]:
    """Of the idle time inside steps: the seconds in all, those under an
    engine span, and those under a span below ``engine.step``."""
    step = {n[len("idle in "):]: v for n, v in gaps
            if n == "idle in step" or n.startswith("idle in step/")}
    total = sum(step.values())
    engine = sum(v for n, v in step.items() if n != "step")
    inner = engine - step.get("step/" + ENGINE_PREFIX + "step", 0.0)
    return {"idle_in_step_s": total, "under_engine_spans_s": engine,
            "under_inner_spans_s": inner}


def main(argv: List[str]) -> int:
    target = argv[0] if argv else str(pathlib.Path(__file__).resolve().parent
                                      / ".traces")
    path = target if target.endswith(".pb") else newest_xplane(target)
    if path is None:
        raise SystemExit(f"no .xplane.pb under {target}")
    tr = from_profile(path)
    gaps = idle_gaps(tr)
    print(json.dumps({"xplane": path, "steps": len(tr.steps()),
                      "engine_spans": len(tr.engine),
                      "scoped_ops": sum(1 for e in tr.ops if e.scope),
                      "idle_gaps": gaps, **in_step_idle(gaps),
                      "decode_by_scope": by_scope(tr)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
