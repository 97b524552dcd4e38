"""The measured window: sends the plan's requests to the engine and stamps
every output token on the host clock.

The engine is driven one ``step`` at a time. Before each step the loop
sends every request that is due; a request's latency counts from its due
time, not from when the loop got round to sending it, and the loop records
how late it sent each one. A request is due at a time the plan fixed in
advance (an open loop), or when an earlier one finishes: the plan's
``after`` gives the requests that a finished or refused one makes due
(a closed loop's next request, a session's next turn). After each step it
reads how many tokens each request in flight has, and stamps the new ones
with the time the step returned (the served tokens are on the host by
then: the engine's argmax is read back inside the step). When nothing is
in flight it sleeps until the next request is due.

A plan may ask for a warm-up (``warm_s``): the loop then runs that long
before the window opens, at negative window times, so that the window
sees the loop's steady state and not its start. Requests due then are
kept apart (``warm``); their tokens inside the window count.

At the window's close no more requests are sent. The loop then keeps
stepping until every request that was due in the window has its first
token, at most ``drain_s`` seconds: TTFT counts every due request, and one
with no first token by then has failed. Output tokens and inter-token gaps
count only inside the window.
"""
from __future__ import annotations

import contextlib
import heapq
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from bench.traffic import Plan, Req


@dataclass
class Record:
    """What the host saw of one request (times in window seconds)."""
    req: Req
    submit: Optional[float] = None
    times: List[float] = field(default_factory=list)   # one per token
    failed: Optional[str] = None
    erid: Optional[int] = None
    out: Optional[List[int]] = None

    @property
    def first(self) -> Optional[float]:
        return self.times[0] if self.times else None


@dataclass
class StepRec:
    start: float
    end: float
    sched: Optional[object] = None       # what the scheduler composed


@dataclass
class WindowResult:
    seconds: float
    records: List[Record]                # requests due in the window
    steps: List[StepRec]                 # steps that started in the window
    drain_s: float
    warm: List[Record] = field(default_factory=list)   # due before it


class EngineAdapter:
    """Adapter over the engine: the loop needs only these calls."""

    def __init__(self, eng):
        self.eng = eng
        self.finished: Dict[int, object] = {}

    def submit(self, prompt, max_tokens) -> int:
        return self.eng.submit(prompt, max_tokens=max_tokens)

    @property
    def has_work(self) -> bool:
        return self.eng.has_work

    def step(self) -> None:
        self.eng.step(self.finished)

    def progress(self, erid: int) -> int:
        """Tokens generated so far for ``erid``. A preempted request holds
        one token fewer until its resume re-injects it; callers keep the
        highest count seen."""
        fin = self.finished.get(erid)
        if fin is not None:
            return len(fin.out_tokens)
        eng = self.eng
        req = eng.active.get(erid)
        if req is not None:
            return len(req.out_tokens) + len(eng.mgr.seqs[erid].tokens)
        req = eng._waiting_reqs.get(erid)
        return len(req.out_tokens) if req is not None else 0

    def output(self, erid: int) -> Optional[List[int]]:
        fin = self.finished.get(erid)
        return list(fin.out_tokens) if fin is not None else None


def run_window(eng: EngineAdapter, plan: Plan, seconds: float, *,
               drain_s: float = 60.0,
               clock: Callable[[], float] = time.perf_counter,
               sleep: Callable[[float], None] = time.sleep,
               span=lambda name: contextlib.nullcontext(),
               sched_log: Optional[list] = None,
               on_time: Optional[Callable[[float], None]] = None
               ) -> WindowResult:
    """Run the window: the plan's due times drive the loop.
    ``sched_log`` is appended to once per engine step by the harness's
    scheduler hook; ``on_time(now)`` is called between steps (the harness
    starts and stops its trace from it)."""
    records: Dict[int, Record] = {}
    due = [(r.due, r.rid, r) for r in plan.first()]
    heapq.heapify(due)
    inflight: Dict[int, Record] = {}            # erid -> record
    seen: Dict[int, int] = {}
    steps: List[StepRec] = []

    def follow(req, out, t):
        for r in plan.after(req, out, t):
            heapq.heappush(due, (r.due, r.rid, r))
    t0 = clock()

    def at():               # window time: it opens after the warm-up
        return clock() - t0 - plan.warm_s
    now = -plan.warm_s
    closed_at = None

    while True:
        now = at()
        if on_time is not None:
            on_time(now)
        if closed_at is None and now >= seconds:
            closed_at = now
        if closed_at is None:
            sent = False
            while due and due[0][0] <= now:
                r = heapq.heappop(due)[2]
                rec = records[r.rid] = Record(r)
                with span("bench.submit"):
                    try:
                        rec.erid = eng.submit(r.prompt, r.max_tokens)
                    except Exception as e:          # refused: a failure
                        rec.failed = f"{type(e).__name__}: {e}"
                rec.submit = at()
                if rec.erid is not None:
                    inflight[rec.erid] = rec
                    seen[rec.erid] = 0
                else:
                    follow(r, None, rec.submit)
                sent = True
            if sent:
                continue
        else:
            waiting = [r for r in inflight.values() if not r.times]
            if not waiting or now - closed_at >= drain_s:
                break
        if eng.has_work:
            start = now
            with span(f"bench.step#{len(steps)}"):
                eng.step()
            end = at()
            if closed_at is None and start >= 0:
                steps.append(StepRec(start, end,
                                     sched_log[-1] if sched_log else None))
            for erid, rec in list(inflight.items()):
                n = eng.progress(erid)
                if n > seen[erid]:
                    rec.times.extend([end] * (n - seen[erid]))
                    seen[erid] = n
                if erid in eng.finished:
                    del inflight[erid]
                    rec.out = eng.output(erid)
                    follow(rec.req, rec.out, end)
        elif closed_at is None:
            nxt = due[0][0] if due else seconds
            with span("bench.wait"):
                sleep(max(0.0, min(nxt, seconds) - now))
        else:
            break
    for rec in inflight.values():
        if not rec.times:
            rec.failed = rec.failed or "no first token by the end of the drain"
    return WindowResult(
        seconds, [r for r in records.values() if r.req.due >= 0], steps,
        (now - closed_at) if closed_at is not None else 0.0,
        [r for r in records.values() if r.req.due < 0])
