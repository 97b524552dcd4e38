"""Window accounting with a fake engine and a fake clock."""
import pytest

from bench import metrics as M
from bench.loop import run_window
from bench.traffic import ClosedPlan, Plan, Req, Session, SessionsPlan


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class FakeEngine:
    """Each step takes ``step_s``; a request's first token comes after
    ``prefill_steps`` steps, then one token per step until ``max_tokens``.
    Requests whose prompt starts with -1 are refused at submit."""

    def __init__(self, clock, step_s=0.1, prefill_steps=2, slots=8):
        self.clock, self.step_s = clock, step_s
        self.prefill_steps, self.slots = prefill_steps, slots
        self.reqs, self.finished, self.age = {}, {}, {}
        self.next = 0

    def submit(self, prompt, max_tokens):
        if prompt and prompt[0] == -1:
            raise ValueError("refused")
        rid = self.next
        self.next += 1
        self.reqs[rid] = [prompt, max_tokens, 0]
        self.age[rid] = 0
        return rid

    @property
    def has_work(self):
        return bool(self.reqs)

    def step(self):
        self.clock.t += self.step_s
        for rid in list(self.reqs)[:self.slots]:
            r = self.reqs[rid]
            self.age[rid] += 1
            if self.age[rid] >= self.prefill_steps:
                r[2] += 1
            if r[2] == r[1]:
                self.finished[rid] = type("F", (), {"out_tokens":
                                                     [7] * r[1]})()
                del self.reqs[rid]

    def progress(self, rid):
        if rid in self.finished:
            return len(self.finished[rid].out_tokens)
        return self.reqs[rid][2]

    def output(self, rid):
        f = self.finished.get(rid)
        return list(f.out_tokens) if f else None


def plan_of(reqs):
    return Plan(reqs)


def test_ttft_from_due_time_and_all_gaps():
    clk = Clock()
    d = FakeEngine(clk)
    reqs = [Req(0, [1, 2], 3, 0.0), Req(1, [1], 2, 0.05)]
    w = run_window(d, plan_of(reqs), 1.0, clock=clk, sleep=clk.sleep)
    r0, r1 = sorted(w.records, key=lambda r: r.req.rid)
    # r0: sent at 0, tokens after steps 2, 3, 4 -> t = 0.2, 0.3, 0.4
    assert r0.times == pytest.approx([0.2, 0.3, 0.4])
    # r1 due at 0.05, sent after the first step (0.1): its TTFT still
    # counts from 0.05; tokens after steps 3 and 4 of the loop
    assert r1.submit == pytest.approx(0.1)
    assert r1.times == pytest.approx([0.3, 0.4])
    assert M.ttfts_ms(w) == pytest.approx([200.0, 250.0])
    assert sorted(M.gaps_ms(w)) == pytest.approx([100.0, 100.0, 100.0])
    assert M.lateness_ms(w) == pytest.approx([0.0, 50.0])
    assert w.drain_s == 0.0 and len(w.steps) == 4


def test_idle_engine_sleeps_until_due_and_window_counts_only_inside():
    clk = Clock()
    d = FakeEngine(clk)
    reqs = [Req(0, [1], 20, 0.5)]
    w = run_window(d, plan_of(reqs), 1.0, clock=clk, sleep=clk.sleep)
    (r,) = w.records
    assert r.submit == pytest.approx(0.5)
    # the drain only waits for first tokens; it stops at once here
    assert r.first == pytest.approx(0.7) and w.drain_s == pytest.approx(0.0)
    inside = [t for t in r.times if t < 1.0]
    ctx = M.Ctx(window=w, counters={}, work=None, peaks=None)
    assert M.reader("output_tok_s")(ctx) == pytest.approx(len(inside))
    assert len(M.gaps_ms(w)) == len(inside) - 1


def test_drain_and_failures():
    clk = Clock()
    d = FakeEngine(clk, prefill_steps=50)
    reqs = [Req(0, [-1], 3, 0.0), Req(1, [1], 3, 0.2)]
    w = run_window(d, plan_of(reqs), 1.0, clock=clk, sleep=clk.sleep,
                   drain_s=2.0)
    bad, slow = sorted(w.records, key=lambda r: r.req.rid)
    assert bad.failed.startswith("ValueError")
    # the slow one gets its first token 5.0 s after it was sent: past the
    # 2 s drain, so it failed and its TTFT is counted to the drain's end
    assert slow.failed and not slow.times
    assert w.drain_s == pytest.approx(2.0, abs=0.11)
    end = 1.0 + w.drain_s
    assert M.ttfts_ms(w) == pytest.approx([end * 1e3, (end - 0.2) * 1e3])


def test_requests_sent_in_due_order_whatever_the_plan_order():
    clk = Clock()
    d = FakeEngine(clk, prefill_steps=1)
    reqs = [Req(0, [1], 2, 0.3), Req(1, [1], 2, 0.0), Req(2, [1], 2, 0.1)]
    w = run_window(d, plan_of(reqs), 1.0, clock=clk, sleep=clk.sleep)
    sent = sorted(w.records, key=lambda r: r.submit)
    assert [r.req.rid for r in sent] == [1, 2, 0]
    assert all(r.failed is None and r.out == [7, 7] for r in w.records)


def test_counters_and_steps_feed_layer_metrics():
    clk = Clock()
    d = FakeEngine(clk)
    log = []
    orig = d.step

    def step():
        log.append({"contexts": [5], "chunks": [], "admitted_tokens": 2})
        orig()
    d.step = step
    w = run_window(d, plan_of([Req(0, [1, 2], 3, 0.0)]), 1.0, clock=clk,
                   sleep=clk.sleep, sched_log=log)
    assert len(w.steps) == 4 and all(s.sched for s in w.steps)
    ctx = M.Ctx(window=w, counters={"admit_s": 0.002}, work=None, peaks=None,
                extra={"prompt_tokens_admitted": 8})
    assert M.reader("sched.host_ms_per_step")(ctx) == pytest.approx(0.5)
    assert M.reader("decode_roofline")(ctx) is None      # no trace


def test_closed_loop_keeps_clients_in_flight():
    """Each finished request makes the next one due at once: the engine
    holds exactly ``clients`` requests after every step, and a request's
    lateness is counted from the moment its client was free."""
    clk = Clock()
    d = FakeEngine(clk, slots=2)
    reqs = [Req(i, [1], 2 + i % 3, 0.0) for i in range(40)]
    plan = ClosedPlan(reqs, clients=3)
    held = []
    orig = d.step

    def step():
        if clk.t - 100.0 < 2.0:              # in the window, not the drain
            held.append(len(d.reqs))
        orig()
    d.step = step
    w = run_window(d, plan, 2.0, clock=clk, sleep=clk.sleep)
    assert len(held) == len(w.steps) and set(held) == {3}
    rs = sorted(w.records, key=lambda r: r.req.rid)
    assert [r.req.rid for r in rs] == list(range(len(rs)))
    assert 3 < len(rs) < 40
    assert all(r.failed is None for r in rs)
    # a request is due when the one it follows finished, and sent then
    finished = sorted(r.times[-1] for r in rs if r.out is not None)
    assert [r.req.due for r in rs[3:]] == pytest.approx(
        finished[:len(rs) - 3])
    assert M.lateness_ms(w) == pytest.approx([0.0] * len(rs))


def test_closed_loop_refused_request_frees_its_client():
    clk = Clock()
    d = FakeEngine(clk)
    reqs = [Req(0, [-1], 2, 0.0)] + [Req(i, [1], 2, 0.0) for i in (1, 2)]
    w = run_window(d, ClosedPlan(reqs, clients=1), 1.0, clock=clk,
                   sleep=clk.sleep)
    rs = sorted(w.records, key=lambda r: r.req.rid)
    assert rs[0].failed and [r.out for r in rs[1:]] == [[7, 7], [7, 7]]


def test_sessions_send_a_turn_after_the_answer_and_the_think_time():
    clk = Clock()
    d = FakeEngine(clk, prefill_steps=1)
    plan = SessionsPlan(systems=[[5, 5, 5]], sessions=[
        Session(0.0, 0, [[1], [2, 2], [3]], [2, 3, 1], [0.0, 0.5, 0.25],
                [0, 1, 2]),
        Session(0.05, 0, [[4]], [2], [0.0], [3])])
    sent = []
    orig = d.submit

    def submit(prompt, max_tokens):
        sent.append((clk.t - 100.0, list(prompt)))
        return orig(prompt, max_tokens)
    d.submit = submit
    w = run_window(d, plan, 3.0, clock=clk, sleep=clk.sleep)
    rs = {r.req.rid: r for r in w.records}
    assert sorted(rs) == [0, 1, 2, 3]
    assert all(r.failed is None and r.out for r in rs.values())
    # turn 1 is due 0.5 s after turn 0's last token; its prompt holds the
    # system prompt, turn 0's message and answer, and its own message
    assert rs[1].req.due == pytest.approx(rs[0].times[-1] + 0.5)
    assert rs[1].req.prompt == [5, 5, 5, 1, 7, 7, 2, 2]
    assert rs[2].req.due == pytest.approx(rs[1].times[-1] + 0.25)
    assert rs[2].req.prompt == rs[1].req.prompt + [7, 7, 7, 3]
    for r in rs.values():
        assert r.submit >= r.req.due
    assert [p for _, p in sent][:2] == [[5, 5, 5, 1], [5, 5, 5, 4]]


def test_warm_up_runs_before_the_window_and_its_tokens_count_inside():
    """A closed loop with a warm-up: the clients start ``warm_s`` before the
    window opens; requests due then are kept apart and not timed, but the
    tokens they produce inside the window count, and the window's steps
    are only those that start in it."""
    clk = Clock()
    d = FakeEngine(clk, prefill_steps=1)
    reqs = [Req(i, [1], 6, 0.0) for i in range(30)]
    w = run_window(d, ClosedPlan(reqs, clients=2, warm_s=0.45), 1.0,
                   clock=clk, sleep=clk.sleep)
    assert w.warm and all(r.req.due < 0 for r in w.warm)
    assert w.records and all(r.req.due >= 0 for r in w.records)
    assert w.warm[0].req.due == pytest.approx(-0.45)
    assert all(s.start >= 0 for s in w.steps) and len(w.steps) == 10
    assert M.ttfts_ms(w) == pytest.approx([100.0] * len(w.records))
    inside = [t for r in w.records + w.warm for t in r.times if 0 <= t < 1]
    ctx = M.Ctx(window=w, counters={}, work=None, peaks=None)
    assert M.reader("output_tok_s")(ctx) == pytest.approx(len(inside))
    assert any(0 <= t < 1 for r in w.warm for t in r.times)
    assert all(0 < g for g in M.gaps_ms(w)) and len(M.gaps_ms(w)) > 0
