"""Trace reduction: busy union, idle share, per-program device time and
gap attribution, on a hand-made trace and on a trace recorded on a TPU v5e
(``fixtures/v5e_chat_trace.json.gz``, a few steps of qwen2-7b.chat-poisson,
reduced by ``profile.from_profile``)."""
import pathlib

import pytest

from bench import metrics as M
from bench.profile import (Ev, Trace, busy_ns, idle_gaps, step_busy, step_of,
                           top_ops, union)

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "v5e_chat_trace.json.gz"


def hand_trace():
    spans = [Ev("bench.step#0", 0, 100), Ev("bench.wait", 100, 200),
             Ev("bench.step#1", 200, 300)]
    modules = [Ev("jit__decode_masked(123)", 10, 60),
               Ev("jit__prefill_zero_copy(9)", 210, 250),
               Ev("jit__decode_masked(123)", 250, 290)]
    ops = [Ev("%fusion.1", 10, 40), Ev("%fusion.2", 30, 60),   # overlap
           Ev("%fusion.3", 210, 250), Ev("%fusion.1", 250, 290)]
    return Trace(ops, modules, spans)


def test_union_and_busy():
    assert union([(0, 10), (5, 20), (30, 40)], 0, 35) == [(0, 20), (30, 35)]
    tr = hand_trace()
    assert tr.window() == (0, 300)
    assert busy_ns(tr, 0, 300) == 50 + 80
    # idle only counts inside steps: 200 ns of steps, 130 busy
    assert step_busy(tr) == (130, 200)
    ctx = M.Ctx(window=None, counters={}, work=None, peaks=None, trace=tr)
    assert M.reader("device.idle_pct")(ctx) == pytest.approx(35.0)


def test_programs_and_steps():
    tr = hand_trace()
    dec = tr.program("_decode_masked")
    assert [e.dur for e in dec] == [50, 40]
    assert [step_of(tr, e) for e in dec] == [0, 1]
    ctx = M.Ctx(window=None, counters={}, work=None, peaks=None, trace=tr)
    assert M.reader("decode.device_ms_per_step")(ctx) == pytest.approx(45e-6)
    assert M.reader("prefill.device_ms_per_call")(ctx) == pytest.approx(4e-5)


def test_gap_attribution_and_top_ops():
    tr = hand_trace()
    g = dict(idle_gaps(tr))
    # gaps: 0-10 in step#0; 60-210 over the end of step#0, the wait and
    # the start of step#1; 290-300 in step#1
    assert g["idle in step"] == pytest.approx((10 + 40 + 10 + 10) / 1e9)
    assert g["idle in wait"] == pytest.approx(100 / 1e9)
    assert g["longest gap, in wait"] == pytest.approx(150 / 1e9)
    ops = dict(top_ops(tr))
    assert ops["jit__decode_masked/fusion.1"] == pytest.approx(70 / 1e9)
    assert ops["jit__prefill_zero_copy/fusion.3"] == pytest.approx(40 / 1e9)


def test_recorded_v5e_trace():
    tr = Trace.from_json(str(FIXTURE))
    lo, hi = tr.window()
    steps = tr.steps()
    assert len(steps) >= 3
    dec = tr.program("_decode_masked")
    assert dec and all(step_of(tr, e) is not None for e in dec)
    busy, total = step_busy(tr)
    assert 0 < busy < total
    assert 0 < busy_ns(tr, lo, hi) <= hi - lo
    names = [n for n, _ in top_ops(tr)]
    assert any(n.startswith("jit__decode_masked/") for n in names)
    labels = [n for n, _ in idle_gaps(tr)]
    assert "idle in step" in labels
    # the reduction of this recording, pinned: 8 steps, each one decode
    # and one prefill call
    assert len(dec) == 8 and len(tr.program("_prefill_zero_copy")) == 8
    assert (busy, total) == (730681362, 759554735)
    ctx = M.Ctx(window=None, counters={}, work=None, peaks=None, trace=tr)
    assert M.reader("decode.device_ms_per_step")(ctx) == \
        pytest.approx(38.025296875)
    assert M.reader("prefill.device_ms_per_call")(ctx) == \
        pytest.approx(53.239759875)
    assert M.reader("device.idle_pct")(ctx) == pytest.approx(3.80135514526)
