"""The reduction of the engine's spans and the device operations' scopes
(``bench/engine_trace.py``) and the four readers built on it: on hand-made
traces; on a recording from a TPU v5e with both
(``fixtures/v5e_engine_trace.json.gz``); on the older v5e recording
(``fixtures/v5e_chat_trace.json.gz``), which has neither, so each reader
finds nothing; on a trace recorded here on the CPU; and through the
harness on the CPU."""
import pytest

from bench import engine_trace as ET
from bench import metrics as M
from bench import profile
from bench.engine_trace import EngineTrace, Event
from bench.tests.test_bench_harness import run_cell, tree  # noqa: F401
from bench.tests.test_bench_profile import FIXTURE, hand_trace

ENGINE_FIXTURE = FIXTURE.parent / "v5e_engine_trace.json.gz"
READERS = ("decode.host_ms_per_step", "prefill.host_ms_per_call",
           "prefill.pad_pct", "decode.attn_device_ms_per_step")


def engine_hand_trace():
    """``hand_trace``'s window (step#0 0-100, wait 100-200, step#1
    200-300; decode 10-60 and 250-290, prefill 210-250), with engine spans
    in both steps and scope paths on the operations."""
    tr = hand_trace()
    d = "jit(_decode_masked)/while/body/"
    ops = [Event("%fusion.1", 10, 40, d + "attention/dot_general"),
           Event("%fusion.2", 30, 60, d + "mlp/dot_general"),
           Event("%fusion.3", 210, 250,
                 "jit(_prefill_zero_copy)/while/body/attention/dot_general"),
           Event("%while.1", 250, 280, d[:-6]),       # encloses the next
           Event("%copy.1", 250, 270),
           Event("%fusion.4", 270, 280, d + "kv_write/scatter"),
           Event("%fusion.5", 280, 290, "jit(_decode_masked)/lm_head/dot")]
    spans = [("step", 2, 98), ("schedule", 2, 6), ("tables", 6, 8),
             ("decode.pack", 8, 9), ("decode.launch", 9, 10),
             ("decode.sync", 10, 62), ("decode.commit", 62, 80),
             ("release", 80, 95),
             ("step", 201, 299), ("schedule", 201, 203),
             ("prefill.pack", 203, 207), ("prefill.launch", 207, 209),
             ("prefill.commit", 209, 211), ("tables", 211, 215),
             ("decode.pack", 215, 220), ("decode.launch", 220, 222),
             ("decode.sync", 222, 292), ("decode.commit", 292, 296),
             ("release", 296, 298)]
    engine = [Event("engine." + n, s, e,
                    args=(("real", 101), ("rows", 2), ("tokens", 128))
                    if n == "prefill.pack" else ())
              for n, s, e in spans]
    return EngineTrace(ops, tr.modules, tr.spans, engine)


def ctx_of(tr, counters=None):
    return M.Ctx(window=None, counters=counters or {}, work=None, peaks=None,
                 trace=tr)


def test_idle_split_by_innermost_engine_span():
    tr = engine_hand_trace()
    g = dict(ET.idle_gaps(tr))
    # gap 0-10: 0-2 before engine.step, then schedule, tables, pack,
    # launch; gap 60-210: sync, commit, release, engine.step alone, the
    # end of step#0, the wait, then step#1 up to the prefill's launch and
    # commit; gap 290-300: sync, commit, release, engine.step, step
    want = {"step": 6, "step/engine.schedule": 6, "step/engine.tables": 2,
            "step/engine.decode.pack": 1, "step/engine.decode.launch": 1,
            "step/engine.decode.sync": 4, "step/engine.decode.commit": 22,
            "step/engine.release": 17, "step/engine.step": 4,
            "step/engine.prefill.pack": 4, "step/engine.prefill.launch": 2,
            "step/engine.prefill.commit": 1, "wait": 100}
    assert {k[len("idle in "):]: round(v * 1e9, 6) for k, v in g.items()
            if k.startswith("idle in ")} == want
    assert g["longest gap, in wait"] == pytest.approx(150e-9)
    assert g["longest gap, in step/engine.schedule"] == pytest.approx(10e-9)
    assert g["longest gap, in step/engine.decode.commit"] == \
        pytest.approx(10e-9)
    # the same step time as the harness's own split, now with names
    assert sum(v for k, v in want.items() if k.startswith("step")) * 1e-9 \
        == pytest.approx(dict(profile.idle_gaps(tr))["idle in step"])
    share = ET.in_step_idle(ET.idle_gaps(tr))
    assert share == pytest.approx({"idle_in_step_s": 70e-9,
                                   "under_engine_spans_s": 64e-9,
                                   "under_inner_spans_s": 60e-9})


def test_idle_split_without_engine_spans_is_the_harness_split():
    for tr in (hand_trace(), profile.Trace.from_json(str(FIXTURE))):
        et = EngineTrace(tr.ops, tr.modules, tr.spans)
        mine, theirs = ET.idle_gaps(et), profile.idle_gaps(tr)
        split = [x for x in theirs if x[0].startswith("idle in ")]
        assert mine[:len(split)] == split
        assert mine[len(split)] == theirs[len(split)]     # longest gap


def test_decode_by_scope_and_attention_reader():
    tr = engine_hand_trace()
    by = ET.by_scope(tr)
    assert {k: round(v * 1e9, 6) for k, v in by.items()} == {
        "attention": 30, "mlp": 30, "kv_write": 10, "lm_head": 10,
        "unscoped": 20}
    assert ET.by_scope(tr, "_prefill_zero_copy")["attention"] == \
        pytest.approx(40e-9)
    # 30 ns of attention over two decode calls
    assert M.reader("decode.attn_device_ms_per_step")(ctx_of(tr)) == \
        pytest.approx(15e-6)


def test_host_readers():
    tr = engine_hand_trace()
    ctx = ctx_of(tr)
    # decode steps: pack + tables + commit = 1 + 2 + 18 and 5 + 4 + 4 ns
    assert M.reader("decode.host_ms_per_step")(ctx) == pytest.approx(17e-6)
    # one prefill call: pack 4 + commit 2 ns
    assert M.reader("prefill.host_ms_per_call")(ctx) == pytest.approx(6e-6)
    # 101 real tokens in 2 rows x 64 slots
    assert M.reader("prefill.pad_pct")(ctx) == pytest.approx(2700 / 128)


@pytest.mark.parametrize("which", ["hand", "v5e_chat"])
def test_readers_find_nothing_without_engine_spans_or_scopes(which):
    tr = hand_trace() if which == "hand" else \
        profile.Trace.from_json(str(FIXTURE))
    ctx = ctx_of(EngineTrace(tr.ops, tr.modules, tr.spans))
    assert {n: M.reader(n)(ctx) for n in READERS} == dict.fromkeys(READERS)
    assert {n: M.reader(n)(ctx_of(None)) for n in READERS} == \
        dict.fromkeys(READERS)


def test_recorded_v5e_engine_trace():
    """Eight steps (four carry a prefill chunk, two of them final) of a
    ``--trace 1`` run of qwen2-7b.chat-poisson on a TPU v5e (seed
    2147483659), reduced by ``engine_trace.from_profile`` (scopes from the
    recording's HLO metadata) and written by
    ``EngineTrace.to_json(path, start of bench.step#350, end of
    bench.step#357)``."""
    tr = EngineTrace.from_json(str(ENGINE_FIXTURE))
    assert len(tr.steps()) == 8
    assert len(tr.program(ET.DECODE)) == 8
    assert len(tr.program("_prefill_zero_copy")) == 4
    assert {e.name for e in tr.engine} == {
        "engine." + n for n in (
            "step", "schedule", "prefill.pack", "prefill.launch",
            "prefill.sync", "prefill.commit", "tables", "decode.pack",
            "decode.launch", "decode.sync", "decode.commit", "release")}
    ctx = ctx_of(tr)
    got = {n: M.reader(n)(ctx) for n in READERS}
    assert got == pytest.approx({
        "decode.host_ms_per_step": 3.006906125,
        "prefill.host_ms_per_call": 2.00400975,
        "prefill.pad_pct": 16.25,
        "decode.attn_device_ms_per_step": 11.268175875})
    # the existing readers read the same recording as before
    assert M.reader("decode.device_ms_per_step")(ctx) == \
        pytest.approx(38.078171)
    # the scopes split the decode program's whole device time
    by = ET.by_scope(tr)
    assert all(v > 0 for v in by.values())
    assert sum(by.values()) * 1e3 / 8 == pytest.approx(38.078171, rel=1e-3)
    # the engine spans cover the device idle inside steps, nearly all of it
    # under a span below engine.step
    share = ET.in_step_idle(ET.idle_gaps(tr))
    assert share["under_inner_spans_s"] > 0.95 * share["idle_in_step_s"]
    labels = [n for n, _ in ET.idle_gaps(tr)]
    assert labels[:2] == ["idle in step/engine.decode.sync",
                          "idle in step/engine.tables"]


def test_fixture_round_trip(tmp_path):
    tr = engine_hand_trace()
    path = str(tmp_path / "t.json.gz")
    tr.to_json(path, 0, 300)
    back = EngineTrace.from_json(path)
    assert back == tr
    tr.to_json(path, 200, 300)
    assert [e.name for e in EngineTrace.from_json(path).spans] == \
        ["bench.step#1"]


def test_of_reduces_the_harness_recording_once(tmp_path):
    """A recording made here: a harness step span around an engine span.
    ``of`` reduces it again only for the window the harness reduced."""
    import jax
    d = str(tmp_path)
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.step#0"):
        with jax.profiler.TraceAnnotation("engine.step", step=7):
            pass
    jax.profiler.stop_trace()
    tr = profile.from_profile(profile.newest_xplane(d))
    ctx = ctx_of(tr)
    et = ET.of(ctx, directory=d)
    assert [(e.name, e.arg("step")) for e in et.engine] == [("engine.step", 7)]
    assert ET.of(ctx) is et                     # reduced once per run
    other = ctx_of(hand_trace())                # not this recording's window
    assert ET.of(other, directory=d).engine == []
    assert ET.of(ctx_of(None), directory=d) is None


def test_traced_harness_run_reads_the_engine_spans(
        tree, monkeypatch, capsys):
    """On the CPU the engine's spans are recorded (the device plane is not:
    no operation, so no scope)."""
    res = run_cell(tree, monkeypatch, capsys, trace=1)
    m = res["metrics"]
    assert res["correct"] is True
    assert m["decode.host_ms_per_step"]["value"] > 0
    assert m["prefill.host_ms_per_call"]["value"] > 0
    assert 0 <= m["prefill.pad_pct"]["value"] < 100
    assert "decode.attn_device_ms_per_step" not in m
