"""The continuous engine's host spans, prefill counters and program names.

A small continuous engine runs a few steps that mix prefill chunks and
decodes under ``jax.profiler``; the recording is reduced with
``bench.engine_trace.from_profile``, as the benchmark's traced run does.
The spans must nest one ``engine.step`` per step, carry only the names in
``engine.SPANS``, in their nesting order, and open prefill spans only on
steps that carry chunks. The jitted programs keep the XLA module names by
which the benchmark finds them in a device trace, and their operations the
model's scopes."""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.engine_trace import engine_steps, from_profile
from bench.profile import newest_xplane
from repro.configs import get_config, reduce_for_smoke
from repro.core.serving.engine import SPANS, ServingEngine
from repro.models import init_params


@pytest.fixture(scope="module")
def engine():
    cfg = dataclasses.replace(reduce_for_smoke(get_config("llama3.2-1b")),
                              sched_token_budget=256, sched_prefill_chunk=64)
    params = init_params(cfg, jax.random.key(0))
    return ServingEngine(cfg, params, n_slots=4, max_len=256, page_size=8)


@pytest.fixture(scope="module")
def traced(engine, tmp_path_factory):
    """Steps 0-4 of a run: step 0 prefills prompts of 64 and 37 tokens in
    one call; a 100-token prompt then arrives and prefills in chunks of 64
    and 36 in steps 1 and 2, beside the first two's decodes."""
    eng = engine
    composed = []
    orig = eng.sched.schedule

    def schedule():
        out = orig()
        composed.append((len(out.chunks), len(out.decode_slots)))
        return out
    eng.sched.schedule = schedule
    rng = np.random.default_rng(0)
    finished = {}
    counts = []
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    try:
        for n in (64, 37):
            eng.submit(rng.integers(1, 100, n).tolist(), max_tokens=8)
        for i in range(5):
            if i == 1:
                eng.submit(rng.integers(1, 100, 100).tolist(), max_tokens=4)
            eng.step(finished)
            counts.append((eng.metrics["prefill_tokens"],
                           eng.metrics["prefill_padded_tokens"]))
    finally:
        jax.profiler.stop_trace()
        eng.sched.schedule = orig
    return from_profile(newest_xplane(d)), composed, counts


def test_one_step_span_per_step_and_nesting(traced):
    tr, composed, _ = traced
    steps = [e for e in tr.engine if e.name == "engine.step"]
    assert len(steps) == len(composed) == 5
    assert [e.arg("step") for e in steps] == list(range(5))
    for e in tr.engine:
        assert e.name in SPANS
        assert any(s.start <= e.start and e.end <= s.end for s in steps)
    # inside a step, spans open in the order of SPANS (a CoW copy may land
    # twice a step, before scheduling and before the decode)
    rank = {n: i for i, n in enumerate(SPANS)}
    for st in steps:
        inner = [e.name for e in tr.engine
                 if st.start <= e.start and e.end <= st.end
                 and e is not st and e.name != "engine.cow"]
        assert [rank[n] for n in inner] == sorted(rank[n] for n in inner)


def test_prefill_and_decode_spans_follow_the_schedule(traced):
    tr, composed, _ = traced
    by_step = engine_steps(tr)
    for parts, (chunks, decodes) in zip(by_step, composed):
        assert ("engine.prefill.launch" in parts) == (chunks > 0)
        assert all(("engine.prefill." + p in parts) == (chunks > 0)
                   for p in ("pack", "commit"))
        assert ("engine.decode.launch" in parts) == (decodes > 0)
        assert "engine.schedule" in parts and "engine.release" in parts
        sched = parts["engine.schedule"][0]
        assert (sched.arg("chunks"), sched.arg("decodes")) == (chunks, decodes)
        if decodes:
            assert parts["engine.decode.pack"][0].arg("rows") == decodes
    assert composed == [(2, 0), (1, 2), (1, 2), (0, 3), (0, 3)]


def test_prefill_token_counters(traced):
    tr, _, counts = traced
    # step 0: chunks of 64 and 37 in one call, 2 rows x 64 padded slots;
    # step 1: the third prompt's first 64; step 2: its last 36, bucket 64
    assert counts == [(101, 128), (165, 192), (201, 256), (201, 256),
                      (201, 256)]
    packs = [e for e in tr.engine if e.name == "engine.prefill.pack"]
    assert [(e.arg("rows"), e.arg("tokens"), e.arg("real")) for e in packs] \
        == [(2, 128, 101), (1, 64, 64), (1, 64, 36)]


def test_decode_kv_pages(engine, traced):
    """The pages the decoding rows hold once the step's token is written,
    which the decode kernel reads: on the decode pack span as ``pages`` and
    summed in ``stats()["decode_kv_pages"]``. Rows of 64 and 37 prompt
    tokens decode from step 1 (65 and 38 tokens: 9 + 5 pages of 8), the
    100-token prompt from step 3 (101 tokens: 13 pages)."""
    tr, _, _ = traced
    pages = [e.arg("pages") for e in tr.engine
             if e.name == "engine.decode.pack"]
    assert pages == [9 + 5, 9 + 5, 9 + 5 + 13, 9 + 6 + 13]
    assert engine.stats()["decode_kv_pages"] == sum(pages)


def test_decode_kernel_keeps_the_attention_scope(engine, monkeypatch):
    """Lowered for a TPU, the decode program reads the pool through the
    ragged kernel, whose custom call sits under the ``attention`` scope that
    ``decode.attn_device_ms_per_step`` reads."""
    eng = engine
    S = eng.n_slots
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # a program of its own: the engine's was traced for the CPU
    decode = jax.jit(functools.partial(ServingEngine._decode_masked, eng))
    text = decode.trace(
        eng.params, jnp.zeros((S, 1), jnp.int32), jnp.zeros((S,), jnp.int32),
        eng._tables_dev, jnp.zeros((S,), bool), eng.cache,
    ).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    calls = re.findall(r"custom_call @tpu_custom_call\(.*loc\((#loc\d+)\)$",
                       text, re.M)
    assert len(calls) == 1
    name, = re.findall(rf'^{calls[0]} = loc\("([^"]*)"', text, re.M)
    assert "/attention/" in name


def test_removed_counters_are_gone(engine):
    assert "decode_s" not in engine.metrics
    assert "prefill_reqs" not in engine.metrics


def test_program_names(engine):
    """The benchmark finds the programs in a device trace by these XLA
    module names (``bench/profile.py`` ``Trace.program``)."""
    eng = engine
    S, P, null = eng.n_slots, eng.max_pages, eng.null_page
    batch = {"tokens": jnp.zeros((1, 8), jnp.int32),
             "lengths": jnp.ones((1,), jnp.int32),
             "tables": jnp.full((1, P), null, jnp.int32),
             "slots": jnp.full((1,), S, jnp.int32),
             "prefix_lens": jnp.zeros((1,), jnp.int32),
             "write_tables": jnp.full((1, P), null, jnp.int32)}
    lowered = {
        "jit__decode_masked": eng._decode_m.lower(
            eng.params, jnp.zeros((S, 1), jnp.int32),
            jnp.zeros((S,), jnp.int32), eng._tables_dev,
            jnp.zeros((S,), bool), eng.cache),
        "jit__prefill_zero_copy": eng._prefill.lower(eng.params, batch,
                                                     eng.cache),
        "jit__cow_copy_pages": eng._cow.lower(
            eng.cache, jnp.zeros((1,), jnp.int32),
            jnp.full((1,), null, jnp.int32)),
    }
    for name, low in lowered.items():
        assert low.as_text().split("\n", 1)[0].startswith(f"module @{name} ")
    # the device scopes the benchmark splits the programs' time by, in the
    # op_name metadata of the compiled programs
    for name in ("jit__decode_masked", "jit__prefill_zero_copy"):
        hlo = lowered[name].compile().as_text()
        paths = set(re.findall(r'op_name="([^"]*)"', hlo))
        for scope in ("attention", "kv_write", "mlp", "lm_head"):
            assert any(f"/{scope}/" in p for p in paths), (name, scope)
