"""The harness end to end on the CPU at a tiny size, through the program's
``launch.serve.build_engine``. The tests steer to the CPU themselves: they
hand ``harness.main`` the CPU device in place of its look for a chip."""
import json
import pathlib
import re
import shutil
import subprocess
import sys

import jax
import pytest

from bench import harness, metrics, model, traffic
from bench.peaks import PEAKS

BENCH = harness.BENCH
TINY = {"name": "tiny", "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 512,
        "serve": {"slots": 4, "max_len": 256, "page_size": 16,
                  "pool_pages": 64},
        "check": {"sample_tokens": 40, "max_logit_gap": 0.005}}
MIX = {"name": "tiny-open", "loop": "open", "rate_per_s": 4.0,
       "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                  "min": 8, "max": 96},
       "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                  "min": 4, "max": 24}}
LOOPS = {
    "closed": {"name": "tiny-closed", "loop": "closed", "clients": 6,
               "warm_s": 0.5, "requests": 1024, "bands": 4,
               "prompt": MIX["prompt"], "output": MIX["output"]},
    "sessions": {"name": "tiny-sessions", "loop": "sessions",
                 "rate_per_s": 3.0, "bands": 4,
                 "system": {"count": 3, "tokens": 40, "zipf": 1.1},
                 "turns": {"dist": "uniform", "min": 2, "max": 4},
                 "user": {"dist": "uniform", "min": 4, "max": 20},
                 "output": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                            "min": 2, "max": 12},
                 "think_s": 0.2},
}
TOY = {"name": "toy", "reference": "toy", "width": 64, "mlp_width": 96,
       "heads": 4, "kv_heads": 2, "depth": 2, "vocab": 384, "theta": 1e4,
       "eps": 1e-6, "initializer_range": 0.02,
       "serve": TINY["serve"], "check": TINY["check"]}
NEW_METRIC = '''"""Engine steps per window second (a metric added as one file)."""


def read(ctx):
    return len(ctx.window.steps) / ctx.window.seconds
'''


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark's data with one more configuration file, one
    more traffic file and one more metric file, and the cell and metric
    entries that name them."""
    root = tmp_path_factory.mktemp("bench")
    for d in ("configs", "traffic", "metrics"):
        shutil.copytree(BENCH / d, root / d)
    cfg = json.loads((BENCH / "configs" / "qwen2-7b.json").read_text())
    cfg.update(TINY)
    (root / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (root / "configs" / "toy.json").write_text(json.dumps(TOY))
    (root / "traffic" / "tiny-open.json").write_text(json.dumps(MIX))
    for mix in LOOPS.values():
        (root / "traffic" / f"{mix['name']}.json").write_text(json.dumps(mix))
    (root / "metrics" / "tiny.steps_per_s.py").write_text(NEW_METRIC)
    b = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cells = ["tiny.open"] + [f"tiny.{k}" for k in LOOPS] + ["toy.open"]
    b["workloads"] += [{"name": c, "config": c.split(".")[0],
                        "traffic": "tiny-" + c.split(".")[1], "chips": 1,
                        "why": "CPU rehearsal"} for c in cells]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] += cells
    b["per_layer"].append({"name": "tiny.steps_per_s", "unit": "1/s",
                           "better": "higher", "source": "host_clock",
                           "layer": "engine step", "moves": "itl_p50_ms",
                           "workloads": ["tiny.open"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


def run_cell(tree, monkeypatch, capsys, trace=0, seed=2**32 + 11, extra=(),
             cell="tiny.open"):
    return run_cell_io(tree, monkeypatch, capsys, trace, seed, extra,
                       cell)[0]


def run_cell_io(tree, monkeypatch, capsys, trace=0, seed=2**32 + 11,
                extra=(), cell="tiny.open"):
    """The result line and the standard error of one run."""
    monkeypatch.setattr(harness, "ROOT", tree)
    monkeypatch.setattr(model, "CONFIG_DIR", tree / "configs")
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", tree / "traffic")
    monkeypatch.setattr(metrics, "HERE", tree / "metrics")
    monkeypatch.setattr(harness, "TRACE_DIR", tree / "traces")
    monkeypatch.setattr(harness, "peaks_for", lambda k: PEAKS["TPU v5 lite"])
    rc = harness.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", "2", "--trace", str(trace), *extra],
                      devices_fn=lambda n: jax.devices("cpu")[:n],
                      cache_fn=lambda: "off")
    assert rc == 0
    io = capsys.readouterr()
    return json.loads(io.out.strip().splitlines()[-1]), io.err


def test_rehearsal_last_line_and_new_cell(tree, monkeypatch, capsys):
    res = run_cell(tree, monkeypatch, capsys)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "check"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 8
    b = json.loads((tree / "BENCHMARK.json").read_text())
    assert set(res["metrics"]) == {
        m["name"] for m in b["end_to_end"]
        if "tiny.open" in m.get("workloads", ["tiny.open"])}
    assert res["device"]["platform"] == "cpu"
    assert set(res["check"]["max_logit_gap"]) == {"value", "limit"}


@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_rehearsal_of_the_other_loops(tree, monkeypatch, capsys, loop):
    """A closed loop and sessions through the engine: every request sent
    is served in full and checked, and sessions' later turns find their
    history in the prefix cache."""
    res = run_cell(tree, monkeypatch, capsys, trace=1, cell=f"tiny.{loop}")
    assert res["correct"] is True and res["failed"] == 0
    assert res["check"]["wrong_lengths"]["value"] == 0
    hit = res["metrics"].get("kv.prefix_hit_pct")
    if loop == "sessions":
        assert 0 < hit["value"] < 100
    else:
        assert res["attempted"] > LOOPS[loop]["clients"]


def test_a_new_architecture_enters_as_new_files(tree, monkeypatch, capsys):
    """An architecture module and its reference in a directory of their own
    (here the tests'), named by a configuration file's ``reference``: the
    harness builds the program's model from it, and the metric readers
    count the steps by its rule."""
    import bench.arch
    import bench.references
    toy = pathlib.Path(__file__).resolve().parent / "toy"
    monkeypatch.setattr(bench.arch, "__path__",
                        [*bench.arch.__path__, str(toy / "arch")])
    monkeypatch.setattr(bench.references, "__path__",
                        [*bench.references.__path__, str(toy / "references")])
    from bench.arch import toy as arch
    arch.CALLS.clear()
    res = run_cell(tree, monkeypatch, capsys, trace=1, cell="toy.open")
    assert res["correct"] is True and res["failed"] == 0
    assert [c[0] for c in arch.CALLS[:2]] == ["model_config", "counts"]
    # mfu_pct from the toy's rule (decode_roofline needs the device's
    # program events, which a CPU trace lacks)
    steps = [c for c in arch.CALLS if c[0] == "step_flops"]
    assert steps and any(c[1] for c in steps) and any(c[2] for c in steps)
    assert res["metrics"]["mfu_pct"]["value"] > 0


def test_only_architecture_modules_name_an_architecture():
    """The harness, the model loader and the metric readers know no
    architecture: that is ``bench/arch``'s and ``bench/references``'."""
    names = re.compile(r"Dims|attn_mlp|family=")
    files = [*BENCH.glob("*.py"), *(BENCH / "metrics").glob("*.py")]
    assert files
    assert [f.name for f in files if names.search(f.read_text())] == []


def test_float8_control_fails_the_limit(tree, monkeypatch, capsys):
    """The control: the reference in float8, one precision below the served
    bfloat16, put in the program's place at the same prompts and served
    tokens, and judged by the harness's own check. At this size the
    program reads at most 0.0023 over seeds 11-14 and the control at least
    0.0096 (measured on the CPU); the tiny configuration's limit is 0.005."""
    res = run_cell(tree, monkeypatch, capsys, extra=("--control", "1"))
    c = res["check"]
    assert res["correct"] is False
    assert c["max_logit_gap"]["value"] > c["max_logit_gap"]["limit"]
    assert c["program_max_logit_gap"]["value"] <= c["max_logit_gap"]["limit"]
    assert c["wrong_lengths"]["value"] == 0


def test_traced_run_reads_the_new_metric_file(tree, monkeypatch, capsys):
    res = run_cell(tree, monkeypatch, capsys, trace=1)
    assert res["correct"] is True
    assert res["metrics"]["tiny.steps_per_s"]["value"] > 0
    assert "setup_s" not in res["metrics"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_token_altered_where_produced_is_not_correct(tree, monkeypatch,
                                                     capsys):
    """The timed path broken underneath: the decode step's logits shifted
    by one vocabulary entry, so every decoded token is the wrong one."""
    from repro.core.serving.engine import ServingEngine
    orig = ServingEngine._decode_masked

    def shifted(self, *a):
        logits, cache = orig(self, *a)
        return jax.numpy.roll(logits, 1, axis=-1), cache
    monkeypatch.setattr(ServingEngine, "_decode_masked", shifted)
    res = run_cell(tree, monkeypatch, capsys)
    assert res["correct"] is False
    gap = res["check"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_decode_that_returns_its_cache_unchanged_is_not_correct(
        tree, monkeypatch, capsys):
    """The timed path broken underneath: the decode step hands back the KV
    cache it was given, so no decoded token's K/V is ever written."""
    from repro.core.serving.engine import ServingEngine
    orig = ServingEngine._decode_masked

    def stale(self, *a):
        logits, _ = orig(self, *a)
        return logits, a[-1]
    monkeypatch.setattr(ServingEngine, "_decode_masked", stale)
    res = run_cell(tree, monkeypatch, capsys)
    assert res["correct"] is False
    gap = res["check"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_prefill_that_skips_the_mapped_prefix_is_not_correct(
        tree, monkeypatch, capsys):
    """The timed path broken underneath, in sessions: a prefill chunk no
    longer reads the prefix that the cache mapped (or that earlier chunks
    wrote), so every later turn is served from its new tokens alone."""
    from repro.core.serving.engine import ServingEngine
    orig = ServingEngine._prefill_zero_copy

    def blind(self, params, batch, cache):
        batch = dict(batch, prefix_lens=jax.numpy.zeros_like(
            batch["prefix_lens"]))
        return orig(self, params, batch, cache)
    monkeypatch.setattr(ServingEngine, "_prefill_zero_copy", blind)
    res = run_cell(tree, monkeypatch, capsys, cell="tiny.sessions")
    assert res["correct"] is False
    gap = res["check"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_compared_numbers_are_the_last_lines_and_pool_use_is_logged(
        tree, monkeypatch, capsys):
    res, err = run_cell_io(tree, monkeypatch, capsys, seed=5)
    assert list(res)[-1] == "check"
    lines = err.strip().splitlines()
    assert lines[-len(res["check"]):] == [
        f"check {k}: {v['value']}" + (f" (limit {v['limit']})"
                                      if "limit" in v else "")
        for k, v in res["check"].items()]
    (pool,) = [x for x in lines if x.startswith("KV pool: ")]
    live, held = [int(w) for w in pool.split() if w.isdigit()][2:4]
    assert 0 < live <= held <= 64


def test_refuses_a_cpu_device(capsys):
    with pytest.raises(SystemExit) as e:
        harness.require_accelerator(1)
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_run_py_exits_nonzero_without_a_result_off_the_chip():
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "qwen2-7b.chat-poisson", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
