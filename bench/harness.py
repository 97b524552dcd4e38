"""One run of one benchmark cell, from the command line of ``run.py``.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

The cell (``BENCHMARK.json`` ``workloads``) names a configuration file
(``bench/configs/<config>.json``), which names its architecture
(``bench/arch/<reference>.py``, with its plain reference
``bench/references/<reference>.py``), and a traffic mix
(``bench/traffic/<traffic>.json``); each metric is read by
``bench/metrics/<metric>.py``. Nothing here names a cell, a configuration,
an architecture, a mix or a metric: adding one is adding its files.

The run, in order: refuse anything but an accelerator with the chips the
cell asks for; turn on JAX's persistent compilation cache; make the
weights on the device from the seed; build the engine the program serves
with (``launch.serve.build_engine``: continuous scheduler, zero-copy
global pool, the program's own defaults); warm every program the window
can run; run the window; read the peak memory; free the engine; check the
outputs against the plain reference; print the result as the last line.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import pathlib
import sys
import time
from typing import Dict, List, Optional

from bench.peaks import peaks_for

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".jax_cache"
TRACE_DIR = BENCH / ".traces"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")
COUNTERS = ("admit_s", "prefill_tokens_saved", "tokens", "decode_steps",
            "prefills", "cow_page_copies", "table_upload_bytes")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_accelerator(chips: int):
    """The devices to run on; exits non-zero, printing no result, unless
    JAX's devices are TPUs and there are at least ``chips`` of them."""
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    if d.platform != "tpu":
        raise SystemExit(f"bench: JAX's device is {d.platform!r}, not a TPU; "
                         "the benchmark never falls back to another platform")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def enable_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts compilations and traces while ``active``."""

    def __init__(self):
        import jax.monitoring as mon
        self.active = False
        self.counts = {e: 0 for e in COMPILE_EVENTS}
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if self.active and event in self.counts:
            self.counts[event] += 1


def buckets(lo: int, hi: int) -> List[int]:
    """Powers of two from ``lo`` below ``hi``, then ``hi``: the engine's
    padded sizes (``ServingEngine._bucket_len`` and its row count)."""
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    return out + [hi]


def warm_up(eng, threads: int = 4) -> Dict[str, int]:
    """Run every program the continuous path can call, once per shape, on
    all-NULL tables (every KV write is dropped) and with no request in the
    engine: chunk prefill for each (rows, tokens) bucket, the masked decode
    step with its argmax, the CoW page copy for each pair count, and the
    delta table upload for each row count. The large programs are first
    compiled side by side on ``threads`` threads, which fills the
    persistent cache; the calls that follow then load them from it."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp
    import numpy as np
    null, S, P = eng.null_page, eng.n_slots, eng.max_pages
    chunk = min(eng.cfg.sched_prefill_chunk, eng.cfg.sched_token_budget)
    lbs = [b for b in buckets(eng.page_size, P * eng.page_size)
           if b // 2 < chunk]
    nbs = buckets(1, S)
    calls = []
    for nb in nbs:
        for lb in lbs:
            batch = {"tokens": jnp.zeros((nb, lb), jnp.int32),
                     "lengths": jnp.ones((nb,), jnp.int32),
                     "tables": jnp.full((nb, P), null, jnp.int32),
                     "slots": jnp.full((nb,), S, jnp.int32),
                     "prefix_lens": jnp.zeros((nb,), jnp.int32),
                     "write_tables": jnp.full((nb, P), null, jnp.int32)}
            calls.append((eng._prefill, (eng.params, batch)))
    decode_args = (eng.params, jnp.zeros((S, 1), jnp.int32),
                   jnp.zeros((S,), jnp.int32), eng._tables_dev,
                   jnp.zeros((S,), bool))
    calls.append((eng._decode_m, decode_args))
    # CoW pair counts round up to a power of two, at most one pair a slot
    pairs = [1 << i for i in range((S - 1).bit_length() + 1)]
    for n in pairs:
        calls.append((eng._cow, (jnp.asarray(np.zeros((n,), np.int32)),
                                 jnp.asarray(np.full((n,), null, np.int32)))))

    def args_of(fn, a):
        return (eng.cache,) + a if fn is eng._cow else a + (eng.cache,)

    with ThreadPoolExecutor(threads) as ex:
        for f in [ex.submit(lambda c: c[0].lower(*args_of(*c)).compile(), c)
                  for c in calls]:
            f.result()
    for fn, a in calls:
        out = fn(*args_of(fn, a))
        if fn is eng._cow:
            eng.cache = out
        else:
            logits, eng.cache = out
            if fn is eng._decode_m:
                np.asarray(jnp.argmax(logits[:, 0], axis=-1))
            else:
                np.asarray(logits)
    for k in range(1, S + 1):
        idx = np.arange(k)
        eng._tables_dev = eng._tables_dev.at[jnp.asarray(idx)].set(
            jnp.asarray(eng.mgr.tables[idx]))
    jax.block_until_ready((eng.cache, eng._tables_dev))
    return {"programs": len(calls), "rows": nbs, "tokens": lbs,
            "cow_pairs": pairs}


class Tracer:
    """The traced window of a ``--trace 1`` run: the profiler runs from a
    third of the window for up to 4 seconds, and the harness's spans are
    recorded into its trace."""

    def __init__(self, directory: str, seconds: float):
        self.dir = directory
        self.t_on = seconds / 3.0
        self.t_off = self.t_on + min(4.0, seconds / 3.0)
        self.on = self.done = False

    def on_time(self, now: float) -> None:
        import jax
        if self.done:
            return
        if not self.on and now >= self.t_on:
            import shutil
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.on = True
        elif self.on and now >= self.t_off:
            self.stop()

    def stop(self) -> None:
        import jax
        if self.on:
            jax.profiler.stop_trace()
            self.on, self.done = False, True

    def span(self, name: str):
        import jax
        if self.on:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def reduce(self):
        from bench.profile import from_profile, newest_xplane
        path = newest_xplane(self.dir)
        return from_profile(path) if path else None


def snapshot(eng) -> Dict[str, float]:
    s = eng.stats()
    out = {k: float(s[k]) for k in COUNTERS if k in s}
    out["preemptions"] = float(s["sched"]["preemptions"])
    return out


def hook_scheduler(eng, log_: list) -> None:
    """Record what the scheduler composed for each step (decoding slots'
    context lengths, chunk spans, prompt tokens admitted, and of those the
    tokens whose prefill the prefix cache skipped: the step's part of the
    counter ``ServingEngine.stats()`` reports as ``prefill_tokens_saved``)
    and the KV pages in use once it has admitted (pages mapped by live
    sequences, and pages held in the pool, which adds the prefix cache's).
    Reads only; the processing happens after the window."""
    orig = eng.sched.schedule
    mgr = eng.mgr

    def saved():
        return mgr.prefix.stats.tokens_saved if mgr.prefix is not None else 0

    def schedule():
        before = saved()
        out = orig()
        log_.append((out, mgr.lengths[out.decode_slots].copy(),
                     [len(eng._waiting_reqs[s].prompt) for s in out.admitted],
                     saved() - before,
                     sum(len(st.pages) for st in mgr.seqs.values()),
                     mgr.pool.n_pages - mgr.pool.n_free))
        return out
    eng.sched.schedule = schedule


def sched_record(entry) -> dict:
    out, lengths, admitted, saved, live, held = entry
    return {"contexts": [int(x) for x in lengths],
            "chunks": [(c.start, c.end, c.is_final) for c in out.chunks],
            "admitted_tokens": int(sum(admitted)),
            "saved_tokens": int(saved),
            "pages_live": int(live), "pages_held": int(held)}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the float8 control in the program's place "
                         "(measures the check's limit, and must come out "
                         "not correct; not part of a benchmark run)")
    return ap.parse_args(argv)


def load_cell(name: str, root: Optional[pathlib.Path] = None):
    bench = json.loads(((root or ROOT) / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]

    def applies(m):
        return "workloads" not in m or name in m["workloads"]
    return (bench, cell, [m for m in bench["end_to_end"] if applies(m)],
            [m for m in bench["per_layer"] if applies(m)])


def main(argv=None, t_start: Optional[float] = None,
         devices_fn=require_accelerator, cache_fn=enable_cache) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    bench, cell, e2e, per_layer = load_cell(args.workload)
    devs = devices_fn(cell["chips"])

    import jax
    from bench import metrics as M
    from bench.check import passed, run_check
    from bench.loop import EngineAdapter, run_window
    from bench.model import arch, load_config, make_weights
    from bench.traffic import load_mix, make_plan
    from repro.launch.serve import build_engine

    log(f"compile cache: {cache_fn()}")
    dev = devs[0]
    cfgj = load_config(cell["config"])
    mix = load_mix(cell["traffic"])
    cfg = arch(cfgj).model_config(cfgj)
    sv = cfgj["serve"]
    params = jax.block_until_ready(
        make_weights(cfg, cfgj["initializer_range"], args.seed))
    eng = build_engine(cfg, params, slots=sv["slots"], max_len=sv["max_len"],
                       page_size=sv["page_size"], pool_pages=sv["pool_pages"])
    t_w = time.perf_counter()
    warmed = warm_up(eng)
    log(f"warm-up: {warmed} in {time.perf_counter() - t_w:.3f} s")
    plan = make_plan(mix, args.seed, args.seconds, cfg.vocab_size,
                     sv["max_len"])
    sched_log: list = []
    hook_scheduler(eng, sched_log)
    counter = CompileCounter()
    c0 = snapshot(eng)

    tracer = Tracer(str(TRACE_DIR), args.seconds) if args.trace else None
    # the window opens once the plan's warm-up has run: set-up too
    setup_s = time.perf_counter() - t_start + plan.warm_s
    counter.active = True
    w = run_window(EngineAdapter(eng), plan, args.seconds,
                   span=tracer.span if tracer else
                   (lambda name: contextlib.nullcontext()),
                   sched_log=sched_log,
                   on_time=tracer.on_time if tracer else None)
    counter.active = False
    if tracer:
        tracer.stop()
    jax.block_until_ready(eng.cache)
    c1 = snapshot(eng)
    counters = {k: c1[k] - c0.get(k, 0.0) for k in c1}
    for s in w.steps:
        if s.sched is not None:
            s.sched = sched_record(s.sched)
    mem = dev.memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    lat = M.lateness_ms(w)
    recs = [s.sched for s in w.steps if s.sched is not None]
    prefix = eng.mgr.prefix
    log(f"KV pool: {eng.mgr.pool.n_pages} pages of {eng.page_size} tokens; "
        f"peak in the window {max((r['pages_live'] for r in recs), default=0)}"
        f" mapped by live sequences, "
        f"{max((r['pages_held'] for r in recs), default=0)} held with the "
        f"prefix cache's; "
        f"{prefix.n_cached_pages if prefix is not None else 0} pages in the "
        f"prefix cache at the close")

    def nbytes(tree):
        return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))
    log(f"memory: peak {peak} B in use of {mem.get('bytes_limit')}; weights "
        f"{nbytes(params)} B, KV pool {nbytes(eng.cache)} B")
    done = sum(1 for r in w.records + w.warm if r.out is not None
               and 0 <= r.times[-1] < w.seconds)
    log(f"window: {args.seconds} s after {plan.warm_s} s of warm-up, "
        f"{len(w.records)} requests due, {done} finished in it, "
        f"{len(w.steps)} steps, drain {w.drain_s:.3f} s; in-window "
        f"compiles={counter.counts[COMPILE_EVENTS[0]]} "
        f"traces={counter.counts[COMPILE_EVENTS[1]]}; generator lateness "
        f"p95 {M.percentile(lat, 95)} ms over {len(lat)} sends; counters "
        f"{json.dumps(counters)}")

    eng.cache = None
    del eng
    gc.collect()

    trace = tracer.reduce() if tracer else None

    ctx = M.Ctx(window=w, counters=counters, work=arch(cfgj).counts(cfgj),
                peaks=peaks_for(dev.device_kind) if args.trace else None,
                trace=trace,
                extra={"setup_s": setup_s,
                       "prompt_tokens_admitted": sum(
                           s.sched["admitted_tokens"] for s in w.steps
                           if s.sched is not None)})
    chosen = e2e if not args.trace else per_layer
    values = {}
    for m in chosen:
        v = M.reader(m["name"])(ctx)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    ttft, gaps = M.ttfts_ms(w), M.gaps_ms(w)
    half = [r for r in w.records if r.req.due < w.seconds / 2]
    rest = [r for r in w.records if r.req.due >= w.seconds / 2]

    def ttft_p50(rs):
        return M.percentile([(r.first - r.req.due) * 1e3 for r in rs
                             if r.first is not None], 50)
    late = sum(1 for r in w.records if r.first is None or r.first >= w.seconds)
    log(f"backlog: {late} of {len(w.records)} due requests had no first "
        f"token at the close; ttft p50 {ttft_p50(half)} ms (due in the first "
        f"half) vs {ttft_p50(rest)} ms (second half); preemptions "
        f"{counters.get('preemptions')}")
    log(f"samples: {len(ttft)} TTFTs ({len(ttft) // 20} beyond p95), "
        f"{len(gaps)} inter-token gaps; metrics on {dev.device_kind}: "
        f"{json.dumps(values)}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    breakdown = None
    if trace is not None and trace.spans:
        from bench.profile import busy_ns, idle_gaps, top_ops
        lo, hi = trace.window()
        device["busy_s"] = busy_ns(trace, lo, hi) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        breakdown = {"device_ops": [list(x) for x in top_ops(trace)],
                     "idle_gaps": [list(x) for x in idle_gaps(trace)]}

    check = run_check(params, cfgj, w.records, args.seed,
                      control=bool(args.control))
    failed = sum(1 for r in w.records if r.failed)
    correct = passed(check) and len(w.records) > 0
    for k, v in check.items():
        lim = f" (limit {v['limit']})" if "limit" in v else ""
        log(f"check {k}: {v['value']}{lim}")
    result = {"correct": correct, "attempted": len(w.records),
              "failed": failed, "metrics": values, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = check
    print(json.dumps(result), flush=True)
    return 0
