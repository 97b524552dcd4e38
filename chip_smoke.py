"""Smoke run of the served path on one TPU chip.

  python chip_smoke.py [--seed 0]

One process, no fallback: it exits non-zero, and prints no result, unless
JAX's first device is a TPU. It builds qwen2-7b at its published widths with
only the depth cut, to the bring-up depth of 14 of 28 layers (28 layers'
weights plus the page pool exceed one v5e's HBM), makes the weights from
``--seed`` on the device, and serves seeded requests through the engine
``launch/serve.py`` builds: the continuous
scheduler over the global zero-copy page pool. It serves them twice, with
the pure-JAX decode attention forced and with the ragged paged-decode
kernel the engine chooses on a TPU, then checks the kernel against a
float32 reference on the live pool, tables and lengths, and that the
compiled decode step holds the kernel. Every wall time
ends in ``block_until_ready`` and names the device; every other number is a
count. The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import pathlib
import sys
import time

import jax
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-7b"
LAYERS = 14          # of 28: the bring-up depth, not a measured limit
REQUESTS = 8
MAX_TOKENS = 32
SLOTS = 8
MAX_LEN = 2048
PAGE = 16
POOL_TOKENS = 65536
PROMPT_LENS = (128, 1024)
# Kernel vs reference: both read the same bf16 pool and compute in float32,
# so they differ by summation order and exp rounding (~1e-6 relative over
# <= 2048 keys). q holds bf16 values, so q.k is exact even in one bf16 pass:
# the limit cannot see that dot's precision. allclose allows
# atol + rtol * |ref| per element, up to ~1.2e-2 where |ref| reaches ~121
# (random weights, PERF.md). What fails it is a wrong page read, or the
# probabilities rounded to bf16 in the PV product: the served pure-JAX
# decode attention at TPU default precision does that, and ``main`` asserts
# that this control fails the limit.
KERNEL_TOL = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu():
    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX's device is {d.platform!r}, not a "
                         "TPU; this script never falls back to another "
                         "platform")
    return d


def make_prompts(n: int, vocab: int, lens, seed: int):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(lens[0], lens[1] + 1, size=n)
    return [rng.integers(0, vocab, size=int(k)).tolist() for k in sizes]


def _first_kv(cache):
    from repro.models.attention import PagedKV
    leaves = jax.tree_util.tree_leaves(
        cache, is_leaf=lambda x: isinstance(x, PagedKV))
    return next(x for x in leaves if isinstance(x, PagedKV))


def _decoding_slots(eng):
    return [eng.buffer.slot_of(sid) for sid in eng.active
            if eng.buffer.is_decoding(eng.buffer.slot_of(sid))]


def serve(eng, prompts, max_tokens: int, *, snapshot: bool = False):
    """Serve ``prompts`` to completion through ``eng.step``. With
    ``snapshot``, also return the live decode state (layer 0's pools, the
    block tables, per-slot KV lengths) at the step where the most sequences
    were decoding."""
    rids = [eng.submit(p, max_tokens=max_tokens) for p in prompts]
    finished, snap, best = {}, None, 0
    while eng.has_work:
        eng.step(finished)
        if not snapshot:
            continue
        slots = _decoding_slots(eng)
        if len(slots) > best:
            best = len(slots)
            kv = _first_kv(eng.cache)
            lengths = np.zeros((eng.n_slots,), np.int32)
            # the newest token of a decoding sequence is pending: its KV is
            # written by the next step
            lengths[slots] = eng.mgr.lengths[slots] - 1
            # layer 0 sliced out: a copy the next step's donation leaves
            snap = {"k_pool": kv.k_pool[0], "v_pool": kv.v_pool[0],
                    "tables": eng.mgr.tables.copy(), "lengths": lengths}
    jax.block_until_ready(eng.cache)
    return [finished[r].out_tokens for r in rids], snap


@contextlib.contextmanager
def jax_decode_path():
    """Engines built and served inside take the pure-JAX decode attention:
    the one choice of path (``attention.ragged_decode_serves``), forced."""
    from repro.models import attention
    serves = attention.ragged_decode_serves
    attention.ragged_decode_serves = lambda kv: False
    try:
        yield
    finally:
        attention.ragged_decode_serves = serves


def within_tol(out, ref) -> bool:
    return bool(np.allclose(out, ref, rtol=KERNEL_TOL, atol=KERNEL_TOL))


def kernel_check(snap, n_heads: int, seed: int) -> dict:
    """The ragged paged decode on a live pool vs the float32 reference, on
    the decoding slots. Also reports how far the served pure-JAX decode
    attention, at default precision, lands from the same reference."""
    import jax.numpy as jnp
    from repro.kernels.paged_attention.ops import paged_decode
    from repro.kernels.paged_attention.ref import paged_attention_global_ref
    from repro.models.attention import PagedKV, paged_decode_attention
    k_pool, v_pool = snap["k_pool"], snap["v_pool"]
    tables = jnp.asarray(snap["tables"])
    lengths = jnp.asarray(snap["lengths"])
    B, D = tables.shape[0], k_pool.shape[-1]
    # queries hold activation-dtype values, as the served decode step's do
    q = jax.random.normal(jax.random.key(seed), (B, n_heads, D)) \
        .astype(k_pool.dtype).astype(jnp.float32)
    out = paged_decode(q, k_pool, v_pool, tables, lengths)
    with jax_decode_path():
        served_jax = paged_decode_attention(
            q[:, None], PagedKV(k_pool, v_pool, tables, lengths))[:, 0]
    with jax.default_matmul_precision("highest"):
        ref = paged_attention_global_ref(
            q, k_pool.astype(jnp.float32), v_pool.astype(jnp.float32),
            tables, lengths)
    rows = np.asarray(snap["lengths"]) > 0
    out = np.asarray(jax.block_until_ready(out))[rows]
    ref = np.asarray(ref)[rows]
    assert np.all(np.isfinite(out)), "kernel output is not finite"
    np.testing.assert_allclose(out, ref, rtol=KERNEL_TOL, atol=KERNEL_TOL)
    served_jax = np.asarray(served_jax)[rows]
    return {"err": float(np.max(np.abs(out - ref))),
            "jax_err": float(np.max(np.abs(served_jax - ref))),
            "jax_within": within_tol(served_jax, ref),
            "scale": float(np.max(np.abs(ref))), "rows": int(rows.sum())}


def first_divergence(a, b):
    """Index of the first differing token of two generations (None if
    identical)."""
    return next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)


def check_lengths(outs, max_tokens: int) -> None:
    got = [len(t) for t in outs]
    assert got == [max_tokens] * len(outs), \
        f"tokens per request {got}, expected {max_tokens} each"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = require_tpu()
    kind = dev.device_kind
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import build_config, build_engine
    from repro.models import init_params, model_specs
    from repro.models.params import param_bytes

    log(f"compile cache: {enable_compile_cache()}")

    full = get_config(ARCH)
    cfg = build_config(ARCH, layers=LAYERS)
    pool_pages = POOL_TOKENS // PAGE
    kv_token_bytes = (2 * cfg.n_kv_heads * cfg.d_head
                      * np.dtype(cfg.activation_dtype).itemsize)
    gib = 2.0 ** 30
    full_bytes = (param_bytes(model_specs(full))
                  + full.n_layers * POOL_TOKENS * kv_token_bytes)
    limit = (dev.memory_stats() or {}).get("bytes_limit", 0)
    log(f"model: {ARCH} d_model={cfg.d_model} heads={cfg.n_heads}/"
        f"{cfg.n_kv_heads}x{cfg.d_head} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} (published widths)")
    log(f"depth cut: {cfg.n_layers} of {full.n_layers} layers, the bring-up "
        f"depth; {full.n_layers} layers + {POOL_TOKENS}-token pool need "
        f"{full_bytes / gib:.2f} GiB, device bytes_limit "
        f"{limit / gib:.2f} GiB")
    log(f"engine: scheduler=continuous offload=zero_copy slots={SLOTS} "
        f"max_len={MAX_LEN} page={PAGE} pool={POOL_TOKENS} tokens "
        f"({cfg.n_layers * POOL_TOKENS * kv_token_bytes / gib:.2f} GiB)")

    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(cfg, jax.random.key(args.seed)))
    log(f"init params: {param_bytes(model_specs(cfg)) / gib:.2f} GiB in "
        f"{time.perf_counter() - t0:.3f} s wall on {kind} (compiles included)")

    prompts = make_prompts(REQUESTS, cfg.vocab_size, PROMPT_LENS,
                           args.seed)
    log(f"requests: {len(prompts)} prompts of {min(map(len, prompts))}-"
        f"{max(map(len, prompts))} tokens, max_tokens={MAX_TOKENS}")

    outs = {}
    for backend in ("jax", "pallas"):
        with (jax_decode_path() if backend == "jax"
              else contextlib.nullcontext()):
            eng = build_engine(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                               page_size=PAGE, pool_pages=pool_pages)
            t0 = time.perf_counter()
            outs[backend], snap = serve(eng, prompts, MAX_TOKENS,
                                        snapshot=backend == "pallas")
        wall = time.perf_counter() - t0
        check_lengths(outs[backend], MAX_TOKENS)
        s = eng.stats()
        log(f"serve[{backend}]: {len(prompts)} requests x "
            f"{MAX_TOKENS} tokens, scheduler={eng.scheduler_mode}; "
            f"output tokens={sum(map(len, outs[backend]))} "
            f"decode tokens={s['tokens']} decode_steps={s['decode_steps']} "
            f"prefills={s['prefills']} staging_copies={s['staging_copies']} "
            f"preemptions={s['sched']['preemptions']}; {wall:.3f} s wall "
            f"on {kind} (compiles included)")
        if backend == "pallas":
            hlo = eng.decode_step_hlo()
            n_calls = hlo.count("tpu_custom_call")
            assert n_calls, "compiled Pallas decode step holds no kernel"
            log(f"pallas decode step: tpu_custom_call x{n_calls} in the "
                "compiled program")
        eng.cache = None
        del eng
        gc.collect()

    div = [first_divergence(a, b)
           for a, b in zip(outs["jax"], outs["pallas"])]
    log(f"token agreement jax vs pallas: "
        f"{sum(d is None for d in div)}/{len(prompts)} requests identical; "
        f"first differing token per request {div} (argmax ties flip on "
        "rounding; not a check)")

    kc = kernel_check(snap, cfg.n_heads, args.seed)
    log(f"kernel vs float32 reference on the live pool (layer 0, "
        f"{kc['rows']} decoding slots): max abs err {kc['err']:.3e} (max "
        f"|ref| {kc['scale']:.3e}), tolerance rtol=atol={KERNEL_TOL}; "
        f"control, the served pure-JAX decode attention at default "
        f"precision: max abs err {kc['jax_err']:.3e}, within tolerance: "
        f"{kc['jax_within']}")
    assert not kc["jax_within"], \
        "the default-precision control passes the kernel tolerance: the " \
        "check cannot tell bf16 PV rounding from float32"

    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"peak_bytes_in_use: {peak} ({(peak or 0) / gib:.2f} GiB) on {kind}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
