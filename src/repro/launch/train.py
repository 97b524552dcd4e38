"""Training launcher: synthetic data -> train_step loop with checkpointing,
failure injection, straggler detection, and restart-from-checkpoint.

Examples:
  # reduced llama on CPU, 30 steps, checkpoint every 10
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --steps 30 --batch 4 --seq 128 --ckpt-dir /tmp/ck

  # inject a failure at step 12 and watch the restart path
  ... --inject-failure-at 12
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.ckpt import latest_step, restore, save
from repro.configs import (TrainConfig, get_config, reduce_for_smoke,
                           with_blocks)
from repro.data.pipeline import make_pipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step
from repro.models import MeshInfo, NO_MESH, init_params, model_specs
from repro.models.params import shardings as spec_shardings
from repro.optim import init_opt_state, opt_state_specs
from repro.runtime.ft import (FailureInjector, StragglerDetector,
                              run_with_restarts)


def build(args):
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    if args.layers:
        cfg = with_blocks(cfg, args.layers)
    if args.d_model:
        head = max(args.d_model // max(cfg.n_heads, 1), 8)
        # scale_embeddings: from-scratch stability — with 0.02-init embeddings
        # the first rmsnorm's 1/rms amplifies backward ~50x into the tied
        # table (measured gnorm 2.6e6 -> 5e2 with the sqrt(d) scale).
        cfg = dataclasses.replace(cfg, d_model=args.d_model, d_head=head,
                                  d_ff=4 * args.d_model,
                                  vocab_size=min(cfg.vocab_size, 32768),
                                  scale_embeddings=True)
    return cfg


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=0, dest="d_model")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "int8_ef"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--inject-failure-at", type=int, default=-1)
    ap.add_argument("--mesh", default="", help="e.g. 4,1 -> data=4,model=1")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=1)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = build(args)
    tc = TrainConfig(lr=args.lr, total_steps=args.steps,
                     warmup_steps=max(args.steps // 10, 1),
                     grad_compression=args.grad_compression,
                     microbatches=args.microbatches)
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split(","))
        mesh = make_host_mesh(data=d, model=m)
        mi = MeshInfo(mesh)
    else:
        mesh, mi = None, NO_MESH

    injector = FailureInjector(
        fail_at_steps=(args.inject_failure_at,) if args.inject_failure_at >= 0
        else ())
    straggler = StragglerDetector()
    executor = ThreadPoolExecutor(max_workers=1)
    step_fn = make_train_step(cfg, tc, mi)
    if mesh is not None:
        with jax.set_mesh(mesh):
            step_fn = jax.jit(step_fn, donate_argnums=(0, 1))
    else:
        step_fn = jax.jit(step_fn, donate_argnums=(0, 1))

    def train_loop(resume) -> int:
        params = init_params(cfg, jax.random.key(args.seed))
        opt = init_opt_state(params, with_ef=tc.grad_compression == "int8_ef")
        start = 0
        if resume is not None and args.ckpt_dir:
            step = latest_step(args.ckpt_dir)
            if step is not None:
                shard_tree = None
                if mesh is not None:
                    specs = model_specs(cfg)
                    shard_tree = {
                        "params": spec_shardings(specs, mesh),
                        "opt": spec_shardings(
                            opt_state_specs(
                                specs,
                                with_ef=tc.grad_compression == "int8_ef"),
                            mesh)}
                state = restore(args.ckpt_dir, step,
                                {"params": params, "opt": opt},
                                shardings=shard_tree)
                params, opt = state["params"], state["opt"]
                start = step
                print(f"[train] restored step {step}", flush=True)
        data = make_pipeline(cfg.vocab_size, args.batch, args.seq, args.seed)
        pending = None
        t_all = time.time()
        try:
            for step in range(start, args.steps):
                injector.check(step)
                toks, labels = next(data)
                batch = {"tokens": jnp.asarray(toks),
                         "labels": jnp.asarray(labels)}
                if cfg.is_encdec:
                    batch["enc_x"] = jnp.zeros((args.batch, 32, cfg.d_model),
                                               jnp.dtype(cfg.activation_dtype))
                elif cfg.n_image_tokens:
                    batch["img_x"] = jnp.zeros(
                        (args.batch, cfg.n_image_tokens, cfg.d_model),
                        jnp.dtype(cfg.activation_dtype))
                t0 = time.time()
                params, opt, metrics = step_fn(params, opt, batch)
                loss = float(metrics["loss"])
                dt = time.time() - t0
                slow = straggler.record(step, dt)
                if step % args.log_every == 0:
                    print(f"step {step:5d} loss {loss:.4f} "
                          f"gnorm {float(metrics['grad_norm']):.3f} "
                          f"lr {float(metrics['lr']):.2e} {dt*1e3:.0f}ms"
                          + ("  [straggler]" if slow else ""), flush=True)
                if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                    if pending is not None:
                        pending.result()
                    pending = save(args.ckpt_dir, step + 1,
                                   {"params": params, "opt": opt},
                                   executor=executor)
        finally:
            # Commit any in-flight async checkpoint even when a failure is
            # raised mid-loop: without this the crash loses the last save and
            # the restart silently begins from step 0.
            if pending is not None:
                pending.result()
        data.close()
        print(f"[train] done {args.steps - start} steps in "
              f"{time.time()-t_all:.1f}s; stragglers={len(straggler.events)}",
              flush=True)
        return args.steps

    run_with_restarts(train_loop, max_restarts=3)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
