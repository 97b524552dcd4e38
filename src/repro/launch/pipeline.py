"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

The block stack (n_blocks, ...) is sharded over stages; microbatches flow
stage-to-stage via collective_permute (lax.ppermute). The schedule is the
classic GPipe fill-drain: T = n_micro + n_stages - 1 ticks, bubble fraction
(n_stages-1)/T. Backward is jax.grad through the loop (ppermute transposes
to the reverse permute), i.e. activations are stashed per tick.

This is an optional execution mode (off for the assigned production meshes,
which use DP x TP); it exists so the framework scales depth-wise across pods
— e.g. mesh ("stage", "data") with the pod axis as "stage".
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(block_params, x_micro, apply_stage: Callable, mesh,
                   stage_axis: str = "stage"):
    """Run microbatches through stage-sharded blocks.

    block_params: pytree, leaves (n_blocks, ...) — sharded over stage_axis.
    x_micro:      (n_micro, mb, S, d) microbatched activations (replicated).
    apply_stage:  fn(stage_block_params, x) -> x, applying the local blocks.

    Returns (n_micro, mb, S, d) outputs (replicated).
    """
    n_stages = mesh.shape[stage_axis]
    n_micro = x_micro.shape[0]
    T = n_micro + n_stages - 1

    def per_stage(p_loc, xs):
        s = jax.lax.axis_index(stage_axis)
        # carries become stage-varying after the first ppermute; mark them so
        varying = lambda v: jax.lax.pcast(v, (stage_axis,), to="varying")
        zero = varying(jnp.zeros_like(xs[0]))
        outs0 = varying(jnp.zeros_like(xs))
        xs = varying(xs)

        def tick(t, state):
            cur, outs = state
            # stage 0 injects microbatch t (when in range)
            mb_in = jnp.clip(t, 0, n_micro - 1)
            inject = xs[mb_in]
            cur = jnp.where(s == 0, inject, cur)
            y = apply_stage(p_loc, cur)
            # last stage records microbatch t-(n_stages-1)
            mb_out = t - (n_stages - 1)
            valid_out = jnp.logical_and(s == n_stages - 1,
                                        jnp.logical_and(mb_out >= 0,
                                                        mb_out < n_micro))
            idx = jnp.clip(mb_out, 0, n_micro - 1)
            outs = jnp.where(valid_out,
                             jax.lax.dynamic_update_index_in_dim(
                                 outs, y, idx, 0),
                             outs)
            # shift activations down the pipe
            perm = [(i, i + 1) for i in range(n_stages - 1)]
            nxt = jax.lax.ppermute(y, stage_axis, perm)
            return (nxt, outs)

        (_, outs) = jax.lax.fori_loop(0, T, tick, (zero, outs0))
        # distribute the last stage's outputs to everyone
        outs = jax.lax.psum(
            jnp.where(s == n_stages - 1, outs, jnp.zeros_like(outs)),
            stage_axis)
        return outs

    in_block_spec = jax.tree.map(lambda _: P(stage_axis), block_params)
    return jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(in_block_spec, P()),
        out_specs=P(),
    )(block_params, x_micro)
