"""Model FLOP utilization of the whole engine step in the traced window:
useful FLOPs of the steps (decodes over their live lengths, prefill chunks
over their context, the LM head where logits are taken; no padding, from
the configuration's architecture, ``bench/arch/<reference>.py``) over the
steps' host-span time times peak bf16 FLOP/s."""


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    steps = ctx.window.steps
    flops = secs = 0.0
    for i, span in ctx.trace.steps().items():
        if i >= len(steps) or steps[i].sched is None:
            continue
        s = steps[i].sched
        flops += ctx.work.step_flops(s["contexts"], s["chunks"])
        secs += span.dur / 1e9
    return 100.0 * flops / (secs * ctx.peaks.bf16_flops) if secs else None
