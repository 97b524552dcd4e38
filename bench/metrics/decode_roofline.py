"""Share of its roofline that the decode program reaches in the traced
window: the least time the chip needs for each decode step (the larger of
its FLOPs over peak FLOP/s and the bytes it must move over peak HBM
bytes/s, from the configuration's architecture,
``bench/arch/<reference>.py``), summed, over the program's device time."""
from bench.profile import step_of


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    steps = ctx.window.steps
    need = spent = 0.0
    for ev in ctx.trace.program("_decode_masked"):
        i = step_of(ctx.trace, ev)
        if i is None or i >= len(steps) or steps[i].sched is None:
            continue
        contexts = steps[i].sched["contexts"]
        if not contexts:
            continue
        flops, nbytes = ctx.work.decode_step(contexts)
        need += max(flops / ctx.peaks.bf16_flops, nbytes / ctx.peaks.hbm_bytes)
        spent += ev.dur / 1e9
    return 100.0 * need / spent if spent else None
