"""Share of the prompt tokens admitted in the window whose prefill the
prefix cache skipped: over the window's steps, the engine's count of
prefill tokens saved (``prefill_tokens_saved`` of
``ServingEngine.stats()``, counted at admission) over the prompt tokens the
scheduler admitted."""


def read(ctx):
    steps = [s.sched for s in ctx.window.steps if s.sched is not None]
    admitted = sum(s["admitted_tokens"] for s in steps)
    if not admitted:
        return None
    return 100.0 * sum(s["saved_tokens"] for s in steps) / admitted
