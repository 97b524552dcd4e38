"""Host time of the scheduler per engine step: the engine's ``admit_s``
counter (host clock around ``Scheduler.schedule``) over the window's
steps."""


def read(ctx):
    n = len(ctx.window.steps)
    if not n or "admit_s" not in ctx.counters:
        return None
    return ctx.counters["admit_s"] * 1e3 / n
