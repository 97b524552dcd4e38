"""Output tokens produced inside the window per window second."""


def read(ctx):
    w = ctx.window
    n = sum(1 for r in w.records for t in r.times if t < w.seconds)
    return n / w.seconds if n else None
