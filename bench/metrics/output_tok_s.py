"""Output tokens produced inside the window per window second."""


def read(ctx):
    w = ctx.window
    n = sum(1 for r in w.records + w.warm for t in r.times
            if 0 <= t < w.seconds)
    return n / w.seconds if n else None
