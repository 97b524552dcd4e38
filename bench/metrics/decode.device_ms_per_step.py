"""Device time of one call of the decode program, averaged over its calls
in the traced window."""


def read(ctx):
    if ctx.trace is None:
        return None
    ev = ctx.trace.program("_decode_masked")
    if not ev:
        return None
    return sum(e.dur for e in ev) / len(ev) / 1e6
