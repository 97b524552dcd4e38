"""Process start to the window's open: imports, weights, engine, warm-up."""


def read(ctx):
    return ctx.extra.get("setup_s")
