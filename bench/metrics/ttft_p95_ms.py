"""95th percentile of time to first token, from each request's due time."""
from bench.metrics import percentile, ttfts_ms


def read(ctx):
    return percentile(ttfts_ms(ctx.window), 95)
