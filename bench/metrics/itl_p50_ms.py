"""Median of all gaps between consecutive output tokens of a request."""
from bench.metrics import gaps_ms, percentile


def read(ctx):
    return percentile(gaps_ms(ctx.window), 50)
