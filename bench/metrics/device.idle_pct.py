"""Share of the engine steps' time in the traced window in which no
operation ran on the device. Time the harness spends waiting for the next
arrival is not counted."""
from bench.profile import step_busy


def read(ctx):
    if ctx.trace is None:
        return None
    busy, total = step_busy(ctx.trace)
    return 100.0 * (1 - busy / total) if total else None
