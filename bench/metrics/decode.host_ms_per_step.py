"""Host time per decode step in the traced window: over the engine steps
that launch the decode program, the mean summed duration of the engine's
``engine.decode.pack``, ``engine.tables`` and ``engine.decode.commit``
spans, on the device trace's clock (``bench/engine_trace.py``)."""
from bench import engine_trace as ET

PARTS = ("engine.decode.pack", "engine.tables", "engine.decode.commit")


def read(ctx):
    tr = ET.of(ctx)
    if tr is None:
        return None
    steps = [s for s in ET.engine_steps(tr) if "engine.decode.launch" in s]
    return ET.summed_ms(steps, PARTS) / len(steps) if steps else None
