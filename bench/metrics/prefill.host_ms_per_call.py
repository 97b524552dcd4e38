"""Host time per call of the chunked-prefill program in the traced window:
the summed duration of the engine's ``engine.prefill.pack`` and
``engine.prefill.commit`` spans over the number of
``engine.prefill.launch`` spans, on the device trace's clock
(``bench/engine_trace.py``)."""
from bench import engine_trace as ET

PARTS = ("engine.prefill.pack", "engine.prefill.commit")


def read(ctx):
    tr = ET.of(ctx)
    if tr is None:
        return None
    steps = ET.engine_steps(tr)
    calls = sum(len(s.get("engine.prefill.launch", ())) for s in steps)
    return ET.summed_ms(steps, PARTS) / calls if calls else None
