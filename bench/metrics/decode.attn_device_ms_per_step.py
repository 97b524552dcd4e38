"""Device time of the decode program's attention per decode call in the
traced window: its operations under the ``attention`` scope (the page
gathers through the block tables and the attention math), over its calls
(``bench/engine_trace.py``)."""
from bench import engine_trace as ET


def read(ctx):
    tr = ET.of(ctx)
    if tr is None:
        return None
    n = len(tr.program(ET.DECODE))
    secs = ET.by_scope(tr)["attention"]
    return secs * 1e3 / n if n and secs else None
