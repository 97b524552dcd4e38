"""Share of the token slots that the prefill program ran over in the traced
window that were padding. The engine counts each call's real chunk tokens
and its padded slots (rows padded to a power of two times the bucketed
length) in ``metrics["prefill_tokens"]`` and
``metrics["prefill_padded_tokens"]``, and gives the same two counts to the
call's ``engine.prefill.pack`` span as ``real`` and ``tokens``; they are
read here from the spans (``bench/engine_trace.py``)."""
from bench import engine_trace as ET


def read(ctx):
    tr = ET.of(ctx)
    if tr is None:
        return None
    packs = [e for e in tr.engine if e.name == "engine.prefill.pack"]
    padded = sum(e.arg("tokens") for e in packs)
    if not padded:
        return None
    return 100.0 * (padded - sum(e.arg("real") for e in packs)) / padded
