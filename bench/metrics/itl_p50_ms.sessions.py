"""``itl_p50_ms`` (``itl_p50_ms.py``) in the session cells, whose runs
spread wider than the open chat's: a bound of its own."""
from bench.metrics import reader

read = reader("itl_p50_ms")
