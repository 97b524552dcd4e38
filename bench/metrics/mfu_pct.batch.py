"""``mfu_pct`` (``mfu_pct.py``) where it moves ``output_tok_s``: in the
cells judged on the tokens a full batch completes."""
from bench.metrics import reader

read = reader("mfu_pct")
