"""``decode_roofline`` (``decode_roofline.py``) where it moves ``output_tok_s``: in the
cells judged on the tokens a full batch completes."""
from bench.metrics import reader

read = reader("decode_roofline")
