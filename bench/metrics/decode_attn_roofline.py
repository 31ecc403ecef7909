"""`decode_attn_roofline`: see `bench/readers.py`."""
from bench.readers import decode_attn_roofline as read  # noqa: F401
