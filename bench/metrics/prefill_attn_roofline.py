"""`prefill_attn_roofline`: see `bench/readers.py`."""
from bench.readers import prefill_attn_roofline as read  # noqa: F401
