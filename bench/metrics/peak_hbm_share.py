"""`peak_hbm_share`: see `bench/readers.py`."""
from bench.readers import peak_hbm_share as read  # noqa: F401
