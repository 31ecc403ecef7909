"""`device_idle_share`: 1 - device busy / traced window, from the trace."""
from bench.readers import device_idle_share as read  # noqa: F401
