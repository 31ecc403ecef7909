"""`step_mfu`: model FLOPs of the traced window over window × bf16 peak."""
from bench.readers import step_mfu as read  # noqa: F401
