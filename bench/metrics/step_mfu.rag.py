"""`step_mfu.rag`: `step_mfu` where it moves `total_tok_s`."""
from bench.readers import step_mfu as read  # noqa: F401
