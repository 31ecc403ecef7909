"""`host_syncs_per_tick`: mean of the `syncs` count on `serve.tick`;
see `bench/spans.py`."""
from bench import spans


def read(run):
    return spans.host_syncs_per_tick(spans.of_run(run))
