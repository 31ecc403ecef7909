"""`tick_host_ms`: mean `serve.tick` length less its `serve.wait`
children; see `bench/spans.py`."""
from bench import spans


def read(run):
    return spans.tick_host_ms(spans.of_run(run))
