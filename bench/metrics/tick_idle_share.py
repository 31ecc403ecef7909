"""`tick_idle_share`: device idle inside `serve.tick` spans over their
length; see `bench/spans.py`."""
from bench import spans


def read(run):
    return spans.tick_idle_share(spans.of_run(run))
