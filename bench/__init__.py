"""Chip benchmark of the serving stack: open-loop cells named in
``BENCHMARK.json``, run one at a time by ``bench/run.py``."""
