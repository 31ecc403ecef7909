"""Finds a cell's files by the names ``BENCHMARK.json`` gives.

A workload names a configuration (``bench/configs/<config>.json``), a
traffic mix (``bench/traffic/<traffic>.json``) and has an operating
point of its own (``bench/cells/<workload>.json``: rate, slots, cache
length, correctness limits).  Per-layer metrics are readers in
``bench/metrics/<metric>.py``.  A configuration whose block is not the
dense one of ``bench/reference.py`` names its own plain reference,
``"reference": "<name>"`` → ``bench/references/<name>.py``.  Adding a
cell, a mix, a metric or a reference adds files; no file here names
one.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import re
from dataclasses import dataclass, field
from types import ModuleType
from typing import Any, Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
REFERENCES = os.path.join(BENCH, "references")


def _load(*parts: str) -> Dict[str, Any]:
    path = os.path.join(*parts)
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    point: Dict[str, Any]
    end_to_end: List[Dict[str, Any]] = field(default_factory=list)
    per_layer: List[Dict[str, Any]] = field(default_factory=list)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, benchmark: Dict[str, Any] = None) -> Cell:
    bm = benchmark if benchmark is not None else _load(ROOT, "BENCHMARK.json")
    entry = next((w for w in bm["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in bm['workloads']]}")
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config=_load(BENCH, "configs", entry["config"] + ".json"),
        traffic=_load(BENCH, "traffic", entry["traffic"] + ".json"),
        point=_load(BENCH, "cells", workload + ".json"),
        end_to_end=[m for m in bm["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bm["per_layer"] if _applies(m, workload)])


def use_compile_cache() -> str:
    """JAX's persistent compilation cache for a run: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``.jax_cache/`` in the
    checkout (a fixed path: the path is part of the cache's key), with
    every program kept, however fast it compiled, so that a cell's
    second run compiles nothing.  Our own directory is not size-bounded:
    its eviction needs an access-time file per entry, and one missing
    file stops every later write."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


@functools.lru_cache(maxsize=None)
def load_module(path: str, name: str) -> ModuleType:
    """The Python file at ``path``, imported once as module ``name``."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(conf: Dict[str, Any]) -> ModuleType:
    """The plain reference that configuration ``conf`` is checked
    against: ``bench/references/<name>.py`` where the file says
    ``"reference": "<name>"``, else the dense block of
    ``bench/reference.py``.

    A reference module imports nothing of the program and defines
    ``logits(params, conf, pattern, tokens, first, max_len, *,
    fp8=False)``: float32 logits at positions ``first ..
    len(tokens) - 1``, and with ``fp8`` the float8 control.  It may
    define ``matmul_params(conf)``, the matmul parameters active per
    token over all layers (embedding and output head left out), which
    ``work.geometry`` then takes in place of the dense block's count."""
    name = conf.get("reference")
    if name is None:
        from bench import reference as dense
        return dense
    if not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", name):
        raise ValueError(f"reference {name!r} is not a name")
    path = os.path.join(REFERENCES, f"{name}.py")
    try:
        return load_module(path, f"bench_reference_{name}")
    except FileNotFoundError:
        raise FileNotFoundError(
            f"configuration {conf.get('name')!r} names reference "
            f"{name!r}, and there is no file {path}") from None
