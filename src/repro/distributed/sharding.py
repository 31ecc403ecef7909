"""Mesh-agnostic sharding hints.

Model code calls ``constrain(x, *axes)`` with *logical* axis names; the
launch layer activates a mesh (``jax.set_mesh``) and a logical→
mesh-axis mapping (``logical_rules``).  Outside any mesh context the
hints are no-ops, so unit tests and CPU smoke runs are unaffected.

Divisibility is checked per dimension against the live (abstract) mesh:
axes that do not evenly divide a dim are dropped (e.g. 8 KV heads on a
16-way "model" axis → replicated KV, the standard GQA-TP fallback).
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Callable, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

_state = threading.local()


def _rules() -> Optional[Dict[str, Optional[Tuple[str, ...]]]]:
    return getattr(_state, "rules", None)


@contextmanager
def logical_rules(rules: Dict[str, Optional[Tuple[str, ...]]]):
    """Activate a logical→mesh axis mapping (launch layer only)."""
    prev = _rules()
    _state.rules = rules
    try:
        yield
    finally:
        _state.rules = prev


def live_mesh():
    """The mesh ``jax.set_mesh`` made ambient, or None outside one."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or not mesh.axis_names:
        return None
    return mesh


def constrain(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical axis names; no-op without an
    active mesh context or rule set."""
    rules = _rules()
    if rules is None:
        return x
    mesh = live_mesh()
    if mesh is None:
        return x
    spec = []
    used: set = set()  # a mesh axis may shard at most one dim
    for dim, a in zip(x.shape, axes):
        mapped = rules.get(a) if a is not None else None
        if mapped is None:
            spec.append(None)
            continue
        usable = [ax for ax in mapped
                  if ax in mesh.axis_names and ax not in used]
        total = int(np.prod([mesh.shape[ax] for ax in usable])) if usable \
            else 0
        if usable and total and dim % total == 0 and dim >= total:
            used.update(usable)
            spec.append(tuple(usable) if len(usable) > 1 else usable[0])
            continue
        picked = None  # single-axis fallback
        for ax in usable:
            s = mesh.shape[ax]
            if dim % s == 0 and dim >= s:
                picked = ax
                used.add(ax)
                break
        spec.append(picked)
    try:
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except (ValueError, TypeError):
        return x


def over_heads(fn: Callable, n_heads: int, n_kv_heads: int) -> Callable:
    """Run a Mosaic (Pallas TPU) attention call ``fn(q, k, v, *rest)``
    under the live mesh.

    The compiler cannot partition a Mosaic kernel, so under a mesh the
    call must sit inside a ``shard_map``.  Attention heads are
    independent, so when the "model" axis divides both the query and
    the KV heads each device runs ``fn`` on its own heads — q, k, v and
    the output split on dim 1, ``rest`` replicated — and no collective
    is needed.  When it does not divide them, every device runs the
    whole call on replicated operands.  Outside a mesh ``fn`` is
    returned as is."""
    mesh = live_mesh()
    if mesh is None:
        return fn
    model = mesh.shape.get("model", 1)
    split = model > 1 and n_heads % model == 0 and n_kv_heads % model == 0
    heads = P(None, "model") if split else P()

    def wrapped(*args):
        specs = (heads,) * 3 + (P(),) * (len(args) - 3)
        return jax.shard_map(fn, mesh=mesh, in_specs=specs,
                             out_specs=heads, check_vma=False)(*args)
    return wrapped
