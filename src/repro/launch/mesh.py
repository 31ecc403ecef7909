"""Production mesh construction.

Target hardware: TPU v5e pods — 16×16 = 256 chips per pod; the
multi-pod configuration spans 2 pods = 512 chips with a leading "pod"
axis (DCN between pods, ICI within).

A FUNCTION, not a module constant: importing this module must never
touch jax device state (the dry-run pins the device count before any
jax initialization).
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh


def _make_mesh(shape, axes, devices) -> Mesh:
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import (see launch/dryrun.py)")
    return _make_mesh(shape, axes, devices[:n])


def make_debug_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Tiny mesh over the real local devices (tests)."""
    n = data * model
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {(data, model)}, have "
            f"{len(devices)} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "before any jax import (see launch/dryrun.py)")
    return _make_mesh((data, model), ("data", "model"), devices[:n])


# Hardware constants (TPU v5e) — used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
