"""``chip_smoke.py`` off the chip: its serving checks pass at a tiny size
on the CPU (Pallas kernels in interpret mode), and its entry point
refuses to run — printing no result — where JAX finds no TPU."""
import dataclasses
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, smoke_variant
from repro.launch.mesh import make_debug_mesh

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod   # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


def _tiny(smoke, dtype=jnp.float32):
    # smoke flux geometry: sink 8 + local 32 = a 40-slot ring, which the
    # 64-token prompts outgrow
    cfg = smoke_variant(get_config("phi3-mini-3.8b")).replace(
        num_layers=4, dtype=dtype, param_dtype=dtype)
    plan = smoke.Plan(cfg.name, (64, 64, 32, 32), max_len=80, n_steps=8,
                      prefill_chunk=32, block_k=16, decode_chunk=4,
                      ref_steps=2)
    return cfg, plan


def test_one_chip_plan_passes_its_checks_at_tiny_size(smoke):
    cfg, plan = _tiny(smoke)
    lines = []
    assert smoke.run(cfg, plan, seed=0, log=lines.append) == [], lines
    assert any("pooled_ring" in l for l in lines)


def test_tp_plan_passes_its_checks_on_a_mesh(smoke):
    """The four-chip path at tiny size on every local device as the
    model axis (a (1, 1) mesh on one CPU device still routes both
    kernels through their shard_map)."""
    cfg, plan = _tiny(smoke)
    mesh = make_debug_mesh(1, len(jax.devices()))
    lines = []
    plan = dataclasses.replace(plan, prompt_lens=(64, 32))
    assert smoke.run(cfg, plan, seed=1, mesh=mesh,
                     log=lines.append) == [], lines


def test_entry_point_refuses_without_a_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
