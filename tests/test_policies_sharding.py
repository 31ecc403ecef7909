"""Static baselines, entropy ranking, sharding rules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.core import policies
from repro.distributed import constrain, logical_rules
from repro.models import model as MD


def test_static_patterns():
    cfg = get_config("stablelm-12b")  # 40 routed layers
    for placement in ("deep", "shallow", "interleave"):
        pat = policies.static_pattern(cfg, 0.5, placement)
        assert pat.shape == (40,)
        assert (pat == 0).sum() == 20
    deep = policies.static_pattern(cfg, 0.25, "deep")
    assert deep[:30].all() and not deep[30:].any()


def test_static_pattern_respects_non_routed():
    cfg = get_config("jamba-1.5-large-398b")  # 9 attn of 72
    pat = policies.static_pattern(cfg, 0.5, "deep")
    routed = cfg.routable_layers()
    assert (pat == 0).sum() == round(0.5 * len(routed))
    for i, k in enumerate(cfg.layer_kinds):
        if k != "attn":
            assert pat[i] == 1  # only attn layers are sparsified


def test_matrix_entropy_orders_information():
    rng = np.random.default_rng(0)
    rich = jnp.asarray(rng.normal(size=(2, 32, 16)), jnp.float32)
    rank1 = jnp.asarray(
        rng.normal(size=(2, 32, 1)) @ rng.normal(size=(1, 16)),
        jnp.float32)
    assert float(policies.matrix_entropy(rich)) > float(
        policies.matrix_entropy(rank1))


def test_entropy_pattern_keeps_high_entropy_layers():
    cfg = smoke_variant(get_config("phi3-mini-3.8b"))
    scores = [0.1, 0.9]
    pat = policies.entropy_pattern(cfg, scores, msr=0.5)
    assert pat[1] == 1 and pat[0] == 0


def test_duo_n_fa_kv():
    cfg = get_config("stablelm-12b")
    assert policies.duo_n_fa_kv(cfg, 0.5) == 4
    assert policies.duo_n_fa_kv(cfg, 1.0) == 1  # at least one


# ---------------------------------------------------------------------------
# Sharding
# ---------------------------------------------------------------------------

def test_constrain_noop_without_rules():
    x = jnp.ones((4, 8))
    y = constrain(x, "batch", "heads")
    assert y is x


def test_param_spec_rules():
    from jax.sharding import PartitionSpec as P
    from repro.launch import shardings as SH
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh(1, 1)

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    spec = SH.param_spec((12288, 12288), FakeMesh())
    assert spec == P("data", "model")
    spec = SH.param_spec((40, 1536, 512), FakeMesh(), skip_leading=1)
    assert spec == P(None, "data", "model")
    # non-divisible dims stay unsharded
    spec = SH.param_spec((7, 13), FakeMesh())
    assert spec == P(None, None)


def test_constrain_divisibility_fallback():
    """8 kv heads on a 16-way model axis must NOT be sharded."""
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh(1, 1)
    rules = {"kv_heads": ("model",), "batch": ("data",)}
    with jax.set_mesh(mesh), logical_rules(rules):
        @jax.jit
        def f(x):
            return constrain(x, "batch", "kv_heads", None, None)
        out = f(jnp.ones((2, 8, 4, 4)))
        assert out.shape == (2, 8, 4, 4)


def test_representative_pattern():
    from repro.launch.workloads import representative_pattern
    cfg = get_config("gemma3-12b")
    pat = representative_pattern(cfg, 0.5)
    assert len(pat) == 48
    routed = [p for p in pat if p is not None]
    assert len(routed) == 8  # 1-in-6 global layers
    assert abs(routed.count("sa") / len(routed) - 0.5) <= 0.13
