"""Distributed decode (shard_map LSE combine) — exactness vs the local
path on a 1-device mesh (semantics are mesh-size independent: the
combine is an exact softmax decomposition)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from repro.distributed.decode import (lse_combine_decode,
                                      make_distributed_dot_decode)
from repro.launch.mesh import make_debug_mesh
from repro.models import model as MD


def test_lse_combine_matches_local():
    rng = np.random.default_rng(0)
    B, Hq, Hkv, S, D = 2, 4, 2, 64, 16
    q = jnp.asarray(rng.normal(size=(B, Hq, 1, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    valid = jnp.arange(S) <= 40
    mesh = make_debug_mesh(1, 1)
    # the mesh is passed explicitly (shard_map mesh=...) — no ambient
    # jax.set_mesh needed
    out = lse_combine_decode(q, k, v, valid, mesh, ("data",))
    ref = MD._dot_decode(q, k, v, valid)
    assert float(jnp.abs(out - ref).max()) < 2e-5


def test_adapter_declines_small_cache():
    mesh = make_debug_mesh(1, 1)
    fn = make_distributed_dot_decode(mesh, ("data",), min_seq=128)
    q = jnp.zeros((1, 2, 1, 8))
    k = v = jnp.zeros((1, 2, 64, 8))
    assert fn(q, k, v, jnp.ones(64, bool)) is None


def test_override_context():
    rng = np.random.default_rng(1)
    B, Hq, Hkv, S, D = 1, 2, 2, 32, 8
    q = jnp.asarray(rng.normal(size=(B, Hq, 1, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    valid = jnp.ones(S, bool)
    marker = {}

    def fake(q, k, v, valid):
        marker["hit"] = True
        return None  # decline → falls back to local

    with MD.use_decode_attn(fake):
        out = MD._dot_decode(q, k, v, valid)
    assert marker.get("hit")
    ref = MD._dot_decode(q, k, v, valid)
    assert float(jnp.abs(out - ref).max()) == 0.0


# ---------------------------------------------------------------------------
# jax-compat shims (launch/mesh.py, distributed/decode.py)
# ---------------------------------------------------------------------------

def test_make_debug_mesh_raises_when_devices_short():
    """Short device counts must fail loudly at mesh construction, not
    as an opaque jax.make_mesh shape error — the message names the fix
    (the XLA_FLAGS host-device override)."""
    n = len(jax.devices()) + 1
    with pytest.raises(RuntimeError, match="host_platform_device_count"):
        make_debug_mesh(1, n)


def test_mesh_context_is_usable_on_any_jax_version():
    """``jax.set_mesh`` activates a debug mesh as a working context
    manager, and the sharding hints see it as the live mesh."""
    from repro.distributed.sharding import live_mesh
    mesh = make_debug_mesh(1, 1)
    assert live_mesh() is None
    with jax.set_mesh(mesh):
        assert live_mesh().axis_names == ("data", "model")
        out = jnp.arange(4.0) + 1
    assert float(out.sum()) == 10.0


def test_shard_map_wrapper_accepts_both_check_kwargs():
    """``jax.shard_map`` builds a working map with the replication
    check on and off."""
    mesh = make_debug_mesh(1, 1)
    x = jnp.arange(4.0)
    for flag in (False, True):
        f = jax.shard_map(lambda a: a * 2, mesh=mesh, in_specs=(P(),),
                          out_specs=P(), check_vma=flag)
        assert np.array_equal(np.asarray(f(x)), np.arange(4.0) * 2)


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs 4 devices (XLA_FLAGS="
                           "--xla_force_host_platform_device_count=8)")
def test_flat_axis_index_over_multi_axis_mesh():
    """Row-major flattening over ("data", "model") on a (2, 2) mesh:
    shard (d, m) gets flat index d·model_size + m, matching the device
    order of a P(("data", "model")) output sharding."""
    from repro.distributed.decode import _flat_axis_index
    mesh = make_debug_mesh(2, 2)
    out = jax.shard_map(
        lambda: _flat_axis_index(("data", "model")).reshape(1),
        mesh=mesh, in_specs=(), out_specs=P(("data", "model")),
        check_vma=False)()
    assert np.array_equal(np.asarray(out), np.arange(4))


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs 4 devices (XLA_FLAGS="
                           "--xla_force_host_platform_device_count=8)")
def test_lse_combine_exact_over_multi_axis_kv_shards():
    """The LSE combine is an exact softmax decomposition regardless of
    how many mesh axes split the sequence: (2, 2) over both axes must
    match the local reference to float32 tolerance."""
    rng = np.random.default_rng(2)
    B, Hq, Hkv, S, D = 1, 4, 2, 64, 16
    q = jnp.asarray(rng.normal(size=(B, Hq, 1, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    valid = jnp.arange(S) <= 50
    mesh = make_debug_mesh(2, 2)
    out = lse_combine_decode(q, k, v, valid, mesh, ("data", "model"))
    ref = MD._dot_decode(q, k, v, valid)
    assert float(jnp.abs(out - ref).max()) < 2e-5


# ---------------------------------------------------------------------------
# Distributed adapter trace protocol (same vocabulary as the Pallas
# kernel adapter — engine counters replay these verbatim)
# ---------------------------------------------------------------------------

def test_distributed_adapter_trace_protocol():
    mesh = make_debug_mesh(1, 1)
    fn = make_distributed_dot_decode(mesh, ("data",), min_seq=128)
    assert fn.supports_pooled is False and fn.supports_scale is True
    assert fn.min_len == 128
    q = jnp.zeros((1, 2, 1, 8))
    k = v = jnp.zeros((1, 2, 64, 8))
    # decline: cache below min_seq
    assert fn(q, k, v, jnp.ones(64, bool)) is None
    # decline: pooled per-slot mask (rank 2)
    assert fn(q, k, v, jnp.ones((1, 64), bool)) is None
    assert fn.drain_log() == [("decline", "min_len"),
                              ("decline", "mask_rank")]
    assert fn.trace_log == []  # drain clears in place
    # hit: long-enough cache with a shared mask
    k2 = v2 = jnp.zeros((1, 2, 128, 8))
    assert fn(q, k2, v2, jnp.ones(128, bool)) is not None
    assert fn.drain_log() == [("hit", "lse_combine")]


def test_distributed_adapter_decline_reasons_are_engine_vocabulary():
    """Every decline reason the adapter can emit must be pre-registered
    by the engine's counter set — a new reason label would otherwise
    silently never export."""
    from repro.serve.engine import DECODE_KERNEL_DECLINE_REASONS
    mesh = make_debug_mesh(1, 1)
    fn = make_distributed_dot_decode(mesh, ("data",), min_seq=128)
    q = jnp.zeros((1, 2, 1, 8))
    k = v = jnp.zeros((1, 2, 64, 8))
    fn(q, k, v, jnp.ones(64, bool))
    fn(q, k, v, jnp.ones((1, 64), bool))
    for event, reason in fn.drain_log():
        assert event == "decline"
        assert reason in DECODE_KERNEL_DECLINE_REASONS
