"""The serving path's Pallas kernels compile for a TPU v5e.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: block shapes off the (8, 128) tiling, scoped-VMEM
overruns, or a Mosaic kernel left for the partitioner under a mesh.
These tests compile each kernel at real widths for a described
``v5e:2x2`` topology — no chip attached, nothing runs:

  * the pooled decode kernel at phi3-mini widths, over a FullKV buffer
    and an SA ring, at a head dim of 128, and at the command-r-plus
    stage's widths (12 query heads a KV head, 8 slots, a 49664 buffer
    and a 2176 ring);
  * the block-sparse chunked-prefill kernel at phi3-mini widths;
  * both kernels shard_mapped over a 4-device (1, 4) mesh at
    stablelm-12b widths (32 query / 8 KV heads of 160), the way
    tensor-parallel serving runs them.

The topology is described only inside the module fixture: describing it
loads the TPU library, which one process at a time may hold.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import modes as M
from repro.kernels.decode_attention import (PooledValid,
                                            make_kernel_decode_attn)

BLOCK = 128
PHI3 = dict(heads=32, kv_heads=32, head_dim=96)
STABLELM = dict(heads=32, kv_heads=8, head_dim=160)
HEAD_DIM_128 = dict(heads=32, kv_heads=8, head_dim=128)
CMDR_STAGE = dict(heads=96, kv_heads=8, head_dim=128)   # G = 12
FULL_LEN = 4224   # 4096-token context + 128 of decode, block-aligned
RING_LEN = 2176   # sink 128 + local 2048
STAGE_LEN = 49664  # 48k-token RAG prompt + 512 of decode
SLOTS = 4
STAGE_SLOTS = 8
CHUNK = 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tp4(topo):
    return Mesh(np.asarray(topo.devices[:4]).reshape(1, 4),
                ("data", "model"))


def _decode_fn(q, k, v, mask, lengths, positions):
    fn = make_kernel_decode_attn(block_k=BLOCK, interpret=False)
    out = fn(q, k, v, PooledValid(mask=mask, lengths=lengths,
                                  positions=positions))
    assert out is not None, fn.drain_log()
    return out


def _decode_args(spec, length, on, heads_on, slots=SLOTS):
    H, Hkv, D = spec["heads"], spec["kv_heads"], spec["head_dim"]
    bf = jnp.bfloat16
    return (jax.ShapeDtypeStruct((slots, H, 1, D), bf, sharding=heads_on),
            jax.ShapeDtypeStruct((slots, Hkv, length, D), bf,
                                 sharding=heads_on),
            jax.ShapeDtypeStruct((slots, Hkv, length, D), bf,
                                 sharding=heads_on),
            jax.ShapeDtypeStruct((slots, 1, length), jnp.bool_, sharding=on),
            jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=on),
            jax.ShapeDtypeStruct((slots, length), jnp.int32, sharding=on))


def _chunk_fn(q, k, v, start):
    with M.chunk_attention_backend("pallas", block=BLOCK, interpret=False):
        return M.chunk_causal_attention(q, k, v, start)


def _chunk_args(spec, on, heads_on):
    H, Hkv, D = spec["heads"], spec["kv_heads"], spec["head_dim"]
    bf = jnp.bfloat16
    return (jax.ShapeDtypeStruct((1, H, CHUNK, D), bf, sharding=heads_on),
            jax.ShapeDtypeStruct((1, Hkv, FULL_LEN, D), bf,
                                 sharding=heads_on),
            jax.ShapeDtypeStruct((1, Hkv, FULL_LEN, D), bf,
                                 sharding=heads_on),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=on))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "spec,length,slots",
    [(PHI3, FULL_LEN, SLOTS), (PHI3, RING_LEN, SLOTS),
     (HEAD_DIM_128, FULL_LEN, SLOTS), (CMDR_STAGE, STAGE_LEN, STAGE_SLOTS),
     (CMDR_STAGE, RING_LEN, STAGE_SLOTS)],
    ids=["full_kv", "ring_kv", "head_dim_128", "cmdr_stage_full_kv",
         "cmdr_stage_ring_kv"])
def test_pooled_decode_kernel_compiles_at_phi3_width(one_chip, spec, length,
                                                     slots):
    """phi3's head dim 96 keeps the cache sequence-minor, which the
    kernel reads as (D, block_k) blocks in place: the compile holds no
    temporary the size of the cache.  A head dim of 128 takes the
    (block_k, D) blocks; the command-r-plus stage widths (96 query heads
    over 8 KV heads) take them as (12, 128) query tiles."""
    compiled = jax.jit(_decode_fn).lower(
        *_decode_args(spec, length, one_chip, one_chip, slots)).compile()
    _assert_kernel(compiled)
    H, D = spec["kv_heads"], spec["head_dim"]
    cache_bytes = 2 * slots * H * length * D * 2   # K and V, bf16
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes // 4


def test_block_sparse_chunk_kernel_compiles_at_phi3_width(one_chip):
    compiled = jax.jit(_chunk_fn).lower(
        *_chunk_args(PHI3, one_chip, one_chip)).compile()
    _assert_kernel(compiled)


def test_shard_mapped_decode_kernel_compiles_on_tp4_mesh(tp4):
    rep = NamedSharding(tp4, P())
    heads = NamedSharding(tp4, P(None, "model"))
    with jax.sharding.use_abstract_mesh(tp4.abstract_mesh):
        compiled = jax.jit(_decode_fn).lower(
            *_decode_args(STABLELM, FULL_LEN, rep, heads)).compile()
    _assert_kernel(compiled)
    # heads stay local to their shard: no collective moves the cache
    assert "all-gather" not in compiled.as_text()


def test_shard_mapped_chunk_kernel_compiles_on_tp4_mesh(tp4):
    rep = NamedSharding(tp4, P())
    heads = NamedSharding(tp4, P(None, "model"))
    with jax.sharding.use_abstract_mesh(tp4.abstract_mesh):
        compiled = jax.jit(_chunk_fn).lower(
            *_chunk_args(STABLELM, rep, heads)).compile()
    _assert_kernel(compiled)
    assert "all-gather" not in compiled.as_text()
