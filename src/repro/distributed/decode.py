"""Distributed flash-decode: attention over sequence-sharded KV.

For ``long_500k`` (batch=1, 524288-token cache) the batch axis cannot
cover the mesh, so the baseline shards the KV *sequence* dim and lets
SPMD insert collectives — XLA materializes an all-gather of the whole
cache per step (gigabytes over ICI).  The production fix, standard in
TPU serving stacks, is flash-decoding across chips: every chip attends
over its local KV shard, then the shards' partial results merge with a
log-sum-exp combine — the collective shrinks from O(S·D) to O(H·D)
per layer (a few KB).

This is a *beyond-paper* optimization (EXPERIMENTS.md §Perf): the
paper's layer routing decides WHICH cache a layer reads; this decides
HOW a full cache is read at 500K.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def lse_combine_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                       valid: jax.Array, mesh, kv_axes: Tuple[str, ...],
                       scale: Optional[float] = None) -> jax.Array:
    """q (B,Hq,1,D) replicated; k/v (B,Hkv,S,D) sharded over ``kv_axes``
    on the sequence dim; valid (S,) likewise sharded.  Returns the
    exact softmax attention output (B,Hq,1,D)."""
    B, Hq, _, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    scale = D ** -0.5 if scale is None else scale
    axes = kv_axes if len(kv_axes) > 1 else kv_axes[0]

    def local(qb, kb, vb, validb):
        # qb (B,Hq,1,D); kb/vb (B,Hkv,S_loc,D); validb (S_loc,)
        q5 = qb.reshape(B, Hkv, G, 1, D)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", q5, kb,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(validb[None, None, None, None, :], s, -1e30)
        m_loc = s.max(-1, keepdims=True)                    # (B,K,G,1,1)
        m_glob = lax.pmax(m_loc, axes)
        p = jnp.exp(s - m_glob)
        l_loc = p.sum(-1, keepdims=True)
        o_loc = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(vb.dtype), vb,
                           preferred_element_type=jnp.float32)
        l_glob = lax.psum(l_loc, axes)                      # O(1) bytes
        o_glob = lax.psum(o_loc, axes)                      # O(H·D) bytes
        out = o_glob / jnp.maximum(l_glob, 1e-20)
        return out.reshape(B, Hq, 1, D).astype(qb.dtype)

    kv_spec = P(None, None, axes, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), kv_spec, kv_spec, P(axes)),
        out_specs=P(),
        check_vma=False,
    )(q, k, v, valid)


def _flat_axis_index(kv_axes: Tuple[str, ...]):
    """Row-major flat shard index over possibly-multiple mesh axes."""
    idx = lax.axis_index(kv_axes[0])
    for a in kv_axes[1:]:
        idx = idx * lax.axis_size(a) + lax.axis_index(a)
    return idx


def sharded_seq_insert(cache_k: jax.Array, cache_v: jax.Array,
                       k_new: jax.Array, v_new: jax.Array, pos,
                       mesh, kv_axes: Tuple[str, ...]):
    """Insert one token into a sequence-sharded KV cache without
    gathering it.

    A plain ``dynamic_update_slice`` at a traced position forces SPMD
    to all-gather the whole cache (observed: 19.3 GB/step for
    command-r at 500K — EXPERIMENTS.md §Perf); here every shard decides
    locally whether the position falls inside its slice and updates in
    place.  cache (B,Hkv,S,D) sharded over ``kv_axes`` on dim 2;
    k_new/v_new (B,Hkv,1,D) replicated."""
    axes = kv_axes if len(kv_axes) > 1 else kv_axes[0]

    def local(ck, cv, kn, vn, p):
        shard_len = ck.shape[2]
        idx = _flat_axis_index(kv_axes)
        start = idx * shard_len
        local_pos = jnp.clip(p - start, 0, shard_len - 1)
        mine = (p >= start) & (p < start + shard_len)
        ck_upd = lax.dynamic_update_slice_in_dim(ck, kn, local_pos, 2)
        cv_upd = lax.dynamic_update_slice_in_dim(cv, vn, local_pos, 2)
        return (jnp.where(mine, ck_upd, ck), jnp.where(mine, cv_upd, cv))

    kv_spec = P(None, None, axes, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(kv_spec, kv_spec, P(), P(), P()),
        out_specs=(kv_spec, kv_spec),
        check_vma=False,
    )(cache_k, cache_v, k_new, v_new, jnp.asarray(pos, jnp.int32))


def make_distributed_insert(mesh, kv_axes: Tuple[str, ...],
                            min_seq: int = 8192):
    """Adapter for ``repro.models.model.use_cache_insert``."""
    def fn(cache_k, cache_v, k_new, v_new, pos):
        if cache_k.shape[2] < min_seq:
            return None
        return sharded_seq_insert(cache_k, cache_v, k_new, v_new, pos,
                                  mesh, kv_axes)
    return fn


def make_distributed_dot_decode(mesh, kv_axes: Tuple[str, ...],
                                min_seq: int = 8192):
    """Adapter matching ``repro.models.model._dot_decode``'s signature,
    installed via ``model.use_decode_attn`` by the launch layer.
    Declines (returns None) for short caches — ring buffers stay on the
    local path — and for any non-shared mask (``valid.ndim != 1``,
    which includes pooled per-slot validity: slot pools batch short
    requests, the opposite regime from sequence-sharded 500K).

    Speaks the same trace protocol as
    ``kernels.decode_attention.make_kernel_decode_attn``: every
    accept/decline decision lands in ``fn.trace_log`` as ``(event,
    reason)`` with the engine's closed decline vocabulary ("min_len",
    "mask_rank"), so the kernel-decision replay and the
    ``decode_kernel_{hit,decline}`` counters cover the distributed
    path identically."""
    trace_log: List[Tuple[str, str]] = []

    def _note(event: str, reason: str) -> None:
        trace_log.append((event, reason))

    def fn(q, k, v, valid, scale=None):
        if valid.ndim != 1 or k.shape[2] < min_seq:
            _note("decline",
                  "mask_rank" if valid.ndim != 1 else "min_len")
            return None
        out = lse_combine_decode(q, k, v, valid, mesh, kv_axes,
                                 scale=scale)
        _note("hit", "lse_combine")
        return out

    def drain_log() -> List[Tuple[str, str]]:
        out = list(trace_log)
        trace_log.clear()
        return out

    fn.supports_pooled = False
    fn.supports_scale = True
    fn.trace_log = trace_log
    fn.drain_log = drain_log
    fn.min_len = min_seq
    return fn
