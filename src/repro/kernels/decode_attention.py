"""Pallas TPU flash-decode kernels: one query token vs a KV cache.

Split-KV with LSE accumulation: the grid's inner axis walks KV blocks;
VMEM scratch carries (acc, m, l).  Works for both the FullKV cache
(positions = arange, validity = pos ≤ cur) and the sink+local RingKV
cache (positions = ring slots' absolute positions, -1 = empty) — the
mask comes from a positions array, so one kernel serves every decode
mode of the paper's sparse-decode deployment (§3.3).

Two entry points:

* ``decode_attention_bh`` — single shared (L,) positions vector for the
  whole batch (the batch-synchronous ``generate`` path).
* ``decode_attention_pooled_bh`` — per-row (B,) live-prefix lengths and
  (B, L) positions for the continuous-batching slot pool, where every
  slot sits at a different decode depth.  A grid row is one (slot, KV
  head) with its G = Hq/Hkv query heads as one tile, so each K/V block
  is fetched once per KV head, not once per query head.  The slot's
  length rides in as a scalar-prefetch operand so the KV BlockSpec
  index map clamps dead grid steps onto the last live block (the
  pipeline elides the repeat fetch → expressed HBM traffic scales with
  the live prefix) and ``pl.when`` short-circuits their compute — block
  *skipping*, not masking.

The decode phase is memory-bandwidth bound; the kernel's useful work
per HBM byte is fixed, so the paper's speedup comes from the *shape*
of the cache this kernel is pointed at (ring ≪ full, live ≪ capacity),
not from the kernel itself — exactly the layer-level contiguity
argument.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.distributed.sharding import over_heads

NEG_INF = -1e30


def pv_precision(dtype):
    """Precision of the p·v product: one MXU pass for bf16 operands,
    which is exact for them — Mosaic refuses the fp32 contract a
    process-wide "highest" matmul precision would otherwise ask for."""
    return jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16 else None


@dataclass(frozen=True)
class PooledValid:
    """Per-slot decode validity, the pooled override vocabulary.

    ``mask`` is the dense-fallback boolean mask exactly as
    ``_dot_decode``'s einsum path expects it ((B, 1, L) for GQA
    caches, (B, S) for MLA absorbed decode); ``lengths`` is the (B,)
    int32 live-prefix count per slot (0 = dead/free slot); and
    ``positions`` is the optional (B, L) int32 absolute-position map
    with -1 marking empty ring entries — ``None`` means the trivial
    FullKV layout (slot i of the buffer holds position i) and lets the
    kernel synthesize arange rather than shipping it.
    """
    mask: jax.Array
    lengths: jax.Array
    positions: Optional[jax.Array] = None

    @property
    def ndim(self) -> int:
        # legacy adapters probe valid.ndim to decline non-1-D masks;
        # answering with the dense mask's rank keeps them declining
        # gracefully instead of crashing
        return self.mask.ndim


def _kernel(q_ref, k_ref, v_ref, pos_ref, cur_ref, o_ref, acc, m_scr, l_scr,
            *, scale: float, block_k: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0].astype(jnp.float32)          # (1, D) — single token
    k = k_ref[0].astype(jnp.float32)          # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = pos_ref[...]                        # (1, bk) int32
    cur = cur_ref[0, 0]
    mask = (pos >= 0) & (pos <= cur)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(-1, keepdims=True)
    acc[...] = acc[...] * alpha + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        precision=pv_precision(v_ref.dtype),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _fin():
        o_ref[0] = (acc[...] / jnp.maximum(l_scr[...], 1e-20)
                    ).astype(o_ref.dtype)


def decode_attention_bh(q: jax.Array, k: jax.Array, v: jax.Array,
                        positions: jax.Array, cur_pos, *,
                        scale: Optional[float] = None, block_k: int = 128,
                        interpret: bool = False) -> jax.Array:
    """q (BH, 1, D); k/v (BHkv, L, D); positions (L,) int32 (-1 empty);
    cur_pos scalar int32.  Returns (BH, 1, D)."""
    BH, _, D = q.shape
    BHkv, L = k.shape[0], k.shape[1]
    G = BH // BHkv
    scale = D ** -0.5 if scale is None else scale
    L_p = -(-L // block_k) * block_k
    k = jnp.pad(k, ((0, 0), (0, L_p - L), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, L_p - L), (0, 0)))
    pos = jnp.pad(positions.astype(jnp.int32), (0, L_p - L),
                  constant_values=-1)[None, :]  # (1, L_p)
    cur = jnp.asarray(cur_pos, jnp.int32).reshape(1, 1)
    grid = (BH, L_p // block_k)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j: (b // G, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j: (b // G, j, 0)),
            pl.BlockSpec((1, block_k), lambda b, j: (0, j)),
            pl.BlockSpec((1, 1), lambda b, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, D), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, 1, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attn",
    )(q, k, v, pos, cur)
    return out


def _pooled_kernel(len_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
                   acc, m_scr, l_scr, *, scale: float, block_k: int,
                   n_kv_heads: int, k_seq_minor: bool, v_seq_minor: bool):
    """Grid (B·Hkv, max-blocks); row r serves slot r//Hkv and KV head
    r%Hkv, with that head's G query heads as one (G, D) tile.

    ``len_ref`` (scalar prefetch, (B,)) is the live-prefix length per
    slot; blocks past ``ceil(n / block_k)`` are short-circuited — their
    KV fetch was already clamped onto the last live block by the index
    map, so skipped steps cost neither HBM bytes nor FLOPs.  Each live
    step is one (G, Dk)·(Dk, bk) score product, a row-wise online
    softmax over the G rows and one (G, bk)·(bk, Dv) p·v product.  A
    ``*_seq_minor`` block arrives as (D, bk) instead of (bk, D)."""
    r = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    n = len_ref[r // n_kv_heads]
    nb = (n + block_k - 1) // block_k     # per-row traced trip count

    @pl.when(j < nb)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (G, Dk) — one token, G heads
        k = k_ref[0].astype(jnp.float32)  # (bk, Dk) or (Dk, bk)
        s = jax.lax.dot_general(
            q, k, (((1,), (0 if k_seq_minor else 1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (G, bk)
        col = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        # live prefix ∧ occupied ring entry (-1 = empty), one (1, bk)
        # row shared by the G query heads; FullKV rows carry arange
        # positions so only the prefix bound bites
        mask = (pos_ref[0] >= 0) & (col < n)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(-1, keepdims=True)
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            (((1,), (1 if v_seq_minor else 0,)), ((), ())),
            precision=pv_precision(v_ref.dtype),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _fin():
        # n = 0 (free slot parked in the pool) finalizes acc=0 / l=0 →
        # zeros: finite garbage the scheduler never reads, matching the
        # dense path's convention that dead rows only need finiteness
        o_ref[0] = (acc[...] / jnp.maximum(l_scr[...], 1e-20)
                    ).astype(o_ref.dtype)


def decode_attention_pooled_bh(q: jax.Array, k: jax.Array, v: jax.Array,
                               positions: jax.Array, lengths: jax.Array,
                               *, scale: Optional[float] = None,
                               block_k: int = 128,
                               interpret: bool = False) -> jax.Array:
    """Batched pooled decode: q (B·Hkv, G, Dk), the G = Hq/Hkv query
    heads of each KV head stacked (a free reshape of (B, Hq, 1, Dk):
    query head h reads KV head h // G); k (B·Hkv, L, Dk); v (B·Hkv, L,
    Dv); positions (B, L) int32 (-1 empty); lengths (B,) int32
    live-prefix counts.  Dk may differ from Dv (MLA absorbed decode:
    Dk = R + rope, Dv = R, one KV head for all query heads).  Returns
    (B·Hkv, G, Dv).  Each K/V block is fetched once per (slot, KV
    head), whatever G is."""
    BHkv, G, Dk = q.shape
    L = k.shape[1]
    Dv = v.shape[2]
    n_kv_heads = BHkv // lengths.shape[0]
    scale = Dk ** -0.5 if scale is None else scale
    L_p = -(-L // block_k) * block_k
    if L_p != L:
        k = jnp.pad(k, ((0, 0), (0, L_p - L), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, L_p - L), (0, 0)))
        positions = jnp.pad(positions.astype(jnp.int32),
                            ((0, 0), (0, L_p - L)), constant_values=-1)
    lengths = jnp.minimum(lengths.astype(jnp.int32), L)
    # (B, 1, L_p): a (1, 1, block_k) block keeps its last two dims equal
    # to the array's / a multiple of 128, the TPU block-shape rule that
    # a (1, block_k) block over a (B, L_p) array breaks for B > 1
    positions = positions.astype(jnp.int32)[:, None, :]
    grid = (BHkv, L_p // block_k)
    # A TPU array whose head dim is not a multiple of 128 lanes is laid
    # out with its sequence dim minor (no lane padding).  Reading such a
    # K or V as (D, block_k) blocks of its (D, L) view makes the swap a
    # relabelling of the same bytes; (block_k, D) blocks would make the
    # compiler copy the whole cache into a lane-padded layout per call.
    k_seq_minor, v_seq_minor = Dk % 128 != 0, Dv % 128 != 0
    if k_seq_minor:
        k = jnp.swapaxes(k, 1, 2)
    if v_seq_minor:
        v = jnp.swapaxes(v, 1, 2)

    def live_block(r, j, len_ref):
        # clamp dead steps onto the last live block: the pipeline sees
        # the same block index as the previous step and elides the
        # fetch, so HBM traffic tracks ceil(n / block_k), not L/block_k
        n = len_ref[r // n_kv_heads]
        nb = jnp.maximum((n + block_k - 1) // block_k, 1)
        return jnp.minimum(j, nb - 1)

    def kv_map(r, j, len_ref):
        return (r, live_block(r, j, len_ref), 0)

    def kv_map_seq_minor(r, j, len_ref):
        return (r, 0, live_block(r, j, len_ref))

    def kv_spec(D, seq_minor):
        if seq_minor:
            return pl.BlockSpec((1, D, block_k), kv_map_seq_minor)
        return pl.BlockSpec((1, block_k, D), kv_map)

    def pos_map(r, j, len_ref):
        return (r // n_kv_heads, 0, live_block(r, j, len_ref))

    out = pl.pallas_call(
        functools.partial(_pooled_kernel, scale=scale, block_k=block_k,
                          n_kv_heads=n_kv_heads, k_seq_minor=k_seq_minor,
                          v_seq_minor=v_seq_minor),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, G, Dk), lambda r, j, lens: (r, 0, 0)),
                kv_spec(Dk, k_seq_minor),
                kv_spec(Dv, v_seq_minor),
                pl.BlockSpec((1, 1, block_k), pos_map),
            ],
            out_specs=pl.BlockSpec((1, G, Dv), lambda r, j, lens: (r, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, Dv), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((BHkv, G, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="pooled_decode_attn",
    )(lengths, q, k, v, positions)
    return out


def make_kernel_decode_attn(*, block_k: int = 128,
                            min_len: int = 2 * 128,
                            interpret: Optional[bool] = None):
    """Adapter installing these kernels as the serving decode backend.

    Returns an fn matching ``repro.models.model.use_decode_attn``'s
    protocol: fn(q (B,Hq,1,Dk), k/v (B,Hkv,L,D*), valid, scale=None) →
    (B,Hq,1,Dv), or None to decline.  ``valid`` is either the legacy
    (L,) shared mask (batch-synchronous ``generate``) — re-expressed in
    the kernel's positions/-1 vocabulary — or a :class:`PooledValid`
    carrying per-slot lengths/positions, which routes to the batched
    pooled kernel (FullKV, RingKV, and — via the q/k/v re-expression in
    ``attention.mla_absorbed_qkv`` — MLA absorbed decode).

    Under a mesh (``jax.set_mesh``) each kernel call runs per shard of
    the attention heads (``distributed.sharding.over_heads``): the
    compiler cannot partition a Mosaic kernel by itself.

    Declines (→ dense fallback) when the cache is shorter than
    ``min_len`` (the dense dot wins on tiny rings) or when the mask
    shape is one the kernels don't speak (per-KV-head duo masks).
    Every install/decline decision is appended to ``fn.trace_log`` as
    ``(event, reason)``, a hit as ``("hit", reason, G)`` with the
    layer's query-group size G = Hq/Hkv (per shard under a mesh) — the
    adapter is consulted at *trace* time (once per attention layer per
    executable), so the engine drains the log after each jit dispatch
    to drive its kernel-path counters.
    """
    trace_log: List[Tuple] = []

    def _note(event: str, reason: str, *group: int) -> None:
        trace_log.append((event, reason, *group))

    def fn(q: jax.Array, k: jax.Array, v: jax.Array,
           valid, scale: Optional[float] = None) -> Optional[jax.Array]:
        interp = (jax.default_backend() != "tpu"
                  if interpret is None else interpret)
        if isinstance(valid, PooledValid):
            L = k.shape[2]
            if L < min_len:
                _note("decline", "min_len")
                return None
            B = q.shape[0]
            if valid.positions is None:
                positions = jnp.broadcast_to(
                    jnp.arange(L, dtype=jnp.int32)[None, :], (B, L))
            else:
                positions = valid.positions

            def pooled(q, k, v, positions, lengths):
                Hq, Dk = q.shape[1], q.shape[3]
                Hkv, Dv = k.shape[1], v.shape[3]
                out = decode_attention_pooled_bh(
                    q.reshape(B * Hkv, Hq // Hkv, Dk),
                    k.reshape(B * Hkv, L, Dk), v.reshape(B * Hkv, L, Dv),
                    positions, lengths, scale=scale, block_k=block_k,
                    interpret=interp)
                return out.reshape(B, Hq, 1, Dv)

            # FullKV rows ship no positions; ring rows do
            _note("hit", "pooled" if valid.positions is None
                  else "pooled_ring", q.shape[1] // k.shape[1])
            return over_heads(pooled, q.shape[1], k.shape[1])(
                q, k, v, positions, valid.lengths)
        if valid.ndim != 1 or k.shape[2] < min_len:
            _note("decline",
                  "mask_rank" if valid.ndim != 1 else "min_len")
            return None
        B, L = q.shape[0], k.shape[2]
        positions = jnp.where(valid, jnp.arange(L, dtype=jnp.int32), -1)

        def shared(q, k, v, positions):
            Hq, D = q.shape[1], q.shape[3]
            Hkv = k.shape[1]
            out = decode_attention_bh(
                q.reshape(B * Hq, 1, D), k.reshape(B * Hkv, L, D),
                v.reshape(B * Hkv, L, D), positions, jnp.int32(L),
                scale=scale, block_k=block_k, interpret=interp)
            return out.reshape(B, Hq, 1, D)

        _note("hit", "shared", q.shape[1] // k.shape[1])
        return over_heads(shared, q.shape[1], k.shape[1])(q, k, v,
                                                          positions)

    def drain_log() -> List[Tuple]:
        out = list(trace_log)
        trace_log.clear()
        return out

    fn.supports_pooled = True
    fn.supports_scale = True
    fn.trace_log = trace_log
    fn.drain_log = drain_log
    fn.block_k = block_k
    fn.min_len = min_len
    return fn
