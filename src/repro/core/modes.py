"""Attention mode zoo: FA, sliding-window, SSA, Triangle, XAttention.

All modes share one blocked execution engine: a ``lax.map`` (scan) over
query blocks, so that (a) no full S×S score tensor is ever materialized
and (b) sparse modes only *express* the FLOPs they need — streaming
attention really does cost O(S·(sink+local)), visible in
``cost_analysis()`` of the lowered computation.  This is the pure-JAX
reference path; ``repro.kernels`` holds the Pallas TPU kernels that
mirror these semantics (validated against them in tests).

Layout convention: q is (B, Hq, Sq, D); k/v are (B, Hkv, Skv, D) with
Hq = G·Hkv (GQA).  Internally q is viewed as (B, Hkv, G, Sq, D).

TPU adaptation notes (DESIGN.md §2):
  * block sizes default to 128/512 multiples (MXU/VMEM alignment);
  * XAttention's dynamic threshold is realized as a *static* top-K block
    budget per query block (K = ceil((1-threshold)·num_kv_blocks)), since
    ragged per-row block counts are unrepresentable in static-shape XLA —
    the antidiagonal scoring estimator is kept.
"""
from __future__ import annotations

import contextlib
import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.sharding import over_heads

NEG_INF = -1e30


@dataclass(frozen=True)
class AttnMode:
    kind: str  # full | window | streaming | triangle | block_topk
    causal: bool = True
    sink: int = 0
    local: int = 0
    chunk: int = 0
    block: int = 128
    stride: int = 16
    threshold: float = 0.9

    def replace(self, **kw) -> "AttnMode":
        return dataclasses.replace(self, **kw)


FULL = AttnMode("full")
BIDIRECTIONAL = AttnMode("full", causal=False)


def window_mode(window: int) -> AttnMode:
    return AttnMode("window", local=window)


def ssa_mode(flux) -> AttnMode:
    return AttnMode("streaming", sink=flux.sink, local=flux.local)


def xa_mode(flux) -> AttnMode:
    return AttnMode("block_topk", sink=flux.sink, local=flux.local,
                    block=flux.block, stride=flux.stride,
                    threshold=flux.threshold)


def ta_mode(flux) -> AttnMode:
    return AttnMode("triangle", sink=flux.sink, local=flux.local,
                    chunk=flux.chunk)


def sa_mode_for(flux) -> AttnMode:
    return {"ssa": ssa_mode, "xa": xa_mode, "ta": ta_mode}[flux.sa_mode](flux)


# ---------------------------------------------------------------------------
# Small helpers
# ---------------------------------------------------------------------------

def _pad_axis(x: jax.Array, axis: int, target: int) -> jax.Array:
    pad = target - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _softmax_attend(scores: jax.Array, v: jax.Array) -> jax.Array:
    """scores (..., Sq, Skv) f32 (already masked), v (..., Skv, D).

    v's batch rank is explicitly aligned to scores' (a GQA group axis may
    be missing from v); ellipsis broadcasting alone would right-align the
    wrong dims.
    """
    while v.ndim < scores.ndim:
        v = jnp.expand_dims(v, -3)
    m = jnp.max(scores, axis=-1, keepdims=True)
    # Guard fully-masked rows (can happen for padded queries).
    m = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(scores - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(denom, 1e-20)
    return jnp.einsum("...qk,...kd->...qd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def _gqa_view(q: jax.Array, num_kv_heads: int) -> jax.Array:
    B, Hq, Sq, D = q.shape
    G = Hq // num_kv_heads
    return q.reshape(B, num_kv_heads, G, Sq, D)


# ---------------------------------------------------------------------------
# Blocked engine
# ---------------------------------------------------------------------------

def attention(q: jax.Array, k: jax.Array, v: jax.Array, mode: AttnMode,
              *, q_offset=0, block_q: int = 512,
              scale: Optional[float] = None,
              split_depth: int = 0) -> jax.Array:
    """Blocked attention under ``mode``.

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D).  ``q_offset`` shifts query
    positions (chunked prefill).  Returns (B, Hq, Sq, D) in q.dtype.

    ``split_depth`` (causal full attention only): recursively split the
    sequence in half — the lower half attends only to its own prefix.
    Dense-XLA causal attention otherwise expresses the full S×S
    rectangle (masked); depth d cuts the expressed FLOPs toward the
    2/3·S² limit (d=1 → 0.75, d=2 → 0.69, d=3 → 0.67).  A §Perf
    compute-term optimization; exactness is unaffected.
    """
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    scale = scale if scale is not None else D ** -0.5

    if (split_depth > 0 and mode.kind == "full" and mode.causal
            and q_offset == 0 and Sq == Skv and Sq >= 4 * block_q
            and Sq % 2 == 0):
        half = Sq // 2
        lower = attention(q[:, :, :half], k[:, :, :half], v[:, :, :half],
                          mode, block_q=block_q, scale=scale,
                          split_depth=split_depth - 1)
        upper = attention(q[:, :, half:], k, v, mode, q_offset=half,
                          block_q=block_q, scale=scale)
        return jnp.concatenate([lower, upper], axis=2)

    if mode.kind == "triangle":
        return _triangle(q, k, v, mode, q_offset=q_offset, block_q=block_q,
                         scale=scale)
    if mode.kind == "block_topk":
        return _block_topk(q, k, v, mode, q_offset=q_offset, scale=scale)

    q5 = _gqa_view(q, Hkv)  # (B, Hkv, G, Sq, D)
    bq = min(block_q, max(Sq, 1))
    Sq_pad = -(-Sq // bq) * bq
    q5 = _pad_axis(q5, 3, Sq_pad)
    nqb = Sq_pad // bq
    q_blocks = jnp.moveaxis(
        q5.reshape(B, Hkv, q5.shape[2], nqb, bq, D), 3, 0)

    kv_pos = jnp.arange(Skv)

    if mode.kind == "full":
        def body(args):
            i, qb = args
            q_pos = q_offset + i * bq + jnp.arange(bq)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qb, k,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((bq, Skv), bool)
            if mode.causal:
                mask &= kv_pos[None, :] <= q_pos[:, None]
            s = jnp.where(mask, s, NEG_INF)
            return _softmax_attend(s, v)

    elif mode.kind in ("window", "streaming"):
        # Local slice of length L (static); optional sink prefix.
        local = max(mode.local, 1)
        L = min(local + bq, Skv) if Skv >= local + bq else local + bq
        k_pad = _pad_axis(k, 2, max(Skv, L))
        v_pad = _pad_axis(v, 2, max(Skv, L))
        Skv_pad = k_pad.shape[2]
        sink_len = min(mode.sink, Skv) if mode.kind == "streaming" else 0

        def body(args):
            i, qb = args
            q_start = q_offset + i * bq
            q_pos = q_start + jnp.arange(bq)
            start = jnp.clip(q_start - local + 1, 0, Skv_pad - L)
            k_loc = lax.dynamic_slice_in_dim(k_pad, start, L, axis=2)
            v_loc = lax.dynamic_slice_in_dim(v_pad, start, L, axis=2)
            loc_pos = start + jnp.arange(L)
            s_loc = jnp.einsum("bhgqd,bhkd->bhgqk", qb, k_loc,
                               preferred_element_type=jnp.float32) * scale
            mask_loc = (loc_pos[None, :] <= q_pos[:, None])
            mask_loc &= (q_pos[:, None] - loc_pos[None, :]) < local
            mask_loc &= loc_pos[None, :] < Skv  # padding validity
            if sink_len > 0:
                # sink tokens are always visible; avoid double counting by
                # excluding them from the local part.
                mask_loc &= loc_pos[None, :] >= sink_len
                s_snk = jnp.einsum(
                    "bhgqd,bhkd->bhgqk", qb, k_pad[:, :, :sink_len],
                    preferred_element_type=jnp.float32) * scale
                mask_snk = kv_pos[None, :sink_len] <= q_pos[:, None]
                s = jnp.concatenate(
                    [jnp.where(mask_snk, s_snk, NEG_INF),
                     jnp.where(mask_loc, s_loc, NEG_INF)], axis=-1)
                vv = jnp.concatenate([v_pad[:, :, :sink_len], v_loc], axis=2)
                return _softmax_attend(s, vv)
            s_loc = jnp.where(mask_loc, s_loc, NEG_INF)
            return _softmax_attend(s_loc, v_loc)

    else:  # pragma: no cover
        raise ValueError(f"unknown mode kind {mode.kind!r}")

    out = lax.map(body, (jnp.arange(nqb), q_blocks))
    out = jnp.moveaxis(out, 0, 3)  # (B,Hkv,G,nqb,bq,Dv)
    out = out.reshape(B, Hkv, -1, Sq_pad, Dv)[:, :, :, :Sq]
    return out.reshape(B, Hq, Sq, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# Position-explicit masked attention (chunked cache-resident prefill)
# ---------------------------------------------------------------------------
#
# A ring decode cache stores keys out of positional order (slot index ≠
# absolute position), so the blocked engine above — which derives key
# positions from array offsets — cannot attend over it.  The chunked
# prefill instead carries explicit per-slot positions and masks against
# them; the kv extent (ring + chunk) is small, so a single dense masked
# softmax is the right shape on TPU.

def streaming_valid(q_positions: jax.Array, kv_positions: jax.Array,
                    sink: int, local: int) -> jax.Array:
    """Sink+local visibility by absolute position.

    q_positions (Sq,) or (B, Sq); kv_positions (B, L) with -1 = empty
    slot.  Returns (B, Sq, L) bool.  ``sink=0`` degenerates to a pure
    sliding window (the "local" layer kind).
    """
    q = (q_positions[None, :, None] if q_positions.ndim == 1
         else q_positions[:, :, None])
    kv = kv_positions[:, None, :]
    vis = (kv >= 0) & (kv <= q)
    return vis & ((kv < sink) | (q - kv < local))


# Execution backend for ``chunk_causal_attention``: "dense" is the
# fori_loop of masked einsums below; "pallas" routes to the
# block-sparse selected-block kernel (kernels/block_sparse_attention),
# which *skips* dead kv blocks instead of masking them; "auto" (the
# ambient default) picks pallas on TPU and dense elsewhere, so CPU
# tier-1 runs stay bitwise those of the reference path.  Like
# ``model.use_decode_attn`` this is trace-time ambient state, not part
# of any jit key — callers must install the same backend around every
# trace of a given executable (the serving engine never switches
# mid-lifetime).
_CHUNK_ATTN_BACKEND = []

CHUNK_ATTN_BACKENDS = ("auto", "dense", "pallas")


@contextlib.contextmanager
def chunk_attention_backend(backend: str, *, block: int = 128,
                            interpret: Optional[bool] = None):
    """Select the chunked-prefill attention engine (see above).
    ``block`` is the Pallas kernel's MXU tile; ``interpret`` forces
    interpret mode (None = interpret off-TPU, the testing convention)."""
    if backend not in CHUNK_ATTN_BACKENDS:
        raise ValueError(
            f"chunk_attention_backend: {backend!r} not in "
            f"{CHUNK_ATTN_BACKENDS}")
    _CHUNK_ATTN_BACKEND.append((backend, block, interpret))
    try:
        yield
    finally:
        _CHUNK_ATTN_BACKEND.pop()


def _chunk_backend() -> Tuple[str, int, Optional[bool]]:
    backend, block, interpret = (_CHUNK_ATTN_BACKEND[-1]
                                 if _CHUNK_ATTN_BACKEND
                                 else ("auto", 128, None))
    if backend == "auto":
        backend = ("pallas" if jax.default_backend() == "tpu"
                   else "dense")
    return backend, block, interpret


def _chunk_causal_block_sparse(q: jax.Array, k: jax.Array, v: jax.Array,
                               start: jax.Array, *, block: int,
                               scale: Optional[float],
                               interpret: Optional[bool]) -> jax.Array:
    """``chunk_causal_attention`` on the block-sparse Pallas kernel.

    The causal structure is expressed as a per-query-block *selection*:
    query block i (absolute rows [start+i·block, …)) selects kv blocks
    [0, last_vis(i)] and marks the rest -1, which the kernel skips via
    ``pl.when`` — dead blocks cost no MXU work.  ``start`` rides into
    the kernel as a traced scalar-prefetch operand (the causal offset),
    so every chunk of a bucket still shares one executable.  Under a
    mesh the kernel runs per shard of the heads (``over_heads``)."""
    from repro.kernels.block_sparse_attention import \
        block_sparse_attention_bh
    interpret = (jax.default_backend() != "tpu"
                 if interpret is None else interpret)

    def call(q, k, v, start):
        B, Hq, C, D = q.shape
        Hkv, M = k.shape[1], k.shape[2]
        nqb = -(-C // block)
        K = -(-M // block)
        qb = jnp.arange(nqb)
        kb = jnp.arange(K)
        # last kv block any live row of query block i can see; rows
        # past C are padding (masked in-kernel), so bound by the last
        # live row
        last_vis = (start + jnp.minimum((qb + 1) * block, C) - 1) // block
        sel = jnp.where(kb[None, :] <= last_vis[:, None], kb[None, :], -1)
        sel = jnp.broadcast_to(sel[None], (B * Hq, nqb, K)).astype(jnp.int32)
        out = block_sparse_attention_bh(
            q.reshape(B * Hq, C, D), k.reshape(B * Hkv, M, D),
            v.reshape(B * Hkv, M, D), sel, q_offset=start, scale=scale,
            block=block, interpret=interpret)
        return out.reshape(B, Hq, C, out.shape[-1])

    return over_heads(call, q.shape[1], k.shape[1])(q, k, v, start)


def chunk_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           start: jax.Array, *, kv_block: int = 512,
                           scale: Optional[float] = None) -> jax.Array:
    """Causal attention of a chunk of queries over a cache buffer.

    q (B,Hq,C,D) at absolute positions [start, start+C); k/v
    (B,Hkv,M,D) hold valid keys at positions [0, start+C) of an
    M-capacity buffer (everything beyond is zeros).  Flash-style
    online-softmax over kv blocks with a **traced trip count**
    ``ceil((start+C)/kv_block)`` — the expressed compute scales with
    the live prefix, not the buffer capacity, so early chunks of a
    chunked prefill don't pay for cache they haven't written yet
    (a dense masked call over M would: XLA cannot skip masked FLOPs).
    ``start`` stays traced, preserving one executable per chunk bucket.

    Under the "pallas" backend (``chunk_attention_backend``; the
    default "auto" resolves to it on TPU) the same contract executes on
    the block-sparse kernel via ``_chunk_causal_block_sparse``.
    """
    B, Hq, C, D = q.shape
    if v.shape[-1] == D:  # the kernel assumes Dk == Dv (GQA layers)
        backend, blk, interp = _chunk_backend()
        if backend == "pallas":
            return _chunk_causal_block_sparse(q, k, v, start, block=blk,
                                              scale=scale,
                                              interpret=interp)
    Hkv, M = k.shape[1], k.shape[2]
    G = Hq // Hkv
    Dv = v.shape[-1]
    scale = scale if scale is not None else D ** -0.5
    kb = min(kv_block, M)
    q5 = q.reshape(B, Hkv, G, C, D)
    q_pos = start + jnp.arange(C)
    nb = (start + C + kb - 1) // kb  # traced: only live blocks run
    neg = jnp.float32(NEG_INF)

    def body(j, carry):
        m, l, acc = carry
        # clamp so the final block stays in bounds; the >= j*kb mask
        # term drops any keys the clamp re-reads from the prior block
        s0 = jnp.minimum(j * kb, M - kb)
        ks = lax.dynamic_slice_in_dim(k, s0, kb, axis=2)
        vs = lax.dynamic_slice_in_dim(v, s0, kb, axis=2)
        kv_pos = s0 + jnp.arange(kb)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", q5, ks,
                       preferred_element_type=jnp.float32) * scale
        mask = ((kv_pos[None, :] <= q_pos[:, None])
                & (kv_pos[None, :] >= j * kb))
        s = jnp.where(mask[None, None, None], s, neg)
        m2 = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        m2 = jnp.maximum(m2, neg / 2)  # guard fully-masked rows
        p = jnp.exp(s - m2)
        corr = jnp.exp(m - m2)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p.astype(v.dtype), vs,
            preferred_element_type=jnp.float32)
        return m2, l, acc

    m0 = jnp.full((B, Hkv, G, C, 1), neg, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, C, 1), jnp.float32)
    acc0 = jnp.zeros((B, Hkv, G, C, Dv), jnp.float32)
    _, l, acc = lax.fori_loop(0, nb, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-20)
    return out.reshape(B, Hq, C, Dv).astype(q.dtype)


def masked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid: jax.Array,
                     scale: Optional[float] = None) -> jax.Array:
    """q (B,Hq,Sq,D), k/v (B,Hkv,L,D), valid (B, 1|Hkv, Sq, L) bool.

    Dense masked softmax attention with caller-supplied validity — no
    positional assumptions about the key layout.  Returns (B,Hq,Sq,Dv)
    in q.dtype."""
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    Dv = v.shape[-1]
    scale = scale if scale is not None else D ** -0.5
    q5 = _gqa_view(q, Hkv)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", q5, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[:, :, None], s, NEG_INF)
    o = _softmax_attend(s, v)
    return o.reshape(B, Hq, Sq, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# Triangle (TriangleMix): streaming body + dense last chunk
# ---------------------------------------------------------------------------

def _triangle(q, k, v, mode: AttnMode, *, q_offset, block_q, scale):
    B, Hq, Sq, D = q.shape
    chunk = mode.chunk
    boundary = max(0, Sq - chunk)
    stream = mode.replace(kind="streaming")
    if boundary == 0:
        return attention(q, k, v, FULL, q_offset=q_offset, block_q=block_q,
                         scale=scale)
    out_pre = attention(q[:, :, :boundary], k, v, stream, q_offset=q_offset,
                        block_q=block_q, scale=scale)
    out_last = attention(q[:, :, boundary:], k, v, FULL,
                         q_offset=q_offset + boundary, block_q=block_q,
                         scale=scale)
    return jnp.concatenate([out_pre, out_last], axis=2)


# ---------------------------------------------------------------------------
# XAttention: antidiagonal block scoring + static top-K block selection
# ---------------------------------------------------------------------------

def xa_keep_blocks(num_kv_blocks: int, threshold: float) -> int:
    """Static per-q-block KV-block budget (TPU adaptation of the paper's
    cumulative-softmax-mass threshold; see module docstring)."""
    return max(2, min(num_kv_blocks,
                      int(-(-(1.0 - threshold) * num_kv_blocks // 1))))


def antidiagonal_scores(q: jax.Array, k: jax.Array, block: int,
                        stride: int, scale: float) -> jax.Array:
    """XAttention block importance estimate.

    q (B,K,G,Sq,D), k (B,K,Skv,D), both already padded to ``block``.
    Samples every ``stride``-th antidiagonal element of each (block×block)
    score tile: score(i,j) = logsumexp over sampled q_r·k_c with
    r+c ≡ 0 (mod stride) realized by pairing strided q rows with strided,
    reversed k rows.  Returns (B,K,G,nqb,nkb) f32.
    """
    B, K, G, Sq, D = q.shape
    Skv = k.shape[2]
    nqb, nkb = Sq // block, Skv // block
    m = block // stride
    # strided q rows: r = s·stride ; matching antidiagonal k col within the
    # tile: c = block-1-r  →  take k rows reversed then strided.
    qs = q.reshape(B, K, G, nqb, block, D)[:, :, :, :, ::stride]
    ks = k.reshape(B, K, nkb, block, D)[:, :, :, ::-1][:, :, :, ::stride]
    # sampled dot per (q block, k block): (m, m) grid of pairwise dots —
    # approximates m antidiagonals; reduce with logsumexp (softmax-mass
    # proxy per the paper's selection-by-mass rule).
    s = jnp.einsum("bkgqrd,bkncd->bkgqnrc", qs, ks,
                   preferred_element_type=jnp.float32) * scale
    return jax.nn.logsumexp(s, axis=(-2, -1))


def _block_topk(q, k, v, mode: AttnMode, *, q_offset, scale):
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    blk = mode.block
    Sq_pad = -(-Sq // blk) * blk
    Skv_pad = -(-Skv // blk) * blk
    q5 = _pad_axis(_gqa_view(q, Hkv), 3, Sq_pad)
    k_p = _pad_axis(k, 2, Skv_pad)
    v_p = _pad_axis(v, 2, Skv_pad)
    nqb, nkb = Sq_pad // blk, Skv_pad // blk
    keep = xa_keep_blocks(nkb, mode.threshold)

    scores = antidiagonal_scores(q5, k_p, blk, mode.stride, scale)
    # causal at block granularity + force sink block 0 and the diagonal.
    qb_idx = q_offset // blk + jnp.arange(nqb)
    kb_idx = jnp.arange(nkb)
    causal_blk = kb_idx[None, :] <= qb_idx[:, None]
    scores = jnp.where(causal_blk, scores, NEG_INF)
    forced = (kb_idx[None, :] == 0) | (kb_idx[None, :] == qb_idx[:, None])
    scores = jnp.where(forced, jnp.inf, scores)
    # static top-K kv blocks per q block
    _, sel = lax.top_k(scores, keep)  # (B,K,G,nqb,keep)

    G = q5.shape[2]
    k_blocks = k_p.reshape(B, Hkv, nkb, blk, D)
    v_blocks = v_p.reshape(B, Hkv, nkb, blk, Dv)
    kv_pos = jnp.arange(Skv_pad).reshape(nkb, blk)

    def body(args):
        i, qb, sel_i = args  # qb (B,K,G,blk,D); sel_i (B,K,G,keep)
        # gather selected kv blocks: (B,K,G,keep,blk,D)
        kg = jnp.take_along_axis(k_blocks[:, :, None],
                                 sel_i[..., None, None], axis=3)
        vg = jnp.take_along_axis(v_blocks[:, :, None],
                                 sel_i[..., None, None], axis=3)
        pos = kv_pos[sel_i]  # (B,K,G,keep,blk)
        q_pos = q_offset + i * blk + jnp.arange(blk)
        s = jnp.einsum("bkgqd,bkgnld->bkgqnl", qb, kg,
                       preferred_element_type=jnp.float32) * scale
        mask = pos[:, :, :, None] <= q_pos[None, None, None, :, None, None]
        mask &= (pos < Skv)[:, :, :, None]
        s = jnp.where(mask, s, NEG_INF)
        s = s.reshape(*s.shape[:4], keep * blk)
        vg = vg.reshape(B, Hkv, G, keep * blk, Dv)
        return _softmax_attend(s, vg)

    q_blocks = jnp.moveaxis(q5.reshape(B, Hkv, G, nqb, blk, D), 3, 0)
    sel_blocks = jnp.moveaxis(sel, 3, 0)
    out = lax.map(body, (jnp.arange(nqb), q_blocks, sel_blocks))
    out = jnp.moveaxis(out, 0, 3).reshape(B, Hkv, G, Sq_pad, Dv)[:, :, :, :Sq]
    return out.reshape(B, Hq, Sq, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# Head-level split (DuoAttention / PruLong baselines)
# ---------------------------------------------------------------------------

def head_split_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         n_fa_kv: int, sa: AttnMode, *, q_offset=0,
                         block_q: int = 512) -> jax.Array:
    """Static head-level hybrid: the first ``n_fa_kv`` KV heads (and
    their GQA query groups) run full attention, the rest run ``sa``.

    This is the paper's *baseline* (DuoAttention/PruLong); splitting is
    at KV-head granularity, which is what those methods use on GQA
    models.  Note the decode-phase criticism (paper §2.3): the ragged
    per-head history cannot shrink the cache — see
    ``repro.models.model`` decode path.
    """
    Hkv = k.shape[1]
    G = q.shape[1] // Hkv
    n_fa_q = n_fa_kv * G
    o_fa = attention(q[:, :n_fa_q], k[:, :n_fa_kv], v[:, :n_fa_kv], FULL,
                     q_offset=q_offset, block_q=block_q)
    if n_fa_kv == Hkv:
        return o_fa
    o_sa = attention(q[:, n_fa_q:], k[:, n_fa_kv:], v[:, n_fa_kv:], sa,
                     q_offset=q_offset, block_q=block_q)
    return jnp.concatenate([o_fa, o_sa], axis=1)


# ---------------------------------------------------------------------------
# FLOP model (napkin math for roofline / benchmarks)
# ---------------------------------------------------------------------------

def mode_flops(mode: AttnMode, Sq: int, Skv: int, num_heads: int,
               head_dim: int, batch: int = 1) -> float:
    """Matmul FLOPs of one attention call (2·per MAC), per the mode's
    *expressed* computation (matches what cost_analysis sees for the jnp
    path, up to softmax)."""
    per_pair = 4.0 * head_dim  # QK^T + PV, 2 FLOPs per MAC each
    if mode.kind == "full":
        pairs = Sq * Skv
    elif mode.kind == "window":
        pairs = Sq * min(mode.local + 512, Skv)
    elif mode.kind == "streaming":
        pairs = Sq * min(mode.sink + mode.local + 512, Skv)
    elif mode.kind == "triangle":
        last = min(mode.chunk, Sq)
        pre = Sq - last
        pairs = pre * min(mode.sink + mode.local + 512, Skv) + last * Skv
    elif mode.kind == "block_topk":
        nkb = -(-Skv // mode.block)
        keep = xa_keep_blocks(nkb, mode.threshold)
        pairs = Sq * keep * mode.block
        # scoring cost
        pairs += (Sq // mode.stride) * (Skv // mode.stride)
    else:
        raise ValueError(mode.kind)
    return batch * num_heads * pairs * per_pair
