"""jit'd public wrappers around the Pallas kernels.

Inputs use the model layout (B, H, S, D); wrappers flatten to the
kernels' (B·H, S, D), choose interpret mode automatically (Python
interpretation on CPU, Mosaic on TPU), and jit with static geometry.

``use_pallas()`` is the global dispatch switch consulted by model code
(dry-run compiles the jnp path; TPU runtime flips to kernels).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.block_sparse_attention import (block_sparse_attention_bh,
                                                  dedupe_selection)
from repro.kernels.decode_attention import (decode_attention_bh,
                                            decode_attention_pooled_bh)
from repro.kernels.flash_attention import flash_attention_bh
from repro.kernels.streaming_attention import streaming_attention_bh


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _flatten(x):
    B, H, S, D = x.shape
    return x.reshape(B * H, S, D)


def _unflatten(x, B, H):
    BH, S, D = x.shape
    return x.reshape(B, H, S, D)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "q_offset", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, q_offset: int = 0,
                    interpret: Optional[bool] = None):
    """q (B,Hq,S,D); k/v (B,Hkv,S,D) → (B,Hq,S,D)."""
    interpret = default_interpret() if interpret is None else interpret
    B, H = q.shape[:2]
    out = flash_attention_bh(
        _flatten(q), _flatten(k), _flatten(v), causal=causal,
        block_q=block_q, block_k=block_k, q_offset=q_offset,
        interpret=interpret)
    return _unflatten(out, B, H)


@functools.partial(jax.jit, static_argnames=("sink", "local", "block_q",
                                             "block_k", "q_offset",
                                             "interpret"))
def streaming_attention(q, k, v, *, sink: int, local: int,
                        block_q: int = 128, block_k: int = 128,
                        q_offset: int = 0,
                        interpret: Optional[bool] = None):
    interpret = default_interpret() if interpret is None else interpret
    B, H = q.shape[:2]
    out = streaming_attention_bh(
        _flatten(q), _flatten(k), _flatten(v), sink=sink, local=local,
        block_q=block_q, block_k=block_k, q_offset=q_offset,
        interpret=interpret)
    return _unflatten(out, B, H)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k, v, positions, cur_pos, *, block_k: int = 128,
                     interpret: Optional[bool] = None):
    """q (B,Hq,1,D); k/v (B,Hkv,L,D); positions (L,); cur_pos scalar."""
    interpret = default_interpret() if interpret is None else interpret
    B, H = q.shape[:2]
    out = decode_attention_bh(
        _flatten(q), _flatten(k), _flatten(v), positions, cur_pos,
        block_k=block_k, interpret=interpret)
    return _unflatten(out, B, H)


@functools.partial(jax.jit, static_argnames=("block_k", "scale",
                                             "interpret"))
def decode_attention_pooled(q, k, v, positions, lengths, *,
                            block_k: int = 128,
                            scale: Optional[float] = None,
                            interpret: Optional[bool] = None):
    """Pooled decode: q (B,Hq,1,Dk); k/v (B,Hkv,L,D*); positions (B,L)
    int32 (-1 empty); lengths (B,) int32 live-prefix counts.  The
    kernel takes each KV head's Hq/Hkv query heads as one tile."""
    interpret = default_interpret() if interpret is None else interpret
    B, H, _, Dk = q.shape
    Hkv = k.shape[1]
    out = decode_attention_pooled_bh(
        q.reshape(B * Hkv, H // Hkv, Dk), _flatten(k), _flatten(v),
        positions, lengths, scale=scale, block_k=block_k,
        interpret=interpret)
    return out.reshape(B, H, 1, out.shape[-1])


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def block_sparse_attention(q, k, v, sel, *, q_offset=0, block: int = 128,
                           interpret: Optional[bool] = None):
    """sel (B,Hq,nqb,K) int32 kv-block indices (scorer output);
    ``q_offset`` (traced scalar ok) shifts the causal comparison for
    chunked callers."""
    interpret = default_interpret() if interpret is None else interpret
    B, H = q.shape[:2]
    sel = dedupe_selection(sel.reshape(B * H, *sel.shape[2:]))
    out = block_sparse_attention_bh(
        _flatten(q), _flatten(k), _flatten(v), sel, q_offset=q_offset,
        block=block, interpret=interpret)
    return _unflatten(out, B, H)
