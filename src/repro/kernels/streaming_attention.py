"""Pallas TPU streaming (sink + local) attention — the SSA prefill
kernel (paper Eq. 2 with the StreamingLLM geometry).

Per query block the grid's inner axis visits only
``n_sink_blocks + n_window_blocks`` kv blocks — O(S·(sink+local))
total, the paper's FLOP saving expressed structurally.  The kv
BlockSpec index map selects: sink blocks first, then the sliding
window around the query block (clamped at 0; overlap with the sink
region is masked out in the body, not double-counted).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import pv_precision

NEG_INF = -1e30


def _win_start_block(i, *, block_q: int, block_k: int, local: int):
    """First kv block of q-block i's window (may dip into sink region)."""
    first_pos = i * block_q - (local - 1)
    return jnp.maximum(first_pos // block_k, 0)


def _kernel(q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr, *, scale: float,
            block_q: int, block_k: int, sink: int, local: int, seq_q: int,
            seq_k: int, n_sink_blocks: int, q_offset: int, nkb: int):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nsel = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q_pos = q_offset + i * block_q + jax.lax.iota(jnp.int32, block_q)
    in_sink_part = j < n_sink_blocks
    wstart = _win_start_block(q_offset // block_q + i, block_q=block_q,
                              block_k=block_k, local=local)
    # must mirror the index map exactly (incl. the upper clamp)
    kv_block = jnp.where(in_sink_part, j,
                         jnp.minimum(wstart + (j - n_sink_blocks), nkb - 1))
    k_pos = kv_block * block_k + jax.lax.iota(jnp.int32, block_k)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = (k_pos[None, :] <= q_pos[:, None]) & (k_pos[None, :] < seq_k)
    mask &= q_pos[:, None] < q_offset + seq_q
    # sink part: positions < sink only; window part: within `local` AND
    # >= sink (sink tokens are owned by the sink part — no double count).
    window_ok = ((q_pos[:, None] - k_pos[None, :]) < local) \
        & (k_pos[None, :] >= sink)
    sink_ok = k_pos[None, :] < sink
    mask &= jnp.where(in_sink_part, sink_ok, window_ok)
    # if the index-map clamped this window step onto an already-visited
    # block, drop the whole step (no double counting)
    unclamped = wstart + (j - n_sink_blocks)
    mask &= in_sink_part | (unclamped < nkb)
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

    @pl.when(j == nsel - 1)
    def _fin():
        o_ref[0] = (acc[...] / jnp.maximum(l_scr[...], 1e-20)
                    ).astype(o_ref.dtype)


def streaming_attention_bh(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           sink: int, local: int,
                           scale: Optional[float] = None,
                           block_q: int = 128, block_k: int = 128,
                           q_offset: int = 0,
                           interpret: bool = False) -> jax.Array:
    """q (BH,Sq,D), k/v (BHkv,Skv,D).  ``sink``/``local`` in tokens."""
    BH, Sq, D = q.shape
    BHkv, Skv = k.shape[0], k.shape[1]
    G = BH // BHkv
    scale = D ** -0.5 if scale is None else scale
    Sq_p = -(-Sq // block_q) * block_q
    Skv_p = -(-Skv // block_k) * block_k
    q = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, Skv_p - Skv), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, Skv_p - Skv), (0, 0)))
    nkb = Skv_p // block_k
    n_sink_blocks = min(-(-sink // block_k), nkb)
    # window span (local-1 back from block start .. block end)
    n_win_blocks = min((local - 1) // block_k + 1 + block_q // block_k, nkb)
    nsel = n_sink_blocks + n_win_blocks
    grid = (BH, Sq_p // block_q, nsel)

    def kv_map(b, i, j):
        wstart = _win_start_block(q_offset // block_q + i, block_q=block_q,
                                  block_k=block_k, local=local)
        blk = jnp.where(j < n_sink_blocks, j,
                        jnp.minimum(wstart + (j - n_sink_blocks), nkb - 1))
        return (b // G, blk, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, sink=sink, local=local,
                          seq_q=Sq, seq_k=Skv, nkb=nkb,
                          n_sink_blocks=n_sink_blocks, q_offset=q_offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="streaming_attn",
    )(q, k, v)
    return out[:, :Sq]
