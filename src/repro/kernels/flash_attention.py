"""Pallas TPU flash-attention forward (causal / bidirectional).

Grid (BH, num_q_blocks, num_kv_blocks); the kv axis is the innermost,
sequentially-accumulated dimension (online softmax in VMEM scratch).
Block shapes default to 128 — the MXU-native tile (DESIGN.md §2).
Causal q-blocks skip kv blocks entirely above the diagonal via
``pl.when`` (FLOPs are truly skipped, unlike a masked dense rectangle).

GQA is handled without materializing repeated KV: the kv BlockSpec
index map folds the q-head → kv-head mapping
(kv_bh = b·Hkv + (h // group)).
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


def _kernel(q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr, *, scale: float,
            block_q: int, block_k: int, causal: bool, seq_q: int,
            seq_k: int, q_offset: int):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q_pos = q_offset + i * block_q + jax.lax.iota(jnp.int32, block_q)
    k_pos = j * block_k + jax.lax.iota(jnp.int32, block_k)
    # Block-level skip: any(k_pos <= max q_pos)?
    run = ((not causal)
           or (j * block_k <= q_offset + i * block_q + block_q - 1))

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        mask = (k_pos[None, :] < seq_k) & (q_pos[:, None] < q_offset + seq_q)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]          # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(-1, keepdims=True)
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            precision=pv_precision(v_ref.dtype),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _fin():
        o_ref[0] = (acc[...] / jnp.maximum(l_scr[...], 1e-20)
                    ).astype(o_ref.dtype)


def flash_attention_bh(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       causal: bool = True, scale: Optional[float] = None,
                       block_q: int = 128, block_k: int = 128,
                       q_offset: int = 0,
                       interpret: bool = False) -> jax.Array:
    """q (BH, Sq, D); k/v (BHkv, Skv, D) with BH = BHkv·G.  Sq/Skv are
    padded to block multiples here; the mask keeps semantics exact."""
    BH, Sq, D = q.shape
    BHkv, Skv = k.shape[0], k.shape[1]
    G = BH // BHkv
    scale = D ** -0.5 if scale is None else scale
    Sq_p = -(-Sq // block_q) * block_q
    Skv_p = -(-Skv // block_k) * block_k
    q = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, Skv_p - Skv), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, Skv_p - Skv), (0, 0)))
    grid = (BH, Sq_p // block_q, Skv_p // block_k)

    kv_map = lambda b, i, j: (b // G, j, 0)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal, seq_q=Sq,
                          seq_k=Skv, q_offset=q_offset),
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
        name="flash_attn",
    )(q, k, v)
    return out[:, :Sq]
