"""Pallas TPU block-sparse attention (XAttention execution kernel).

The jnp scorer (``repro.core.modes.antidiagonal_scores``) selects a
*static-K* set of kv blocks per query block; this kernel executes only
those blocks.  The selection indices arrive as a scalar-prefetch
operand (``PrefetchScalarGridSpec``) so the kv BlockSpec index map can
dereference them — the TPU analogue of the paper's block-sparse CUDA
kernel [13], with 128×128 MXU tiles instead of 64 (DESIGN.md §2).
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


def _kernel(sel_ref, off_ref, q_ref, k_ref, v_ref, o_ref, acc, m_scr,
            l_scr, *, scale: float, block: int, seq_q: int, seq_k: int):
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    kv_block = sel_ref[b, i, j]

    # Duplicate selections must be resolved by the CALLER: this kernel
    # only *skips* entries ``dedupe_selection`` (or a causal truncator)
    # marked -1 — it has no cross-j view, so a repeated non-negative
    # index would be accumulated twice.  The skip is pl.when, not a
    # mask: a -1 step issues no MXU work (and its KV fetch collapses
    # onto a repeat of an already-resident block).
    @pl.when(kv_block >= 0)
    def _compute():
        row = i * block + jax.lax.iota(jnp.int32, block)
        q_pos = off_ref[0] + row            # absolute query positions
        k_pos = kv_block * block + jax.lax.iota(jnp.int32, block)
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = (k_pos[None, :] <= q_pos[:, None]) & (k_pos[None, :] < seq_k)
        mask &= row[:, None] < seq_q        # q padding rows
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

    @pl.when(j == pl.num_programs(2) - 1)
    def _fin():
        o_ref[0] = (acc[...] / jnp.maximum(l_scr[...], 1e-20)
                    ).astype(o_ref.dtype)


def dedupe_selection(sel: jax.Array) -> jax.Array:
    """Mark repeated block indices (per row) as -1 (skipped by the
    kernel's mask).  sel (..., K) int32, assumed small K."""
    K = sel.shape[-1]
    eq = sel[..., :, None] == sel[..., None, :]
    first = jnp.tril(jnp.ones((K, K), bool), k=-1)
    dup = (eq & first).any(-1)
    return jnp.where(dup, -1, sel)


def block_sparse_attention_bh(q: jax.Array, k: jax.Array, v: jax.Array,
                              sel: jax.Array, *, q_offset=0,
                              scale: Optional[float] = None,
                              block: int = 128,
                              interpret: bool = False) -> jax.Array:
    """q (BH,Sq,D), k/v (BHkv,Skv,D), sel (BH, nqb, K) int32 kv-block
    indices per q block (use ``dedupe_selection`` first).

    ``q_offset`` (scalar int32, may be *traced*) offsets the causal
    comparison: query row r attends kv positions ≤ q_offset + r.  The
    chunked prefill passes its chunk ``start`` here, so every chunk of
    a bucket shares one executable — the offset rides in as a
    scalar-prefetch operand, not a static shape."""
    BH, Sq, D = q.shape
    BHkv, Skv = k.shape[0], k.shape[1]
    G = BH // BHkv
    scale = D ** -0.5 if scale is None else scale
    Sq_p = -(-Sq // block) * block
    Skv_p = -(-Skv // block) * block
    q = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, Skv_p - Skv), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, Skv_p - Skv), (0, 0)))
    nqb, K = sel.shape[1], sel.shape[2]
    assert nqb == Sq_p // block, (nqb, Sq_p, block)
    grid = (BH, nqb, K)
    off = jnp.asarray(q_offset, jnp.int32).reshape(1)

    def kv_map(b, i, j, sel_ref, off_ref):
        return (b // G, jnp.maximum(sel_ref[b, i, j], 0), 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block=block, seq_q=Sq,
                          seq_k=Skv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block, D),
                             lambda b, i, j, s, o: (b, i, 0)),
                pl.BlockSpec((1, block, D), kv_map),
                pl.BlockSpec((1, block, D), kv_map),
            ],
            out_specs=pl.BlockSpec((1, block, D),
                                   lambda b, i, j, s, o: (b, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((block, D), jnp.float32),
                pltpu.VMEM((block, 1), jnp.float32),
                pltpu.VMEM((block, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((BH, Sq_p, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="block_sparse_attn",
    )(sel.astype(jnp.int32), off, q, k, v)
    return out[:, :Sq]
