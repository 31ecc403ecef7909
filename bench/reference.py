"""Plain float32 reference of the served decoder, independent of the
program: it imports nothing from ``repro`` and reads only the
benchmark's weights (a dict laid out as the program's parameter tree).

The block follows the published dense decoder: pre-norm RMSNorm,
half-split RoPE, grouped-query attention, SwiGLU FFN, an untied or tied
output projection.  Each layer attends causally over the whole prefix
(FA) or over the first ``sink`` and the last ``local`` positions (SA),
as the cell's fixed routing pattern says.  Matrix products run at
``Precision.HIGHEST`` on float32 copies of the weights.

``fp8=True`` is the control: every linear layer's operands are rounded
to float8 e4m3 (per-row scales for activations, per-column scales for
weights) before the product, the step below the bfloat16 the
configurations state.

Work is split so that it fits beside the weights: 512-token row blocks
for the projections and the FFN, attention in sub-blocks of query rows
against a key buffer of the cell's ``max_len``.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
ROWS = 512


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 with an absmax scale along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _linear(x, w, fp8: bool):
    w = w.astype(F32)
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(F32)


def _rope(x, pos, theta):
    """x (T, H, D), pos (T,): rotate the two halves of each head."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None, None] * inv
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


@partial(jax.jit, static_argnames=("hkv", "theta", "eps", "fp8"),
         donate_argnames=("kbuf", "vbuf"))
def _kv(lw, h, pos, kbuf, vbuf, *, hkv, theta, eps, fp8):
    x = _rms(h, lw["norm1"]["scale"], eps)
    t = h.shape[0]
    k = _linear(x, lw["attn"]["wk"], fp8).reshape(t, hkv, -1)
    v = _linear(x, lw["attn"]["wv"], fp8).reshape(t, hkv, -1)
    k = _rope(k, pos, theta)
    kbuf = jax.lax.dynamic_update_slice_in_dim(kbuf, k, pos[0], 0)
    vbuf = jax.lax.dynamic_update_slice_in_dim(vbuf, v, pos[0], 0)
    return kbuf, vbuf


@partial(jax.jit, static_argnames=("fa", "sink", "local", "hq", "theta",
                                   "eps", "sub", "fp8"))
def _block(lw, h, pos, kbuf, vbuf, n_valid, *, fa, sink, local, hq, theta,
           eps, sub, fp8):
    """One row block through attention and FFN of one layer."""
    t = h.shape[0]
    x = _rms(h, lw["norm1"]["scale"], eps)
    q = _rope(_linear(x, lw["attn"]["wq"], fp8).reshape(t, hq, -1), pos,
              theta)
    L, hkv, d = kbuf.shape
    g = hq // hkv
    kj = jnp.arange(L)

    def attend(args):
        qs, ps = args                        # (sub, hq, d), (sub,)
        s = jnp.einsum("qhgd,khd->hgqk", qs.reshape(-1, hkv, g, d), kbuf,
                       precision=HIGHEST) * d ** -0.5
        vis = (kj[None, :] <= ps[:, None]) & (kj[None, :] < n_valid)
        if not fa:
            vis &= (kj[None, :] < sink) | (ps[:, None] - kj[None, :] < local)
        s = jnp.where(vis[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", p, vbuf, precision=HIGHEST)
        return o.reshape(-1, hq * d)

    o = jax.lax.map(attend, (q.reshape(t // sub, sub, hq, -1),
                             pos.reshape(t // sub, sub)))
    h = h + _linear(o.reshape(t, hq * d), lw["attn"]["wo"], fp8)
    x2 = _rms(h, lw["norm2"]["scale"], eps)
    f = lw["ffn"]
    y = jax.nn.silu(_linear(x2, f["gate"], fp8)) * _linear(x2, f["up"], fp8)
    return h + _linear(y, f["down"], fp8)


@partial(jax.jit, static_argnames=("eps", "tied", "fp8"))
def _logits(w, h, *, eps, tied, fp8):
    x = _rms(h, w["final_norm"]["scale"], eps)
    head = w["embed"].T if tied else w["out_w"]
    return _linear(x, head, fp8)


def _layer(params, layer: int, period: int):
    per, pos = divmod(layer, period)
    return jax.tree.map(lambda a: a[per], params["trunk"][pos])


def logits(params: Dict, conf: Dict, pattern: Sequence[str],
           tokens: np.ndarray, first: int, max_len: int, *,
           fp8: bool = False) -> np.ndarray:
    """Reference logits (float32, (len(tokens) - first, vocab)) at
    positions first .. len(tokens)-1 of one sequence: row i predicts
    token first + i + 1."""
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    d, eps = conf["head_dim"], conf["rms_norm_eps"]
    theta = conf["rope_theta"]
    sink, local = conf["flux"]["sink"], conf["flux"]["local"]
    n = len(tokens)
    n_pad = -(-n // ROWS) * ROWS
    cap = -(-max(max_len, n_pad) // ROWS) * ROWS
    # query rows per attention sub-block: a (hq, sub, cap) f32 score
    # tile of at most 512 MiB
    sub = ROWS
    while sub > 8 and hq * sub * cap * 4 > 2 ** 29:
        sub //= 2
    ids = np.zeros(n_pad, np.int32)
    ids[:n] = tokens
    emb = params["embed"]
    hs = [jnp.take(emb, jnp.asarray(ids[i:i + ROWS]), axis=0).astype(F32)
          for i in range(0, n_pad, ROWS)]
    poss = [jnp.arange(i, i + ROWS, dtype=jnp.int32)
            for i in range(0, n_pad, ROWS)]
    n_valid = jnp.int32(n)
    for layer, mode in enumerate(pattern):
        lw = _layer(params, layer, len(params["trunk"]))
        kbuf = jnp.zeros((cap, hkv, d), F32)
        vbuf = jnp.zeros((cap, hkv, d), F32)
        for h, pos in zip(hs, poss):
            kbuf, vbuf = _kv(lw, h, pos, kbuf, vbuf, hkv=hkv, theta=theta,
                             eps=eps, fp8=fp8)
        for i, pos in enumerate(poss):   # in place: one copy of h
            hs[i] = _block(lw, hs[i], pos, kbuf, vbuf, n_valid,
                           fa=mode == "fa", sink=sink, local=local, hq=hq,
                           theta=theta, eps=eps, sub=sub, fp8=fp8)
        del kbuf, vbuf
    head = {"final_norm": params["final_norm"], "embed": params["embed"],
            "out_w": params.get("out_w")}
    rows = [np.asarray(_logits(head, hs[b], eps=eps,
                               tied=conf["tie_word_embeddings"], fp8=fp8))
            for b in range(first // ROWS, n_pad // ROWS)]
    base = (first // ROWS) * ROWS
    return np.concatenate(rows)[first - base:n - base]
