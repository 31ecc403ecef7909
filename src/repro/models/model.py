"""Model assembly: init + three drivers (train / prefill / decode).

Layer layout.  ``cfg.layer_pattern`` defines a period of block kinds
(e.g. Jamba's 8-layer Mamba/attention interleave); the trunk params are
stored *stacked by period position* — ``trunk[pos]`` is a pytree whose
leaves carry a leading ``n_periods`` axis.  Train and prefill drivers
``lax.scan`` over periods (compile time stays O(period), not O(L));
decode unrolls a python loop over layers because the per-layer cache
*shapes* depend on the cache geometry chosen at repack time — the
paper's sparse-decode memory saving is structural (kv_cache.py).
Generation itself is a second ``lax.scan`` over decode steps
(``decode_many``): sampling stays on device and the sampled-token →
next-step dependency never round-trips to the host.

Flux routing contexts:
  ("soft", tau, rng)   — Gumbel-Softmax blend of FA and SA (Eq. 5), train.
  ("hard",)            — router argmax per layer (batch consensus) at
                         prefill, executed via lax.cond (§3.3).
  ("fixed", pattern)   — externally forced decisions (static baselines,
                         dry-run patterns, ablations).
  ("fa_only",)         — backbone as-is (flux disabled).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.tree_util import register_dataclass

from repro.configs.base import ModelConfig
from repro.core import modes as M
from repro.core import router as R
from repro.distributed import constrain
from repro.models import attention as A
from repro.models import moe as MOE
from repro.models import ssm as S
from repro.kernels.decode_attention import PooledValid
from repro.models.layers import (dense_init, embed_init, ffn_apply, ffn_init,
                                 rms_norm, rms_norm_init)
from repro.serve import kv_cache as KC


# ---------------------------------------------------------------------------
# Structure helpers
# ---------------------------------------------------------------------------

def period_len(cfg: ModelConfig) -> int:
    return len(cfg.layer_pattern)


def n_periods(cfg: ModelConfig) -> int:
    assert cfg.num_layers % period_len(cfg) == 0, (
        f"{cfg.name}: num_layers {cfg.num_layers} not divisible by "
        f"pattern length {period_len(cfg)}")
    return cfg.num_layers // period_len(cfg)


def has_ffn(cfg: ModelConfig, layer_idx: int) -> bool:
    return cfg.d_ff > 0 or cfg.moe_layer_mask()[layer_idx]


def is_routed(cfg: ModelConfig, layer_idx: int) -> bool:
    return (cfg.flux.enabled
            and cfg.layer_kinds[layer_idx] == "attn")


def router_in_dim(cfg: ModelConfig) -> int:
    if cfg.use_mla:
        return cfg.num_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    return cfg.q_dim


def sa_mode(cfg: ModelConfig) -> M.AttnMode:
    return M.sa_mode_for(cfg.flux)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _block_init(key, cfg: ModelConfig, layer_idx: int) -> Dict[str, Any]:
    kind = cfg.layer_kinds[layer_idx]
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {"norm1": rms_norm_init(cfg.d_model, cfg.param_dtype)}
    if kind in ("attn", "local"):
        p["attn"] = (A.mla_init(ks[0], cfg) if cfg.use_mla
                     else A.gqa_init(ks[0], cfg))
        if is_routed(cfg, layer_idx):
            p["router"] = R.router_init(ks[1], router_in_dim(cfg), cfg.flux)
        if cfg.num_encoder_layers:  # whisper decoder: cross attention
            p["norm_x"] = rms_norm_init(cfg.d_model, cfg.param_dtype)
            d = cfg.d_model
            kx = jax.random.split(ks[2], 4)
            p["xattn"] = {
                "wq": dense_init(kx[0], d, cfg.q_dim, cfg.param_dtype),
                "wk": dense_init(kx[1], d, cfg.q_dim, cfg.param_dtype),
                "wv": dense_init(kx[2], d, cfg.q_dim, cfg.param_dtype),
                "wo": dense_init(kx[3], cfg.q_dim, d, cfg.param_dtype),
            }
    elif kind == "mamba":
        p["mamba"] = S.mamba_init(ks[0], cfg)
    if has_ffn(cfg, layer_idx):
        p["norm2"] = rms_norm_init(cfg.d_model, cfg.param_dtype)
        if cfg.moe_layer_mask()[layer_idx]:
            p["moe"] = MOE.moe_init(ks[3], cfg)
        else:
            p["ffn"] = ffn_init(ks[3], cfg.d_model, cfg.d_ff, cfg.param_dtype)
    return p


def _enc_block_init(key, cfg: ModelConfig) -> Dict[str, Any]:
    ks = jax.random.split(key, 2)
    d = cfg.d_model
    kx = jax.random.split(ks[0], 4)
    return {
        "norm1": rms_norm_init(d, cfg.param_dtype),
        "attn": {
            "wq": dense_init(kx[0], d, cfg.q_dim, cfg.param_dtype),
            "wk": dense_init(kx[1], d, cfg.q_dim, cfg.param_dtype),
            "wv": dense_init(kx[2], d, cfg.q_dim, cfg.param_dtype),
            "wo": dense_init(kx[3], cfg.q_dim, d, cfg.param_dtype),
        },
        "norm2": rms_norm_init(d, cfg.param_dtype),
        "ffn": ffn_init(ks[1], d, cfg.d_ff, cfg.param_dtype),
    }


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    P, NP = period_len(cfg), n_periods(cfg)
    keys = jax.random.split(key, cfg.num_layers + cfg.num_encoder_layers + 2)
    # trunk: for each period position, params stacked over periods.  A
    # vmap over the periods' keys builds each stacked leaf in place (the
    # same values as initializing layer by layer and stacking, without
    # holding every per-layer copy alongside the stack); every layer at
    # one period position has the same block kind, so ``pos`` stands in
    # for its layer index.
    trunk = [jax.vmap(partial(_block_init, cfg=cfg, layer_idx=pos))(
        keys[pos:NP * P:P]) for pos in range(P)]
    params: Dict[str, Any] = {
        "embed": embed_init(keys[-1], cfg.vocab_size, cfg.d_model,
                            cfg.param_dtype),
        "final_norm": rms_norm_init(cfg.d_model, cfg.param_dtype),
        "trunk": tuple(trunk),
    }
    if not cfg.tie_embeddings:
        params["out_w"] = dense_init(keys[-2], cfg.d_model, cfg.vocab_size,
                                     cfg.param_dtype)
    if cfg.num_encoder_layers:
        enc_keys = keys[cfg.num_layers:cfg.num_layers + cfg.num_encoder_layers]
        params["encoder"] = {
            "layers": jax.vmap(partial(_enc_block_init, cfg=cfg))(enc_keys),
            "final_norm": rms_norm_init(cfg.d_model, cfg.param_dtype),
        }
    return params


def init_params_on_device(key, cfg: ModelConfig, mesh=None) -> Dict[str, Any]:
    """``init_params`` as one compiled program, its output placed where
    serving keeps it.

    With ``mesh`` every leaf is born in its tensor-parallel serving
    sharding (``launch.shardings.param_shardings_decode_tp``): each
    device generates only its own shard, so no device ever holds the
    whole model — the only way a model larger than one chip can start.
    Without it the parameters land on the default device.  Either way
    the eager per-leaf dispatch and its transient copies are gone."""
    init = partial(init_params, cfg=cfg)
    shardings = None
    if mesh is not None:
        from repro.launch.shardings import param_shardings_decode_tp
        shardings = param_shardings_decode_tp(jax.eval_shape(init, key),
                                              mesh)
    return jax.jit(init, out_shardings=shardings)(key)


def router_param_filter(params: Dict[str, Any]) -> Dict[str, Any]:
    """Pytree mask: True on Layer-Router leaves (the only trainable part
    when reproducing the paper's parameter-efficient training)."""
    def mark(path, leaf):
        return any(getattr(p, "key", None) == "router" for p in path)
    return jax.tree_util.tree_map_with_path(mark, params)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _cross_attention(p, cfg: ModelConfig, h: jax.Array,
                     enc_out: jax.Array) -> jax.Array:
    """Whisper decoder cross-attention (bidirectional over encoder)."""
    B, S, _ = h.shape
    E = enc_out.shape[1]
    q = (h @ p["wq"]).reshape(B, S, cfg.num_heads, cfg.head_dim
                              ).transpose(0, 2, 1, 3)
    k = (enc_out @ p["wk"]).reshape(B, E, cfg.num_heads, cfg.head_dim
                                    ).transpose(0, 2, 1, 3)
    v = (enc_out @ p["wv"]).reshape(B, E, cfg.num_heads, cfg.head_dim
                                    ).transpose(0, 2, 1, 3)
    o = M.attention(q, k, v, M.BIDIRECTIONAL)
    return o.transpose(0, 2, 1, 3).reshape(B, S, -1) @ p["wo"]


def _route_and_attend(bp, cfg: ModelConfig, q, k, v, x_q, ctx,
                      q_offset=0):
    """Run FA / SA / blend per the routing context.

    Returns (attn_out, r) where r is:
      soft  → r_soft (B,) FA probability
      hard  → (decision scalar {0,1}, p_fa mean)
      fixed → (decision, decision)
      fa_only → None
    """
    flux = cfg.flux
    sa = sa_mode(cfg)
    kind = ctx[0]
    if kind == "fa_only":
        return M.attention(q, k, v, M.FULL, q_offset=q_offset,
                           split_depth=cfg.causal_split_depth), None
    if kind == "head_split":
        # DuoAttention/PruLong-style static head-level baseline.
        return M.head_split_attention(q, k, v, ctx[1], sa,
                                      q_offset=q_offset), None
    if kind == "soft":
        _, tau, rng = ctx
        r = R.soft_route(bp["router"], x_q, flux, tau, rng)  # (B,)
        o_fa = M.attention(q, k, v, M.FULL, q_offset=q_offset,
                           split_depth=cfg.causal_split_depth)
        o_sa = M.attention(q, k, v, sa, q_offset=q_offset)
        rb = r[:, None, None, None].astype(o_fa.dtype)
        return rb * o_fa + (1 - rb) * o_sa, r
    if kind in ("hard", "hard_prefix"):
        # "hard_prefix" pools the prefix only — the chunk-invariant
        # serving variant (router.pool_prefix); "hard" is the paper's
        # prefix+suffix pooling over the full sequence.
        pooling = "prefix" if kind == "hard_prefix" else "prefix_suffix"
        r_hard, p_fa = R.hard_route(bp["router"], x_q, flux, pooling)
        # batch-consensus scalar decision (per-request when B=1; the
        # engine buckets requests by routing pattern otherwise).  The
        # threshold is a *traced* scalar when the load-adaptive
        # sparsity dial is engaged (router.sa_biased_threshold) — 0.5
        # is the paper's argmax, and tracing keeps every dial setting
        # on one compiled prefill executable.
        thr = ctx[1] if len(ctx) > 1 else 0.5
        decision = (jnp.mean(p_fa) > thr).astype(jnp.int32)
    else:  # fixed
        decision = ctx[1]
        p_fa = None
    out = lax.cond(
        decision > 0,
        lambda qkv: M.attention(*qkv, M.FULL, q_offset=q_offset,
                                split_depth=cfg.causal_split_depth),
        lambda qkv: M.attention(*qkv, sa, q_offset=q_offset),
        (q, k, v))
    p_mean = jnp.mean(p_fa) if p_fa is not None else decision.astype(
        jnp.float32) if hasattr(decision, "astype") else jnp.float32(decision)
    return out, (decision, p_mean)


def block_apply(bp, cfg: ModelConfig, layer_idx: int, h: jax.Array,
                positions: jax.Array, ctx, enc_out=None,
                mamba_state=None, want_cache: bool = False):
    """One transformer block (train/prefill path over a full sequence).

    Returns (h, r, cache, aux): r is the routing record for routed
    layers else None; cache is the layer's prefill KV when
    ``want_cache`` (k/v | (ckv, kr) | (ssd_state, conv_tail)).
    """
    kind = cfg.layer_kinds[layer_idx]
    cache = None
    aux: Dict[str, Any] = {}
    r = None
    x = rms_norm(bp["norm1"], h, cfg.norm_eps)
    if kind == "mamba":
        y, (ssd_state, conv_tail) = S.mamba_apply(bp["mamba"], cfg, x,
                                                  mamba_state)
        if want_cache:
            cache = (ssd_state, conv_tail)
        h = h + y
    elif kind in ("attn", "local"):
        if cfg.use_mla:
            ckv, kr = A.mla_latent(bp["attn"], cfg, x, positions)
            q, x_q = A.mla_q(bp["attn"], cfg, x, positions)
            k, v = A.mla_expand_kv(bp["attn"], cfg, ckv, kr)
            if want_cache:
                cache = (ckv, kr)
        else:
            q, k, v, x_q = A.gqa_qkv(bp["attn"], cfg, x, positions)
            if want_cache:
                cache = (k, v)
        if kind == "local":
            o = M.attention(q, k, v, M.window_mode(cfg.sliding_window))
        elif is_routed(cfg, layer_idx) and ctx[0] != "fa_only":
            o, r = _route_and_attend(bp, cfg, q, k, v, x_q, ctx)
        else:
            o = M.attention(q, k, v, M.FULL,
                            split_depth=cfg.causal_split_depth)
        h = h + (A.mla_out(bp["attn"], cfg, o) if cfg.use_mla
                 else A.gqa_out(bp["attn"], cfg, o))
        if "xattn" in bp and enc_out is not None:
            hx = rms_norm(bp["norm_x"], h, cfg.norm_eps)
            h = h + _cross_attention(bp["xattn"], cfg, hx, enc_out)
    if has_ffn(cfg, layer_idx):
        x2 = rms_norm(bp["norm2"], h, cfg.norm_eps)
        if "moe" in bp:
            y2, moe_aux = MOE.moe_apply(bp["moe"], cfg, x2)
            aux["moe_balance"] = moe_aux["balance_loss"]
            aux["moe_drop"] = moe_aux["drop_fraction"]
        else:
            y2 = ffn_apply(bp["ffn"], x2)
        h = h + y2
    return h, r, cache, aux


# ---------------------------------------------------------------------------
# Encoder (whisper backbone)
# ---------------------------------------------------------------------------

def encode(params, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """Bidirectional encoder over precomputed frame embeddings."""
    enc = params["encoder"]

    def body(h, bp):
        x = rms_norm(bp["norm1"], h, cfg.norm_eps)
        B, E, _ = x.shape
        q = (x @ bp["attn"]["wq"]).reshape(B, E, cfg.num_heads, cfg.head_dim
                                           ).transpose(0, 2, 1, 3)
        k = (x @ bp["attn"]["wk"]).reshape(B, E, cfg.num_heads, cfg.head_dim
                                           ).transpose(0, 2, 1, 3)
        v = (x @ bp["attn"]["wv"]).reshape(B, E, cfg.num_heads, cfg.head_dim
                                           ).transpose(0, 2, 1, 3)
        o = M.attention(q, k, v, M.BIDIRECTIONAL)
        h = h + o.transpose(0, 2, 1, 3).reshape(B, E, -1) @ bp["attn"]["wo"]
        x2 = rms_norm(bp["norm2"], h, cfg.norm_eps)
        return h + ffn_apply(bp["ffn"], x2), None

    h, _ = lax.scan(body, frames.astype(cfg.dtype), enc["layers"])
    return rms_norm(enc["final_norm"], h, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Embedding / logits
# ---------------------------------------------------------------------------

def embed_tokens(params, cfg: ModelConfig, tokens: jax.Array,
                 prefix_embeddings=None) -> jax.Array:
    h = params["embed"].astype(cfg.dtype)[tokens]
    if prefix_embeddings is not None:
        h = jnp.concatenate([prefix_embeddings.astype(cfg.dtype), h], axis=1)
    return constrain(h, "batch", "seq", "embed")


def unembed_matrix(params, cfg: ModelConfig) -> jax.Array:
    return params["embed"].T if cfg.tie_embeddings else params["out_w"]


def logits_from_hidden(params, cfg: ModelConfig, h: jax.Array) -> jax.Array:
    h = rms_norm(params["final_norm"], h, cfg.norm_eps)
    logits = h @ unembed_matrix(params, cfg).astype(h.dtype)
    return constrain(logits, "batch", None, "vocab")


# ---------------------------------------------------------------------------
# Train / prefill drivers (scan over periods)
# ---------------------------------------------------------------------------

@register_dataclass
@dataclass
class ForwardOut:
    logits: jax.Array           # (B, S, V) train / (B, V) prefill-last
    r_soft: Optional[jax.Array]   # (B, n_routed) FA probs (train)
    routing: Optional[jax.Array]  # (n_routed,) hard decisions (prefill)
    p_fa: Optional[jax.Array]     # (n_routed,) mean FA prob (prefill)
    aux: Dict[str, jax.Array]
    caches: Any = None


def _trunk_scan(params, cfg: ModelConfig, h: jax.Array, positions,
                ctx_builder, enc_out=None, want_cache: bool = False,
                remat: bool = False):
    """Scan over periods; python loop over the period positions inside."""
    P = period_len(cfg)

    def body(carry, xs):
        h = carry
        per_idx, trunk_slice = xs
        rs, caches, auxes = [], [], {}
        for pos in range(P):
            layer_idx_static = pos  # static within period
            ctx = ctx_builder(per_idx, pos)
            h, r, cache, aux = block_apply(
                trunk_slice[pos], cfg, layer_idx_static, h, positions, ctx,
                enc_out=enc_out, want_cache=want_cache)
            if r is not None:
                rs.append(r)
            if cache is not None:
                caches.append(cache)
            for k_, v_ in aux.items():
                auxes[k_] = auxes.get(k_, 0.0) + v_
        # keep the carried residual stream sharded (the scan's saved
        # activations dominate training memory at 100B scale; "seq" maps
        # to the model axis under the launch layer's Megatron-SP-style
        # rules)
        h = constrain(h, "batch", "seq", "embed")
        return h, (tuple(rs), tuple(caches), auxes)

    if remat:
        body = jax.checkpoint(body)
    xs = (jnp.arange(n_periods(cfg)), params["trunk"])
    h, (rs, caches, auxes) = lax.scan(body, h, xs)
    return h, rs, caches, auxes


def forward_train(params, cfg: ModelConfig, tokens: jax.Array, *,
                  rng=None, tau=1.0, prefix_embeddings=None,
                  encoder_frames=None, remat: bool = True,
                  flux_soft: bool = True,
                  output_hidden: bool = False) -> ForwardOut:
    """Training forward with Gumbel-Softmax soft routing (Eq. 4–5).

    ``output_hidden=True`` returns the final-normed hidden states in
    ``.logits`` instead of vocabulary logits — callers then use
    ``chunked_cross_entropy`` so the (B,S,V) tensor is never
    materialized (essential at 256k vocab)."""
    B, Stok = tokens.shape
    enc_out = (encode(params, cfg, encoder_frames)
               if cfg.num_encoder_layers else None)
    h = embed_tokens(params, cfg, tokens, prefix_embeddings)
    positions = jnp.arange(h.shape[1])
    P = period_len(cfg)

    use_soft = flux_soft and cfg.flux.enabled and rng is not None

    def ctx_builder(per_idx, pos):
        if not use_soft or cfg.layer_kinds[pos] != "attn":
            return ("fa_only",)
        layer_rng = jax.random.fold_in(jax.random.fold_in(rng, pos), per_idx)
        return ("soft", tau, layer_rng)

    h, rs, _, auxes = _trunk_scan(params, cfg, h, positions, ctx_builder,
                                  enc_out=enc_out, remat=remat)
    prefix = h.shape[1] - Stok
    h = h[:, prefix:] if prefix else h
    if output_hidden:
        logits = rms_norm(params["final_norm"], h, cfg.norm_eps)
    else:
        logits = logits_from_hidden(params, cfg, h)
    r_soft = None
    if use_soft and rs:
        # rs: tuple over routed positions of (n_periods, B) → (B, n_routed)
        stacked = jnp.stack(rs, axis=1)  # (n_periods, n_pos_routed, B)
        r_soft = jnp.transpose(stacked, (2, 0, 1)).reshape(B, -1)
    return ForwardOut(logits=logits, r_soft=r_soft, routing=None, p_fa=None,
                      aux=auxes)


def prefill(params, cfg: ModelConfig, tokens: jax.Array, *,
            routing_ctx: str = "hard", fixed_pattern=None,
            head_split_n: int = 0, prefix_embeddings=None,
            encoder_frames=None, want_cache: bool = True,
            fa_threshold=None) -> ForwardOut:
    """Serving prefill: hard routing (or a fixed pattern), full KV out.

    ``fixed_pattern``: (num_layers,) int array (1=FA, 0=SA) or None.
    ``routing_ctx="head_split"`` runs the DuoAttention-style baseline
    with ``head_split_n`` retrieval KV heads per layer.
    ``routing_ctx="hard_prefix"`` is hard routing with prefix-only
    pooling — decisions depend only on the first ``pool_size`` tokens,
    so a chunked prefill routing on its first chunk reproduces them
    exactly (DESIGN.md §Prefill pipeline).
    ``fa_threshold``: traced scalar FA-decision threshold for the hard
    routing contexts (None = the paper's 0.5 argmax).  The serving
    engine passes ``router.sa_biased_threshold`` rungs here for the
    load-adaptive sparsity dial; tracing it keeps one executable
    across every dial setting.
    """
    B, Stok = tokens.shape
    enc_out = (encode(params, cfg, encoder_frames)
               if cfg.num_encoder_layers else None)
    h = embed_tokens(params, cfg, tokens, prefix_embeddings)
    positions = jnp.arange(h.shape[1])
    P = period_len(cfg)
    if fixed_pattern is not None:
        fixed_pattern = jnp.asarray(fixed_pattern).reshape(n_periods(cfg), P)
    thr = (None if fa_threshold is None
           else jnp.asarray(fa_threshold, jnp.float32))

    def ctx_builder(per_idx, pos):
        if cfg.layer_kinds[pos] != "attn":
            return ("fa_only",)
        if routing_ctx == "head_split":
            return ("head_split", head_split_n)
        if not cfg.flux.enabled or routing_ctx == "fa_only":
            return ("fa_only",)
        if routing_ctx == "fixed":
            return ("fixed", fixed_pattern[per_idx, pos])
        key = "hard_prefix" if routing_ctx == "hard_prefix" else "hard"
        return (key,) if thr is None else (key, thr)

    h, rs, caches, auxes = _trunk_scan(params, cfg, h, positions,
                                       ctx_builder, enc_out=enc_out,
                                       want_cache=want_cache)
    logits = logits_from_hidden(params, cfg, h[:, -1])
    routing = p_fa = None
    if rs:
        # rs: tuple over routed positions of tuples (decision (n_periods,),
        # p_mean (n_periods,)) — stack to (n_routed,) in layer order.
        dec = jnp.stack([r[0] for r in rs], axis=1)   # (n_periods, n_pos)
        pfa = jnp.stack([r[1] for r in rs], axis=1)
        routing = dec.reshape(-1)
        p_fa = pfa.reshape(-1)
    return ForwardOut(logits=logits, r_soft=None, routing=routing,
                      p_fa=p_fa, aux=auxes, caches=caches if want_cache
                      else None)


# ---------------------------------------------------------------------------
# Decode driver (python loop over layers; polymorphic on cache geometry)
#
# The static axis of the compiled decode step is the per-layer *cache
# geometry* — FullKV vs RingKV vs LatentKV vs RingLatentKV, which
# genuinely changes compiled buffer shapes and flows in implicitly as
# the caches pytree structure.  The fa/sa/duo routing pattern itself is
# NOT static: any residual behavioral distinction between patterns that
# share a geometry (today: how many KV heads of a full-cache layer run
# full vs streaming attention) is traced data (``fa_heads``), so one
# executable serves every routing pattern with the same geometry
# (DESIGN.md §Serving) instead of one per pattern (2^routable worst
# case for the old routing-tuple static argument).
# ---------------------------------------------------------------------------

def layer_params(params, cfg: ModelConfig, layer_idx: int):
    P = period_len(cfg)
    per, pos = divmod(layer_idx, P)
    return jax.tree.map(lambda a: a[per], params["trunk"][pos])


def _rope_positions(pos: jax.Array) -> jax.Array:
    """Decode-step RoPE positions: (1,) shared when ``pos`` is scalar,
    (B, 1) per-slot when ``pos`` is (B,) — ``apply_rope`` broadcasts
    either against the length-1 sequence axis."""
    return pos[None] if jnp.ndim(pos) == 0 else pos[:, None]


def _causal_valid(L: int, pos: jax.Array, batch: int) -> jax.Array:
    """(B, L) per-row causal mask over a full cache (slots ≤ pos)."""
    idx = jnp.arange(L)
    if jnp.ndim(pos) == 0:
        return jnp.broadcast_to(idx <= pos, (batch, L))
    return idx[None, :] <= pos[:, None]


def _decode_attn_full(bp, cfg, x, pos, cache: KC.FullKV):
    positions = _rope_positions(pos)
    if cfg.use_mla:
        ckv, kr = A.mla_latent(bp["attn"], cfg, x, positions)
        cache = KC.latent_insert(cache, ckv, kr, pos)
        valid = _causal_valid(cache.ckv.shape[1], pos, x.shape[0])
        y = _mla_decode(bp, cfg, x, positions, cache.ckv, cache.kr,
                        valid, lengths=cache.length, ring_positions=None)
        return y, cache
    q, k, v, _ = A.gqa_qkv(bp["attn"], cfg, x, positions)
    cache = _full_kv_insert(cache, k, v, pos)
    if jnp.ndim(pos) == 0:
        # uniform positions → 1-D mask, eligible for the kernel /
        # distributed decode overrides
        valid = jnp.arange(cache.k.shape[2]) <= pos  # (Smax,)
    else:
        # per-slot positions → pooled validity: the dense (B, 1, Smax)
        # mask plus the (B,) live-prefix lengths a pooled kernel trips
        # on (FullKV slot i holds position i, so positions=None)
        valid = PooledValid(
            mask=_causal_valid(cache.k.shape[2], pos,
                               x.shape[0])[:, None],
            lengths=cache.length)
    o = _dot_decode(q, cache.k, cache.v, valid)
    return A.gqa_out(bp["attn"], cfg, o), cache


def _mla_decode(bp, cfg, x, positions, ckv, kr, valid, *, lengths,
                ring_positions):
    """Absorbed MLA decode step with the override fast path.

    When a pooled-capable decode override is installed, the absorbed
    attention is re-expressed as GQA-shaped (q, k, v) with Hkv = 1
    (``mla_absorbed_qkv``) and offered with per-slot validity; the
    kernel returns the latent context and ``mla_absorbed_finish``
    applies the absorbed output projection.  Decline → dense absorbed
    softmax, bit-for-bit the old path."""
    if _DECODE_ATTN_OVERRIDE and getattr(
            _DECODE_ATTN_OVERRIDE[-1], "supports_pooled", False):
        q_eff, k_eff, v_eff, scale = A.mla_absorbed_qkv(
            bp["attn"], cfg, x, positions, ckv, kr)
        pv = PooledValid(mask=valid, lengths=lengths,
                         positions=ring_positions)
        ctx = _consult_decode_attn(q_eff, k_eff, v_eff, pv, scale=scale)
        if ctx is not None:
            return A.mla_absorbed_finish(bp["attn"], cfg, ctx)
    return A.mla_absorbed_decode(bp["attn"], cfg, x, positions, ckv, kr,
                                 valid)


def _decode_attn_ring(bp, cfg, x, pos, cache, sink: int, local: int):
    positions = _rope_positions(pos)
    pos_col = pos if jnp.ndim(pos) == 0 else pos[:, None]
    if cfg.use_mla:
        ckv, kr = A.mla_latent(bp["attn"], cfg, x, positions)
        cache = KC.ring_latent_insert(cache, ckv, kr, pos, sink, local)
        valid = (cache.positions >= 0) & (cache.positions <= pos_col)
        ring = cache.positions.shape[1]
        y = _mla_decode(bp, cfg, x, positions, cache.ckv, cache.kr,
                        valid,
                        lengths=jnp.minimum(cache.length, ring),
                        ring_positions=jnp.where(valid, cache.positions,
                                                 -1))
        return y, cache
    q, k, v, _ = A.gqa_qkv(bp["attn"], cfg, x, positions)
    cache = KC.ring_insert(cache, k, v, pos, sink, local)
    if jnp.ndim(pos) == 0:
        # uniform positions keep every row of cache.positions identical
        # (repack + scalar-pos inserts), so a 1-D mask is exact — and
        # keeps ring layers eligible for the kernel/distributed
        # decode-attention overrides
        valid = (cache.positions[0] >= 0) & (cache.positions[0] <= pos)
    else:
        # per-slot (B, ring) bookkeeping → pooled validity.  Ring
        # occupancy is a contiguous slot prefix of min(length, ring)
        # entries, so a pooled kernel trips on that count; positions
        # outside the dense mask are re-marked -1 so the kernel's
        # occupancy test is structurally identical to the dense mask.
        mask2 = (cache.positions >= 0) & (cache.positions <= pos_col)
        valid = PooledValid(
            mask=mask2[:, None],
            lengths=jnp.minimum(cache.length,
                                cache.positions.shape[1]),
            positions=jnp.where(mask2, cache.positions, -1))
    o = _dot_decode(q, cache.k, cache.v, valid)
    return A.gqa_out(bp["attn"], cfg, o), cache


import contextlib as _contextlib

# Pluggable decode-attention implementation: the launch layer installs
# the shard_map LSE-combine path for sequence-sharded caches
# (repro.distributed.decode); default is the local dot product.
_DECODE_ATTN_OVERRIDE = []
_CACHE_INSERT_OVERRIDE = []


@_contextlib.contextmanager
def use_decode_attn(fn):
    _DECODE_ATTN_OVERRIDE.append(fn)
    try:
        yield
    finally:
        _DECODE_ATTN_OVERRIDE.pop()


@_contextlib.contextmanager
def use_cache_insert(fn):
    """Install a sharded FullKV insert (repro.distributed.decode)."""
    _CACHE_INSERT_OVERRIDE.append(fn)
    try:
        yield
    finally:
        _CACHE_INSERT_OVERRIDE.pop()


def _full_kv_insert(cache: KC.FullKV, k_new, v_new, pos) -> KC.FullKV:
    # the distributed sharded insert handles uniform (scalar) positions
    # only; per-slot inserts stay on the local scatter path
    if _CACHE_INSERT_OVERRIDE and jnp.ndim(pos) == 0:
        out = _CACHE_INSERT_OVERRIDE[-1](cache.k, cache.v, k_new, v_new,
                                         pos)
        if out is not None:
            return KC.FullKV(k=out[0], v=out[1],
                             length=jnp.broadcast_to(
                                 pos + 1, cache.length.shape).astype(
                                     cache.length.dtype))
    return KC.full_insert(cache, k_new, v_new, pos)


def _consult_decode_attn(q, k, v, valid, scale=None):
    """Offer a decode to the installed override; None = run dense.

    Capability negotiation keeps legacy overrides (the distributed
    LSE-combine adapter, test fakes) callable with their historical
    4-positional signature: :class:`PooledValid` is only handed to fns
    advertising ``supports_pooled``, and a non-default ``scale`` only
    to fns advertising ``supports_scale``."""
    if not _DECODE_ATTN_OVERRIDE:
        return None
    fn = _DECODE_ATTN_OVERRIDE[-1]
    if isinstance(valid, PooledValid) and not getattr(
            fn, "supports_pooled", False):
        return None
    if scale is not None:
        if not getattr(fn, "supports_scale", False):
            return None
        return fn(q, k, v, valid, scale=scale)
    return fn(q, k, v, valid)


def _dot_decode(q, k, v, valid):
    """q (B,H,1,D), k/v (B,Hkv,L,D) → (B,H,1,D).

    valid is (L,) shared, (Hkv,L) per-kv-head (head-split baselines),
    (B,Hkv_or_1,L) per-row, or a :class:`PooledValid` carrying per-slot
    lengths/positions next to its dense (B,1,L) mask (continuous-
    batching slot pools, where every row is a different request at its
    own position — the batched pooled kernel's home turf)."""
    if isinstance(valid, PooledValid):
        out = _consult_decode_attn(q, k, v, valid)
        if out is not None:
            return out
        valid = valid.mask  # decline → dense per-row path
    elif _DECODE_ATTN_OVERRIDE and valid.ndim == 1:
        out = _consult_decode_attn(q, k, v, valid)
        if out is not None:  # override may decline (e.g. small ring)
            return out
    B, Hq, _, D = q.shape
    Hkv = k.shape[1]
    q5 = q.reshape(B, Hkv, Hq // Hkv, 1, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", q5, k,
                   preferred_element_type=jnp.float32) * D ** -0.5
    if valid.ndim == 1:
        vmask = valid[None, None, None, None, :]
    elif valid.ndim == 2:  # per-kv-head mask (head-split baselines)
        vmask = valid[None, :, None, None, :]
    else:  # (B, Hkv or 1, L) per-row mask
        vmask = valid[:, :, None, None, :]
    s = jnp.where(vmask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Hq, 1, D).astype(q.dtype)


def _decode_attn_headsplit(bp, cfg, x, pos, cache: KC.FullKV, n_fa_kv):
    """DuoAttention-style decode: the cache stays *full-shape* (ragged
    per-head histories are unrepresentable — the paper's §2.3 point);
    streaming heads merely mask, saving FLOPs but no HBM traffic.

    ``n_fa_kv`` may be a traced int32 scalar: the full/streaming head
    split only shapes a mask, so patterns differing in it share one
    executable (n_fa_kv == num_kv_heads reduces to full attention).
    """
    positions = _rope_positions(pos)
    q, k, v, _ = A.gqa_qkv(bp["attn"], cfg, x, positions)
    cache = _full_kv_insert(cache, k, v, pos)
    L = cache.k.shape[2]
    idx = jnp.arange(L)
    head_is_full = jnp.arange(cfg.num_kv_heads) < n_fa_kv
    if jnp.ndim(pos) == 0:
        full_valid = idx <= pos
        stream_valid = full_valid & ((idx < cfg.flux.sink)
                                     | (pos - idx < cfg.flux.local))
        per_head = jnp.where(head_is_full[:, None],
                             full_valid[None, :], stream_valid[None, :])
    else:  # per-slot positions → (B, Hkv, L)
        full_valid = idx[None, :] <= pos[:, None]
        stream_valid = full_valid & (
            (idx[None, :] < cfg.flux.sink)
            | (pos[:, None] - idx[None, :] < cfg.flux.local))
        per_head = jnp.where(head_is_full[None, :, None],
                             full_valid[:, None, :],
                             stream_valid[:, None, :])
    o = _dot_decode(q, cache.k, cache.v, per_head)
    return A.gqa_out(bp["attn"], cfg, o), cache


def decode_core(params, cfg: ModelConfig, token: jax.Array, caches: List,
                pos: jax.Array, enc_out=None, fa_heads=None,
                duo_layers: Optional[Tuple[int, ...]] = None):
    """One autoregressive step, dispatched on cache geometry.

    token (B,1) int32; ``pos`` is () int32 — all rows at the same
    position (single-request serving) — or (B,) int32 per-slot
    positions (continuous-batching slot pools: every row is an
    independent request, with per-row RoPE angles, causal masks and
    ring arithmetic).  Per-layer behavior derives from the cache
    *type* (ring ⇒ sink+local streaming attention, full/latent ⇒ full
    attention), so the compiled executable is keyed by geometry alone.
    ``duo_layers`` (static tuple of layer indices) marks full-cache GQA
    layers running a DuoAttention-style head split; for those,
    ``fa_heads`` (num_layers,) int32 — *traced* — gives the number of
    KV heads on full attention, so duo patterns differing only in the
    split share one executable.  Layers outside ``duo_layers`` keep the
    plain full-attention path (1-D validity mask, eligible for the
    kernel / distributed decode overrides).
    Returns (logits (B,V), new_caches).
    """
    h = embed_tokens(params, cfg, token)
    new_caches = []
    flux = cfg.flux
    for i, kind in enumerate(cfg.layer_kinds):
        bp = layer_params(params, cfg, i)
        cache = caches[i]
        x = rms_norm(bp["norm1"], h, cfg.norm_eps)
        if kind == "mamba":
            y, hstate, tail = S.mamba_decode_step(bp["mamba"], cfg, x,
                                                  cache.h, cache.conv_tail)
            cache = KC.MambaCache(h=hstate, conv_tail=tail)
            h = h + y
        else:
            if isinstance(cache, (KC.RingKV, KC.RingLatentKV)):
                sink = 0 if kind == "local" else flux.sink
                ring = (cache.ckv.shape[1]
                        if isinstance(cache, KC.RingLatentKV)
                        else cache.k.shape[2])
                y, cache = _decode_attn_ring(bp, cfg, x, pos, cache,
                                             sink, ring - sink)
            elif (duo_layers is not None and i in duo_layers
                  and fa_heads is not None and not cfg.use_mla):
                y, cache = _decode_attn_headsplit(bp, cfg, x, pos, cache,
                                                  fa_heads[i])
            else:
                y, cache = _decode_attn_full(bp, cfg, x, pos, cache)
            h = h + y
            if "xattn" in bp and enc_out is not None:
                hx = rms_norm(bp["norm_x"], h, cfg.norm_eps)
                h = h + _cross_attention(bp["xattn"], cfg, hx, enc_out)
        if has_ffn(cfg, i):
            x2 = rms_norm(bp["norm2"], h, cfg.norm_eps)
            if "moe" in bp:
                y2, _ = MOE.moe_apply(bp["moe"], cfg, x2)
            else:
                y2 = ffn_apply(bp["ffn"], x2)
            h = h + y2
        new_caches.append(cache)
    logits = logits_from_hidden(params, cfg, h[:, -1])
    return logits, new_caches


# ---------------------------------------------------------------------------
# Chunked cache-resident prefill (DESIGN.md §Prefill pipeline)
#
# Streams one prompt chunk through the trunk writing *directly into
# decode-geometry caches*: ``full_insert_chunk`` at FA layers,
# ``ring_insert_chunk`` at SA layers — peak live KV at SA layers is
# bounded by the ring, not the prompt, and the monolithic
# prefill→repack pass disappears from the hot path.  Like
# ``decode_core``, per-layer behavior derives from the cache *type*
# (ring ⇒ sink+local streaming, full/latent ⇒ full causal), so the
# compiled executable is keyed by (cache geometry, chunk bucket) and
# ``start`` stays traced — every chunk offset shares one executable.
# ---------------------------------------------------------------------------

def _chunk_attn_ring(bp, cfg: ModelConfig, x, positions, start, cache,
                     sink: int, local: int):
    """Chunk attention at a ring-cache layer: queries see the pre-insert
    ring (explicit per-slot positions) plus the chunk's own keys under
    the sink+local mask, then the chunk is ring-inserted.  Computing
    attention *before* eviction is what makes chunks longer than the
    ring exact: mid-chunk queries still see keys the insert is about to
    overwrite."""
    B, C, _ = x.shape
    if isinstance(cache, KC.RingLatentKV):
        ckv, kr = A.mla_latent(bp["attn"], cfg, x, positions)
        q, _ = A.mla_q(bp["attn"], cfg, x, positions)
        k_ctx, v_ctx = A.mla_expand_kv(bp["attn"], cfg, cache.ckv, cache.kr)
        k_new, v_new = A.mla_expand_kv(bp["attn"], cfg, ckv, kr)
    else:
        q, k_new, v_new, _ = A.gqa_qkv(bp["attn"], cfg, x, positions)
        k_ctx, v_ctx = cache.k, cache.v
    kv_pos = jnp.concatenate(
        [cache.positions, jnp.broadcast_to(positions, (B, C))], axis=1)
    k_all = jnp.concatenate([k_ctx, k_new], axis=2)
    v_all = jnp.concatenate([v_ctx, v_new], axis=2)
    valid = M.streaming_valid(positions, kv_pos, sink, local)  # (B,C,L)
    o = M.masked_attention(q, k_all, v_all, valid[:, None])
    if isinstance(cache, KC.RingLatentKV):
        cache = KC.ring_latent_insert_chunk(cache, ckv, kr, start, sink,
                                            local)
        return A.mla_out(bp["attn"], cfg, o), cache
    cache = KC.ring_insert_chunk(cache, k_new, v_new, start, sink, local)
    return A.gqa_out(bp["attn"], cfg, o), cache


def _chunk_attn_full(bp, cfg: ModelConfig, x, positions, start, cache):
    """Chunk attention at a full-cache layer: insert the chunk at
    [start, start+C), then causal attention over the cache buffer via
    the kv-blocked online softmax (``modes.chunk_causal_attention``) —
    slots past the chunk hold zeros at positions > every query, and the
    traced block trip count never visits them."""
    B, C, _ = x.shape
    if isinstance(cache, KC.LatentKV):
        ckv, kr = A.mla_latent(bp["attn"], cfg, x, positions)
        cache = KC.latent_insert_chunk(cache, ckv, kr, start)
        Smax = cache.ckv.shape[1]
        valid = jnp.arange(Smax)[None, None, :] <= positions[None, :, None]
        y = A.mla_absorbed_attend(bp["attn"], cfg, x, positions, cache.ckv,
                                  cache.kr,
                                  jnp.broadcast_to(valid, (B, C, Smax)))
        return y, cache
    q, k_new, v_new, _ = A.gqa_qkv(bp["attn"], cfg, x, positions)
    cache = KC.full_insert_chunk(cache, k_new, v_new, start)
    # kv-blocked online softmax with a traced trip count: compute
    # scales with the live prefix [0, start+C), not the buffer
    o = M.chunk_causal_attention(q, cache.k, cache.v, start)
    return A.gqa_out(bp["attn"], cfg, o), cache


def prefill_chunk(params, cfg: ModelConfig, tokens: jax.Array, caches: List,
                  start: jax.Array, enc_out=None):
    """Stream one chunk of a chunked cache-resident prefill.

    tokens (B, C) int32 — the chunk (static, bucketed length C);
    ``start`` () int32 traced — its absolute offset; ``caches`` — the
    decode-geometry cache list being filled (routing already frozen:
    the pattern was fixed on the first chunk, §3.3).  Mamba layers
    thread their SSD state / conv tail through the same cache slots.
    Returns (last-token logits (B, V), updated caches).
    """
    B, C = tokens.shape
    flux = cfg.flux
    h = embed_tokens(params, cfg, tokens)
    positions = start + jnp.arange(C)
    new_caches = []
    for i, kind in enumerate(cfg.layer_kinds):
        bp = layer_params(params, cfg, i)
        cache = caches[i]
        x = rms_norm(bp["norm1"], h, cfg.norm_eps)
        if kind == "mamba":
            y, (hs, tail) = S.mamba_apply(bp["mamba"], cfg, x,
                                          (cache.h, cache.conv_tail))
            cache = KC.MambaCache(h=hs, conv_tail=tail)
            h = h + y
        else:
            if isinstance(cache, (KC.RingKV, KC.RingLatentKV)):
                sink = 0 if kind == "local" else flux.sink
                ring = (cache.ckv.shape[1]
                        if isinstance(cache, KC.RingLatentKV)
                        else cache.k.shape[2])
                y, cache = _chunk_attn_ring(bp, cfg, x, positions, start,
                                            cache, sink, ring - sink)
            else:
                y, cache = _chunk_attn_full(bp, cfg, x, positions, start,
                                            cache)
            h = h + y
            if "xattn" in bp and enc_out is not None:
                hx = rms_norm(bp["norm_x"], h, cfg.norm_eps)
                h = h + _cross_attention(bp["xattn"], cfg, hx, enc_out)
        if has_ffn(cfg, i):
            x2 = rms_norm(bp["norm2"], h, cfg.norm_eps)
            if "moe" in bp:
                y2, _ = MOE.moe_apply(bp["moe"], cfg, x2)
            else:
                y2 = ffn_apply(bp["ffn"], x2)
            h = h + y2
        new_caches.append(cache)
    logits = logits_from_hidden(params, cfg, h[:, -1])
    return logits, new_caches


def snapshot_state(caches, logits: jax.Array):
    """Bitwise copy of a chunk-boundary admission state — the per-layer
    decode-geometry cache pytree plus the boundary's last-token logits
    — into fresh buffers.  This is the snapshot the shared-prefix radix
    cache stores and restores (serve/prefix_cache.py): the chunked
    prefill and decode jits *donate* their cache buffers, so a snapshot
    must not alias them.  ``jnp.copy`` rather than an arithmetic
    identity: ``x + 0`` would flip ``-0.0`` sign bits and break the
    bitwise-exact reuse guarantee.  Under jit this compiles to one
    executable per cache geometry (the engine's restore jit, counted by
    its executable guard)."""
    return jax.tree.map(jnp.copy, caches), jnp.copy(logits)


def routing_head_split(cfg: ModelConfig, routing):
    """Translate a routing pattern into (fa_heads, duo_layers):
    the traced per-layer full-KV-head counts and the *static* tuple of
    duo layer indices — (None, None) when no entry needs a head split
    (pure geometry dispatch keeps the 1-D validity mask that
    kernel/distributed overrides expect on every layer)."""
    duo = tuple(i for i, r in enumerate(routing)
                if isinstance(r, tuple) and r[0] == "duo")
    if not duo:
        return None, None
    if cfg.use_mla:
        raise ValueError(
            "duo head-split routing requires per-KV-head GQA caches; "
            "MLA shares one latent across heads (cfg.use_mla=True) so "
            f"a split is meaningless — got duo at layers {duo}")
    fa_heads = jnp.asarray(
        [r[1] if isinstance(r, tuple) and r[0] == "duo"
         else cfg.num_kv_heads for r in routing], jnp.int32)
    return fa_heads, duo


def decode_step(params, cfg: ModelConfig, token: jax.Array, caches: List,
                routing: Tuple[str, ...], pos: jax.Array, enc_out=None):
    """One autoregressive step (pattern-tuple convenience wrapper).

    token (B,1) int32; ``routing`` is the per-layer pattern
    ("fa" | "sa" | ("duo", n) | None) cached from prefill (§3.3 — the
    router runs once).  The fa/sa entries are *informational* here: the
    cache geometry built by ``repack_caches``/``init_decode_caches``
    already encodes them, and ``decode_core`` dispatches on it — only
    duo head splits survive (split counts as traced data, the duo
    layer set as static structure).
    Returns (logits (B,V), new_caches).
    """
    fa_heads, duo_layers = routing_head_split(cfg, routing)
    return decode_core(params, cfg, token, caches, pos, enc_out=enc_out,
                       fa_heads=fa_heads, duo_layers=duo_layers)


def decode_many(params, cfg: ModelConfig, logits: jax.Array, caches: List,
                pos: jax.Array, rng: jax.Array, *, n_steps: int,
                greedy: bool = True, enc_out=None, fa_heads=None,
                duo_layers: Optional[Tuple[int, ...]] = None,
                unroll: int = 4):
    """Fused generation: sample → decode for ``n_steps`` in one
    ``lax.scan``, entirely on device.

    logits (B,V): next-token logits from prefill (or a previous chunk);
    pos () or (B,) int32: absolute position of the first generated
    token — per-slot when rows are independent requests in a
    continuous-batching pool; rng: PRNG key (ignored when ``greedy``).  Under jit, mark ``n_steps``,
    ``greedy`` and ``unroll`` static and donate ``caches`` so every
    cache append is an in-place ``dynamic_update_slice`` on the
    original buffers — no per-step host sync, no per-step cache copy.
    ``unroll`` trades compile time for cross-step fusion inside the
    scan (semantics are unchanged — same per-step graph, repeated).

    Returns (tokens (B, n_steps) int32, last logits (B,V), caches).
    Token i is sampled from the logits *before* decode step i, exactly
    matching a per-step sample→decode python loop.
    """
    def step(carry, _):
        logits, caches, pos, rng = carry
        if greedy:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            rng, k = jax.random.split(rng)
            nxt = jax.random.categorical(k, logits).astype(jnp.int32)
        logits, caches = decode_core(params, cfg, nxt[:, None], caches,
                                     pos, enc_out=enc_out,
                                     fa_heads=fa_heads,
                                     duo_layers=duo_layers)
        return (logits, caches, pos + 1, rng), nxt

    (logits, caches, _, _), toks = lax.scan(
        step, (logits, caches, jnp.asarray(pos, jnp.int32), rng),
        length=n_steps, unroll=max(1, min(unroll, n_steps)))
    return jnp.moveaxis(toks, 0, 1), logits, caches


# ---------------------------------------------------------------------------
# Diagnostics
# ---------------------------------------------------------------------------

def capture_hidden(params, cfg: ModelConfig, tokens: jax.Array,
                   prefix_embeddings=None, encoder_frames=None) -> jax.Array:
    """Hidden states after every layer (L, B, S_total, d) — used by the
    UnComp entropy ranking (paper App. C) and analysis benches."""
    enc_out = (encode(params, cfg, encoder_frames)
               if cfg.num_encoder_layers else None)
    h = embed_tokens(params, cfg, tokens, prefix_embeddings)
    positions = jnp.arange(h.shape[1])
    P = period_len(cfg)

    def body(carry, xs):
        h = carry
        _, trunk_slice = xs
        snaps = []
        for pos in range(P):
            h, _, _, _ = block_apply(trunk_slice[pos], cfg, pos, h,
                                     positions, ("fa_only",),
                                     enc_out=enc_out)
            snaps.append(h)
        return h, jnp.stack(snaps)

    xs = (jnp.arange(n_periods(cfg)), params["trunk"])
    _, snaps = lax.scan(body, h, xs)  # (n_periods, P, B, S, d)
    return snaps.reshape(cfg.num_layers, *snaps.shape[2:])


def attention_mass_coverage(params, cfg: ModelConfig, tokens: jax.Array,
                            *, length=None, prefix_embeddings=None,
                            encoder_frames=None) -> jax.Array:
    """Per-routed-layer FA attention-mass retained by the SA window —
    the serving stack's routing-fidelity probe (DESIGN.md
    §Observability).

    Runs an FA-only forward and, at every routed layer, asks: of the
    full-attention softmax mass the *last* live query spreads over the
    prefix, what fraction lands on keys the SA mode would have kept?
    1.0 means routing this layer to SA loses nothing for the next
    decoded token; low coverage means the router is trading real
    attention mass away.  Exact for the streaming (ssa) mode; for
    triangle the last query sits in the dense tail chunk so coverage is
    exactly 1; for block_topk the sink+local window is a conservative
    lower bound (the selector keeps at least the forced sink/diagonal
    blocks).

    ``tokens`` may be padded past the real prompt: ``length`` (a
    *traced* scalar) marks the live prefix, and causal masking makes
    the padded forward exact for positions < length — the engine pads
    probe prompts to a power-of-two bucket so probing adds O(log
    max_len) executables, not one per prompt length.

    Returns (n_routed,) float32 in ``cfg.routable_layers()`` order.
    """
    enc_out = (encode(params, cfg, encoder_frames)
               if cfg.num_encoder_layers else None)
    h = embed_tokens(params, cfg, tokens, prefix_embeddings)
    S = h.shape[1]
    positions = jnp.arange(S)
    P = period_len(cfg)
    if not any(is_routed(cfg, pos) for pos in range(P)):
        return jnp.zeros((0,), jnp.float32)
    length = jnp.asarray(S if length is None else length, jnp.int32)
    q_idx = length - 1
    sa = sa_mode(cfg)
    kv_pos = jnp.arange(S)
    live = kv_pos < length
    if sa.kind == "triangle":
        vis = live  # dense tail chunk: the last query sees everything
    else:
        vis = live & ((kv_pos < sa.sink) | (q_idx - kv_pos < sa.local))

    def body(carry, xs):
        h = carry
        _, trunk_slice = xs
        covs = []
        for pos in range(P):
            bp = trunk_slice[pos]
            if is_routed(cfg, pos):
                # duplicate the (cheap) qk projection rather than thread
                # probe plumbing through block_apply's cache contract
                x = rms_norm(bp["norm1"], h, cfg.norm_eps)
                if cfg.use_mla:
                    ckv, kr = A.mla_latent(bp["attn"], cfg, x, positions)
                    q, _ = A.mla_q(bp["attn"], cfg, x, positions)
                    k, _ = A.mla_expand_kv(bp["attn"], cfg, ckv, kr)
                else:
                    q, k, _, _ = A.gqa_qkv(bp["attn"], cfg, x, positions)
                    G = q.shape[1] // k.shape[1]
                    if G > 1:  # kv-major head order, as in M._gqa_view
                        k = jnp.repeat(k, G, axis=1)
                q_last = jnp.take(q, q_idx, axis=2)  # (B, H, D)
                s = jnp.einsum("bhd,bhsd->bhs", q_last, k,
                               preferred_element_type=jnp.float32)
                s = s * (q.shape[-1] ** -0.5)
                s = jnp.where(live[None, None, :], s, M.NEG_INF)
                p = jax.nn.softmax(s, axis=-1)
                covs.append(jnp.mean(
                    jnp.sum(jnp.where(vis[None, None, :], p, 0.0),
                            axis=-1)))
            h, _, _, _ = block_apply(bp, cfg, pos, h, positions,
                                     ("fa_only",), enc_out=enc_out)
        return h, jnp.stack(covs)

    xs = (jnp.arange(n_periods(cfg)), params["trunk"])
    _, covs = lax.scan(body, h, xs)  # (n_periods, n_routed_per_period)
    return covs.reshape(-1)
