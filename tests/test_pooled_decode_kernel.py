"""Pooled Pallas decode kernel + block-sparse chunked prefill
(DESIGN.md §Kernels).

Three layers of guarantees, all on CPU via interpret mode:
  1. kernel-level: ``decode_attention_pooled`` matches dense masked
     softmax on ragged FullKV / RingKV / MLA-shaped pools, including
     the degenerate rows (empty ring row, L not a block_k multiple);
  2. adapter-level: ``make_kernel_decode_attn`` hits/declines per its
     published rules and logs every decision for the engine counters;
  3. serving-level: a scheduler drain with the kernel installed is
     BITWISE equal to the dense pooled drain (incl. preemption churn)
     and adds zero decode executables beyond the geometry count.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.core import modes
from repro.kernels import decode_attention_pooled
from repro.kernels.decode_attention import (PooledValid,
                                            make_kernel_decode_attn)
from repro.models import model as MD
from repro.serve import ContinuousScheduler, Request, ServeEngine

ARCHS = ["phi3-mini-3.8b", "jamba-1.5-large-398b", "deepseek-v2-236b"]


def _setup(arch):
    cfg = smoke_variant(get_config(arch))
    params = MD.init_params(jax.random.key(0), cfg)
    return cfg, params


def _ref_pooled(q, k, v, mask, scale=None):
    """Dense masked softmax — the `_dot_decode` semantics the kernel
    must reproduce.  q (B,Hq,1,Dk); k (B,Hkv,L,Dk); v (B,Hkv,L,Dv);
    mask (B,L) bool."""
    Hq, Hkv = q.shape[1], k.shape[1]
    k = jnp.repeat(k, Hq // Hkv, 1)
    v = jnp.repeat(v, Hq // Hkv, 1)
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    s = jnp.einsum("bhqd,bhld->bhql", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhql,bhlv->bhqv", p, v.astype(jnp.float32))


def _rand(key, *shape):
    return jax.random.normal(key, shape, jnp.float32)


# ---------------------------------------------------------------------------
# 1. kernel parity
# ---------------------------------------------------------------------------

# the first four cases (G = 2) keep their original ids
@pytest.mark.parametrize("L,block_k,D,G", [
    pytest.param(64, 16, 32, 2, id="64-16-32"),
    pytest.param(40, 16, 32, 2, id="40-16-32"),
    pytest.param(24, 8, 32, 2, id="24-8-32"),
    pytest.param(40, 16, 128, 2, id="40-16-128"),
    (40, 16, 96, 1), (40, 16, 128, 1), (40, 16, 96, 12), (64, 16, 128, 12),
    (24, 8, 96, 12)])
def test_fullkv_ragged_parity(L, block_k, D, G):
    """FullKV pool: positions are arange, lengths ragged, the last slot
    free (length 0); L deliberately includes non-multiples of block_k.
    D=128 reads (block_k, D) blocks, other head dims (D, block_k)
    blocks of the sequence-minor view.  G query heads share each of
    the two KV heads, read as one (G, D) tile."""
    B, Hkv = 5, 2
    Hq = G * Hkv
    ks = jax.random.split(jax.random.key(0), 3)
    q = _rand(ks[0], B, Hq, 1, D)
    k = _rand(ks[1], B, Hkv, L, D)
    v = _rand(ks[2], B, Hkv, L, D)
    lengths = jnp.asarray([1, L // 3, L - 1, L, 0], jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None],
                                 (B, L))
    out = decode_attention_pooled(q, k, v, positions, lengths,
                                  block_k=block_k, interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    ref = _ref_pooled(q, k, v, jnp.arange(L)[None, :] < lengths[:, None])
    np.testing.assert_allclose(np.asarray(out)[:-1], np.asarray(ref)[:-1],
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("G,D", [(1, 32), (2, 96), (12, 128), (12, 96)])
def test_ringkv_ragged_parity_and_empty_row(G, D):
    """RingKV pool: live entries form a contiguous prefix holding
    arbitrary absolute positions, the rest are -1.  Row 0 is an ALL
    EMPTY ring (length 0, all positions -1): the kernel must stay
    finite there while matching dense exactly on the live rows, for
    every query-group size G."""
    B, Hkv, L, block_k = 4, 4, 20, 16
    Hq = G * Hkv
    ks = jax.random.split(jax.random.key(1), 3)
    q = _rand(ks[0], B, Hq, 1, D)
    k = _rand(ks[1], B, Hkv, L, D)
    v = _rand(ks[2], B, Hkv, L, D)
    lengths = jnp.asarray([0, 5, 13, L], jnp.int32)
    rng = np.random.default_rng(0)
    pos = np.full((B, L), -1, np.int32)
    for b, n in enumerate(np.asarray(lengths)):
        pos[b, :n] = rng.choice(100, size=n, replace=False)
    positions = jnp.asarray(pos)
    out = decode_attention_pooled(q, k, v, positions, lengths,
                                  block_k=block_k, interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    mask = (positions >= 0) & (jnp.arange(L)[None, :] < lengths[:, None])
    ref = _ref_pooled(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out)[1:], np.asarray(ref)[1:],
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("Dk,Dv,Hq", [
    pytest.param(48, 32, 4, id="48-32"),
    pytest.param(160, 128, 4, id="160-128"),
    (576, 512, 32), (160, 128, 16)])
def test_mla_shaped_parity(Dk, Dv, Hq):
    """MLA absorbed decode: single kv head serving every query head
    (G = Hq, one tile), Dk != Dv (latent+rope vs latent), explicit
    scale; (160, 128) mixes a sequence-minor K with a head-dim-minor V,
    (576, 512) is deepseek-v2's latent+rope width."""
    B, L = 3, 40
    ks = jax.random.split(jax.random.key(2), 3)
    q = _rand(ks[0], B, Hq, 1, Dk)
    k = _rand(ks[1], B, 1, L, Dk)
    v = _rand(ks[2], B, 1, L, Dv)
    lengths = jnp.asarray([2, 17, L], jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None],
                                 (B, L))
    scale = 64 ** -0.5       # (nope+rope)^-1/2, NOT Dk^-1/2
    out = decode_attention_pooled(q, k, v, positions, lengths,
                                  block_k=16, scale=scale, interpret=True)
    ref = _ref_pooled(q, k, v, jnp.arange(L)[None, :] < lengths[:, None],
                      scale=scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# 2. adapter hit/decline protocol
# ---------------------------------------------------------------------------

def test_adapter_pooled_hit_and_decline_round_trip():
    B, Hq, Hkv, L, D = 2, 4, 2, 64, 16
    ks = jax.random.split(jax.random.key(3), 3)
    q = _rand(ks[0], B, Hq, 1, D)
    k = _rand(ks[1], B, Hkv, L, D)
    v = _rand(ks[2], B, Hkv, L, D)
    lengths = jnp.asarray([7, L], jnp.int32)
    valid = PooledValid(mask=(jnp.arange(L)[None, :]
                              < lengths[:, None])[:, None],
                        lengths=lengths)
    fn = make_kernel_decode_attn(block_k=16, min_len=16, interpret=True)
    assert fn.supports_pooled and fn.supports_scale
    out = fn(q, k, v, valid)
    assert out is not None
    # a hit carries the layer's query-group size G = Hq/Hkv
    assert fn.drain_log() == [("hit", "pooled", 2)]
    ref = _ref_pooled(q, k, v, jnp.arange(L)[None, :] < lengths[:, None])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # decline: cache extent below min_len → None, reason logged, and
    # the caller (model._dot_decode) falls back to dense
    tall = make_kernel_decode_attn(block_k=16, min_len=4 * L,
                                   interpret=True)
    assert tall(q, k, v, valid) is None
    assert tall.drain_log() == [("decline", "min_len")]
    # drain_log clears: a second drain sees only new decisions
    assert tall.drain_log() == []


def test_model_falls_back_to_dense_on_decline():
    """_dot_decode with a declining override returns the dense result
    (the decline is silent at the math layer, logged at the adapter)."""
    B, Hq, Hkv, L, D = 2, 4, 2, 32, 16
    ks = jax.random.split(jax.random.key(4), 3)
    q = _rand(ks[0], B, Hq, 1, D)
    k = _rand(ks[1], B, Hkv, L, D)
    v = _rand(ks[2], B, Hkv, L, D)
    lengths = jnp.asarray([5, L], jnp.int32)
    valid = PooledValid(mask=(jnp.arange(L)[None, :]
                              < lengths[:, None])[:, None],
                        lengths=lengths)
    dense = MD._dot_decode(q, k, v, valid.mask)
    tall = make_kernel_decode_attn(block_k=16, min_len=4 * L,
                                   interpret=True)
    with MD.use_decode_attn(tall):
        out = MD._dot_decode(q, k, v, valid)
    assert np.array_equal(np.asarray(out), np.asarray(dense))
    assert tall.drain_log() == [("decline", "min_len")]


# ---------------------------------------------------------------------------
# 3. serving parity + executable guard
# ---------------------------------------------------------------------------

def _kernel():
    return make_kernel_decode_attn(block_k=16, min_len=16,
                                   interpret=True)


def _drain(cfg, params, reqs, decode_attn, **kw):
    eng = ServeEngine(params, cfg, max_len=64, decode_attn=decode_attn,
                      **kw)
    eng.scheduler(slots_per_bucket=3, chunk=4)
    for r in reqs:
        eng.submit(r)
    out = eng.drain()
    return eng, out


@pytest.mark.parametrize("arch", ARCHS)
def test_scheduler_drain_bitwise_parity(arch):
    cfg, params = _setup(arch)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, tokens=rng.integers(
        0, cfg.vocab_size, size=(18, 26, 34)[i % 3]).astype(np.int32),
        n_steps=5) for i in range(4)]
    _, ref = _drain(cfg, params, reqs, None)
    eng, out = _drain(cfg, params, reqs, _kernel())
    for r in reqs:
        assert np.array_equal(out[r.rid].tokens, ref[r.rid].tokens), r.rid
    summary = out.summary["decode_kernel"]
    assert summary["installed"] and summary["hit_layers"] > 0
    assert summary["decline_layers"] == {}
    eng._check_executable_guard()


def test_kernel_adds_zero_executables_and_survives_churn():
    """Preemption churn over 3 geometries with the kernel installed:
    outputs bitwise-equal to the dense-pooled run, decode jit cache
    still ≤ #geometries (the kernel rides INSIDE the pooled decode
    executable — it must not add its own)."""
    cfg, params = _setup("phi3-mini-3.8b")
    kinds = cfg.layer_kinds
    fa = tuple("fa" if k == "attn" else None for k in kinds)
    sa = tuple("sa" if k == "attn" else None for k in kinds)

    def churn(decode_attn):
        rng = np.random.default_rng(4)
        eng = ServeEngine(params, cfg, max_len=64,
                          decode_attn=decode_attn)
        sched = eng.scheduler(slots_per_bucket=1, chunk=2,
                              prefill_chunks_per_tick=12)
        rid = itertools.count()
        done = {}
        for wave, prio in enumerate((0, 1, 2)):
            for p in (fa, sa):
                i = next(rid)
                eng.submit(Request(
                    rid=i, tokens=rng.integers(
                        0, cfg.vocab_size,
                        size=20 + 4 * wave).astype(np.int32),
                    n_steps=5, priority=prio, routing_override=p))
            for f in sched.tick():
                done[f.rid] = f
        for f in sched.drain().values():
            done[f.rid] = f
        return eng, sched, done

    _, _, ref = churn(None)
    eng, sched, done = churn(_kernel())
    assert len(done) == 6
    assert any(f.metrics.preemptions > 0 for f in done.values())
    for rid, f in done.items():
        assert np.array_equal(f.tokens, ref[rid].tokens), rid
    assert eng.decode_cache_size() <= sched.n_geometries()
    eng._check_executable_guard()
    assert eng.decode_kernel_summary()["hit_layers"] > 0


@pytest.mark.parametrize("arch,G", [("command-r-plus-104b", 2),
                                    ("phi3-mini-3.8b", 1)])
def test_hit_groups_count_layers_by_query_group(arch, G):
    """``hit_groups`` counts every hit layer under its query-group size
    (GQA smoke: G = 2, phi3's MHA: G = 1).  A second identical drain
    compiles nothing new, so its counts are replays of the recorded
    trace-time decisions, and they count the same as the fresh trace."""
    cfg, params = _setup(arch)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, size=20).astype(np.int32)
               for _ in range(2)]
    eng = ServeEngine(params, cfg, max_len=64, decode_attn=_kernel())
    eng.scheduler(slots_per_bucket=3, chunk=4)
    rid = itertools.count()

    def drain():
        for p in prompts:
            eng.submit(Request(rid=next(rid), tokens=p, n_steps=4))
        done = 0
        while done < len(prompts):
            done += len(eng.step())
        return eng.decode_kernel_summary()

    first = drain()
    n = first["hit_layers"]
    assert n > 0 and first["hit_groups"] == {G: n}
    executables = eng.decode_cache_size()
    second = drain()
    assert eng.decode_cache_size() == executables
    assert second["dispatches"] == 2 * first["dispatches"]
    assert second["hit_groups"] == {G: 2 * n}


def test_drain_summary_metrics_counters():
    """kernel_hit / kernel_decline land in the MetricsRegistry and the
    drain summary — the satellite fixing the silent-decline gap."""
    cfg, params = _setup("phi3-mini-3.8b")
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, tokens=rng.integers(
        0, cfg.vocab_size, size=20).astype(np.int32), n_steps=4)
        for i in range(2)]
    eng, out = _drain(cfg, params, reqs, _kernel(), telemetry=True)
    s = out.summary["decode_kernel"]
    assert s["dispatches"] > 0 and s["hit_layers"] > 0
    hits = eng.telemetry.counter("decode_kernel_hit_layers_total").value
    assert hits == s["hit_layers"]
    # a declining kernel shows up in the decline counter, not hits
    eng2, out2 = _drain(cfg, params, reqs,
                        make_kernel_decode_attn(block_k=16, min_len=10 ** 6,
                                                interpret=True))
    s2 = out2.summary["decode_kernel"]
    assert s2["hit_layers"] == 0
    assert s2["decline_layers"].get("min_len", 0) > 0


# ---------------------------------------------------------------------------
# 4. block-sparse chunked prefill backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("start,C", [(0, 16), (17, 16), (40, 11)])
def test_chunk_causal_pallas_backend_parity(start, C):
    """chunk_causal_attention under the pallas backend matches the
    dense fori_loop backend at arbitrary chunk starts, including a
    chunk length that is not a block multiple."""
    B, Hq, Hkv, M, D = 2, 4, 2, 64, 32
    ks = jax.random.split(jax.random.key(5), 3)
    q = _rand(ks[0], B, Hq, C, D)
    k = _rand(ks[1], B, Hkv, M, D)
    v = _rand(ks[2], B, Hkv, M, D)
    k = k.at[:, :, start + C:].set(0)
    v = v.at[:, :, start + C:].set(0)
    ref = modes.chunk_causal_attention(q, k, v, jnp.int32(start))
    with modes.chunk_attention_backend("pallas", block=16,
                                       interpret=True):
        out = modes.chunk_causal_attention(q, k, v, jnp.int32(start))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_chunk_backend_validation_and_default():
    with pytest.raises(ValueError):
        with modes.chunk_attention_backend("nope"):
            pass
    # default resolution off-TPU is dense — CPU tier-1 stays bitwise
    assert modes._chunk_backend()[0] in ("auto", "dense", "pallas")
