"""Tensor-parallel pooled serving (DESIGN.md §Distributed serving).

The load-bearing guarantees of the mesh path:
  1. pooled greedy decode on a (1, N) mesh is token-identical to the
     single-device scheduler for every cache kind (FullKV / RingKV /
     LatentKV / Mamba across phi3 / jamba / deepseek), through
     preemption churn and prefix-cache warm restores;
  2. the executable guard holds per-(geometry, mesh): committed
     shardings must not split jit entries, so admission/retire/
     preemption churn on a mesh adds ZERO extra decode executables;
  3. the per-step decode collectives are activation-sized (O(H·D) /
     O(d_model) per token), never cache-sized (O(S·D)) — asserted via
     the hlo_costs analytic on the lowered decode scan;
  4. mesh=None stays bitwise- and dispatch-count-identical to an
     engine constructed without the kwarg (the mesh path is purely
     additive).

Mesh tests skip below 2 devices: CI runs them under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.launch import hlo_costs as HL
from repro.launch.mesh import make_debug_mesh
from repro.models import model as MD
from repro.serve import Request, ServeEngine

ARCHS = ["phi3-mini-3.8b", "jamba-1.5-large-398b", "deepseek-v2-236b"]

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >= 2 devices (XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")


def _setup(arch):
    cfg = smoke_variant(get_config(arch))
    params = MD.init_params(jax.random.key(0), cfg)
    return cfg, params


def _mixed_requests(cfg, n, seed=0, n_steps=7, lens=(20, 28, 36), **kw):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    tokens=rng.integers(0, cfg.vocab_size,
                                        size=lens[i % len(lens)]
                                        ).astype(np.int32),
                    n_steps=n_steps, **kw)
            for i in range(n)]


def _patterns3(cfg):
    kinds = cfg.layer_kinds
    fa = tuple("fa" if k == "attn" else None for k in kinds)
    sa = tuple("sa" if k == "attn" else None for k in kinds)
    flip, mixed = True, []
    for k in kinds:
        mixed.append(("fa" if flip else "sa") if k == "attn" else None)
        flip = not flip if k == "attn" else flip
    return [fa, sa, tuple(mixed)]


def _drain(engine, reqs, **sched_kw):
    engine.scheduler(**sched_kw)
    for r in reqs:
        engine.submit(r)
    return engine.drain()


# ---------------------------------------------------------------------------
# Token parity: mesh vs single-device pooled drain
# ---------------------------------------------------------------------------

@needs_mesh
@pytest.mark.parametrize("arch", ARCHS)
def test_mesh_pooled_drain_matches_single_device(arch):
    cfg, params = _setup(arch)
    mesh = make_debug_mesh(1, 2)
    eng = ServeEngine(params, cfg, max_len=64, mesh=mesh)
    out = _drain(eng, _mixed_requests(cfg, 6),
                 slots_per_bucket=3, chunk=4)
    ref = _drain(ServeEngine(params, cfg, max_len=64),
                 _mixed_requests(cfg, 6), slots_per_bucket=3, chunk=4)
    assert sorted(out) == sorted(ref)
    for rid in ref:
        assert np.array_equal(out[rid].tokens, ref[rid].tokens), rid
        assert out[rid].routing == ref[rid].routing
    # the guard's mesh half: churn on the mesh added no executables
    # beyond the geometries served
    sched = eng._scheduler
    assert eng.decode_cache_size() <= sched.n_geometries()
    eng._check_executable_guard()


@needs_mesh
@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "deepseek-v2-236b"])
def test_mesh_drain_with_pallas_kernels_matches_single_device(arch):
    """Both Pallas kernels installed on the mesh — the block-sparse
    chunk kernel and the pooled decode kernel, each shard_mapped over
    the heads (or replicated where the heads do not split, as MLA's one
    latent) — serve the same tokens as the single-device engine with
    the same kernels."""
    from repro.core import modes as M
    from repro.kernels.decode_attention import make_kernel_decode_attn
    cfg, params = _setup(arch)
    mesh = make_debug_mesh(1, 2)

    def drain(mesh):
        eng = ServeEngine(params, cfg, max_len=64, prefill_chunk=16,
                          decode_attn=make_kernel_decode_attn(
                              block_k=16, min_len=32),
                          mesh=mesh)
        with M.chunk_attention_backend("pallas", block=16):
            out = _drain(eng, _mixed_requests(cfg, 4, lens=(48, 32)),
                         slots_per_bucket=2, chunk=4)
        assert eng.decode_kernel_summary()["hit_layers"] > 0
        return out

    out, ref = drain(mesh), drain(None)
    assert sorted(out) == sorted(ref)
    for rid in ref:
        assert np.array_equal(out[rid].tokens, ref[rid].tokens), rid


@needs_mesh
def test_mesh_executable_guard_across_preemption_churn():
    """Admit/retire/preempt over 3 geometries on the mesh: the decode
    jit cache must end ≤ #geometries (committed shardings must not
    split entries), and every preempted request must still produce the
    tokens of an uninterrupted single-device generate."""
    cfg, params = _setup("phi3-mini-3.8b")
    mesh = make_debug_mesh(1, 2)
    patterns = _patterns3(cfg)
    rng = np.random.default_rng(4)
    eng = ServeEngine(params, cfg, max_len=64, mesh=mesh)
    sched = eng.scheduler(slots_per_bucket=1, chunk=2,
                          prefill_chunks_per_tick=12)
    rid, done, reqs = itertools.count(), {}, {}
    for wave, prio in enumerate((0, 1, 2)):
        for p in patterns:
            i = next(rid)
            toks = rng.integers(0, cfg.vocab_size,
                                size=20 + 4 * wave).astype(np.int32)
            reqs[i] = (toks, p)
            eng.submit(Request(rid=i, tokens=toks, n_steps=6,
                               priority=prio, routing_override=p))
        for f in sched.tick():
            done[f.rid] = f
    for f in sched.drain().values():
        done[f.rid] = f
    assert len(done) == 9
    assert any(f.metrics.preemptions > 0 for f in done.values())
    assert sched.n_geometries() == 3
    assert eng.decode_cache_size() <= 3
    eng._check_executable_guard()
    ref = ServeEngine(params, cfg, max_len=64)
    for i, (toks, p) in reqs.items():
        gen = ref.generate(toks[None], 6, routing_override=p)
        assert np.array_equal(done[i].tokens, gen.tokens[0]), i


@needs_mesh
@pytest.mark.parametrize("arch", ARCHS)
def test_mesh_prefix_warm_restore_matches_cold(arch):
    """Snapshot publish/restore must round-trip through the committed
    shardings: a warm prefix-cache admission on the mesh must be
    token-identical to the cold chunked path, and the restore must not
    mint extra executables (restore-path and fresh-prefill state commit
    to the same pool shardings before every consumer jit)."""
    cfg, params = _setup(arch)
    mesh = make_debug_mesh(1, 2)
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, cfg.vocab_size, size=32).astype(np.int32)
    tails = [rng.integers(0, cfg.vocab_size, size=t).astype(np.int32)
             for t in (16, 13)]
    prompts = [np.concatenate([prefix, t]) for t in tails]
    cold = ServeEngine(params, cfg, max_len=80, prefill_chunk=16)
    refs = [cold.generate(p[None], 6) for p in prompts]

    eng = ServeEngine(params, cfg, max_len=80, prefill_chunk=16,
                      prefix_cache_mb=64, mesh=mesh)
    sched = eng.scheduler(slots_per_bucket=2, chunk=3)
    eng.submit(Request(rid=0, tokens=prompts[0], n_steps=6))
    out = dict(eng.drain())  # warm the store with prompt A, then reopen
    eng2 = ServeEngine(params, cfg, max_len=80, prefill_chunk=16,
                       prefix_cache_mb=64, mesh=mesh)
    eng2.prefix_store = eng.prefix_store  # shared store, warm hits
    eng2.scheduler(slots_per_bucket=2, chunk=3)
    eng2.submit(Request(rid=1, tokens=prompts[1], n_steps=6))
    out2 = eng2.drain()
    assert np.array_equal(out[0].tokens, refs[0].tokens[0])
    assert np.array_equal(out2[1].tokens, refs[1].tokens[0])
    assert out2[1].metrics.prefix_hit_tokens >= 16  # warm restore ran
    eng2._check_executable_guard()
    assert eng2.decode_cache_size() <= eng2._scheduler.n_geometries()


@needs_mesh
def test_mesh_generate_matches_single_device():
    """The batch frontend (``generate``) on the mesh: same tokens as
    the single-device engine, chunked and monolithic admission alike."""
    cfg, params = _setup("phi3-mini-3.8b")
    mesh = make_debug_mesh(1, 2)
    rng = np.random.default_rng(9)
    toks = rng.integers(0, cfg.vocab_size, size=24).astype(np.int32)
    for kw in ({}, {"prefill_chunk": 16}):
        ref = ServeEngine(params, cfg, max_len=64, **kw)
        eng = ServeEngine(params, cfg, max_len=64, mesh=mesh, **kw)
        a = ref.generate(toks[None], 6)
        b = eng.generate(toks[None], 6)
        assert np.array_equal(a.tokens, b.tokens), kw
        assert a.routing == b.routing


# ---------------------------------------------------------------------------
# Collective-bytes analytic: O(H·D) per step, never the cache
# ---------------------------------------------------------------------------

@needs_mesh
def test_mesh_decode_collectives_are_activation_sized():
    """Lower the pooled decode scan with mesh-committed inputs and
    count collective bytes in the compiled HLO: the whole n_steps scan
    must move fewer bytes than ONE copy of the pool's KV payload, and
    the per-step collectives must stay under one layer's cache bytes —
    the head-sharded layout attends locally and only combines
    activation-sized partials (row-parallel all-reduce, O(d_model))."""
    from repro.serve.engine import kv_cache_stats
    from repro.serve.slots import SlotPool
    cfg, params = _setup("phi3-mini-3.8b")
    mesh = make_debug_mesh(1, 2)
    eng = ServeEngine(params, cfg, max_len=64, mesh=mesh)
    fa = tuple("fa" if k == "attn" else None for k in cfg.layer_kinds)
    logits_like = jnp.zeros((1, cfg.vocab_size), jnp.float32)
    pool = SlotPool.create(cfg, fa, 2, 64, logits_like, mesh=mesh)
    n_steps = 4
    lowered = eng._decode_many.lower(
        params=eng.params, logits=pool.logits, caches=pool.caches,
        pos=pool.pos, rng=jax.random.key(0), n_steps=n_steps,
        greedy=True, enc_out=None, fa_heads=None, duo_layers=None,
        unroll=eng.decode_unroll)
    cost = HL.loop_aware_costs(lowered.compile().as_text())
    stats = kv_cache_stats(pool.caches)
    assert cost.coll_bytes > 0, "sharded decode lowered no collectives"
    # not O(S·D): the scan's total collective traffic is below one
    # cache copy, and each step moves less than a single layer's KV
    assert cost.coll_bytes < stats.payload_bytes, cost.coll_by_kind
    n_attn = sum(k == "attn" for k in cfg.layer_kinds)
    per_layer_cache = stats.payload_bytes / n_attn
    assert cost.coll_bytes / n_steps < per_layer_cache, cost.coll_by_kind


# ---------------------------------------------------------------------------
# mesh=None: purely additive — bitwise and dispatch-count identical
# ---------------------------------------------------------------------------

def test_mesh_none_is_bitwise_and_dispatch_identical():
    cfg, params = _setup("phi3-mini-3.8b")
    outs, counts = [], []
    for kw in ({}, {"mesh": None}):
        eng = ServeEngine(params, cfg, max_len=64, **kw)
        out = _drain(eng, _mixed_requests(cfg, 4),
                     slots_per_bucket=2, chunk=4)
        outs.append({k: v.tokens for k, v in out.items()})
        counts.append(eng.dispatch_count)
    assert counts[0] == counts[1]
    assert sorted(outs[0]) == sorted(outs[1])
    assert all(np.array_equal(outs[0][k], outs[1][k]) for k in outs[0])


def test_kv_stats_shard_bytes_equal_global_without_mesh():
    """On one device the per-shard figures are the global figures —
    the split only diverges under a committed 'model' axis."""
    from repro.serve import kv_cache
    from repro.serve.engine import kv_cache_stats
    cfg, _ = _setup("phi3-mini-3.8b")
    fa = tuple("fa" if k == "attn" else None for k in cfg.layer_kinds)
    caches = kv_cache.init_decode_caches(cfg, fa, 2, 64)
    stats = kv_cache_stats(caches)
    assert stats.payload_shard_bytes == stats.payload_bytes
    assert stats.overhead_shard_bytes == stats.overhead_bytes


@needs_mesh
def test_mesh_kv_stats_split_shard_vs_global_bytes():
    """Head-sharded k/v leaves divide by the model-axis size per shard;
    replicated bookkeeping does not.  Global figures are untouched, so
    the memory ledger's reconciliation stays exact, and the flight
    recorder's tick records carry the mesh shape."""
    cfg, params = _setup("phi3-mini-3.8b")
    mesh = make_debug_mesh(1, 2)
    eng = ServeEngine(params, cfg, max_len=64, mesh=mesh,
                      memory_ledger=True, telemetry=True)
    _drain(eng, _mixed_requests(cfg, 3, n_steps=4),
           slots_per_bucket=3, chunk=4)
    rep = eng.ledger_report()
    st = rep["kv_cache_stats"]
    # phi3 smoke is all-attention FullKV: every payload leaf is a
    # head-sharded k or v, so per-shard is exactly half of global
    assert st["payload_shard_bytes"] * 2 == st["payload_bytes"]
    assert st["overhead_shard_bytes"] == st["overhead_bytes"]
    assert rep["mesh"] == [1, 2]
    recon = rep["reconciliation"]
    assert recon["payload_delta"] == 0
    assert recon["overhead_delta"] == rep["aux_bytes"]
    rec = eng.flight_recorder.last()
    assert rec is not None and rec.mesh == (1, 2)
    assert rec.as_dict()["mesh"] == [1, 2]
