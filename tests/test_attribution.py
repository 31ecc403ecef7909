"""Cost attribution: memory ledger, fidelity probes (DESIGN.md
§Observability).

The load-bearing claims:
  1. attribution OFF is free — and attribution ON (ledger, no probes)
     is *still* bitwise-identical on tokens, dispatch counts and the
     decode-executable census: the ledger is pure host arithmetic;
  2. the ledger reconciles against an independent ``kv_cache_stats``
     walk exactly on payload and prefix tiers, and within exactly
     ``aux_bytes`` on overhead — at every tick, under churn;
  3. fidelity probes add exactly the probe forwards (one per sampled
     admission) and only probe-bucket executables, and their coverage
     is exact: a prompt inside the SA sink+local window must measure
     coverage == 1.0, and the padded probe form is bitwise equal to
     the unpadded forward;
  4. the analytic tick-cost join (hlo_costs) splits kernel-hit vs
     declined layers and scales with steps — checked against the
     per-layer cost model it is built from.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.core import router as RT
from repro.launch import hlo_costs as HL
from repro.models import model as MD
from repro.serve import Request, ServeEngine
from repro.serve import telemetry as TM
from repro.serve.engine import kv_cache_stats
from repro.serve.scheduler import ContinuousScheduler


def _setup(arch="phi3-mini-3.8b"):
    cfg = smoke_variant(get_config(arch))
    params = MD.init_params(jax.random.key(0), cfg)
    return cfg, params


def _prompt(cfg, n=20, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)


def _drain(cfg, params, n=5, **engine_kw):
    eng = ServeEngine(params, cfg, max_len=64, **engine_kw)
    sched = ContinuousScheduler(eng, slots_per_bucket=2, chunk=2,
                                prefill_chunks_per_tick=4)
    for i in range(n):
        sched.submit(Request(rid=i, tokens=_prompt(cfg, 12 + 5 * i, seed=i),
                             n_steps=6))
    return eng, sched, sched.drain()


# ---------------------------------------------------------------------------
# Off is free; ledger on is bitwise and census-identical
# ---------------------------------------------------------------------------

def test_attribution_on_bitwise_parity_and_zero_new_executables():
    cfg, params = _setup()
    eng0, _, res0 = _drain(cfg, params)
    eng1, _, res1 = _drain(cfg, params, memory_ledger=True)
    assert set(res0) == set(res1)
    for rid in res0:
        assert np.array_equal(res0[rid].tokens, res1[rid].tokens), rid
        assert res0[rid].status == res1[rid].status
    assert eng0.dispatch_count == eng1.dispatch_count
    assert eng0.decode_cache_size() == eng1.decode_cache_size()
    assert eng0._decode_keys == eng1._decode_keys
    # the default engine holds no attribution objects at all
    assert eng0.ledger is None
    assert eng0.fidelity_probe_every == 0


def test_attribution_disabled_reports_raise():
    cfg, params = _setup()
    eng = ServeEngine(params, cfg, max_len=64)
    with pytest.raises(ValueError, match="ledger is disabled"):
        eng.ledger_report()
    with pytest.raises(ValueError):
        ServeEngine(params, cfg, max_len=64, fidelity_probe_every=-1)


# ---------------------------------------------------------------------------
# Memory ledger: exact reconciliation under churn, fragmentation
# ---------------------------------------------------------------------------

def test_ledger_reconciles_exactly_at_every_tick_under_churn():
    cfg, params = _setup()
    eng = ServeEngine(params, cfg, max_len=64, memory_ledger=True)
    # slots_per_bucket=1 with mixed lengths + priorities forces churn:
    # admissions, waiting, retirement all overlap across ticks
    sched = ContinuousScheduler(eng, slots_per_bucket=1, chunk=2,
                                prefill_chunks_per_tick=2)
    for i in range(6):
        sched.submit(Request(rid=i, tokens=_prompt(cfg, 10 + 7 * i, seed=i),
                             n_steps=5, priority=i % 3))
    checked = 0
    while sched.waiting or sched.n_active():
        sched.tick()
        rep = eng.ledger_report()
        recon = rep["reconciliation"]
        assert recon["payload_delta"] == 0, (sched.ticks, recon)
        assert recon["prefix_device_delta"] == 0, recon
        assert recon["prefix_host_delta"] == 0, recon
        # ledger overhead exceeds the cache walk by exactly the pool
        # aux (logits/pos) buffers the walk never sees
        assert recon["overhead_delta"] == rep["aux_bytes"], recon
        checked += 1
        if checked > 500:
            pytest.fail("drain did not converge")
    assert checked > 1  # churn actually spanned multiple ticks
    snap = eng.ledger.last()
    assert snap.device_bytes <= eng.ledger.high_watermark
    # everything idle now: no queued work, so all stranded bytes are
    # fragmentation, and nothing is live
    assert snap.pool_live_bytes == 0
    assert snap.fragmentation_bytes == snap.pool_stranded_bytes > 0
    # params are part of the tracked device figure
    assert snap.params_bytes == eng._params_cost()[1] > 0


def test_ledger_tick_records_and_gauges():
    cfg, params = _setup()
    eng, sched, _ = _drain(cfg, params, memory_ledger=True)
    recs = eng.flight_recorder.dump()
    assert recs, "telemetry (implied by ledger) records ticks"
    assert any(r["ledger_device_bytes"] > 0 for r in recs)
    text = eng.metrics_text()
    samples = TM.parse_prometheus_text(text)
    assert "serve_ledger_device_bytes" in samples
    assert "serve_ledger_device_high_watermark_bytes" in samples
    (_, hwm), = samples["serve_ledger_device_high_watermark_bytes"]
    assert hwm == eng.ledger.high_watermark > 0


def test_pool_ledger_entry_fragmentation_semantics():
    e = TM.PoolLedgerEntry(pool="g0", capacity=4, occupied=1,
                           slot_payload_bytes=100, slot_overhead_bytes=8,
                           aux_bytes=64, queued_match=False)
    assert e.live_bytes == 100
    assert e.stranded_bytes == 300
    assert e.fragmentation_bytes == 300  # nobody queued wants this pool
    assert e.overhead_bytes == 4 * 8 + 64
    assert e.total_bytes == 4 * 108 + 64
    # a queued request routing here makes the empty slots useful again
    e.queued_match = True
    assert e.fragmentation_bytes == 0
    assert e.stranded_bytes == 300  # stranded is occupancy, not demand


def test_queued_geometry_suppresses_fragmentation():
    cfg, params = _setup()
    eng = ServeEngine(params, cfg, max_len=64, memory_ledger=True)
    sched = ContinuousScheduler(eng, slots_per_bucket=1, chunk=2)
    # two identical prompts: same routing → same geometry bucket; with
    # one slot the second request queues behind the first
    sched.submit(Request(rid=0, tokens=_prompt(cfg, 16), n_steps=8))
    sched.submit(Request(rid=1, tokens=_prompt(cfg, 16), n_steps=8))
    for _ in range(50):
        sched.tick()
        if sched.pools and sched.waiting:
            resident = {inf.req.rid for p in sched.pools.values()
                        for inf in p.active.values()}
            waiter = sched.waiting[0]
            if resident and (waiter.job is not None
                             and waiter.job.caches is not None
                             or waiter.cached_key is not None):
                snap = eng.ledger.last()
                # pool is full (occupied == capacity): nothing stranded,
                # and the waiter's known geometry matches the pool
                assert all(p.queued_match or p.stranded_bytes == 0
                           for p in snap.pools)
                assert snap.fragmentation_bytes == 0
                break
    sched.drain()


def test_prefix_store_watermarks_track_peaks():
    cfg, params = _setup()
    eng = ServeEngine(params, cfg, max_len=96, prefill_chunk=16,
                      prefix_cache_mb=0.2, prefix_cache_host_mb=0.2,
                      memory_ledger=True)
    sched = ContinuousScheduler(eng, slots_per_bucket=2, chunk=2)
    shared = _prompt(cfg, 32, seed=99)
    for i in range(4):
        toks = np.concatenate([shared, _prompt(cfg, 8, seed=i)])
        sched.submit(Request(rid=i, tokens=toks.astype(np.int32),
                             n_steps=4))
    sched.drain()
    s = eng.prefix_store.stats()
    assert s.device_high_watermark >= s.device_bytes
    assert s.device_high_watermark > 0
    assert s.host_high_watermark >= s.host_bytes
    # the ledger's prefix tier agrees with the store exactly
    recon = eng.ledger_report()["reconciliation"]
    assert recon["prefix_device_delta"] == 0
    assert recon["prefix_host_delta"] == 0


# ---------------------------------------------------------------------------
# hlo_costs tick-cost join
# ---------------------------------------------------------------------------

def test_pooled_decode_tick_cost_matches_per_layer_model():
    lengths = [5, 40, 1]
    specs = [(64, 8, 2, 32, 32, 4), (20, 8, 8, 16, 16, 2)]
    hits = [True, False]
    out = HL.pooled_decode_tick_cost(lengths, specs, n_steps=3,
                                     kernel_hits=hits, block_k=8)
    expect_f = expect_b = 0.0
    for (buf, hq, hkv, dk, dv, db), hit in zip(specs, hits):
        c = HL.pooled_decode_attn_cost(lengths, buf, n_q_heads=hq,
                                       n_kv_heads=hkv, d_k=dk, d_v=dv,
                                       block_k=8, dtype_bytes=db)
        expect_f += (c["kernel_flops"] if hit else c["dense_flops"]) * 3
        expect_b += (c["kernel_hbm_bytes"] if hit
                     else c["dense_hbm_bytes"]) * 3
    assert out["flops"] == expect_f
    assert out["hbm_bytes"] == expect_b
    assert out["kernel_hit"]["layers"] == 3      # 1 hit layer × 3 steps
    assert out["kernel_decline"]["layers"] == 3
    assert (out["kernel_hit"]["flops"] + out["kernel_decline"]["flops"]
            == out["flops"])
    # default = all-dense
    dense = HL.pooled_decode_tick_cost(lengths, specs, block_k=8)
    assert dense["kernel_hit"]["layers"] == 0
    with pytest.raises(ValueError, match="kernel_hits"):
        HL.pooled_decode_tick_cost(lengths, specs, kernel_hits=[True])


def test_decode_linear_cost():
    c = HL.decode_linear_cost(1_000, 4_000, batch=4, n_steps=8)
    assert c["flops"] == 2.0 * 1_000 * 4 * 8
    assert c["hbm_bytes"] == 4_000.0 * 8  # batch shares one param read


# ---------------------------------------------------------------------------
# Fidelity probes
# ---------------------------------------------------------------------------

def test_fidelity_probes_bitwise_tokens_and_bounded_executables():
    cfg, params = _setup()
    eng0, _, res0 = _drain(cfg, params)
    eng1, _, res1 = _drain(cfg, params, fidelity_probe_every=1)
    for rid in res0:
        assert np.array_equal(res0[rid].tokens, res1[rid].tokens), rid
    # probes add exactly one dispatch per sampled admission, nothing on
    # the decode path
    assert (eng1.dispatch_count - eng0.dispatch_count
            == eng1._probe_admissions)
    assert eng1.decode_cache_size() == eng0.decode_cache_size()
    assert eng1._decode_keys == eng0._decode_keys
    # probe executables are bounded by the padded power-of-two buckets
    assert eng1._coverage._cache_size() <= len(eng1._probe_keys)
    # every-1 probing: every finished request carries a fidelity score
    for rid, f in res1.items():
        assert f.metrics.fidelity is not None, rid
        assert 0.0 <= f.metrics.fidelity <= 1.0 + 1e-5
    # sampled cadence: every-3 probes ~1/3 of admissions
    eng3, _, res3 = _drain(cfg, params, fidelity_probe_every=3)
    probed = [f for f in res3.values()
              if f.metrics.fidelity is not None]
    assert 0 < len(probed) < len(res3)


def test_probe_coverage_one_inside_sa_window():
    cfg, params = _setup()
    sa = cfg.flux
    short = sa.sink + sa.local  # whole prompt visible to the SA mask
    eng, sched, res = _drain(cfg, params, n=1, fidelity_probe_every=1)
    assert res[0].metrics.fidelity is not None
    cov = eng._maybe_fidelity_probe(_prompt(cfg, min(short, 48)),
                                    ("sa",) * cfg.num_layers)
    np.testing.assert_allclose(np.asarray(cov), 1.0, atol=1e-6)


def test_padded_probe_matches_unpadded():
    # the probe pads prompts to power-of-two buckets and masks by
    # length; the padded form must agree with the direct forward to
    # reduction-order noise (XLA sums in shape-dependent order, so
    # bitwise equality across shapes is not a meaningful target)
    cfg, params = _setup()
    S = 27  # pads to 32
    toks = _prompt(cfg, S, seed=3)
    direct = MD.attention_mass_coverage(params, cfg,
                                        jnp.asarray(toks)[None])
    padded = np.zeros((1, 32), np.int32)
    padded[0, :S] = toks
    via_pad = MD.attention_mass_coverage(params, cfg,
                                         jnp.asarray(padded),
                                         length=jnp.int32(S))
    np.testing.assert_allclose(np.asarray(direct), np.asarray(via_pad),
                               rtol=1e-5, atol=1e-6)


def test_fidelity_histograms_and_drain_summary():
    cfg, params = _setup()
    eng, sched, res = _drain(cfg, params, fidelity_probe_every=1)
    samples = TM.parse_prometheus_text(eng.metrics_text())
    assert "flux_fidelity_coverage" in samples
    summ = eng._drain_summary(res)
    assert summ["fidelity_probed"] == len(res)
    assert 0.0 <= summ["fidelity_p50"] <= 1.0 + 1e-5
    assert 0.0 <= summ["fidelity_min"] <= 1.0 + 1e-5


# ---------------------------------------------------------------------------
# Margin drift tracker
# ---------------------------------------------------------------------------

def test_margin_drift_tracker_math():
    md = RT.MarginDriftTracker(window=4)
    for m in (0.1, 0.2, 0.3):
        md.observe(0, 0, m)
    assert md.drift(0, 0) == pytest.approx(0.0)  # window == lifetime
    # lifetime mean drags behind a shifted recent window
    for m in (0.9, 0.9, 0.9, 0.9):
        md.observe(0, 0, m)
    lifetime = (0.1 + 0.2 + 0.3 + 4 * 0.9) / 7
    assert md.drift(0, 0) == pytest.approx(0.9 - lifetime)
    assert md.drift(5, 1) == 0.0  # unseen key
    md.observe(1, 2, -0.5)
    assert md.keys() == ((0, 0), (1, 2))
    rep = md.report()
    assert rep["0:0"]["count"] == 7
    assert rep["1:2"]["drift"] == pytest.approx(0.0)
    with pytest.raises(ValueError, match="window"):
        RT.MarginDriftTracker(0)


def test_margin_drift_exported_from_drain():
    cfg, params = _setup()
    eng, _, _ = _drain(cfg, params, telemetry=True)
    rep = eng.attribution_report()
    assert rep["margin_drift"], "routed layers must have observed margins"
    for st in rep["margin_drift"].values():
        assert st["count"] > 0
    samples = TM.parse_prometheus_text(eng.metrics_text())
    assert "flux_router_margin_drift" in samples
