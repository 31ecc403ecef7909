"""Continuous batching vs batch-synchronous serving (DESIGN.md §Scheduler).

Mixed-length Poisson traffic against the two frontends of the same
engine:

  serve_batch — buckets requests by exact (length, n_steps), waits for
      the full arrival window, runs each bucket to completion.  A
      request's first token only exists when its whole bucket's fused
      decode scan returns.
  ContinuousScheduler — slot-pool decode; requests join a persistent
      batch at the next tick after arrival and stream out per chunk.

Reports token throughput (busy tok/s) and p50/p95 TTFT for both, and
writes ``BENCH_serving.json`` for the perf trajectory.  The acceptance
bar for this subsystem is ≥1.5× throughput on the mixed-length
workload (continuous batching merges the per-length buckets into one
resident decode batch, amortizing per-step dispatch across requests).
"""
from __future__ import annotations

import json
import os
import sys
import time
from typing import Dict, List

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np

from benchmarks.common import (CACHE_DIR, Row, bench_cfg, device_sync,
                               mixed_pattern, pct)
from repro.models import model as MD
from repro.serve import ContinuousScheduler, Request, ServeEngine

# all-distinct prompt lengths: the mixed-traffic shape the subsystem
# exists for — real traffic rarely collides on exact length, so
# exact-length bucketing degenerates to B=1 buckets that serialize,
# while the slot pool still decodes everything as one batch
LENS = tuple(range(24, 88, 4))  # 16 unique lengths


def _requests(cfg, n: int, n_steps: int, seed: int = 0) -> List[Request]:
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    tokens=rng.integers(0, cfg.vocab_size,
                                        size=LENS[i % len(LENS)]
                                        ).astype(np.int32),
                    n_steps=n_steps)
            for i in range(n)]


def _arrivals(n: int, mean_gap_s: float, seed: int = 1) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(mean_gap_s, size=n))


def _run_batch(eng: ServeEngine, reqs: List[Request],
               arrivals: np.ndarray) -> Dict:
    """serve_batch semantics with per-bucket timing: serving starts once
    every request has arrived; a request's TTFT is its bucket's
    completion (the fused scan yields no earlier tokens)."""
    buckets: Dict[tuple, List[int]] = {}
    for i, r in enumerate(reqs):
        buckets.setdefault((len(r.tokens), r.n_steps), []).append(i)
    t = float(arrivals.max())  # batch frontend waits for stragglers
    busy = 0.0
    ttft, tokens = [], 0
    for (_, n_steps), idxs in buckets.items():
        toks = np.stack([reqs[i].tokens for i in idxs])
        t0 = time.perf_counter()
        gen = eng.generate(toks, n_steps)  # tokens land on host here
        dt = time.perf_counter() - t0
        busy += dt
        t += dt
        tokens += gen.tokens.size
        ttft.extend(t - arrivals[i] for i in idxs)
    return {"tokens": tokens, "busy_s": busy,
            "tokens_per_sec": tokens / busy,
            "ttft_p50_s": pct(ttft, 50), "ttft_p95_s": pct(ttft, 95)}


def _run_continuous(eng: ServeEngine, reqs: List[Request],
                    arrivals: np.ndarray, *, slots: int,
                    chunk: int) -> Dict:
    """Submit on the (wall-clock) Poisson schedule, tick until drained."""
    sched = ContinuousScheduler(eng, slots_per_bucket=slots, chunk=chunk)
    t0 = time.perf_counter()
    pending = sorted(range(len(reqs)), key=lambda i: arrivals[i])
    submitted_at = {}
    done = {}
    while len(done) < len(reqs):
        now = time.perf_counter() - t0
        while pending and arrivals[pending[0]] <= now:
            i = pending.pop(0)
            sched.submit(reqs[i])
            submitted_at[reqs[i].rid] = now
        if sched.n_active() or sched.waiting:
            for f in sched.tick():
                done[f.rid] = f
        elif pending:  # idle until the next Poisson arrival
            time.sleep(min(max(arrivals[pending[0]] - now, 0.0), 0.005))
    # measurement boundary (common.py docstring): every tick already
    # synced on np.asarray(toks), but close the interval on an explicit
    # barrier so in-flight device work cannot leak past the stop-clock
    device_sync()
    busy = time.perf_counter() - t0
    tokens = sum(f.metrics.n_generated for f in done.values())
    ttft = [f.metrics.ttft for f in done.values()]
    qd = [f.metrics.queue_delay for f in done.values()]
    return {"tokens": tokens, "busy_s": busy,
            "tokens_per_sec": tokens / busy,
            "ttft_p50_s": pct(ttft, 50), "ttft_p95_s": pct(ttft, 95),
            "queue_delay_p50_s": pct(qd, 50),
            "geometries": sched.n_geometries(),
            "decode_executables": eng.decode_cache_size(),
            "ticks": sched.ticks}


def run(n_requests: int = 16, n_steps: int = 128, slots: int = 16,
        chunk: int = 8, mean_gap_s: float = 0.005) -> List[Row]:
    cfg = bench_cfg()
    params = MD.init_params(jax.random.key(0), cfg)
    reqs = _requests(cfg, n_requests, n_steps)
    arrivals = _arrivals(n_requests, mean_gap_s)
    max_len = max(LENS) + n_steps + 2

    # pin one realistic FA/SA mix on both paths: the bench isolates the
    # *scheduling* transformation (bucketed run-to-completion vs slot
    # pool); an untrained router would scatter requests over arbitrary
    # geometries and measure router noise instead.  Multi-geometry
    # admission is covered by tests/test_continuous_batching.py.
    pattern = mixed_pattern(cfg)
    # separate engines (separate jit caches) — warm each path once on
    # the full workload so compile time stays out of the timings, then
    # keep the best of ``reps`` interleaved measurements per path (the
    # host's available CPU throughput drifts by integer factors between
    # runs; min-time is the standard estimator under such contamination)
    reps = 3
    eng_b = ServeEngine(params, cfg, max_len=max_len,
                        routing_override=pattern)
    eng_c = ServeEngine(params, cfg, max_len=max_len,
                        routing_override=pattern)
    _run_batch(eng_b, reqs, arrivals)
    _run_continuous(eng_c, reqs, arrivals, slots=slots, chunk=chunk)
    batch = cont = None
    for _ in range(reps):
        b = _run_batch(eng_b, reqs, arrivals)
        c = _run_continuous(eng_c, reqs, arrivals, slots=slots,
                            chunk=chunk)
        if batch is None or b["tokens_per_sec"] > batch["tokens_per_sec"]:
            batch = b
        if cont is None or c["tokens_per_sec"] > cont["tokens_per_sec"]:
            cont = c

    speedup = cont["tokens_per_sec"] / batch["tokens_per_sec"]

    # telemetry overhead leg: the same continuous workload with the
    # metrics registry + span tracer + flight recorder enabled.  The
    # acceptance bar (ISSUE 7 / DESIGN.md §Observability) is zero extra
    # compiled executables and ≤5% tok/s overhead.
    eng_t = ServeEngine(params, cfg, max_len=max_len,
                        routing_override=pattern, telemetry=True)
    _run_continuous(eng_t, reqs, arrivals, slots=slots, chunk=chunk)
    # attribution leg: telemetry PLUS the cost-attribution layer at its
    # default cadences — fidelity probes on every 16th admission and
    # the per-tick memory ledger.  Same ≤5% bar.
    eng_a = ServeEngine(params, cfg, max_len=max_len,
                        routing_override=pattern, telemetry=True,
                        fidelity_probe_every=16, memory_ledger=True)
    _run_continuous(eng_a, reqs, arrivals, slots=slots, chunk=chunk)
    # overhead is measured with every request submitted up front: the
    # off and on runs then execute the *identical* tick/batch sequence
    # (the telemetry parity test proves bitwise-equal tokens), so the
    # ratio isolates instrumentation cost instead of folding in the
    # Poisson arrival/tick-phase coupling of the wall-clock workload.
    # Legs rotate order within each rep so host drift cancels too.
    now_arrivals = np.zeros_like(arrivals)
    best = {"ref": None, "tele": None, "attr": None}
    legs = [(eng_c, "ref"), (eng_t, "tele"), (eng_a, "attr")]
    for r in range(2 * reps):
        for eng, label in legs[r % 3:] + legs[:r % 3]:
            m = _run_continuous(eng, reqs, now_arrivals, slots=slots,
                                chunk=chunk)
            if (best[label] is None
                    or m["tokens_per_sec"] > best[label]["tokens_per_sec"]):
                best[label] = m
    ref, tele, attr = best["ref"], best["tele"], best["attr"]
    overhead = max(0.0, 1.0 - tele["tokens_per_sec"]
                   / ref["tokens_per_sec"])
    attr_overhead = max(0.0, 1.0 - attr["tokens_per_sec"]
                        / ref["tokens_per_sec"])
    extra_execs = (eng_t.decode_cache_size() - eng_c.decode_cache_size())
    attr_extra_execs = (eng_a.decode_cache_size()
                        - eng_c.decode_cache_size())
    # the ledger report artifact CI uploads: the attribution
    # engine's full JSON-ready report, reconciliation deltas included
    attr_report = eng_a.attribution_report()
    # probes performed = admissions the every-Nth gate sampled (first
    # admission always probes)
    n_adm = attr_report["probe_admissions"]
    every = attr_report["fidelity_probe_every"]
    n_probed = 0 if not n_adm else (n_adm - 1) // every + 1

    results = {
        "n_requests": n_requests, "n_steps": n_steps,
        "prompt_lens": list(LENS), "slots_per_bucket": slots,
        "chunk": chunk, "mean_arrival_gap_s": mean_gap_s,
        "serve_batch": batch, "continuous": cont,
        "throughput_speedup": speedup,
        "continuous_telemetry": tele,
        "telemetry_overhead_frac": overhead,
        "telemetry_extra_executables": extra_execs,
        "continuous_attribution": attr,
        "attribution_overhead_frac": attr_overhead,
        "attribution_extra_decode_executables": attr_extra_execs,
        "attribution_report": attr_report,
    }
    os.makedirs(CACHE_DIR, exist_ok=True)
    with open(os.path.join(CACHE_DIR, "BENCH_serving.json"), "w") as f:
        json.dump({"timestamp": time.time(),
                   "device": jax.default_backend(),
                   "results": results}, f, indent=2)
    rows = [
        Row("continuous-batching/serve_batch", batch["busy_s"] * 1e6,
            f"tps={batch['tokens_per_sec']:.0f};"
            f"ttft_p50={batch['ttft_p50_s'] * 1e3:.0f}ms;"
            f"ttft_p95={batch['ttft_p95_s'] * 1e3:.0f}ms"),
        Row("continuous-batching/slot-pool", cont["busy_s"] * 1e6,
            f"tps={cont['tokens_per_sec']:.0f};"
            f"ttft_p50={cont['ttft_p50_s'] * 1e3:.0f}ms;"
            f"ttft_p95={cont['ttft_p95_s'] * 1e3:.0f}ms;"
            f"speedup={speedup:.2f}x;"
            f"geoms={cont['geometries']};"
            f"execs={cont['decode_executables']}"),
        Row("continuous-batching/telemetry-on", tele["busy_s"] * 1e6,
            f"tps={tele['tokens_per_sec']:.0f};"
            f"overhead={overhead:.1%};"
            f"extra_execs={extra_execs}"),
        Row("continuous-batching/attribution-on", attr["busy_s"] * 1e6,
            f"tps={attr['tokens_per_sec']:.0f};"
            f"overhead={attr_overhead:.1%};"
            f"extra_decode_execs={attr_extra_execs};"
            f"probed={n_probed}/{attr_report['probe_admissions']}"),
    ]
    return rows


def main() -> None:
    smoke = "--smoke" in sys.argv
    rows = (run(n_requests=6, n_steps=8, slots=4, chunk=4)
            if smoke else run())
    for r in rows:
        print(r.csv())
    data = json.load(open(os.path.join(CACHE_DIR, "BENCH_serving.json")))
    speedup = data["results"]["throughput_speedup"]
    # correctness is gated in tests; the throughput ratio is advisory on
    # shared/smoke runners but the full run should clear 1.5×
    if speedup < 1.5:
        print(f"# WARN continuous-batching speedup {speedup:.2f}x < 1.5x"
              + (" (smoke shapes — advisory)" if smoke else ""))
    else:
        print(f"# ok continuous-batching speedup {speedup:.2f}x")
    overhead = data["results"]["telemetry_overhead_frac"]
    extra = data["results"]["telemetry_extra_executables"]
    if extra:
        print(f"# WARN telemetry added {extra} compiled executables "
              f"(must be 0)")
    if overhead > 0.05:
        print(f"# WARN telemetry overhead {overhead:.1%} > 5%"
              + (" (smoke shapes — advisory)" if smoke else ""))
    else:
        print(f"# ok telemetry overhead {overhead:.1%} "
              f"(extra executables: {extra})")
    attr_overhead = data["results"]["attribution_overhead_frac"]
    recon = data["results"]["attribution_report"]["ledger"][
        "reconciliation"]
    if attr_overhead > 0.05:
        print(f"# WARN attribution overhead {attr_overhead:.1%} > 5%"
              + (" (smoke shapes — advisory)" if smoke else ""))
    else:
        print(f"# ok attribution overhead {attr_overhead:.1%}")
    if recon["payload_delta"] or recon["prefix_device_delta"]:
        print(f"# WARN ledger reconciliation not exact: {recon}")
    else:
        print(f"# ok ledger reconciles (payload_delta=0)")


if __name__ == "__main__":
    main()
