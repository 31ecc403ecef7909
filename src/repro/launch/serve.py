"""Serving launcher: batched or continuous requests through the engine.

    PYTHONPATH=src python -m repro.launch.serve \
        --arch phi3-mini-3.8b --smoke --requests 4 --prompt-len 128

    # continuous batching: Poisson arrivals into the slot-pool scheduler
    PYTHONPATH=src python -m repro.launch.serve \
        --arch phi3-mini-3.8b --smoke --continuous --requests 8

    # with telemetry: Prometheus text + Perfetto trace of the drain
    PYTHONPATH=src python -m repro.launch.serve \
        --arch phi3-mini-3.8b --smoke --continuous --requests 8 \
        --metrics-out metrics.prom --trace-out trace.json
"""
from __future__ import annotations

import argparse
import contextlib
import json
import time

import jax
import numpy as np

from repro.configs import ALL_ARCHS, get_config, smoke_variant
from repro.data.synthetic import SyntheticTasks
from repro.kernels.decode_attention import make_kernel_decode_attn
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as MD
from repro.serve import (Request, ServeEngine, SLOConfig, STATUS_OK,
                         SHED_POLICIES, SHED_REJECT_NEWEST,
                         kv_cache, serve_batch_finished)
from repro.train import checkpoint


def _requests(cfg, args) -> list:
    gen = SyntheticTasks(cfg.vocab_size, seed=0)
    rng = np.random.default_rng(0)
    shared = (rng.integers(0, cfg.vocab_size,
                           size=args.shared_prefix).astype(np.int32)
              if args.shared_prefix else None)
    reqs = []
    for rid in range(args.requests):
        task = "needle" if rid % 2 == 0 else "markov"
        # continuous mode mixes prompt lengths — the traffic shape the
        # slot-pool scheduler exists for
        plen = (args.prompt_len if not args.continuous
                else args.prompt_len // (1 + rid % 3))
        b = gen.batch(rng, task, 1, max(plen, 16))
        toks = b.tokens[0]
        if shared is not None:
            # shared-system-prompt traffic: every request opens with the
            # same preamble — the prefix-cache hit path's home turf
            toks = np.concatenate([shared, toks]).astype(np.int32)
        reqs.append(Request(rid=rid, tokens=toks, n_steps=args.gen_len,
                            prefix_reuse=not args.no_prefix_reuse))
    return reqs


def _serve_continuous(engine: ServeEngine, reqs, args) -> None:
    sched = engine.scheduler(slots_per_bucket=args.slots, chunk=args.chunk)
    rng = np.random.default_rng(1)
    arrivals = np.cumsum(rng.exponential(args.mean_gap, len(reqs)))
    t0 = time.monotonic()
    pending = list(reqs)
    next_arrival = 0
    done = {}
    # loop on submitted-work-left, not result count: a shed request
    # retires at submit() time and is only announced by the next tick
    while pending or sched.waiting or sched.n_active():
        now = time.monotonic() - t0
        while pending and arrivals[next_arrival] <= now:
            engine.submit(pending.pop(0))
            next_arrival += 1
        if sched.waiting or sched.n_active():
            for f in engine.step():
                done[f.rid] = f
        elif pending:  # idle until the next Poisson arrival
            time.sleep(min(max(arrivals[next_arrival] - now, 0.0), 0.05))
    for f in sched.tick():  # announce any final submit-time sheds
        done[f.rid] = f
    wall = time.monotonic() - t0
    total = 0
    probes = engine.fidelity_probe_every > 0
    for rid in sorted(done):
        f, m = done[rid], done[rid].metrics
        total += m.n_generated
        # routing-fidelity columns only when probing was enabled: mean
        # attention-mass coverage + the worst SA-layer coverage for the
        # sampled admissions, '-' for the unsampled rest
        fid = ""
        if probes:
            fid = (f" cov={m.fidelity:.3f}" if m.fidelity is not None
                   else " cov=    -")
            fid += (f" sa_min={m.fidelity_sa_min:.3f}"
                    if m.fidelity_sa_min is not None else " sa_min=    -")
        print(f"req {rid} [{f.status:>9}]: {f.tokens[:8].tolist()} ... | "
              f"ttft={m.ttft * 1e3:6.1f}ms queue={m.queue_delay * 1e3:5.1f}ms "
              f"tps={m.decode_tps:6.1f} preempt={m.preemptions}{fid}")
    by_status = {}
    for f in done.values():
        by_status[f.status] = by_status.get(f.status, 0) + 1
    status_str = " ".join(f"{s}={n}" for s, n in sorted(by_status.items()))
    n_ok = by_status.get(STATUS_OK, 0)
    print(f"{len(done)} requests ({status_str}) | {total} tokens in "
          f"{wall:.2f}s ({total / wall:.0f} tok/s, "
          f"{n_ok}/{len(done)} ok) | geometries={sched.n_geometries()} "
          f"decode_executables={engine.decode_cache_size()} "
          f"ticks={sched.ticks} sa_level={engine.sa_level}")
    if engine.prefix_store is not None:
        s = engine.prefix_store.stats()
        hit = sum(done[r].metrics.prefix_hit_tokens for r in done)
        prompt = sum(done[r].metrics.prompt_len for r in done)
        print(f"prefix cache: {s.hits} hits / {s.misses} misses | "
              f"{hit}/{prompt} prompt tokens warm "
              f"({hit / max(prompt, 1):.0%}) | "
              f"device={s.device_bytes} B host={s.host_bytes} B "
              f"snapshots={s.snapshots}")


def _decode_kernel(cfg, args, max_len: int):
    """Build the decode-attention backend named by --decode-kernel.

    'on' is the loud variant: if every geometry this engine can route
    (FullKV buffers at ``max_len``, SA rings at sink+local) falls under
    the adapter's ``min_len`` decline threshold, the kernel would be
    accepted at construction yet decline every single call — the
    silent-forever failure ISSUE 8 closes.  Refuse to start instead."""
    if args.decode_kernel == "off":
        return None
    block_k = args.kernel_block_k
    min_len = 2 * block_k
    if args.decode_kernel == "on":
        candidates = {"full-cache": max_len}
        if cfg.flux.enabled:
            candidates["sa-ring"] = min(kv_cache.ring_size(cfg.flux),
                                        max_len)
        if all(length < min_len for length in candidates.values()):
            detail = " ".join(f"{name}={length}" for name, length
                              in sorted(candidates.items()))
            raise SystemExit(
                f"--decode-kernel on: no routed geometry can satisfy "
                f"the kernel's shape constraints — every cache extent "
                f"({detail}) is below min_len={min_len} "
                f"(= 2·block_k), so the adapter would decline every "
                f"call and serve dense forever.  Lower --kernel-block-k "
                f"or raise --prompt-len/--gen-len.")
    return make_kernel_decode_attn(block_k=block_k, min_len=min_len)


def _print_kernel_summary(engine: ServeEngine) -> None:
    if engine.decode_attn is None:
        return
    s = engine.decode_kernel_summary()
    hits = " ".join(f"{r}={n}" for r, n in
                    sorted(s["hit_reasons"].items())) or "none"
    groups = " ".join(f"G{g}={n}" for g, n in
                      sorted(s["hit_groups"].items())) or "none"
    declines = " ".join(f"{r}={n}" for r, n in
                        sorted(s["decline_layers"].items())) or "none"
    print(f"decode kernel: dispatches={s['dispatches']} "
          f"hit_layers={s['hit_layers']} ({hits}; {groups}) "
          f"declines: {declines}")


def _write_telemetry(engine: ServeEngine, args) -> None:
    """Export the run's telemetry to the paths the flags named (no-op
    when neither flag was passed)."""
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(engine.metrics_text())
        print(f"metrics → {args.metrics_out}")
    if args.trace_out:
        engine.export_trace(args.trace_out)
        print(f"trace   → {args.trace_out} "
              f"(open in https://ui.perfetto.dev)")
    if args.ledger_out:
        rep = engine.attribution_report()
        led = rep["ledger"]
        with open(args.ledger_out, "w") as f:
            json.dump(rep, f, indent=2)
        recon, snap = led["reconciliation"], led["snapshot"]
        if snap is None:
            # batch-synchronous path: no scheduler ticked, so the ledger
            # never snapshotted — the report still carries kv_cache_stats
            print(f"ledger  → {args.ledger_out} (no tick snapshots; "
                  f"use --continuous for the per-tick ledger)")
        else:
            print(f"ledger  → {args.ledger_out} | "
                  f"device={snap['device_bytes']} B "
                  f"hwm={snap['device_high_watermark_bytes']} B "
                  f"frag={snap['fragmentation_bytes']} B | "
                  f"reconciliation payload_delta={recon['payload_delta']} "
                  f"overhead_delta={recon['overhead_delta']} "
                  f"(aux={led['aux_bytes']})")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ALL_ARCHS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--load", default=None)
    ap.add_argument("--dense", action="store_true",
                    help="disable sparse decode (paper's non-shaded rows)")
    ap.add_argument("--decode-kernel", choices=("off", "auto", "on"),
                    default="auto",
                    help="Pallas flash-decode backend for the decode "
                         "scan: 'auto' installs it and lets the adapter "
                         "decline per-layer (dense fallback below "
                         "min_len = 2·block_k); 'on' additionally "
                         "refuses to start if NO routed geometry could "
                         "ever satisfy the kernel's shape constraints "
                         "(the silently-declining-forever trap)")
    ap.add_argument("--kernel-block-k", type=int, default=128,
                    help="KV block size of the decode kernel; the "
                         "adapter's min_len heuristic is 2·block_k")
    ap.add_argument("--continuous", action="store_true",
                    help="slot-pool continuous batching instead of "
                         "batch-synchronous bucketing")
    ap.add_argument("--slots", type=int, default=4,
                    help="slot-pool capacity per geometry bucket")
    ap.add_argument("--chunk", type=int, default=8,
                    help="decode steps per scheduler tick")
    ap.add_argument("--mean-gap", type=float, default=0.02,
                    help="mean Poisson interarrival gap (s)")
    ap.add_argument("--prefill-chunk", type=int, default=512,
                    help="max chunk of the chunked cache-resident "
                         "prefill (prefix snapshots land at multiples "
                         "of this; 0 = monolithic admission)")
    ap.add_argument("--prefix-cache-mb", type=float, default=0.0,
                    help="device byte budget (MB) for the shared-prefix "
                         "snapshot store; 0 disables prefix reuse")
    ap.add_argument("--prefix-cache-host-mb", type=float, default=0.0,
                    help="host offload tier budget (MB): evicted "
                         "snapshots demote to CPU instead of dropping")
    ap.add_argument("--no-prefix-reuse", action="store_true",
                    help="submit requests opted out of prefix reuse "
                         "(store stays configured but untouched)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend a shared system prompt of this many "
                         "tokens to every request")
    # SLO guardrails (serve/slo.py); all off by default
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bound on the waiting queue; overflow is shed "
                         "per --shed-policy (0 = unbounded)")
    ap.add_argument("--shed-policy", choices=SHED_POLICIES,
                    default=SHED_REJECT_NEWEST,
                    help="who a full queue rejects")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="default per-request deadline in seconds; "
                         "expired work retires with status 'timeout' "
                         "(0 = no deadline)")
    ap.add_argument("--preemption-budget", type=int, default=-1,
                    help="max recompute-preemptions per request before "
                         "it becomes non-evictable (-1 = unbudgeted)")
    ap.add_argument("--aging-s", type=float, default=0.0,
                    help="waiting seconds per +1 admission priority "
                         "(anti-starvation; 0 = no aging)")
    ap.add_argument("--adaptive-sparsity", action="store_true",
                    help="bias Layer Router decisions toward SA under "
                         "queue pressure (load-adaptive sparsity dial)")
    # telemetry (DESIGN.md §Observability); either flag enables it
    ap.add_argument("--metrics-out", default=None,
                    help="write Prometheus text exposition of the run's "
                         "metrics here (enables engine telemetry)")
    ap.add_argument("--trace-out", default=None,
                    help="write the request-span Chrome-trace/Perfetto "
                         "JSON here (enables engine telemetry; open in "
                         "https://ui.perfetto.dev)")
    # cost attribution (DESIGN.md §Observability); all off by default
    ap.add_argument("--profile-dir", default=None,
                    help="record a jax.profiler trace of the serving run "
                         "here: the scheduler's serve.* spans and the "
                         "device's ops on one clock (TensorBoard / "
                         "Perfetto; nothing is added to the tick)")
    ap.add_argument("--fidelity-probe-every", type=int, default=0,
                    help="probe every Nth admission's attention-mass "
                         "coverage per routed layer (0 = off)")
    ap.add_argument("--ledger-out", default=None,
                    help="enable the device-memory ledger and write its "
                         "reconciled JSON report here")
    ap.add_argument("--mesh-model", type=int, default=0,
                    help="tensor-parallel width: shard KV heads and "
                         "attention/MLP weights over an N-way 'model' "
                         "mesh axis (0 = single-device serving; on CPU "
                         "set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8 "
                         "before launch)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    mesh = None
    if args.mesh_model > 1:
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(data=1, model=args.mesh_model)
        print(f"mesh: (1, {args.mesh_model}) over "
              f"{len(jax.devices())} devices")
    # parameters are born in their serving shardings: no device ever
    # holds a whole model that only fits the mesh
    params = MD.init_params_on_device(jax.random.key(0), cfg, mesh)
    if args.load:
        params = checkpoint.load(args.load, params)

    reqs = _requests(cfg, args)
    slo = SLOConfig(
        max_queue=args.max_queue or None,
        shed_policy=args.shed_policy,
        default_deadline_s=args.deadline_s or None,
        preemption_budget=(None if args.preemption_budget < 0
                           else args.preemption_budget),
        aging_s=args.aging_s or None,
        adaptive_sparsity=args.adaptive_sparsity)
    telemetry = bool(args.metrics_out or args.trace_out)
    max_len = args.prompt_len + args.shared_prefix + args.gen_len + 8
    decode_attn = _decode_kernel(cfg, args, max_len)
    engine = ServeEngine(params, cfg, max_len=max_len,
                         sparse_decode=not args.dense,
                         decode_attn=decode_attn,
                         prefill_chunk=args.prefill_chunk or None,
                         prefix_cache_mb=args.prefix_cache_mb or None,
                         prefix_cache_host_mb=args.prefix_cache_host_mb,
                         slo=slo, telemetry=telemetry,
                         fidelity_probe_every=args.fidelity_probe_every,
                         memory_ledger=bool(args.ledger_out),
                         mesh=mesh)
    profile = (jax.profiler.trace(args.profile_dir) if args.profile_dir
               else contextlib.nullcontext())
    if args.continuous:
        with profile:
            _serve_continuous(engine, reqs, args)
        _print_kernel_summary(engine)
        _write_telemetry(engine, args)
        return
    t0 = time.time()
    with profile:
        results = serve_batch_finished(engine, reqs)
    dt = time.time() - t0
    for rid in sorted(results):
        f = results[rid]
        print(f"req {rid} [{f.status:>7}]: {f.tokens[:8].tolist()} ...")
    n_ok = sum(f.status == STATUS_OK for f in results.values())
    print(f"{len(reqs)} requests ({n_ok} ok), {args.gen_len} tokens each, "
          f"{dt:.2f}s wall")
    _print_kernel_summary(engine)
    _write_telemetry(engine, args)


if __name__ == "__main__":
    main()
