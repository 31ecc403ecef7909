#!/usr/bin/env python3
"""Chip smoke: the serving path end to end on a TPU, at published width.

    python chip_smoke.py              # phi3-mini-3.8b on one chip
    python chip_smoke.py --chips 4    # stablelm-12b, tensor-parallel on four

A smoke, not a benchmark.  Weights are drawn from ``--seed`` by one
compiled init (no checkpoint exists), placed where serving keeps them —
on the four-chip path each device is born holding only its shard.  A few
requests then go through ``ServeEngine`` and its continuous scheduler:
chunked cache-resident prefill on the block-sparse Pallas kernel, pooled
Pallas decode on FullKV and SA-ring slot pools.  One request carries an
alternating FA/SA routing override, so both cache geometries run
whatever the random router picks; the long prompts outgrow the SA ring,
so ring eviction happens.  Before that, the same request's served logits
— at the prefill boundary and for the first decode steps — are checked
against a plain reference: monolithic ``prefill`` plus dense decode, no
kernels, fed the same tokens.  Times are one cold run's and include
compilation; the persistent compilation cache makes a second run faster.

It fails (non-zero exit, no result line) when JAX finds no TPU, and when
any check fails.  The last line of a passing run is
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

# Agreement of served and reference logits, as the RMS of their
# difference over the RMS of the reference logits.  Both run in bf16
# with f32 accumulation; they differ in summation order (chunked vs
# monolithic prefill, kernel vs dense decode), which bf16 rounding
# carries through every layer.  A masking or indexing fault moves the
# logits by O(1) of their RMS.
LOGIT_TOL = 0.05


@dataclass(frozen=True)
class Plan:
    """One smoke configuration.  Prompt lengths are multiples of the
    prefill chunk, so every chunk has one size and the executables stay
    few; ``max_len`` is a multiple of the decode kernel's block, so no
    cache is padded on the way into it."""
    arch: str
    prompt_lens: Tuple[int, ...]
    max_len: int
    n_steps: int = 32
    prefill_chunk: int = 512
    block_k: int = 128
    slots: int = 2
    # decode steps per scheduler tick.  One: a longer decode scan is a
    # loop whose carry holds a lane-padded copy of every cache of the
    # pool at head dims that are not a multiple of 128 (3.9 GiB for a
    # phi3 pool of 2 slots at max_len 2688), which with a second pool
    # and the weights overruns the chip's 16 GiB.
    decode_chunk: int = 1
    ref_steps: int = 4        # decode steps compared with the reference
    mixed: int = 0            # request carrying the FA/SA override


PLANS: Dict[int, Plan] = {
    # phi3-mini: 32 layers × d_model 3072, 32 heads of 96, vocab 32064 —
    # 7.6 GB of bf16 weights.  The SA ring is sink 128 + local 2048, so
    # the two 2560-token prompts evict from it.
    1: Plan("phi3-mini-3.8b", (2560, 2560, 1024, 512), max_len=2688),
    # stablelm-12b: 40 layers × d_model 5120, 32/8 heads of 160, vocab
    # 100352 — about 24 GB of bf16 weights, more than one chip holds.
    4: Plan("stablelm-12b", (2560, 1024), max_len=2688),
}


class CompileClock:
    """Backend compile seconds and persistent-cache hits, per phase."""

    def __init__(self):
        import jax
        self.phase = "setup"
        self.seconds: Dict[str, float] = {}
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds[self.phase] = self.seconds.get(self.phase, 0.0) + secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def memory_line(devices) -> str:
    parts = []
    for d in devices:
        st = d.memory_stats() or {}
        parts.append(f"{d.id}:in_use={st.get('bytes_in_use', 'n/a')} "
                     f"peak={st.get('peak_bytes_in_use', 'n/a')}")
    return " | ".join(parts)


def make_requests(cfg, plan: Plan, seed: int):
    """Random prompts.  The router-routed ones open with one shared
    system prompt of a chunk: routing reads only a prompt's first
    ``flux.pool_size`` tokens, so they share one routed geometry and
    one slot pool (every distinct geometry holds a pool of its own, and
    four phi3 pools with the weights fill the chip)."""
    import numpy as np
    from benchmarks.common import mixed_pattern
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    system = rng.integers(0, cfg.vocab_size, size=plan.prefill_chunk,
                          dtype=np.int32)
    reqs = []
    for i, n in enumerate(plan.prompt_lens):
        tokens = rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32)
        mixed = i == plan.mixed
        if not mixed:
            tokens[:len(system)] = system
        reqs.append(Request(rid=i, tokens=tokens, n_steps=plan.n_steps,
                            routing_override=(mixed_pattern(cfg) if mixed
                                              else None)))
    return reqs


def compare_with_reference(engine, req, steps: int, mesh) -> List[Dict]:
    """Served vs reference logits of one request, fed the same tokens.

    Served: the engine's chunked prefill (block-sparse kernel on FA
    layers), then decode steps with the pooled kernel installed — the
    same ``decode_core`` the scheduler's scan runs, with per-slot
    positions.  Reference: monolithic ``prefill`` under the dense chunk
    backend, the monolithic repack into decode caches, and dense decode
    with no kernel.  Greedy tokens come from the served logits."""
    import contextlib
    import jax
    import jax.numpy as jnp
    from repro.core import modes as M
    from repro.models import model as MD
    from repro.serve.engine import repack_caches

    cfg, params = engine.cfg, engine.params
    tokens = jnp.asarray(req.tokens)[None]
    n = tokens.shape[1]
    step = jax.jit(partial(MD.decode_core, cfg=cfg),
                   donate_argnames=("caches",))

    job = engine.prefill_chunked(tokens, req.routing_override, reuse=False)
    served, fed = [job.logits], []
    caches = job.caches
    ctx = jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext()
    with ctx, MD.use_decode_attn(engine.decode_attn):
        for i in range(steps):
            tok = jnp.argmax(served[-1], axis=-1).astype(jnp.int32)
            fed.append(tok)
            logits, caches = step(params=params, token=tok[:, None],
                                  caches=caches,
                                  pos=jnp.full((1,), n + i, jnp.int32))
            served.append(logits)
    pattern, n_chunks = job.pattern, job.n_chunks
    del job, caches

    fixed = jnp.asarray([0 if p == "sa" else 1 for p in pattern], jnp.int32)
    with M.chunk_attention_backend("dense"):
        pf = jax.jit(partial(MD.prefill, cfg=cfg),
                     static_argnames=("routing_ctx",))(
            params=params, tokens=tokens, routing_ctx="fixed",
            fixed_pattern=fixed)
        caches = jax.jit(partial(repack_caches, cfg),
                         static_argnames=("routing", "seq_len", "max_len"))(
            pf.caches, routing=pattern, seq_len=n, max_len=engine.max_len)
        ref = [pf.logits]
        del pf
        for i, tok in enumerate(fed):
            logits, caches = step(params=params, token=tok[:, None],
                                  caches=caches, pos=jnp.int32(n + i))
            ref.append(logits)
    del caches

    rows = []
    for i, (s, r) in enumerate(zip(served, ref)):
        s = jnp.asarray(s, jnp.float32)
        r = jnp.asarray(r, jnp.float32)
        rms_ref = float(jnp.sqrt(jnp.mean(r * r)))
        rms_diff = float(jnp.sqrt(jnp.mean((s - r) ** 2)))
        rows.append({
            "at": "prefill" if i == 0 else f"decode{i}",
            "rel_rms": rms_diff / max(rms_ref, 1e-30),
            "max_abs": float(jnp.max(jnp.abs(s - r))),
            "ref_rms": rms_ref,
            "same_argmax": bool(jnp.argmax(s) == jnp.argmax(r)),
            "chunks": n_chunks,
        })
    return rows


def run(cfg, plan: Plan, *, seed: int, mesh=None, log=print,
        clock: Optional[CompileClock] = None) -> List[str]:
    """Serve ``plan`` on ``cfg``; returns the failed checks (empty when
    every check passed).  ``mesh`` runs it tensor-parallel."""
    import jax
    from repro.core import modes as M
    from repro.kernels.decode_attention import make_kernel_decode_attn
    from repro.models import model as MD
    from repro.serve import ServeEngine

    def phase(name):
        if clock is not None:
            clock.phase = name

    devices = (list(mesh.devices.flat) if mesh is not None
               else jax.devices()[:1])
    failed: List[str] = []
    on_chip = jax.devices()[0].platform == "tpu"

    phase("init")
    t0 = time.perf_counter()
    params = MD.init_params_on_device(jax.random.key(seed), cfg, mesh)
    jax.block_until_ready(params)
    n_bytes = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    log(f"init: {cfg.name} {n_bytes} B of weights in "
        f"{time.perf_counter() - t0:.1f}s | {memory_line(devices)}")
    if mesh is not None:
        held = [sum(s.data.nbytes for x in jax.tree_util.tree_leaves(params)
                    for s in x.addressable_shards if s.device == d)
                for d in devices]
        log(f"weights per device: {held} B of {n_bytes} B")
        if len(devices) > 1 and max(held) >= n_bytes:
            failed.append("a device holds the whole model")

    engine = ServeEngine(params, cfg, max_len=plan.max_len,
                         prefill_chunk=plan.prefill_chunk,
                         decode_attn=make_kernel_decode_attn(
                             block_k=plan.block_k,
                             min_len=2 * plan.block_k),
                         decode_unroll=1, telemetry=True, mesh=mesh)
    reqs = make_requests(cfg, plan, seed)

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        with M.chunk_attention_backend("pallas", block=plan.block_k):
            phase("compare")
            t0 = time.perf_counter()
            rows = compare_with_reference(engine, reqs[plan.mixed],
                                          plan.ref_steps, mesh)
            log(f"reference check ({time.perf_counter() - t0:.1f}s, "
                f"request {plan.mixed}, {rows[0]['chunks']} chunks), "
                f"tolerance rel_rms <= {LOGIT_TOL}:")
            for r in rows:
                ok = r["rel_rms"] <= LOGIT_TOL
                log(f"  {r['at']:>8}: rel_rms={r['rel_rms']:.5f} "
                    f"max_abs={r['max_abs']:.5f} ref_rms={r['ref_rms']:.4f} "
                    f"same_argmax={r['same_argmax']} "
                    f"{'ok' if ok else 'FAIL'}")
                if not ok:
                    failed.append(f"logits differ at {r['at']}")
            log(f"after reference check | {memory_line(devices)}")

            phase("serve")
            engine.scheduler(slots_per_bucket=plan.slots,
                             chunk=plan.decode_chunk)
            t0 = time.perf_counter()
            for r in reqs:
                engine.submit(r)
            done = engine.drain()
            wall = time.perf_counter() - t0
    if on_chip:
        donation = [str(w.message) for w in caught
                    if "donat" in str(w.message).lower()]
        for msg in donation[:3]:
            log(f"donation warning: {msg}")
        if donation:
            failed.append(f"{len(donation)} donation warnings: a cache "
                          f"was copied instead of updated in place")

    chunks: Dict[int, int] = {}
    for ev in engine.tracer.events:
        if ev.get("name") == "prefill_chunk":
            chunks[ev["tid"]] = chunks.get(ev["tid"], 0) + 1
    log(f"served {len(done)} requests in {wall:.1f}s (smoke, includes "
        f"compilation — not a benchmark):")
    for r in reqs:
        f = done.get(r.rid)
        if f is None:
            failed.append(f"request {r.rid} never finished")
            continue
        m = f.metrics
        n_sa = sum(p == "sa" for p in (f.routing or ()))
        log(f"  req {r.rid}: prompt={len(r.tokens)} status={f.status} "
            f"tokens={len(f.tokens)} chunks={chunks.get(r.rid, 0)} "
            f"sa_layers={n_sa}/{cfg.num_layers} ttft={m.ttft:.3f}s "
            f"tok/s={m.decode_tps:.1f}")
        if f.status != "ok" or len(f.tokens) != plan.n_steps:
            failed.append(f"request {r.rid} ended {f.status} with "
                          f"{len(f.tokens)} tokens")
        if len(r.tokens) > plan.prefill_chunk and chunks.get(r.rid, 0) < 2:
            failed.append(f"request {r.rid} streamed fewer than 2 chunks")
    ks = engine.decode_kernel_summary()
    log(f"decode kernel: {ks}")
    for reason, geometry in (("pooled", "FullKV"), ("pooled_ring", "ring")):
        if not ks["hit_reasons"].get(reason):
            failed.append(f"no decode kernel hit on the {geometry} pool")
    log(f"executables: decode={engine.decode_cache_size()} "
        f"prefill_chunk={engine.prefill_chunk_cache_size()} "
        f"pools={len(engine.scheduler().pools)}")
    log(f"after serving | {memory_line(devices)}")
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=sorted(PLANS), default=1,
                    help="1: phi3-mini-3.8b on one chip; 4: stablelm-12b "
                         "tensor-parallel over a (1, 4) mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform is "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_debug_mesh

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    plan = PLANS[args.chips]
    cfg = get_config(plan.arch)
    mesh = make_debug_mesh(1, args.chips) if args.chips > 1 else None
    print(f"device: {devices[0].device_kind} x{len(devices)} "
          f"({devices[0].platform}); using {args.chips} | model "
          f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.num_heads}/{cfg.num_kv_heads} heads of {cfg.head_dim}, "
          f"vocab {cfg.vocab_size} | compile cache {cache_dir}", flush=True)
    t0 = time.perf_counter()
    failed = run(cfg, plan, seed=args.seed, mesh=mesh, clock=clock,
                 log=lambda s: print(s, flush=True))
    compile_s = " ".join(f"{k}={v:.1f}s" for k, v in clock.seconds.items())
    print(f"compile seconds: {compile_s} (persistent cache hits="
          f"{clock.hits} misses={clock.misses}) | total "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    if failed:
        for f in failed:
            print(f"FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
