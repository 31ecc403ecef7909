"""Serving engine: route on the first chunk → stream the rest into
decode-geometry caches → device-resident sparse decode.

Admission (paper §3.3 + DESIGN.md §Prefill pipeline) is a chunked,
cache-resident pipeline:
  1. The prompt is decomposed into bucketed chunks (``chunk_plan``).
     The first chunk runs as a small monolithic prefill with
     prefix-pooled hard routing — the Layer Router fires exactly once
     per layer and the per-layer FA/SA pattern is frozen (§3.3).
  2. Decode-geometry caches are allocated from the pattern and seeded
     with the first chunk's KV (``seed_caches``); remaining chunks
     stream through ``MD.prefill_chunk`` writing *directly* into them —
     ``full_insert`` at FA layers, ``ring_insert`` at SA layers.  Peak
     live KV at SA layers is bounded by the ring, not the prompt, and
     no full-sequence KV is ever materialized or repacked.
  3. ``decode_many`` generates all requested tokens in ONE compiled
     call: a ``lax.scan`` over decode steps with on-device sampling and
     donated cache buffers.  Every compiled artifact on the serving
     path — seed, stream chunk, decode — is keyed by the *cache
     geometry* (× chunk bucket for prefill), never by the fa/sa routing
     tuple, and ``ServeEngine`` asserts those jit caches stay bounded.

``prefill_route_repack`` (full prefill → host-planned repack) remains
as the fallback for cases the chunked path excludes — see its
docstring.  ``sparse_decode=False`` reproduces the paper's non-shaded
rows: routing affects prefill only and decode keeps full KV everywhere.
"""
from __future__ import annotations

import contextlib
import time
import warnings
from collections import Counter
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
# module (not name) import: core.router itself imports models.layers,
# whose package chain loads repro.serve — a name import here would trip
# that cycle at interpreter start
from repro.core import router as RT
from repro.distributed import pool_sharding as PSH
from repro.launch import shardings as SHD
from repro.models import model as MD
from repro.serve import kv_cache as KC
from repro.serve import prefix_cache as PXC
from repro.serve import slo as SLO
from repro.serve import telemetry as TM
from repro.serve import tracing as TR

# Closed decline vocabulary shared by every decode-attention adapter
# (kernels.decode_attention, distributed.decode): the engine
# pre-registers one counter label per reason, so a new reason MUST be
# added here or its declines silently never export.
DECODE_KERNEL_DECLINE_REASONS = ("min_len", "mask_rank")


# ---------------------------------------------------------------------------
# Chunk planning (host-side, static)
# ---------------------------------------------------------------------------

def chunk_plan(seq_len: int, chunk: int) -> List[Tuple[int, int]]:
    """Decompose a prompt into bucketed chunks: [(start, size), ...].

    Sizes are drawn from the static ladder {chunk} ∪ {2^k : 2^k < chunk},
    largest first, covering ``seq_len`` *exactly* — padding is never an
    option because padded tokens would be ring-inserted (corrupting
    ``positions``) and would advance Mamba state with garbage.  The
    ladder bounds compiled chunk executables at O(#geometries ×
    #buckets) with #buckets ≤ log2(chunk)+2, the engine's guard budget.
    """
    if seq_len <= 0:
        raise ValueError(f"chunk_plan: seq_len={seq_len} must be positive")
    if chunk <= 0:
        raise ValueError(f"chunk_plan: chunk={chunk} must be positive")
    plan: List[Tuple[int, int]] = []
    start = 0
    while seq_len - start >= chunk:
        plan.append((start, chunk))
        start += chunk
    rem = seq_len - start
    if rem:
        b = 1 << (rem.bit_length() - 1)  # largest power of two <= rem
        while rem:
            if b <= rem:
                plan.append((start, b))
                start += b
                rem -= b
            b >>= 1
    return plan


# ---------------------------------------------------------------------------
# Chunk-0 seeding: first-chunk prefill KV → fresh decode-geometry caches
# ---------------------------------------------------------------------------

def seed_caches(cfg: ModelConfig, prefill_caches, pattern,
                batch: int, max_len: int):
    """Build decode-geometry caches for ``pattern`` and insert a
    routing-chunk's per-layer KV (stacked per period position, as
    ``MD.prefill`` returns it) at position 0 — one compiled call,
    entirely device-side: the chunked replacement for the host-planned
    gathers of ``repack_caches``.  ``pattern`` is static but fa/sa
    patterns map 1:1 onto cache geometries, so the jit cache still
    holds one entry per (geometry, first-chunk bucket)."""
    caches = KC.init_decode_caches(cfg, pattern, batch, max_len)
    flux = cfg.flux
    P = MD.period_len(cfg)
    start = jnp.int32(0)
    out = []
    for i, kind in enumerate(cfg.layer_kinds):
        per, pos = divmod(i, P)
        c = jax.tree.map(lambda a: a[per], prefill_caches[pos])
        dec = caches[i]
        if kind == "mamba":
            h, tail = c
            out.append(KC.MambaCache(h=h, conv_tail=tail))
            continue
        if cfg.use_mla:
            ckv, kr = c
            if isinstance(dec, KC.RingLatentKV):
                ring = dec.ckv.shape[1]
                sink = 0 if kind == "local" else flux.sink
                out.append(KC.ring_latent_insert_chunk(
                    dec, ckv, kr, start, sink, ring - sink))
            else:
                out.append(KC.latent_insert_chunk(dec, ckv, kr, start))
            continue
        k, v = c
        if isinstance(dec, KC.RingKV):
            ring = dec.k.shape[2]
            sink = 0 if kind == "local" else flux.sink
            out.append(KC.ring_insert_chunk(dec, k, v, start, sink,
                                            ring - sink))
        else:
            out.append(KC.full_insert_chunk(dec, k, v, start))
    return out


# ---------------------------------------------------------------------------
# Cache repacking (monolithic fallback path)
# ---------------------------------------------------------------------------

def _ring_src(seq_len: int, sink: int, local: int, ring: int) -> np.ndarray:
    """Per-ring-slot source position in the prefill KV (-1 = empty)."""
    src = np.full((ring,), -1, np.int64)
    ns = min(sink, seq_len, ring)
    src[:ns] = np.arange(ns)
    for p in range(max(sink, seq_len - local), seq_len):
        src[sink + (p - sink) % local] = p
    return src


def _gather_ring(k_full: jax.Array, src: np.ndarray, axis: int) -> jax.Array:
    idx = jnp.asarray(np.maximum(src, 0))
    g = jnp.take(k_full, idx, axis=axis)
    shape = [1] * g.ndim
    shape[axis] = len(src)
    mask = jnp.asarray(src >= 0).reshape(shape)
    return jnp.where(mask, g, 0)


def repack_caches(cfg: ModelConfig, prefill_caches, routing,
                  seq_len: int, max_len: int):
    """Prefill caches (stacked per period position) → decode cache list.

    FALLBACK PATH: the chunked admission (``seed_caches`` + the
    device-side chunk inserts in ``kv_cache``) replaced this in the
    serving hot path — the host-planned ``_ring_src`` gather plans here
    survive only for admissions ``chunked_eligible`` excludes.

    routing[i] ∈ {"fa","sa",("duo",n),None}; seq_len = prompt length
    (incl. any modality prefix); max_len = decode cache capacity for FA
    layers.  Only "sa" changes the geometry (ring); duo layers keep the
    full cache (ragged per-head histories are unrepresentable — §2.3).
    Every row of the resulting caches starts at the same ``seq_len``;
    per-slot ``positions``/``length`` diverge once the caches join a
    continuous-batching slot pool (DESIGN.md §Scheduler).
    """
    flux = cfg.flux
    P = MD.period_len(cfg)
    out = []

    def _full_pad(layer: int) -> int:
        # ring layers truncate long prompts structurally; full-cache
        # layers cannot — seq_len > max_len would be a negative pad
        # surfacing as a cryptic XLA shape error, so refuse loudly.
        if seq_len > max_len:
            raise ValueError(
                f"repack_caches: prompt length seq_len={seq_len} exceeds "
                f"the decode cache capacity max_len={max_len} at full-"
                f"cache layer {layer}; raise the engine's max_len or "
                f"truncate the prompt")
        return max_len - seq_len

    def _positions(src: np.ndarray, batch: int) -> jax.Array:
        return jnp.broadcast_to(jnp.asarray(src, jnp.int32),
                                (batch, len(src)))

    def _length(batch: int) -> jax.Array:
        return jnp.full((batch,), seq_len, jnp.int32)

    for i, kind in enumerate(cfg.layer_kinds):
        per, pos = divmod(i, P)
        c = jax.tree.map(lambda a: a[per], prefill_caches[pos])
        if kind == "mamba":
            h, tail = c
            out.append(KC.MambaCache(h=h, conv_tail=tail))
            continue
        if cfg.use_mla:
            ckv, kr = c  # (B,S,R), (B,1,S,rope)
            B = ckv.shape[0]
            if kind == "attn" and routing[i] == "sa":
                ring, sink = KC.sa_ring(flux, max_len)
                src = _ring_src(seq_len, sink, ring - sink, ring)
                out.append(KC.RingLatentKV(
                    ckv=_gather_ring(ckv, src, 1),
                    kr=_gather_ring(kr, src, 2),
                    positions=_positions(src, B), length=_length(B)))
            else:
                pad = _full_pad(i)
                out.append(KC.LatentKV(
                    ckv=jnp.pad(ckv, ((0, 0), (0, pad), (0, 0))),
                    kr=jnp.pad(kr, ((0, 0), (0, 0), (0, pad), (0, 0))),
                    length=_length(B)))
            continue
        k, v = c  # (B,Hkv,S,D)
        B = k.shape[0]
        if kind == "local":
            ring = min(cfg.sliding_window, max_len)
            src = _ring_src(seq_len, 0, ring, ring)
            out.append(KC.RingKV(
                k=_gather_ring(k, src, 2), v=_gather_ring(v, src, 2),
                positions=_positions(src, B), length=_length(B)))
        elif kind == "attn" and routing[i] == "sa":
            ring, sink = KC.sa_ring(flux, max_len)
            src = _ring_src(seq_len, sink, ring - sink, ring)
            out.append(KC.RingKV(
                k=_gather_ring(k, src, 2), v=_gather_ring(v, src, 2),
                positions=_positions(src, B), length=_length(B)))
        else:
            pad = _full_pad(i)
            out.append(KC.FullKV(
                k=jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))),
                v=jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))),
                length=_length(B)))
    return out


# ---------------------------------------------------------------------------
# Cache accounting: KV payload vs. bookkeeping overhead
# ---------------------------------------------------------------------------

@dataclass
class KVStats:
    """Decode-cache footprint, split the way the paper counts it:
    ``payload_bytes`` is the KV (or SSM-state) tensors the routing
    decision actually shrinks; ``overhead_bytes`` is bookkeeping
    (``positions``/``length``) that exists for every geometry alike.
    ``prefix_device_bytes``/``prefix_host_bytes`` report the
    shared-prefix snapshot store's occupancy per tier alongside —
    the store holds whole boundary states, so its bytes are neither
    payload nor overhead of any live request.

    ``payload_shard_bytes``/``overhead_shard_bytes`` are the bytes one
    device actually holds: for mesh-sharded pools the head-sharded k/v
    leaves divide by the "model" axis while replicated leaves count in
    full, so shard < global; on a single device (or a fully replicated
    tree) they equal the global figures.  The memory ledger reconciles
    against the *global* figures — exact under any mesh by
    construction (DESIGN.md §Distributed serving)."""
    payload_bytes: int
    overhead_bytes: int
    prefix_device_bytes: int = 0
    prefix_host_bytes: int = 0
    payload_shard_bytes: int = 0
    overhead_shard_bytes: int = 0

    @property
    def total_bytes(self) -> int:
        return self.payload_bytes + self.overhead_bytes


def _leaf_bytes(leaf) -> Tuple[int, int]:
    """(global, per-shard) bytes of one cache leaf.  The per-shard
    figure reads ``sharding.shard_shape`` when the leaf carries one
    (committed mesh arrays); host arrays and abstract specs fall back
    to global = shard."""
    nbytes = leaf.size * leaf.dtype.itemsize
    sharding = getattr(leaf, "sharding", None)
    if sharding is None or not hasattr(sharding, "shard_shape"):
        return nbytes, nbytes
    shard = int(np.prod(sharding.shard_shape(tuple(leaf.shape)),
                        dtype=np.int64)) if leaf.ndim else 1
    return nbytes, shard * leaf.dtype.itemsize


def kv_cache_stats(caches, prefix_store=None) -> KVStats:
    payload = overhead = payload_shard = overhead_shard = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(caches)[0]:
        name = getattr(path[-1], "name", None) if path else None
        nbytes, shard_bytes = _leaf_bytes(leaf)
        if name in KC.OVERHEAD_FIELDS:
            overhead += nbytes
            overhead_shard += shard_bytes
        else:
            payload += nbytes
            payload_shard += shard_bytes
    pd = ph = 0
    if prefix_store is not None:
        pd = prefix_store.device_bytes
        ph = prefix_store.host_bytes
    return KVStats(payload_bytes=payload, overhead_bytes=overhead,
                   prefix_device_bytes=pd, prefix_host_bytes=ph,
                   payload_shard_bytes=payload_shard,
                   overhead_shard_bytes=overhead_shard)


def kv_cache_bytes(caches) -> int:
    """KV *payload* bytes only — the quantity the paper's KV-reduction
    claim is about.  Bookkeeping arrays (``positions``, ``length``) are
    reported separately via ``kv_cache_stats``."""
    return kv_cache_stats(caches).payload_bytes


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

def _arr_sig(a) -> Optional[Tuple]:
    """Traced-array structure (shape, dtype) that keys a jit entry."""
    return None if a is None else (tuple(a.shape), str(a.dtype))


def decode_executable_key(caches, pos, n_steps: int, greedy: bool,
                          duo_layers, enc_out, rng,
                          mesh_sig: Optional[Tuple] = None) -> Tuple:
    """The full static+structural signature of one ``decode_many``
    executable.  ``ServeEngine`` and ``ContinuousScheduler`` both record
    these so the executable-count guard can compare against the jit
    cache — the pos signature matters because a slot pool decodes with
    per-slot (B,) positions while ``generate`` uses a shared scalar.
    ``mesh_sig`` (``pool_sharding.mesh_signature``) distinguishes the
    mesh-committed variants: sharding-committed inputs key separate jit
    entries, so the guard counts per-(geometry, mesh), never letting a
    mesh hide a pattern-keyed recompile."""
    return (KC.cache_geometry(caches), _arr_sig(jnp.asarray(pos)),
            n_steps, greedy, duo_layers, _arr_sig(enc_out), _arr_sig(rng),
            mesh_sig)


@dataclass
class ChunkedPrefill:
    """An in-flight route-then-stream admission (DESIGN.md §Prefill
    pipeline).

    ``step()`` processes exactly one chunk, so the continuous scheduler
    can interleave prefill chunks with decode ticks (Sarathi-style
    mixed ticks).  Step 0 is the *routing chunk*: a monolithic prefill
    over the first bucket (the Layer Router fires once per layer,
    prefix-pooled), then decode-geometry caches are allocated from the
    frozen pattern and seeded with the chunk's KV.  Every further step
    streams one bucketed chunk through ``MD.prefill_chunk`` directly
    into those caches.  After ``done``, the results live in
    ``pattern`` / ``caches`` / ``logits`` / ``p_fa``.

    Shared-prefix reuse (DESIGN.md §Prefix cache): when the engine has
    a prefix store and ``reuse`` holds, the job starts from the deepest
    matching chunk-boundary snapshot (``prefix_hit_tokens`` covered
    tokens skip straight past their chunks — no prefill work is issued
    for them) and publishes a new snapshot at every full-chunk boundary
    it streams, so the store warms as a side effect of serving.
    """
    engine: "ServeEngine"
    tokens: jax.Array                      # (B, S)
    override: Optional[Tuple[Any, ...]]
    plan: List[Tuple[int, int]]
    idx: int = 0
    dispatches: int = 0                    # compiled calls issued so far
    pattern: Optional[Tuple[Any, ...]] = None
    caches: Any = None
    logits: Optional[jax.Array] = None
    p_fa: Optional[np.ndarray] = None
    reuse: bool = True                     # participate in the prefix store
    # load-adaptive sparsity rung at admission time (serve/slo.py):
    # frozen when the job starts so a mid-prefill dial change cannot
    # split one request across two routing regimes
    sa_level: int = 0
    prefix_hit_tokens: int = 0             # prompt tokens seeded from a hit
    chunks_streamed: int = 0               # chunks actually computed
    published: int = 0                     # boundary snapshots published
    _geom: Optional[Tuple] = None

    @property
    def seq_len(self) -> int:
        return self.tokens.shape[1]

    @property
    def done(self) -> bool:
        return self.idx >= len(self.plan)

    @property
    def n_chunks(self) -> int:
        return len(self.plan)

    def step(self) -> None:
        """Process the next chunk (no-op when done)."""
        if self.done:
            return
        eng = self.engine
        start, size = self.plan[self.idx]
        chunk = self.tokens[:, start:start + size]
        if self.idx == 0:
            self._route_chunk(chunk)
        else:
            eng._stream_keys.add((self._geom, size))
            with eng._dispatch_ctx():
                self.logits, self.caches = eng._stream_chunk(
                    params=eng.params, tokens=chunk, caches=self.caches,
                    start=jnp.int32(start))
            self.caches, self.logits = eng._commit_state(
                self.caches, self.logits)
            self.dispatches += 1
        self.chunks_streamed += 1
        self.idx += 1
        eng._maybe_publish(self, start, size)

    def _route_chunk(self, chunk: jax.Array) -> None:
        eng, cfg = self.engine, self.engine.cfg
        routing_ctx, fixed = eng._routing_ctx(self.override)
        # sparsity dial: bias the hard-routing threshold toward SA at
        # this job's frozen rung.  Traced (not static), so every rung
        # shares one prefill executable; level 0 passes None and stays
        # bit-identical to the dial-free path.
        thr = (jnp.float32(eng.fa_threshold(self.sa_level))
               if self.sa_level > 0
               and routing_ctx in ("hard", "hard_prefix") else None)
        pf = eng._prefill(params=eng.params, tokens=chunk,
                          routing_ctx=routing_ctx, fixed_pattern=fixed,
                          prefix_embeddings=None, encoder_frames=None,
                          fa_threshold=thr)
        decisions = (eng.fetch(pf.routing, "routing")
                     if pf.routing is not None else None)
        self.pattern = eng._pattern(decisions, self.override)
        self.p_fa = None if pf.p_fa is None else eng.fetch(pf.p_fa, "p_fa")
        eng._record_routing(self.pattern, self.p_fa, self.sa_level)
        # geometry from abstract shapes only — the real buffers are
        # built inside the seed jit (no eager per-admission allocs)
        spec = jax.eval_shape(lambda: KC.init_decode_caches(
            cfg, self.pattern, chunk.shape[0], eng.max_len))
        self._geom = KC.cache_geometry(spec)
        eng._seed_keys.add((self._geom, chunk.shape[1]))
        self.caches = eng._seed_chunk(pf.caches, pattern=self.pattern,
                                      batch=chunk.shape[0],
                                      max_len=eng.max_len)
        self.caches, self.logits = eng._commit_state(self.caches,
                                                     pf.logits)
        self.dispatches += 2  # routing prefill + the seed insert


@dataclass
class GenerationResult:
    tokens: np.ndarray            # (B, n_steps)
    routing: Tuple[Any, ...]      # per-layer decode pattern
    msr: float                    # SA fraction over routed layers
    kv_bytes: int                 # decode-cache footprint
    p_fa: Optional[np.ndarray] = None
    dispatches: int = 0           # compiled calls issued for this request
    prefix_hit_tokens: int = 0    # prompt tokens served from a warm prefix


class DrainResult(dict):
    """``{rid: FinishedRequest}`` plus an aggregate ``summary`` dict
    (TTFT split percentiles, prefix hit accounting, and the
    KV/prefix-store occupancy split from ``kv_cache_stats``)."""

    def __init__(self, finished, summary: Dict[str, Any]):
        super().__init__(finished)
        self.summary = summary


class ServeEngine:
    """Single-model serving with flux routing.

    ``routing_override``: force a per-layer pattern (baselines /
    ablations) instead of consulting the router; entries may be "fa",
    "sa", ("duo", n_fa_kv) or None.  ``generate`` also accepts a
    per-request override.

    Decode dispatch discipline: one ``decode_many`` scan per request
    (``dispatch_count`` tracks compiled calls), one executable per
    distinct (cache geometry, n_steps, sampling mode) — two routing
    patterns with the same geometry reuse one executable, and
    ``_check_executable_guard`` raises if a pattern-keyed recompile
    ever sneaks back in.
    """

    def __init__(self, params, cfg: ModelConfig, *, max_len: int = 4096,
                 sparse_decode: bool = True, routing_override=None,
                 decode_attn=None, decode_unroll: int = 4,
                 prefill_chunk: Optional[int] = 512,
                 routing_pooling: str = "prefix",
                 prefix_cache_mb: Optional[float] = None,
                 prefix_cache_host_mb: float = 0.0,
                 slo: Optional[SLO.SLOConfig] = None,
                 telemetry: bool = False,
                 flight_recorder_ticks: int = 512,
                 fidelity_probe_every: int = 0,
                 memory_ledger: bool = False,
                 mesh=None):
        if routing_pooling not in ("prefix", "prefix_suffix"):
            raise ValueError(
                f"routing_pooling={routing_pooling!r}: expected 'prefix' "
                f"(chunk-invariant serving default) or 'prefix_suffix' "
                f"(the paper's pooling; forces the monolithic prefill)")
        # Tensor-parallel serving (DESIGN.md §Distributed serving):
        # ``mesh`` commits weights tensor-parallel (column/row per
        # launch/shardings.py) and every slot pool head-sharded on the
        # mesh "model" axis; GSPMD propagates, so the per-step
        # collectives stay O(H·D)/O(d_model) activation combines — the
        # cache never moves.  ``mesh=None`` keeps every array
        # uncommitted: bitwise + dispatch-count identical to before.
        # Parameters from ``MD.init_params_on_device(..., mesh)`` are
        # born in these shardings (the commit below is then a no-op);
        # host-side ones (checkpoints, tests) are placed here.
        self.mesh = mesh
        self._mesh_sig = PSH.mesh_signature(mesh)
        if mesh is not None:
            params = jax.device_put(
                params, SHD.param_shardings_decode_tp(params, mesh))
        self.params = params
        self.cfg = cfg
        self.max_len = max_len
        self.sparse_decode = sparse_decode
        self.routing_override = routing_override
        self.decode_unroll = decode_unroll
        # max chunk size of the chunked cache-resident prefill; None/0
        # disables it (every admission takes the monolithic fallback)
        self.prefill_chunk = int(prefill_chunk) if prefill_chunk else 0
        self.routing_pooling = routing_pooling
        # shared-prefix radix cache: snapshots at chunk boundaries,
        # device budget prefix_cache_mb (+ optional host offload tier)
        self.prefix_store = self._build_prefix_store(
            prefix_cache_mb, prefix_cache_host_mb)
        # SLO guardrails (serve/slo.py); the default config is all-off.
        # ``sa_level`` is the load-adaptive sparsity rung — 0 (neutral
        # argmax routing) unless a scheduler's LoadTracker turns it.
        self.slo = slo if slo is not None else SLO.SLOConfig()
        self.sa_level = 0
        # attribution layer (DESIGN.md §Observability): routing-fidelity
        # probes and the memory ledger.  Both default off; either implies
        # the telemetry surfaces exist since they export through the
        # registry/flight recorder.
        if fidelity_probe_every < 0:
            raise ValueError(
                f"fidelity_probe_every={fidelity_probe_every} must be "
                f">= 0 (0 disables)")
        self.fidelity_probe_every = int(fidelity_probe_every)
        self._probe_admissions = 0    # admissions seen by the probe dial
        self._params_cost_cache: Optional[Tuple[int, int]] = None
        telemetry = bool(telemetry or fidelity_probe_every or memory_ledger)
        # decision-margin drift per (layer, sa_level) rung: pure-host
        # Welford bookkeeping fed by _record_routing, so it rides any
        # telemetry-enabled engine for free
        self.margin_drift = (RT.MarginDriftTracker()
                             if telemetry else None)
        self.ledger = (TM.MemoryLedger(params_bytes=self._params_cost()[1])
                       if memory_ledger else None)
        # serving telemetry (DESIGN.md §Observability): a metrics
        # registry, a request-span tracer and a per-tick flight
        # recorder — all host-side.  Disabled (None) by default: the
        # instrumented paths reduce to ``is not None`` checks, so the
        # off state is bitwise- and executable-guard-identical to an
        # uninstrumented engine (asserted in tests/test_telemetry.py).
        if telemetry:
            self.telemetry: Optional[TM.MetricsRegistry] = \
                TM.MetricsRegistry()
            self.tracer: Optional[TR.SpanTracer] = TR.SpanTracer()
            self.flight_recorder: Optional[TM.FlightRecorder] = \
                TM.FlightRecorder(flight_recorder_ticks)
            self._register_core_metrics()
        else:
            self.telemetry = None
            self.tracer = None
            self.flight_recorder = None
        self._scheduler = None  # lazy ContinuousScheduler (submit/step)
        # optional decode-attention backend (e.g. the Pallas flash-decode
        # kernel via kernels.decode_attention.make_kernel_decode_attn);
        # installed at trace time, baked into the compiled scan.
        self.decode_attn = decode_attn
        # kernel-path accounting: the adapter logs one (hit|decline,
        # reason) per attention layer at *trace* time (lax.scan traces
        # its body once), so the first dispatch of each decode key
        # records the layer decisions and every later dispatch replays
        # them — counters move per compiled call with zero device work.
        self._decode_attn_trace: Dict[Any, Tuple] = {}
        self._decode_kernel_stats: Dict[str, Any] = {
            "dispatches": 0, "hit_layers": 0, "hit_reasons": Counter(),
            "hit_groups": Counter(), "decline_layers": Counter()}
        self.dispatch_count = 0           # compiled calls, engine lifetime
        self.host_syncs = 0               # blocking reads (``fetch``), lifetime
        self._decode_keys: set = set()    # expected decode executables
        self._stream_keys: set = set()    # expected (geometry, bucket)
        self._seed_keys: set = set()      # expected chunk-0 seeds
        self._prefill = jax.jit(partial(MD.prefill, cfg=cfg),
                                static_argnames=("routing_ctx",))
        # chunked-prefill executables: keyed by (cache geometry, chunk
        # bucket) — ``start`` is traced, so every offset of a bucket
        # shares one executable and the jit cache stays
        # O(#geometries × #buckets), guard-asserted.
        self._stream_chunk = jax.jit(partial(MD.prefill_chunk, cfg=cfg),
                                     donate_argnames=("caches",))
        self._seed_chunk = jax.jit(
            partial(seed_caches, cfg),
            static_argnames=("pattern", "batch", "max_len"))
        # repack is a long chain of tiny gathers/pads — eager dispatch
        # costs more than the math at serving rates, so compile it per
        # (pattern, seq_len).  Only the monolithic fallback runs it.
        self._repack = jax.jit(
            partial(repack_caches, cfg),
            static_argnames=("routing", "seq_len", "max_len"))
        self._decode_many = jax.jit(
            partial(MD.decode_many, cfg=cfg),
            static_argnames=("n_steps", "greedy", "duo_layers", "unroll"),
            donate_argnames=("caches",))
        # prefix-snapshot copy: one executable per cache geometry,
        # shared between publication (copy before the next chunk
        # donates the live buffers) and restore (copy so a hit never
        # hands the store's own buffers to a donating jit).  The
        # partial wrapper gives each engine its own jit cache — bare
        # ``jax.jit(MD.snapshot_state)`` would share one across
        # engines and break per-engine executable accounting.
        self._snapshot = jax.jit(partial(MD.snapshot_state))
        self._snap_keys: set = set()      # expected snapshot geometries
        self._snap_skip_warned: set = set()
        self._encode = (jax.jit(partial(MD.encode, cfg=cfg))
                        if cfg.num_encoder_layers else None)
        # routing-fidelity probe: FA attention-mass coverage of the
        # routed SA window (MD.attention_mass_coverage), one jitted
        # sweep per power-of-two prompt bucket — ``length`` is traced,
        # so the probe jit cache stays O(log max_len), guard-counted
        # like every other serving-path jit.  Constructing the jit
        # wrapper compiles nothing; with the probe dial at 0 this cache
        # stays empty (asserted by the off-path tests).
        self._coverage = jax.jit(partial(MD.attention_mass_coverage,
                                         cfg=cfg))
        self._probe_keys: set = set()     # expected probe prompt buckets

    def _build_prefix_store(self, prefix_cache_mb,
                            prefix_cache_host_mb) -> Optional[PXC.PrefixStore]:
        if not prefix_cache_mb:
            return None
        cfg = self.cfg
        if not self.prefill_chunk:
            raise ValueError(
                f"prefix_cache_mb={prefix_cache_mb:g} requires the chunked "
                f"prefill: prefix snapshots are chunk-boundary objects and "
                f"the monolithic prefill→repack path has no boundaries to "
                f"snapshot — set prefill_chunk (or drop prefix_cache_mb)")
        override = self.routing_override
        if override is not None and any(isinstance(p, tuple)
                                        for p in override):
            raise ValueError(
                f"prefix_cache_mb={prefix_cache_mb:g} with a duo "
                f"head-split routing_override: duo admissions take the "
                f"repack fallback (chunked_eligible=False), so the store "
                f"could never hold a snapshot — drop the duo override or "
                f"the prefix cache")
        budget = int(prefix_cache_mb * 2 ** 20)
        host_budget = int(prefix_cache_host_mb * 2 ** 20)
        if override is not None:
            pattern = self._pattern(None, override)
            what = "the overridden routing geometry"
        else:
            # smallest geometry the router can pick: SA rings wherever a
            # routed layer may stream — if even that snapshot overflows
            # the budget, no admission could ever publish
            can_sa = cfg.flux.enabled and cfg.flux.sa_mode == "ssa"
            pattern = tuple(
                ("sa" if can_sa else "fa") if k == "attn" else None
                for k in cfg.layer_kinds)
            what = "the smallest routed geometry"
        need = PXC.snapshot_spec_bytes(cfg, pattern, self.max_len)
        if budget < need:
            raise ValueError(
                f"prefix_cache_mb={prefix_cache_mb:g} ({budget} bytes) "
                f"cannot hold one chunk-boundary snapshot for {what} "
                f"({need} bytes at max_len={self.max_len}): raise "
                f"prefix_cache_mb to at least {need / 2 ** 20:.2f} MB or "
                f"lower max_len")
        return PXC.PrefixStore(chunk=self.prefill_chunk,
                               budget_bytes=budget,
                               host_budget_bytes=host_budget)

    # -- routing pattern ---------------------------------------------------
    def _pattern(self, decisions: Optional[np.ndarray],
                 override=None) -> Tuple[Any, ...]:
        cfg = self.cfg
        override = override if override is not None else \
            self.routing_override
        routed = list(cfg.routable_layers())
        pattern: List[Any] = [None] * cfg.num_layers
        for i, kind in enumerate(cfg.layer_kinds):
            if kind != "attn":
                continue
            if not cfg.flux.enabled:
                pattern[i] = "fa"
            elif override is not None:
                pattern[i] = override[i]
            elif decisions is None or not self.sparse_decode:
                pattern[i] = "fa"
            else:
                j = routed.index(i)
                pattern[i] = "fa" if int(decisions[j]) else "sa"
        return tuple(pattern)

    def _routing_ctx(self, override=None):
        """(routing_ctx, fixed_pattern) for an admission prefill.

        No override → hard routing, pooled per ``routing_pooling``; an
        override → the "fixed" context, so SA layers really run sparse
        attention during prefill (the paper's prefill saving) instead of
        full attention followed by a lossy ring truncation."""
        cfg = self.cfg
        override = (override if override is not None
                    else self.routing_override)
        if not (cfg.flux.enabled and cfg.routable_layers()):
            return "fa_only", None
        if override is None:
            return ("hard" if self.routing_pooling == "prefix_suffix"
                    else "hard_prefix"), None
        fixed = jnp.asarray([0 if override[i] == "sa" else 1
                             for i in range(cfg.num_layers)], jnp.int32)
        return "fixed", fixed

    # -- load-adaptive sparsity dial (serve/slo.py) -------------------------
    def set_sa_level(self, level: int) -> None:
        """Set the sparsity rung for *subsequent* admissions (running
        jobs keep the rung they started with).  Clamped to the config's
        quantized ladder, so the reachable pattern — and geometry — set
        stays finite and the executable guard keeps holding."""
        self.sa_level = max(0, min(int(level), self.slo.sa_level_max))

    def fa_threshold(self, level: Optional[int] = None) -> float:
        """FA-decision threshold at ``level`` (default: the current
        rung) on the config's ladder."""
        lv = self.sa_level if level is None else level
        return RT.sa_biased_threshold(lv, step=self.slo.sa_threshold_step,
                                      max_level=self.slo.sa_level_max)

    # -- telemetry (DESIGN.md §Observability) -------------------------------
    def _register_core_metrics(self) -> None:
        """Pre-register the always-present metrics so ``metrics_text``
        exposes a stable schema from the first scrape (gauges read 0
        until the scheduler ticks), and hook the prefix store's
        eviction events into the registry."""
        reg = self.telemetry
        reg.gauge("flux_sa_level",
                  "load-adaptive sparsity rung (0 = neutral routing)")
        reg.gauge("flux_load_pressure",
                  "LoadTracker queue-pressure signal in [0, 1]")
        reg.gauge("serve_queue_depth", "waiting requests after admission")
        reg.gauge("serve_slots_active", "resident decode slots, all pools")
        reg.gauge("serve_slots_capacity", "total decode slots, all pools")
        reg.counter("serve_ticks_total", "scheduler ticks")
        reg.counter("serve_tokens_generated_total",
                    "tokens accepted from decode chunks")
        reg.counter("serve_requests_submitted_total", "requests submitted")
        reg.counter("serve_prefill_chunks_total",
                    "prefill chunks streamed as tick work")
        reg.counter("serve_preemptions_total", "recompute preemptions")
        reg.counter("serve_dispatches_total", "compiled calls issued")
        reg.counter("serve_host_syncs_total",
                    "blocking device-to-host reads on the tick path")
        reg.counter("serve_prompt_tokens_total",
                    "prompt tokens streamed through prefill chunks")
        reg.counter("flux_sa_transitions_total",
                    "sparsity-dial rung changes, either direction")
        reg.gauge("prefix_store_device_bytes",
                  "prefix snapshot store occupancy, device tier")
        reg.gauge("prefix_store_host_bytes",
                  "prefix snapshot store occupancy, host tier")
        for status in SLO.STATUSES:
            reg.counter("serve_requests_finished_total",
                        "retired requests by terminal status",
                        status=status)
        # decode-kernel path counters (ISSUE 8: no more silent decline)
        # — pre-registered with the adapter's decline vocabulary so the
        # scrape schema is stable even before the first decode
        reg.counter("decode_kernel_hit_layers_total",
                    "attention layers served by the decode kernel, "
                    "accumulated per compiled decode call")
        for reason in DECODE_KERNEL_DECLINE_REASONS:
            reg.counter("decode_kernel_decline_layers_total",
                        "attention layers where the kernel adapter "
                        "declined and dense decode ran instead",
                        reason=reason)
        # per-layer FA/SA decision counters exist from the first scrape
        # so dashboards see every routed layer, not just the ones the
        # traffic so far happened to exercise
        for i in self.cfg.routable_layers():
            for d in ("fa", "sa"):
                reg.counter("flux_router_decisions_total",
                            "hard routing decisions at admission time",
                            layer=str(i), decision=d)
        # fidelity probes opt in per engine; pre-register their
        # histograms so the scrape schema is stable before the first
        # probe admission fires
        if self.fidelity_probe_every:
            for i in self.cfg.routable_layers():
                for d in ("fa", "sa"):
                    reg.histogram(
                        "flux_fidelity_coverage",
                        "fraction of the full-attention mass of the last "
                        "prompt token retained by the routed SA window, "
                        "per routed layer (probe admissions only)",
                        layer=str(i), decision=d)
        if self.prefix_store is not None:
            self.prefix_store.on_event = self._prefix_store_event

    def _prefix_store_event(self, event: str) -> None:
        self.telemetry.counter("prefix_store_events_total",
                               "prefix store lifecycle events",
                               event=event).inc()

    def _record_routing(self, pattern, p_fa: Optional[np.ndarray],
                        sa_level: int) -> None:
        """Count per-layer FA/SA decisions and threshold-vs-score
        margins for one admission.  Called where the routing decision
        lands on host anyway (``fetch(pf.routing)`` in the admission
        paths), so this reads already-materialized host state and never
        adds a device sync."""
        reg = self.telemetry
        if reg is None or pattern is None:
            return
        routed = self.cfg.routable_layers()
        for j, i in enumerate(routed):
            d = pattern[i]
            if d not in ("fa", "sa"):
                continue  # duo head-splits have no binary decision
            reg.counter("flux_router_decisions_total",
                        layer=str(i), decision=d).inc()
            if p_fa is not None and j < len(p_fa):
                margin = RT.decision_margin(
                    float(p_fa[j]), sa_level,
                    step=self.slo.sa_threshold_step,
                    max_level=self.slo.sa_level_max)
                reg.histogram(
                    "flux_router_margin",
                    "router p_fa minus the (possibly SA-biased) decision "
                    "threshold; positive = FA side",
                    layer=str(i)).observe(margin)
                if self.margin_drift is not None:
                    # same already-materialized host float — drift
                    # tracking is keyed by the admission's rung, so the
                    # sparsity dial gets per-rung traffic-shift signals
                    self.margin_drift.observe(i, sa_level, margin)

    def _refresh_gauges(self) -> None:
        """Point-in-time gauges from host state (scheduler occupancy,
        prefix store tiers, sparsity dial) — called per scheduler tick
        and at scrape time so ``metrics_text`` is current even between
        ticks."""
        reg = self.telemetry
        reg.gauge("flux_sa_level").set(self.sa_level)
        if self.prefix_store is not None:
            reg.gauge("prefix_store_device_bytes").set(
                self.prefix_store.device_bytes)
            reg.gauge("prefix_store_host_bytes").set(
                self.prefix_store.host_bytes)
        sched = self._scheduler
        if sched is not None:
            reg.gauge("flux_load_pressure").set(sched.load.pressure)
            reg.gauge("serve_queue_depth").set(len(sched.waiting))
            reg.gauge("serve_slots_active").set(sched.n_active())
            reg.gauge("serve_slots_capacity").set(
                sum(p.capacity for p in sched.pools.values()))
        md = self.margin_drift
        if md is not None:
            for layer, level in md.keys():
                reg.gauge(
                    "flux_router_margin_drift",
                    "recent-minus-lifetime mean router decision margin, "
                    "per (layer, sparsity rung) — nonzero means the "
                    "traffic mix shifted under a fixed dial setting",
                    layer=str(layer), sa_level=str(level)).set(
                        md.drift(layer, level))
        led = self.ledger
        snap = led.last() if led is not None else None
        if snap is not None:
            reg.gauge("serve_ledger_device_bytes",
                      "memory ledger: tracked device bytes (pools + "
                      "prefix device tier + params)").set(snap.device_bytes)
            reg.gauge("serve_ledger_pool_live_bytes",
                      "memory ledger: payload bytes in occupied slots"
                      ).set(snap.pool_live_bytes)
            reg.gauge("serve_ledger_pool_stranded_bytes",
                      "memory ledger: payload bytes in empty slots"
                      ).set(snap.pool_stranded_bytes)
            reg.gauge("serve_ledger_fragmentation_bytes",
                      "memory ledger: empty-slot bytes in pools whose "
                      "geometry matches no queued work").set(
                          snap.fragmentation_bytes)
            reg.gauge("serve_ledger_device_high_watermark_bytes",
                      "memory ledger: lifetime peak of tracked device "
                      "bytes").set(led.high_watermark)

    def metrics_text(self) -> str:
        """Current metrics as Prometheus text exposition format."""
        if self.telemetry is None:
            raise ValueError(
                "metrics_text: telemetry is disabled — construct the "
                "ServeEngine with telemetry=True (or pass --metrics-out "
                "to launch/serve.py)")
        self._refresh_gauges()
        return self.telemetry.render()

    def export_trace(self, path: str) -> None:
        """Write the request-span trace as Chrome-trace/Perfetto JSON
        (open in chrome://tracing or https://ui.perfetto.dev)."""
        if self.tracer is None:
            raise ValueError(
                "export_trace: telemetry is disabled — construct the "
                "ServeEngine with telemetry=True (or pass --trace-out "
                "to launch/serve.py)")
        self.tracer.export(path)

    # -- tensor-parallel state normalization --------------------------------
    def mesh_shape(self) -> Optional[Tuple[int, ...]]:
        """Axis sizes of the serving mesh (telemetry's TickRecord shape);
        None on the single-device path."""
        if self.mesh is None:
            return None
        return tuple(int(self.mesh.shape[a]) for a in self.mesh.axis_names)

    def _commit_state(self, caches, logits):
        """Commit (caches, logits) to the pool shardings — k/v
        head-sharded on "model", everything else replicated.  Called at
        every producer boundary of the admission pipeline (seed, stream
        chunk, prefix restore) so each consumer jit (stream, snapshot,
        slot write, decode) sees exactly ONE input sharding per
        geometry: compiler-chosen output shardings would otherwise vary
        between the fresh-prefill and warm-restore paths and split jit
        entries, breaking the O(#geometries) guard.  A no-op when the
        state already carries the target shardings, and the identity on
        the mesh=None path."""
        if self.mesh is None:
            return caches, logits
        caches = PSH.shard_pool_caches(caches, self.mesh)
        if logits is not None:
            logits = PSH.replicate(logits, self.mesh)
        return caches, logits

    # -- decode-attention backend (kernel-path accounting) -----------------
    def _dispatch_ctx(self):
        """Context around every compiled call that may trace a Pallas
        kernel: chunk streaming, ``generate``'s decode and the
        scheduler's pooled tick.  It installs the engine's
        decode-attention backend and makes the serving mesh ambient, so
        each kernel runs per shard of the heads
        (``distributed.sharding.over_heads``); every such site goes
        through it, so the ambient state always matches what each
        executable was traced under.  Donation warnings are silenced on
        the CPU backend only, where donation is a no-op: on an
        accelerator one means a cache was copied instead of updated in
        place, and stays loud."""
        stack = contextlib.ExitStack()
        if self.mesh is not None:
            stack.enter_context(jax.set_mesh(self.mesh))
        if self.decode_attn is not None:
            stack.enter_context(MD.use_decode_attn(self.decode_attn))
        stack.enter_context(warnings.catch_warnings())
        if jax.default_backend() == "cpu":
            warnings.filterwarnings("ignore", message=".*[Dd]onat.*")
        return stack

    def _note_decode_dispatch(self, key) -> None:
        """Account one decode dispatch against the kernel-path
        counters.  A dispatch that traced (jit cache miss) drains the
        adapter's trace log — one (hit|decline, reason) entry per
        attention layer — and records it under ``key``; cached
        dispatches replay the recorded decisions."""
        fn = self.decode_attn
        if fn is None:
            return
        st = self._decode_kernel_stats
        st["dispatches"] += 1
        if not hasattr(fn, "drain_log"):
            return  # legacy backend: no per-layer decision log
        fresh = fn.drain_log()
        if fresh:
            self._decode_attn_trace[key] = tuple(fresh)
        reg = self.telemetry
        for event, reason, *group in self._decode_attn_trace.get(key, ()):
            if event == "hit":
                st["hit_layers"] += 1
                st["hit_reasons"][reason] += 1
                if group:
                    st["hit_groups"][group[0]] += 1
                if reg is not None:
                    reg.counter("decode_kernel_hit_layers_total").inc()
            else:
                st["decline_layers"][reason] += 1
                if reg is not None:
                    reg.counter("decode_kernel_decline_layers_total",
                                reason=reason).inc()

    def decode_kernel_summary(self) -> Dict[str, Any]:
        """Kernel-path accounting over the engine's lifetime: compiled
        decode dispatches, and per-layer hit/decline(reason) tallies
        replayed from the adapters' trace-time decisions.  Hit reasons
        name the path: "pooled" (FullKV slot pool), "pooled_ring" (SA
        ring slot pool), "shared" (batch-synchronous ``generate``).
        ``hit_groups`` counts the hit layers by query-group size G =
        Hq/Hkv, the tile height of the pooled kernel, where the adapter
        reports it."""
        st = self._decode_kernel_stats
        return {
            "installed": self.decode_attn is not None,
            "dispatches": st["dispatches"],
            "hit_layers": st["hit_layers"],
            "hit_reasons": dict(st["hit_reasons"]),
            "hit_groups": dict(st["hit_groups"]),
            "decline_layers": dict(st["decline_layers"]),
        }

    # -- cost attribution (ledger / probes) ---------------------------------
    def _params_cost(self) -> Tuple[int, int]:
        """(parameter count, parameter bytes) — shape metadata only,
        walked once and cached (the ledger's params line reads it)."""
        pc = self._params_cost_cache
        if pc is None:
            leaves = jax.tree_util.tree_leaves(self.params)
            pc = self._params_cost_cache = (
                int(sum(l.size for l in leaves)),
                int(sum(l.size * l.dtype.itemsize for l in leaves)))
        return pc

    def fetch(self, x, what: str) -> np.ndarray:
        """Wait for ``x`` and copy it to the host: every blocking
        device-to-host read of the serving path goes through here.  It
        counts into ``host_syncs`` and runs inside a ``serve.wait`` span
        whose ``what`` names the read."""
        self.host_syncs += 1
        with TR.span("serve.wait", what=what):
            return np.asarray(x)

    def _maybe_fidelity_probe(self, tokens_1d, pattern
                              ) -> Optional[np.ndarray]:
        """Every ``fidelity_probe_every``-th admission becomes a probe
        request: one extra jitted sweep (MD.attention_mass_coverage)
        measures, per routed layer, the fraction of the FA attention
        mass of the last prompt token that the routed SA window
        retains.  The prompt pads to its power-of-two bucket with a
        traced true length — bitwise-identical coverage to the unpadded
        form, and O(log max_len) probe executables.  Probe admissions
        pay one dispatch plus a host sync; with the dial at 0 this
        method is a single int test."""
        if not self.fidelity_probe_every:
            return None
        self._probe_admissions += 1
        if (self._probe_admissions - 1) % self.fidelity_probe_every:
            return None
        routed = self.cfg.routable_layers()
        if not routed:
            return None
        toks = np.asarray(tokens_1d).reshape(-1)
        S = int(toks.size)
        if S < 1:
            return None
        bucket = 1 if S <= 1 else 1 << (S - 1).bit_length()
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :S] = toks.astype(np.int32)
        self._probe_keys.add(bucket)
        cov = self._coverage(self.params, tokens=jnp.asarray(padded),
                             length=jnp.int32(S))
        self.dispatch_count += 1
        cov = self.fetch(cov, "coverage")
        reg = self.telemetry
        if reg is not None:
            for j, i in enumerate(routed):
                if j < cov.size and pattern[i] in ("fa", "sa"):
                    reg.histogram("flux_fidelity_coverage",
                                  layer=str(i),
                                  decision=pattern[i]).observe(
                                      float(cov[j]))
        return cov

    def ledger_report(self) -> Dict[str, Any]:
        """Fresh memory-ledger snapshot reconciled against an
        independent ``kv_cache_stats`` walk of the same pools + prefix
        store.  Pool payload and prefix tiers must agree exactly; the
        ledger's overhead exceeds kv_cache_stats by exactly the
        pool-level aux buffers (logits/pos) the cache walk never sees —
        ``reconciliation`` carries the deltas so callers can assert."""
        if self.ledger is None:
            raise ValueError(
                "ledger_report: the memory ledger is disabled — construct "
                "the ServeEngine with memory_ledger=True (or pass "
                "--ledger-out to launch/serve.py)")
        sched = self._scheduler
        snap = (sched.ledger_snapshot() if sched is not None
                else self.ledger.last())
        pools = list(sched.pools.values()) if sched is not None else []
        stats = kv_cache_stats([p.caches for p in pools],
                               self.prefix_store)
        out: Dict[str, Any] = {
            "snapshot": snap.as_dict() if snap is not None else None,
            "kv_cache_stats": {
                "payload_bytes": stats.payload_bytes,
                "overhead_bytes": stats.overhead_bytes,
                "prefix_device_bytes": stats.prefix_device_bytes,
                "prefix_host_bytes": stats.prefix_host_bytes,
                # per-device bytes: < global for mesh-sharded pools
                # (k/v divide by the "model" axis), == global on one
                # device.  Reconciliation stays on the global figures.
                "payload_shard_bytes": stats.payload_shard_bytes,
                "overhead_shard_bytes": stats.overhead_shard_bytes,
            },
            "mesh": (list(self.mesh_shape())
                     if self.mesh is not None else None),
            "reconciliation": None,
            "aux_bytes": 0,
        }
        if snap is not None:
            out["reconciliation"] = snap.reconcile(
                stats.payload_bytes, stats.overhead_bytes,
                stats.prefix_device_bytes, stats.prefix_host_bytes)
            out["aux_bytes"] = sum(p.aux_bytes for p in snap.pools)
        if self.prefix_store is not None:
            out["prefix_store"] = self.prefix_store.stats().as_dict()
        return out

    def attribution_report(self) -> Dict[str, Any]:
        """Everything the attribution layer knows, JSON-ready: the
        reconciled ledger, decision-margin drift and kernel-path
        accounting.  Disabled parts report None."""
        return {
            "ledger": (self.ledger_report()
                       if self.ledger is not None else None),
            "margin_drift": (self.margin_drift.report()
                             if self.margin_drift is not None else None),
            "decode_kernel": self.decode_kernel_summary(),
            "fidelity_probe_every": self.fidelity_probe_every,
            "probe_admissions": self._probe_admissions,
        }

    # -- jit-cache bookkeeping ---------------------------------------------
    def decode_cache_size(self) -> int:
        """Number of compiled decode executables held by this engine."""
        return self._decode_many._cache_size()

    def prefill_chunk_cache_size(self) -> int:
        """Compiled stream-chunk executables held by this engine."""
        return self._stream_chunk._cache_size()

    def prefix_restore_cache_size(self) -> int:
        """Compiled snapshot copy/restore executables (O(#geometries):
        publication and restore of one geometry share the entry)."""
        return self._snapshot._cache_size()

    def _check_executable_guard(self) -> None:
        """Every serving-path jit cache must stay geometry-bounded —
        decode at O(#geometries), the chunked-prefill stream and seed at
        O(#geometries × #chunk-buckets) — never O(2^routable_layers)
        pattern-keyed entries."""
        compiled, expected = self.decode_cache_size(), len(self._decode_keys)
        if compiled > expected:
            raise RuntimeError(
                f"decode executable explosion: {compiled} compiled for "
                f"{expected} (geometry, n_steps, sampling) keys — a "
                f"routing-pattern-static argument has leaked into the "
                f"decode jit signature")
        for jitted, keys, name in (
                (self._stream_chunk, self._stream_keys, "stream-chunk"),
                (self._seed_chunk, self._seed_keys, "chunk-0 seed")):
            compiled = jitted._cache_size()
            if compiled > len(keys):
                raise RuntimeError(
                    f"{name} executable explosion: {compiled} compiled "
                    f"for {len(keys)} (geometry, chunk-bucket) keys — a "
                    f"non-bucketed chunk size or pattern-static argument "
                    f"has leaked into the chunked-prefill jit signature")
        compiled = self._snapshot._cache_size()
        if compiled > len(self._snap_keys):
            raise RuntimeError(
                f"prefix-snapshot executable explosion: {compiled} "
                f"compiled for {len(self._snap_keys)} geometry keys — "
                f"the snapshot copy/restore jit must stay O(#geometries) "
                f"(publication and restore of one geometry share an "
                f"executable); something pattern- or length-shaped has "
                f"leaked into its signature")
        compiled = self._coverage._cache_size()
        if compiled > len(self._probe_keys):
            raise RuntimeError(
                f"fidelity-probe executable explosion: {compiled} "
                f"compiled for {len(self._probe_keys)} prompt buckets — "
                f"probe prompts must pad to power-of-two buckets with a "
                f"traced length (O(log max_len) executables), never "
                f"trace per prompt length")

    # -- admission: chunked hot path --------------------------------------
    def chunked_eligible(self, seq_len: int, override=None, *,
                         prefix_embeddings=None,
                         encoder_frames=None) -> bool:
        """True when the chunked cache-resident admission can serve this
        request; False routes it to the monolithic repack fallback."""
        cfg = self.cfg
        if not self.prefill_chunk or seq_len <= 0:
            return False
        if (prefix_embeddings is not None or encoder_frames is not None
                or cfg.num_encoder_layers or cfg.num_prefix_tokens):
            return False  # modality side inputs ride the monolithic path
        override = (override if override is not None
                    else self.routing_override)
        if override is not None and any(isinstance(p, tuple)
                                        for p in override):
            return False  # duo head-splits keep the repack path
        routable = bool(cfg.flux.enabled and cfg.routable_layers())
        if routable and override is None:
            if not self.sparse_decode:
                # decisions would diverge from geometry (the ablation
                # rows where SA prefill feeds a full decode cache)
                return False
            if self.routing_pooling != "prefix":
                return False  # paper pooling needs the full sequence
            if (chunk_plan(seq_len, self.prefill_chunk)[0][1]
                    < min(cfg.flux.pool_size, seq_len)):
                return False  # first chunk can't cover the router pool
        needs_sa = routable and (override is None
                                 or any(p == "sa" for p in override))
        if needs_sa and cfg.flux.sa_mode != "ssa":
            return False  # xa/ta prefill has no ring-resident equivalent
        return True

    def start_chunked_prefill(self, tokens: jax.Array, override=None, *,
                              reuse: bool = True) -> ChunkedPrefill:
        """Begin a route-then-stream admission; the caller drives
        ``job.step()`` (the continuous scheduler interleaves steps with
        decode ticks; ``prefill_chunked`` runs them back-to-back).

        When the engine has a prefix store and ``reuse`` holds, the job
        starts from the deepest matching chunk-boundary snapshot: its
        covered chunks are skipped outright (``prefix_hit_tokens``) and
        only the uncovered suffix streams.  ``reuse=False`` opts the
        request out of both lookup and publication."""
        tokens = jnp.asarray(tokens)
        job = ChunkedPrefill(
            engine=self, tokens=tokens,
            override=(override if override is not None
                      else self.routing_override),
            plan=chunk_plan(tokens.shape[1], self.prefill_chunk),
            reuse=reuse, sa_level=self.sa_level)
        if (self.prefix_store is not None and reuse
                and tokens.shape[0] == 1
                and self.chunked_eligible(tokens.shape[1], job.override)):
            self._try_prefix_restore(job)
        return job

    def prefill_chunked(self, tokens: jax.Array, override=None, *,
                        reuse: bool = True) -> ChunkedPrefill:
        """The chunked admission run to completion.  Returns the
        finished job (``pattern``/``caches``/``logits``/``p_fa``)."""
        job = self.start_chunked_prefill(tokens, override, reuse=reuse)
        while not job.done:
            job.step()
        return job

    # -- shared-prefix snapshot reuse (DESIGN.md §Prefix cache) -------------
    def _routable(self) -> bool:
        return bool(self.cfg.flux.enabled and self.cfg.routable_layers())

    def _snap_sig(self, caches, logits) -> Tuple:
        return (KC.cache_geometry(caches), _arr_sig(logits),
                self._mesh_sig)

    def _restore_state(self, node: PXC._Node):
        """Snapshot → fresh device buffers the admission may own (and
        later donate).  Host-tier snapshots prefetch to device and are
        promoted in place (the next hit skips the transfer); either
        tier then hits the same per-geometry copy executable
        (uncommitted inputs on the single-device path; under a mesh,
        the pool shardings the whole admission pipeline is normalized
        to), so restores stay O(#geometries) (guard-asserted)."""
        snap = node.snap
        if self.mesh is not None:
            # commit to the pool shardings — the same flavor every
            # other producer boundary emits, so the snapshot copy jit
            # keeps one entry per geometry under the mesh too
            caches, logits = self._commit_state(snap.caches, snap.logits)
        else:
            # deviceless device_put: prefetches host (numpy) tiers to
            # the default device and is a no-op for device tiers —
            # either way the result is *uncommitted*, keying the same
            # jit entry
            caches, logits = jax.device_put((snap.caches, snap.logits))
        if node.on_host:
            # the prefetched copy is nobody else's buffer (the job only
            # ever receives the jit copy below) — hand it to the store
            self.prefix_store.promote(node, caches, logits)
        self._snap_keys.add(self._snap_sig(caches, logits))
        return self._snapshot(caches, logits)

    def _try_prefix_restore(self, job: ChunkedPrefill) -> None:
        """Longest-prefix-match ``job``'s prompt against the store and,
        on a hit, seed the job from the snapshot: caches/logits/pattern
        adopted, ``idx`` advanced past every covered chunk."""
        store, cfg = self.prefix_store, self.cfg
        toks = self.fetch(job.tokens[0], "prompt")
        node = store.match(toks, PXC.routing_key(job.override,
                                                 job.sa_level))
        if (node is not None and job.override is None
                and not RT.prefix_routing_reusable(
                    cfg.flux, node.depth, toks.size,
                    routable=self._routable())):
            node = None  # routing not prefix-determined for this pair
        if node is None:
            store.misses += 1
            if self.telemetry is not None:
                self._prefix_store_event("miss")
            return
        store.acquire(node)  # pin against eviction while restoring
        try:
            snap = node.snap
            job.caches, job.logits = self._restore_state(node)
        finally:
            store.release(node)
        store.hits += 1
        store.hit_tokens += snap.boundary
        if self.telemetry is not None:
            self._prefix_store_event("hit")
        job.pattern = snap.pattern
        job.p_fa = None if snap.p_fa is None else np.array(snap.p_fa)
        job._geom = KC.cache_geometry(job.caches)
        job.idx = snap.boundary // self.prefill_chunk
        job.prefix_hit_tokens = snap.boundary
        job.dispatches += 1  # the restore copy

    def _maybe_publish(self, job: ChunkedPrefill, start: int,
                       size: int) -> None:
        """Publish the boundary the job just crossed, when canonical:
        B=1, a *full*-chunk boundary (ragged ladder tails differ per
        prompt length and are never shared), and — router-driven — a
        prefix the routing decision actually transfers across."""
        store = self.prefix_store
        if (store is None or not job.reuse or job.tokens.shape[0] != 1
                or size != self.prefill_chunk
                or not self.chunked_eligible(job.seq_len, job.override)):
            return
        toks = self.fetch(job.tokens[0], "prompt")
        if self.publish_prefix(toks, start + size, job.caches, job.logits,
                               job.pattern, p_fa=job.p_fa,
                               override=job.override,
                               sa_level=job.sa_level):
            job.dispatches += 1  # the snapshot copy
            job.published += 1

    def publish_prefix(self, tokens, boundary: int, caches, logits,
                       pattern, p_fa=None, override=None,
                       sa_level: int = 0) -> bool:
        """Insert a chunk-boundary snapshot of ``tokens[:boundary]``
        into the prefix store.  Returns True iff a snapshot was
        actually copied and inserted (False: duplicate, non-transferable
        routing, or an over-budget geometry — skipped with a warning).

        Raises ``ValueError`` for states that are not chunk-boundary
        snapshots at all: publication from a repack-fallback admission
        (``chunked_eligible`` False — full-sequence repack state has no
        boundary snapshots, and ``routing_ctx="hard"`` decisions depend
        on the prompt suffix) or a boundary off the full-chunk grid."""
        store = self.prefix_store
        if store is None:
            raise ValueError(
                "publish_prefix: engine has no prefix store — construct "
                "the ServeEngine with prefix_cache_mb")
        toks = np.asarray(tokens)
        override = override if override is not None else \
            self.routing_override
        if not self.chunked_eligible(toks.size, override):
            raise ValueError(
                f"publish_prefix: this admission takes the monolithic "
                f"repack fallback (chunked_eligible=False for seq_len="
                f"{toks.size}), which has no chunk-boundary state to "
                f"snapshot — its caches are a full-sequence repack and "
                f"its routing may depend on the prompt suffix.  Serve "
                f"the request through the chunked path (prefill_chunk "
                f"set, prefix-pooled routing, no duo/modality inputs) "
                f"or skip publication for it")
        if (boundary <= 0 or boundary > toks.size
                or boundary % self.prefill_chunk):
            raise ValueError(
                f"publish_prefix: boundary={boundary} is not a full-chunk "
                f"plan boundary of a length-{toks.size} prompt (chunk="
                f"{self.prefill_chunk}) — snapshots are shareable only at "
                f"multiples of the chunk size")
        if override is None and not RT.prefix_routing_reusable(
                self.cfg.flux, boundary, toks.size,
                routable=self._routable()):
            return False  # decision pooled from tokens past the boundary
        key = PXC.routing_key(override, sa_level)
        if store.covered(toks, boundary, key):
            return False  # already published (LRU slot bumped)
        nbytes = PXC.state_bytes(caches, logits)
        if nbytes > store.budget_bytes + store.host_budget_bytes:
            geom = self._snap_sig(caches, logits)
            if geom not in self._snap_skip_warned:
                self._snap_skip_warned.add(geom)
                warnings.warn(
                    f"prefix cache: one snapshot of this routed geometry "
                    f"({nbytes} bytes) exceeds the whole store budget "
                    f"({store.budget_bytes + store.host_budget_bytes} "
                    f"bytes); skipping publication — raise "
                    f"prefix_cache_mb to cache these admissions")
            return False
        self._snap_keys.add(self._snap_sig(caches, logits))
        snap_caches, snap_logits = self._snapshot(caches, logits)
        store.insert(toks, PXC.Snapshot(
            caches=snap_caches, logits=snap_logits, pattern=pattern,
            p_fa=None if p_fa is None else np.array(p_fa),
            boundary=boundary, nbytes=nbytes), key)
        return True

    # -- admission: monolithic fallback ------------------------------------
    def prefill_route_repack(self, tokens: jax.Array, override=None, *,
                             prefix_embeddings=None, encoder_frames=None):
        """Monolithic admission FALLBACK: full-sequence prefill (router
        fires once) → per-request pattern → host-planned repack into
        decode geometry.  The hot path is ``prefill_chunked``; this
        path materializes O(S) KV at every layer and is retained only
        for what the chunked pipeline excludes (``chunked_eligible``):
        ``routing_ctx="hard"`` soft-metric runs needing the paper's
        prefix+suffix pooling / full-sequence p_fa, modality side
        inputs, duo head-split overrides, and non-ssa SA modes.
        Returns (pf, pattern, caches, seq_len)."""
        override = (override if override is not None
                    else self.routing_override)
        routing_ctx, fixed = self._routing_ctx(override)
        thr = (jnp.float32(self.fa_threshold())
               if self.sa_level > 0
               and routing_ctx in ("hard", "hard_prefix") else None)
        pf = self._prefill(params=self.params, tokens=tokens,
                           routing_ctx=routing_ctx, fixed_pattern=fixed,
                           prefix_embeddings=prefix_embeddings,
                           encoder_frames=encoder_frames,
                           fa_threshold=thr)
        decisions = (self.fetch(pf.routing, "routing")
                     if pf.routing is not None else None)
        pattern = self._pattern(decisions, override)
        if self.telemetry is not None:
            self._record_routing(
                pattern,
                None if pf.p_fa is None else self.fetch(pf.p_fa, "p_fa"),
                self.sa_level)
        seq_len = tokens.shape[1] + (prefix_embeddings.shape[1]
                                     if prefix_embeddings is not None else 0)
        if seq_len > self.max_len:
            # fail here, loudly, instead of at repack trace depth: ring
            # layers truncate long prompts structurally but full-cache
            # layers cannot hold them at all.
            off = [i for i, k in enumerate(self.cfg.layer_kinds)
                   if k == "attn" and pattern[i] != "sa"]
            if off:
                raise ValueError(
                    f"prefill_route_repack: prompt length seq_len="
                    f"{seq_len} exceeds the decode cache capacity "
                    f"max_len={self.max_len} at full-cache layer "
                    f"{off[0]}; raise the engine's max_len or truncate "
                    f"the prompt")
        caches = self._repack(pf.caches, routing=pattern,
                              seq_len=seq_len, max_len=self.max_len)
        return pf, pattern, caches, seq_len

    def generate(self, tokens: np.ndarray, n_steps: int, *,
                 prefix_embeddings=None, encoder_frames=None,
                 greedy: bool = True, rng=None,
                 routing_override=None,
                 prefix_reuse: bool = True) -> GenerationResult:
        cfg = self.cfg
        tokens = jnp.asarray(tokens)
        seq_len = tokens.shape[1] + (prefix_embeddings.shape[1]
                                     if prefix_embeddings is not None else 0)
        if seq_len > self.max_len:
            raise ValueError(
                f"generate: prompt length {seq_len} exceeds the engine's "
                f"cache capacity max_len={self.max_len}; raise max_len "
                f"or truncate the prompt")
        dispatches = 0
        enc_out = None
        if self._encode is not None:
            enc_out = self._encode(params=self.params, frames=encoder_frames)
            dispatches += 1
        prefix_hit = 0
        if self.chunked_eligible(seq_len, routing_override,
                                 prefix_embeddings=prefix_embeddings,
                                 encoder_frames=encoder_frames):
            job = self.prefill_chunked(tokens, routing_override,
                                       reuse=prefix_reuse)
            pattern, caches = job.pattern, job.caches
            logits, p_fa = job.logits, job.p_fa
            dispatches += job.dispatches
            prefix_hit = job.prefix_hit_tokens
        else:
            pf, pattern, caches, seq_len = self.prefill_route_repack(
                tokens, routing_override,
                prefix_embeddings=prefix_embeddings,
                encoder_frames=encoder_frames)
            logits = pf.logits
            p_fa = None if pf.p_fa is None else self.fetch(pf.p_fa, "p_fa")
            dispatches += 2  # prefill + the jitted repack
            # the monolithic repack emits compiler-chosen shardings
            # under a mesh; normalize so decode sees the pool flavor
            caches, logits = self._commit_state(caches, logits)
        if (seq_len + n_steps > self.max_len
                and any(isinstance(c, (KC.FullKV, KC.LatentKV))
                        for c in caches)):
            raise ValueError(
                f"generate: prompt ({seq_len}) + n_steps ({n_steps}) = "
                f"{seq_len + n_steps} exceeds the cache capacity "
                f"max_len={self.max_len}; full-cache layers would "
                f"silently clamp decode appends")
        kv_bytes = kv_cache_bytes(caches)

        greedy = bool(greedy or rng is None)
        rng = rng if rng is not None else jax.random.key(0)
        fa_heads, duo_layers = MD.routing_head_split(cfg, pattern)
        pos = jnp.int32(seq_len)
        dk = decode_executable_key(caches, pos, n_steps, greedy,
                                   duo_layers, enc_out, rng,
                                   mesh_sig=self._mesh_sig)
        self._decode_keys.add(dk)
        with self._dispatch_ctx():
            toks, _, _ = self._decode_many(
                params=self.params, logits=logits, caches=caches,
                pos=pos, rng=rng, n_steps=n_steps,
                greedy=greedy, enc_out=enc_out, fa_heads=fa_heads,
                duo_layers=duo_layers, unroll=self.decode_unroll)
        self._note_decode_dispatch(dk)
        dispatches += 1
        self.dispatch_count += dispatches
        self._check_executable_guard()
        routed = [p for p in pattern if p is not None]
        msr_val = (sum(p == "sa" for p in routed) / len(routed)
                   if routed else float("nan"))
        return GenerationResult(
            tokens=self.fetch(toks, "tokens"), routing=pattern,
            msr=msr_val, kv_bytes=kv_bytes,
            p_fa=p_fa, dispatches=dispatches,
            prefix_hit_tokens=prefix_hit)

    # -- continuous-batching (streaming) frontend ---------------------------
    def scheduler(self, **kw):
        """The engine's ``ContinuousScheduler`` (created on first use;
        kwargs configure it then — slots_per_bucket, chunk, clock)."""
        if self._scheduler is None:
            from repro.serve.scheduler import ContinuousScheduler
            kw.setdefault("slo", self.slo)
            self._scheduler = ContinuousScheduler(self, **kw)
        elif kw:
            raise ValueError(
                "scheduler already created; configure it on first call")
        return self._scheduler

    def submit(self, req: "Request") -> int:
        """Queue a request for continuous batching; returns its rid."""
        return self.scheduler().submit(req)

    def step(self):
        """One scheduling tick: admit, decode one chunk per geometry
        bucket, retire.  Returns the requests finished this tick."""
        return self.scheduler().tick()

    def drain(self):
        """Tick until every submitted request finished.  Returns a
        ``DrainResult``: the usual {rid: FinishedRequest} mapping plus
        a ``.summary`` with the TTFT split (queue vs prefill), prefix
        hit accounting, per-status counts/rates, and the
        KV/prefix-store occupancy split."""
        finished = self.scheduler().drain()
        return DrainResult(finished, self._drain_summary(finished))

    def cancel(self, rid: int) -> bool:
        """Cooperatively cancel a continuous-batching request (status
        ``cancelled``, partial tokens kept).  False when unknown,
        already finished, or no scheduler exists yet."""
        if self._scheduler is None:
            return False
        return self._scheduler.cancel(rid)

    def inject_fault(self, rid: int) -> None:
        """Chaos-engineering hook: poison request ``rid``'s resident
        decode slot with NaNs (``SlotPool.poison_slot``).  The next
        tick's non-finite sentinel retires exactly that request with
        status ``failed`` and returns the slot to the pool; sibling
        slots continue bitwise-identically (every decode op is
        row-independent).  Raises ``ValueError`` when ``rid`` is not
        resident — the hook corrupts live state, so the request must
        hold a slot (tick until admitted)."""
        if self._scheduler is None:
            raise ValueError(
                "inject_fault: no continuous scheduler exists — submit "
                "and tick the request into a decode slot first")
        self._scheduler.inject_fault(rid)

    def _drain_summary(self, finished) -> Dict[str, Any]:
        ms = [f.metrics for f in finished.values()]
        sched = self._scheduler
        pools = list(sched.pools.values()) if sched is not None else []
        stats = kv_cache_stats([p.caches for p in pools],
                               self.prefix_store)
        prompt_tokens = sum(m.prompt_len for m in ms)
        hit_tokens = sum(m.prefix_hit_tokens for m in ms)
        fid = [m.fidelity for m in ms
               if getattr(m, "fidelity", None) is not None]
        n = len(ms)
        # requests retired without a first token carry ttft = NaN —
        # percentiles are over the requests that actually served
        status_counts = Counter(f.status for f in finished.values())

        def p50(xs: List[float]) -> float:
            xs = [x for x in xs if np.isfinite(x)]
            return float(np.median(xs)) if xs else float("nan")

        return {
            "n_requests": n,
            "status_counts": {s: status_counts.get(s, 0)
                              for s in SLO.STATUSES},
            "shed_rate": (status_counts.get(SLO.STATUS_SHED, 0) / n
                          if n else 0.0),
            "timeout_rate": (status_counts.get(SLO.STATUS_TIMEOUT, 0) / n
                             if n else 0.0),
            "sa_level": self.sa_level,
            "ttft_p50_s": p50([m.ttft for m in ms]),
            "prefill_time_p50_s": p50([m.prefill_time for m in ms]),
            "slot_wait_p50_s": p50([m.slot_wait for m in ms]),
            "prompt_tokens": prompt_tokens,
            "prefix_hit_tokens": hit_tokens,
            "prefix_hit_fraction": (hit_tokens / prompt_tokens
                                    if prompt_tokens else 0.0),
            "kv_payload_bytes": stats.payload_bytes,
            "kv_overhead_bytes": stats.overhead_bytes,
            "prefix_device_bytes": stats.prefix_device_bytes,
            "prefix_host_bytes": stats.prefix_host_bytes,
            "prefix_store": (self.prefix_store.stats()
                             if self.prefix_store is not None else None),
            "decode_kernel": self.decode_kernel_summary(),
            # routing-fidelity probe aggregates (NaN/0 when the probe
            # dial is off — no request carries a fidelity then)
            "fidelity_probed": len(fid),
            "fidelity_p50": p50(fid),
            "fidelity_min": (min(fid) if fid else float("nan")),
        }


# ---------------------------------------------------------------------------
# Request frontends: batch-synchronous and continuous (streaming)
# ---------------------------------------------------------------------------

@dataclass
class Request:
    rid: int
    tokens: np.ndarray  # (S,)
    n_steps: int        # max new tokens
    eos_id: Optional[int] = None   # stop early on this token
    # higher preempts lower when continuous-batching pools fill;
    # meaningless under serve_batch (no slot contention there)
    priority: int = 0
    routing_override: Optional[Tuple[Any, ...]] = None
    # opt this request out of shared-prefix snapshot reuse — neither
    # seeded from nor published to the engine's prefix store (e.g.
    # privacy-scoped prompts that must not warm other tenants)
    prefix_reuse: bool = True
    # TTFT/total budget in seconds from submission (None = the
    # engine's ``slo.default_deadline_s``, which itself defaults to
    # none).  Expired requests retire with status ``timeout`` at the
    # next tick boundary, whether queued, mid-prefill, or mid-decode.
    deadline_s: Optional[float] = None


def _trim_eos(tokens: np.ndarray, eos_id: Optional[int]) -> np.ndarray:
    """Cut a generated stream after the first EOS (inclusive)."""
    if eos_id is None:
        return tokens
    hits = np.flatnonzero(tokens == eos_id)
    return tokens[:hits[0] + 1] if hits.size else tokens


def serve_batch_finished(engine: ServeEngine, requests: Sequence[Request],
                         clock: Callable[[], float] = time.monotonic
                         ) -> Dict[int, "FinishedRequest"]:
    """``serve_batch`` with the continuous frontend's status lifecycle:
    every request returns as a ``FinishedRequest`` whose ``status`` is
    ``ok`` or ``timeout``, so both frontends speak the same vocabulary.

    Deadlines count from the call (the batch frontend has no per-request
    arrival).  Buckets run whole: a request whose deadline expires
    before its bucket starts retires ``timeout`` with no tokens; one
    that expires while its bucket decodes keeps its tokens but is still
    marked ``timeout`` — the batch frontend cannot stop a fused scan
    mid-flight, it can only report the SLO miss honestly.  Shedding,
    preemption and fault quarantine are scheduler concepts and do not
    apply here.
    """
    from repro.serve.scheduler import FinishedRequest, RequestMetrics
    t0 = clock()

    def _deadline(r: Request) -> Optional[float]:
        d = (r.deadline_s if r.deadline_s is not None
             else engine.slo.default_deadline_s)
        if d is not None and d <= 0:
            raise ValueError(
                f"request {r.rid}: deadline_s={d} must be positive — a "
                f"non-positive deadline is expired at submission")
        return None if d is None else t0 + d

    buckets: Dict[Tuple, List[Request]] = {}
    for r in requests:
        buckets.setdefault((len(r.tokens), r.n_steps, r.routing_override,
                            r.prefix_reuse), []).append(r)
    results: Dict[int, FinishedRequest] = {}

    def _finish(r: Request, tokens: np.ndarray, status: str,
                now: float) -> None:
        m = RequestMetrics(prompt_len=len(r.tokens),
                           n_generated=len(tokens), arrival_t=t0,
                           finish_t=now)
        if len(tokens):
            m.admitted_t = t0
        results[r.rid] = FinishedRequest(
            rid=r.rid, tokens=np.asarray(tokens, np.int64),
            routing=None, metrics=m, status=status)

    for (_, n_steps, override, reuse), rs in buckets.items():
        now = clock()
        live = []
        for r in rs:
            dl = _deadline(r)
            if dl is not None and now >= dl:
                _finish(r, np.asarray([], np.int64), SLO.STATUS_TIMEOUT,
                        now)
            else:
                live.append(r)
        if not live:
            continue
        toks = np.stack([r.tokens for r in live])
        gen = engine.generate(toks, n_steps, routing_override=override,
                              prefix_reuse=reuse)
        now = clock()
        for i, r in enumerate(live):
            dl = _deadline(r)
            status = (SLO.STATUS_TIMEOUT if dl is not None and now >= dl
                      else SLO.STATUS_OK)
            _finish(r, _trim_eos(gen.tokens[i], r.eos_id), status, now)
            results[r.rid].routing = gen.routing
    return results


def serve_batch(engine: ServeEngine, requests: Sequence[Request]
                ) -> Dict[int, np.ndarray]:
    """Bucket requests by (length, n_steps, routing_override) and serve
    each bucket batched.  ``eos_id`` trims each stream host-side (the
    fused scan still decodes all n_steps — early exit is what the
    continuous frontend is for), so both frontends return the same
    tokens for the same Request.

    Layer routing is per-bucket (batch-consensus inside the model); the
    paper evaluates per-request routing at B=1 — buckets of size 1
    reproduce that exactly.

    Token-only view of ``serve_batch_finished`` — statuses (and any
    deadline expiries) are dropped; callers that care about the SLO
    lifecycle should use the finished variant directly.
    """
    return {rid: f.tokens
            for rid, f in serve_batch_finished(engine, requests).items()}
