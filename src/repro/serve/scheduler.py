"""Continuous-batching admission/step scheduler (DESIGN.md §Scheduler,
§Robustness & SLO).

The engine's ``generate`` serves one request end-to-end; ``serve_batch``
buckets by exact (length, n_steps) and runs buckets to completion —
mixed-length traffic serializes.  This scheduler instead keeps a
persistent decode batch that requests join and leave per step:

  admit   — stream a waiting request's prompt through the chunked
            cache-resident prefill at B=1 (the Layer Router fires once
            per request, on the first chunk), then pack its decode
            caches into a free slot of the pool matching its *cache
            geometry*.  Prefill chunks are SCHEDULABLE TICK WORK: at
            most ``prefill_chunks_per_tick`` chunks run per tick,
            interleaved with the decode chunks below (Sarathi-style
            mixed ticks), so a long prompt cannot stall the resident
            batch and TTFT of running requests stays fair under load.
            Geometry-bucketed admission is the Flux-specific twist: the
            decode executable is keyed by geometry (PR 1), so mixing
            geometries in one pool would force recompiles — grouping
            by geometry preserves the O(#geometries) guarantee.
            Requests ``chunked_eligible`` excludes (duo overrides,
            non-ssa SA) admit through the monolithic repack fallback.
  step    — per tick, run ONE compiled ``decode_many`` chunk (default
            8 steps) for every pool with active slots: chunked scans,
            not run-to-completion, so new arrivals wait at most one
            chunk before joining.
  retire  — finished slots (EOS / max_new_tokens) are freed; their
            rows are overwritten by the next admission.
  preempt — when a pool is full, an arrival with strictly higher
            priority evicts the lowest-priority slot; the victim is
            re-queued and later re-prefilled over prompt + tokens
            generated so far (recompute preemption — the standard
            trade of prefill FLOPs for pool memory).

On top of that sits the SLO/robustness layer (serve/slo.py): every
request retires exactly once with an explicit ``status`` — ``ok``,
``timeout`` (deadline expired, queued or resident), ``shed`` (bounded
queue rejected it), ``cancelled`` (cooperative ``cancel``), or
``failed`` (non-finite decode state quarantined) — and overload walks
a degradation ladder (shed → budgeted preemption → SA-biased routing)
instead of falling off a cliff.  All guardrails default OFF; a
default ``SLOConfig`` reproduces the unguarded scheduler bit-for-bit.

Decoding is greedy: pooled categorical sampling could not reproduce
the B=1 sampling stream anyway, and greedy pooled decode is *bitwise*
equal to sequential ``generate`` (asserted in tests) because every op
on the decode path is row-independent.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve import kv_cache as KC
from repro.serve import slo as SLO
from repro.serve import telemetry as TM
from repro.serve import tracing as TR
from repro.serve.engine import (KVStats, _trim_eos, decode_executable_key,
                                kv_cache_stats)
from repro.serve.slots import SlotPool


@dataclass
class RequestMetrics:
    """Per-request serving metrics (seconds, ``clock`` domain)."""
    prompt_len: int = 0
    n_generated: int = 0
    arrival_t: float = 0.0
    admitted_t: Optional[float] = None   # first admission
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    preemptions: int = 0
    # queue-time vs prefill-time split: [prefill_start_t, prefill_done_t]
    # brackets the chunked prefill of the admission that finally landed
    # (reset on preemption), so a TTFT regression is attributable to
    # waiting vs prefilling.
    prefill_start_t: Optional[float] = None
    prefill_done_t: Optional[float] = None
    # decode-cache footprint at admission (payload/overhead split)
    kv_stats: Optional[KVStats] = None
    # prompt tokens seeded from a warm prefix snapshot (the admission
    # that landed): these tokens issued NO prefill chunks, so a TTFT
    # win is attributable to reuse vs queueing via the prefill split
    prefix_hit_tokens: int = 0
    # routing-fidelity probe results (only for sampled admissions when
    # the engine runs with fidelity_probe_every=N; None = not probed):
    # mean attention-mass coverage across routed layers, and the worst
    # coverage among layers the router sent down the SA path — the
    # number that quantifies what sparse attention actually discarded
    fidelity: Optional[float] = None
    fidelity_sa_min: Optional[float] = None

    @property
    def queue_delay(self) -> float:
        return (self.admitted_t or self.arrival_t) - self.arrival_t

    @property
    def prefill_time(self) -> float:
        """Wall-clock spent streaming this request's prefill chunks."""
        if self.prefill_start_t is None or self.prefill_done_t is None:
            return 0.0
        return self.prefill_done_t - self.prefill_start_t

    @property
    def slot_wait(self) -> float:
        """Queue delay net of prefill: time spent purely waiting (for a
        tick's prefill budget or a free slot)."""
        return max(self.queue_delay - self.prefill_time, 0.0)

    @property
    def ttft(self) -> float:
        """Time to first token, from arrival.  NaN for a request that
        never produced one (shed, cancelled, or expired before its
        first decode chunk) — partial lifecycles must not read as a
        zero-latency first token in drain summaries."""
        if self.first_token_t is None:
            return float("nan")
        return self.first_token_t - self.arrival_t

    @property
    def decode_tps(self) -> float:
        if self.finish_t is None or self.admitted_t is None:
            return float("nan")
        dt = self.finish_t - self.admitted_t
        return self.n_generated / dt if dt > 0 else float("inf")


@dataclass
class FinishedRequest:
    rid: int
    tokens: np.ndarray           # (n_generated,)
    # pattern of the final admission; None when the request retired
    # before ever routing (shed / expired / cancelled in queue)
    routing: Optional[Tuple[Any, ...]]
    metrics: RequestMetrics
    status: str = SLO.STATUS_OK  # one of slo.STATUSES


@dataclass
class _InFlight:
    """Host-side record of a submitted request."""
    req: Any                     # serve.Request
    metrics: RequestMetrics
    generated: List[int] = field(default_factory=list)
    pattern: Optional[Tuple[Any, ...]] = None
    pool_key: Optional[Tuple] = None
    slot: int = -1
    # absolute expiry time in the clock domain (None = no deadline)
    deadline_t: Optional[float] = None
    # in-flight chunked prefill (engine.ChunkedPrefill); advanced by the
    # tick's prefill budget, packed into a slot once done.  A finished
    # job whose bucket is full simply waits — its caches are already
    # decode-geometry, nothing is recomputed.
    job: Optional[Any] = None
    # geometry bucket seen at the last failed MONOLITHIC admission —
    # lets the scheduler skip re-prefilling a fallback request whose
    # bucket is still full (tokens don't change while waiting, so the
    # routing is stable)
    cached_key: Optional[Tuple] = None


class ContinuousScheduler:
    """Slot-pool continuous batching over a ``ServeEngine``.

    ``slots_per_bucket``: capacity of each geometry bucket's pool.
    ``chunk``: decode steps per tick per pool — the scheduling quantum.
    ``prefill_chunks_per_tick``: prefill chunks streamed per tick across
    all in-flight admissions — the prefill scheduling quantum.
    ``clock``: injectable time source (tests pass a virtual clock).
    ``slo``: guardrail config (serve/slo.py); defaults to all-off.
    """

    def __init__(self, engine, *, slots_per_bucket: int = 4,
                 chunk: int = 8, prefill_chunks_per_tick: int = 1,
                 clock: Callable[[], float] = time.monotonic,
                 slo: Optional[SLO.SLOConfig] = None):
        if engine.cfg.num_encoder_layers or engine.cfg.num_prefix_tokens:
            raise ValueError(
                "continuous batching supports decoder-only text requests; "
                "encoder/prefix modalities carry per-request side inputs "
                "the slot pool does not hold yet")
        if slots_per_bucket < 1:
            raise ValueError(
                f"slots_per_bucket={slots_per_bucket} must be >= 1: a "
                f"zero-capacity pool can never admit, so every request "
                f"would wait forever (drain would spin to its progress "
                f"guard instead of serving)")
        if chunk < 1:
            raise ValueError(
                f"chunk={chunk} must be >= 1 decode step per tick: a "
                f"zero-step scan generates no tokens and no request can "
                f"ever finish")
        if prefill_chunks_per_tick < 1:
            raise ValueError(
                f"prefill_chunks_per_tick={prefill_chunks_per_tick} must "
                f"be >= 1: with a zero budget a chunked-eligible request "
                f"can never admit (its prefill job never advances).  To "
                f"disable mixed ticks, build the engine with "
                f"prefill_chunk=None instead")
        self.engine = engine
        self.slots_per_bucket = int(slots_per_bucket)
        self.chunk = int(chunk)
        self.prefill_chunks_per_tick = int(prefill_chunks_per_tick)
        self.clock = clock
        self.slo = slo if slo is not None else SLO.SLOConfig()
        # one source of truth for the sparsity ladder: the engine's
        # dial (generate() + chunked admissions) follows this config
        engine.slo = self.slo
        # register with the engine so ledger_report / attribution_report
        # see this scheduler's pools even when it was constructed
        # directly rather than via engine.scheduler()
        engine._scheduler = self
        self.load = SLO.LoadTracker(self.slo)
        self.waiting: List[_InFlight] = []
        self.pools: Dict[Tuple, SlotPool] = {}
        self.finished: List[FinishedRequest] = []
        self.closed = False           # set by drain(); submit then raises
        self._announce: List[FinishedRequest] = []  # retired since last tick
        self._rng = jax.random.key(0)
        self.ticks = 0
        self.tokens_generated = 0
        self.prefill_chunk_ticks = 0  # prefill chunks streamed, lifetime
        # a stable small-int id per geometry bucket for span, trace and
        # recorder labels; telemetry bookkeeping (engine.telemetry is
        # None ⇒ never read): non-ok lifecycle events since the last
        # tick and the LoadTracker.transitions watermark for the delta
        # counter
        self._tm_events: List[str] = []
        self._tm_pool_ids: Dict[Tuple, int] = {}
        self._tm_transitions = 0
        # prefix-cache (hits, misses) watermark and the last sa_level
        # seen, for per-tick deltas / transition events in TickRecord
        self._tm_prefix: Tuple[int, int] = (0, 0)
        self._tm_sa_level = engine.sa_level

    # -- submission --------------------------------------------------------
    def submit(self, req) -> int:
        """Queue a request (``serve.Request``); returns its rid.

        A bounded queue (``slo.max_queue``) may retire the arrival — or
        a lower-priority waiter — immediately with status ``shed``;
        the retirement is announced by the next ``tick`` and appears in
        ``drain`` like any other terminal state.
        """
        if self.closed:
            raise ValueError(
                f"submit after drain: request {req.rid} would queue on a "
                f"drained scheduler that no longer ticks, and would "
                f"silently never be served — create a new scheduler (or "
                f"submit before draining)")
        if len(req.tokens) > self.engine.max_len:
            raise ValueError(
                f"request {req.rid}: prompt length {len(req.tokens)} "
                f"exceeds the engine's cache capacity max_len="
                f"{self.engine.max_len}; raise max_len or truncate the "
                f"prompt")
        need = len(req.tokens) + req.n_steps
        if need > self.engine.max_len:
            raise ValueError(
                f"request {req.rid}: prompt ({len(req.tokens)}) + n_steps "
                f"({req.n_steps}) = {need} exceeds the engine's cache "
                f"capacity max_len={self.engine.max_len}; slot-pool rows "
                f"past capacity would silently drop KV writes (and a "
                f"preemption-recompute would crash mid-drain)")
        deadline = getattr(req, "deadline_s", None)
        if deadline is not None and deadline <= 0:
            raise ValueError(
                f"request {req.rid}: deadline_s={deadline} must be "
                f"positive — a non-positive deadline is expired at "
                f"submission and can never be served")
        if deadline is None:
            deadline = self.slo.default_deadline_s
        now = self.clock()
        inf = _InFlight(req=req, metrics=RequestMetrics(
            prompt_len=len(req.tokens), arrival_t=now),
            deadline_t=(now + deadline) if deadline is not None else None)
        eng = self.engine
        if eng.telemetry is not None:
            eng.telemetry.counter("serve_requests_submitted_total").inc()
        if eng.tracer is not None:
            eng.tracer.name_thread(TR.PID_REQUESTS, req.rid,
                                   f"req{req.rid}", sort_index=req.rid)
            eng.tracer.instant(
                "submit", TR.PID_REQUESTS, req.rid, now,
                args={"prompt_len": len(req.tokens),
                      "n_steps": req.n_steps,
                      "priority": getattr(req, "priority", 0)})
        if (self.slo.max_queue is not None
                and len(self.waiting) >= self.slo.max_queue):
            victim = self._shed_victim(inf)
            if victim is not inf:
                self.waiting.remove(victim)
                self.waiting.append(inf)
            self._retire(victim, SLO.STATUS_SHED, now)
            return req.rid
        self.waiting.append(inf)
        return req.rid

    def _shed_victim(self, inf: _InFlight) -> _InFlight:
        """Pick who the over-bound queue rejects: the arrival itself
        (``reject_newest``) or the lowest-priority waiter when the
        arrival strictly outranks it (``drop_lowest_priority``; ties
        shed the arrival, so equal-priority waiters keep FIFO order)."""
        if self.slo.shed_policy == SLO.SHED_REJECT_NEWEST:
            return inf
        victim = min(self.waiting,
                     key=lambda w: (w.req.priority, -w.metrics.arrival_t))
        return victim if victim.req.priority < inf.req.priority else inf

    # -- terminal transitions ----------------------------------------------
    def _retire(self, inf: _InFlight, status: str, now: float, *,
                pool: Optional[SlotPool] = None,
                slot: Optional[int] = None) -> FinishedRequest:
        """The single terminal transition: every request leaves the
        scheduler through here exactly once, with an explicit status
        and whatever tokens it generated before retiring.  Frees the
        decode slot when the request was resident."""
        m = inf.metrics
        m.finish_t = now
        m.n_generated = len(inf.generated)
        inf.job = None
        if pool is not None and slot is not None:
            pool.active.pop(slot)
            pool.free.append(slot)
            inf.slot, inf.pool_key = -1, None
        f = FinishedRequest(rid=inf.req.rid,
                            tokens=np.asarray(inf.generated, np.int64),
                            routing=inf.pattern, metrics=m, status=status)
        self.finished.append(f)
        self._announce.append(f)
        if self.engine.telemetry is not None:
            self._tm_retire(f, now)
        return f

    def _tm_retire(self, f: FinishedRequest, now: float) -> None:
        """Telemetry for one terminal transition: status counter,
        latency histograms, and the request's lifetime span with its
        queue/prefill/decode phase sub-spans (all from RequestMetrics
        timestamps already taken — no extra clock reads)."""
        eng = self.engine
        m = f.metrics
        if f.status != SLO.STATUS_OK:
            self._tm_events.append(f"{f.status}:{f.rid}")
        reg = eng.telemetry
        reg.counter("serve_requests_finished_total", status=f.status).inc()
        if m.first_token_t is not None:
            reg.histogram("serve_ttft_seconds",
                          "time to first token, from arrival"
                          ).observe(m.ttft)
        if m.admitted_t is not None:
            reg.histogram("serve_queue_delay_seconds",
                          "arrival to (final) admission"
                          ).observe(m.queue_delay)
        if m.prefill_time > 0:
            reg.histogram("serve_prefill_seconds",
                          "wall clock streaming the landed admission's "
                          "prefill chunks").observe(m.prefill_time)
        tracer = eng.tracer
        if tracer is None:
            return
        rid = f.rid
        tracer.name_thread(TR.PID_REQUESTS, rid, f"req{rid}",
                           sort_index=rid)
        span_args = {"status": f.status, "prompt_len": m.prompt_len,
                     "n_generated": m.n_generated,
                     "preemptions": m.preemptions,
                     "prefix_hit_tokens": m.prefix_hit_tokens}
        if m.fidelity is not None:
            span_args["fidelity"] = round(m.fidelity, 6)
            if m.fidelity_sa_min is not None:
                span_args["fidelity_sa_min"] = round(m.fidelity_sa_min, 6)
        tracer.complete(
            f"req{rid}", TR.PID_REQUESTS, rid, m.arrival_t, now,
            args=span_args)
        if m.admitted_t is not None:
            tracer.complete("queue", TR.PID_REQUESTS, rid,
                            m.arrival_t, m.admitted_t, cat="phase")
            tracer.complete("decode", TR.PID_REQUESTS, rid,
                            m.admitted_t, now, cat="phase")
        if m.prefill_start_t is not None and m.prefill_done_t is not None:
            tracer.complete("prefill", TR.PID_REQUESTS, rid,
                            m.prefill_start_t, m.prefill_done_t,
                            cat="phase")
        tracer.instant(f"retire:{f.status}", TR.PID_REQUESTS, rid, now)

    def cancel(self, rid: int) -> bool:
        """Cooperative cancellation: retire ``rid`` with status
        ``cancelled`` (partial tokens kept).  A resident request leaves
        at the current tick boundary — its slot frees immediately and
        is overwritten by the next admission.  Returns False when the
        rid is unknown or already finished."""
        now = self.clock()
        for inf in self.waiting:
            if inf.req.rid == rid:
                self.waiting.remove(inf)
                self._retire(inf, SLO.STATUS_CANCELLED, now)
                return True
        for pool in self.pools.values():
            for slot, inf in list(pool.active.items()):
                if inf.req.rid == rid:
                    self._retire(inf, SLO.STATUS_CANCELLED, now,
                                 pool=pool, slot=slot)
                    return True
        return False

    def inject_fault(self, rid: int) -> None:
        """Chaos hook (see ``ServeEngine.inject_fault``): poison the
        resident decode state of ``rid`` with NaNs.  The next decode
        chunk's non-finite sentinel quarantines exactly that slot
        (status ``failed``); sibling slots must stay bitwise identical
        to an unfaulted run (chaos-tested)."""
        for pool in self.pools.values():
            for slot, inf in pool.active.items():
                if inf.req.rid == rid:
                    pool.poison_slot(slot)
                    return
        raise ValueError(
            f"inject_fault: request {rid} is not resident in any decode "
            f"slot (still waiting, already finished, or unknown) — the "
            f"fault hook poisons live slot state, so admit the request "
            f"first (tick until it holds a slot)")

    def _expire(self, now: float) -> None:
        """Retire everything past its deadline — queued (possibly
        mid-prefill: the in-flight job is simply dropped) and resident
        alike.  Cooperative by construction: expiry is checked at tick
        boundaries, so a resident request finishes its current decode
        chunk and keeps the tokens generated before the boundary."""
        keep = []
        for inf in self.waiting:
            if inf.deadline_t is not None and now >= inf.deadline_t:
                self._retire(inf, SLO.STATUS_TIMEOUT, now)
            else:
                keep.append(inf)
        self.waiting = keep
        for pool in self.pools.values():
            for slot, inf in list(pool.active.items()):
                if inf.deadline_t is not None and now >= inf.deadline_t:
                    self._retire(inf, SLO.STATUS_TIMEOUT, now,
                                 pool=pool, slot=slot)

    # -- priorities: aging + preemption budget ------------------------------
    def _eff_priority(self, inf: _InFlight, now: float) -> float:
        """Admission priority with anti-starvation aging: a waiter gains
        one priority unit per ``aging_s`` seconds, so a much-preempted
        victim eventually outranks fresh arrivals for free slots.
        Aging is deliberately *admission-only* — ``_preempt`` compares
        raw priorities, so two aged requests can never enter a
        mutual-eviction ping-pong."""
        if self.slo.aging_s is None:
            return float(inf.req.priority)
        return (inf.req.priority
                + (now - inf.metrics.arrival_t) / self.slo.aging_s)

    def _evictable(self, inf: _InFlight) -> bool:
        """Preemption budget: once a request has been recompute-preempted
        ``slo.preemption_budget`` times it becomes non-evictable, so a
        preemption storm ends in its admission, not a livelock of
        re-prefills."""
        budget = self.slo.preemption_budget
        return budget is None or inf.metrics.preemptions < budget

    # -- admission ---------------------------------------------------------
    def _prefill_tokens(self, inf: _InFlight) -> np.ndarray:
        """Prompt plus tokens generated before a preemption: recompute
        preemption replays the request's own history through prefill."""
        if not inf.generated:
            return np.asarray(inf.req.tokens)
        return np.concatenate([np.asarray(inf.req.tokens),
                               np.asarray(inf.generated, np.int32)])

    def _has_victim(self, pool: SlotPool, priority: int) -> bool:
        return any(v.req.priority < priority and self._evictable(v)
                   for v in pool.active.values())

    def _prefill_work(self, pending: List[_InFlight]) -> int:
        """Stream up to ``prefill_chunks_per_tick`` chunks across the
        waiting requests' admission jobs, priority-then-arrival order —
        prefill is tick work on equal footing with decode chunks.
        Returns the prompt tokens streamed."""
        eng = self.engine
        budget = self.prefill_chunks_per_tick
        tokens_streamed = 0
        for inf in pending:
            if budget <= 0:
                break
            if inf.job is None:
                tokens = self._prefill_tokens(inf)
                if not eng.chunked_eligible(
                        len(tokens),
                        getattr(inf.req, "routing_override", None)):
                    continue  # monolithic fallback admits in _admit
                inf.job = eng.start_chunked_prefill(
                    jnp.asarray(tokens)[None],
                    getattr(inf.req, "routing_override", None),
                    reuse=getattr(inf.req, "prefix_reuse", True))
                # clamp to the prompt: a preemption-recompute replays
                # prompt+generated, and its hit boundary may cover
                # tokens this request generated itself — those are not
                # "prompt tokens served warm"
                inf.metrics.prefix_hit_tokens = min(
                    inf.job.prefix_hit_tokens, inf.metrics.prompt_len)
                inf.metrics.prefill_start_t = self.clock()
            while budget > 0 and not inf.job.done:
                rid = inf.req.rid
                start, size = inf.job.plan[inf.job.idx]
                with TR.span("serve.prefill_chunk", eng.tracer,
                             event=("prefill_chunk", TR.PID_REQUESTS, rid,
                                    "phase", None),
                             clock=self.clock, rid=rid, start=start,
                             size=size):
                    inf.job.step()
                tokens_streamed += size
                self.prefill_chunk_ticks += 1
                budget -= 1
                if eng.telemetry is not None:
                    eng.telemetry.counter("serve_prefill_chunks_total").inc()
            if inf.job.done and inf.metrics.prefill_done_t is None:
                inf.metrics.prefill_done_t = self.clock()
        return tokens_streamed

    def _admit(self, inf: _InFlight) -> bool:
        eng = self.engine
        if inf.job is not None:
            # chunked admission: pack only once the stream finished
            if not inf.job.done:
                return False
            pattern, caches = inf.job.pattern, inf.job.caches
            logits, seq_len = inf.job.logits, inf.job.seq_len
        elif eng.chunked_eligible(len(self._prefill_tokens(inf)),
                                  getattr(inf.req, "routing_override",
                                          None)):
            # chunked-eligible but this tick's prefill budget ran out
            # before its job started — wait, don't fall back
            return False
        else:
            if inf.cached_key is not None:
                known = self.pools.get(inf.cached_key)
                if (known is not None and not known.free
                        and not self._has_victim(known, inf.req.priority)):
                    return False  # bucket still full — skip the re-prefill
            tokens = self._prefill_tokens(inf)
            pf, pattern, caches, seq_len = eng.prefill_route_repack(
                jnp.asarray(tokens)[None],
                getattr(inf.req, "routing_override", None))
            logits = pf.logits
            eng.dispatch_count += 2  # prefill + the jitted repack
        if any(isinstance(p, tuple) for p in pattern):
            raise ValueError(
                "duo head-split patterns carry traced per-layer state the "
                "slot pool does not thread yet; serve them via generate()")
        key = KC.slot_geometry(caches)
        pool = self.pools.get(key)
        if pool is None:
            pool = SlotPool.create(eng.cfg, pattern, self.slots_per_bucket,
                                   eng.max_len, logits, mesh=eng.mesh)
            if KC.slot_geometry(pool.caches) != key:
                raise AssertionError(
                    "init_decode_caches geometry diverged from "
                    "admission cache geometry for one pattern")
            self.pools[key] = pool
        if pool.free:
            slot = pool.free.pop()
        else:
            slot = self._preempt(pool, inf.req.priority)
            if slot is None:
                inf.cached_key = key
                return False  # bucket full of equal/higher priority work
        now = self.clock()
        if inf.metrics.admitted_t is None:
            inf.metrics.admitted_t = now
        inf.metrics.kv_stats = kv_cache_stats(caches)
        inf.pattern, inf.pool_key, inf.slot = pattern, key, slot
        inf.cached_key = None
        pool.patterns_served.add(pattern)
        pool.write(slot, caches, logits, seq_len)
        pool.active[slot] = inf
        if eng.fidelity_probe_every:
            cov = eng._maybe_fidelity_probe(self._prefill_tokens(inf),
                                            pattern)
            if cov is not None and cov.size:
                inf.metrics.fidelity = float(np.mean(cov))
                sa = [float(cov[j]) for j, i in
                      enumerate(eng.cfg.routable_layers())
                      if j < cov.size and pattern[i] == "sa"]
                if sa:
                    inf.metrics.fidelity_sa_min = min(sa)
        if inf.job is not None:
            eng.dispatch_count += inf.job.dispatches
            inf.job = None
        return True

    def _preempt(self, pool: SlotPool, priority: int) -> Optional[int]:
        """Evict the lowest-priority *evictable* active slot if it is
        strictly below ``priority``; the victim re-queues for recompute
        admission.  Budget-exhausted slots (``_evictable`` False) are
        skipped — they already paid ``slo.preemption_budget`` recompute
        prefills and now run to completion."""
        cands = [(s, v) for s, v in pool.active.items()
                 if self._evictable(v)]
        if not cands:
            return None
        slot, victim = min(
            cands, key=lambda kv: (kv[1].req.priority,
                                   -kv[1].metrics.arrival_t))
        if victim.req.priority >= priority:
            return None
        pool.active.pop(slot)
        victim.metrics.preemptions += 1
        victim.slot, victim.pool_key = -1, None
        victim.cached_key = None  # its tokens grew; routing may change
        victim.job = None         # recompute prefill over prompt+generated
        # re-bracket the prefill split around the admission that lands
        victim.metrics.prefill_start_t = None
        victim.metrics.prefill_done_t = None
        victim.metrics.prefix_hit_tokens = 0
        self.waiting.append(victim)
        eng = self.engine
        if eng.telemetry is not None:
            eng.telemetry.counter("serve_preemptions_total").inc()
            self._tm_events.append(f"preempt:{victim.req.rid}")
        if eng.tracer is not None:
            eng.tracer.instant("preempt", TR.PID_REQUESTS,
                               victim.req.rid, self.clock(),
                               args={"by_priority": priority})
        return slot

    # -- one scheduling tick -----------------------------------------------
    def tick(self) -> List[FinishedRequest]:
        """Expire deadlines, adjust the sparsity dial, stream prefill
        chunks, admit finished admissions, decode one chunk per bucket,
        retire finished slots, quarantine non-finite ones.  Returns
        every request that retired since the last tick (including
        submission-time sheds).

        The tick is the span ``serve.tick``; each phase is a child span
        (``serve.queue``, ``serve.prefill``, ``serve.admit``, one
        ``serve.decode`` per pool) and every blocking read a
        ``serve.wait`` (``engine.fetch``).  At its end the tick span
        carries its counts: prefill chunks, prompt tokens, slots
        decoded, host syncs and compiled calls."""
        eng = self.engine
        self.ticks += 1
        with TR.span("serve.tick", eng.tracer,
                     event=("tick", TR.PID_SCHEDULER, 0, "serve", None),
                     clock=self.clock) as tick_span:
            now = self.clock()
            d0, p0 = eng.dispatch_count, self.prefill_chunk_ticks
            s0, tok0 = eng.host_syncs, self.tokens_generated
            with TR.span("serve.queue", waiting=len(self.waiting)):
                self._expire(now)
                if self.slo.adaptive_sparsity:
                    cap = sum(p.capacity for p in self.pools.values())
                    eng.set_sa_level(self.load.observe(
                        len(self.waiting), cap or self.slots_per_bucket))
                # admit in (aged) priority order, oldest first within a
                # priority.  _admit may re-queue preemption victims onto
                # self.waiting, so iterate a snapshot and let victims
                # wait for the next tick.
                pending = sorted(self.waiting,
                                 key=lambda i: (-self._eff_priority(i, now),
                                                i.metrics.arrival_t))
            with TR.span("serve.prefill"):
                prompt_tokens = self._prefill_work(pending)
            with TR.span("serve.admit") as admit_span:
                self.waiting = []
                n_admitted = 0
                for inf in pending:
                    if self._admit(inf):
                        n_admitted += 1
                    else:
                        self.waiting.append(inf)
                admit_span.set(admitted=n_admitted)
            decoded = 0
            for key, pool in self.pools.items():
                if pool.active:
                    decoded += len(pool.active)
                    self._decode_pool(key, pool)
            eng._check_executable_guard()
            counts = dict(tick=self.ticks,
                          prefill_chunks=self.prefill_chunk_ticks - p0,
                          prompt_tokens=prompt_tokens, decoded=decoded,
                          syncs=eng.host_syncs - s0,
                          dispatches=eng.dispatch_count - d0)
            tick_span.set(**counts)
            if eng.telemetry is not None:
                self._tm_tick(tok0, counts)
        done, self._announce = self._announce, []
        return done

    def _decode_pool(self, key: Tuple, pool: SlotPool) -> None:
        """One compiled ``decode_many`` chunk for every slot of
        ``pool``, its tokens and non-finite sentinel read back, finished
        slots retired and non-finite ones quarantined."""
        eng = self.engine
        pi = self._tm_pool_ids.setdefault(key, len(self._tm_pool_ids))
        rids = " ".join(str(pool.active[s].req.rid)
                        for s in sorted(pool.active))
        tracer = eng.tracer
        t_decode = self.clock() if tracer is not None else 0.0
        with TR.span("serve.decode", tracer,
                     event=(f"decode g{pi}", TR.PID_SCHEDULER, 1, "serve",
                            {"batch": len(pool.active),
                             "chunk": self.chunk}),
                     clock=self.clock, pool=pi, rids=rids):
            dk = decode_executable_key(pool.caches, pool.pos, self.chunk,
                                       True, None, None, self._rng,
                                       mesh_sig=eng._mesh_sig)
            eng._decode_keys.add(dk)
            with eng._dispatch_ctx():
                # the engine's decode backend and mesh for the pooled
                # scan, same trace-time protocol as ``generate``
                toks, logits, caches = eng._decode_many(
                    params=eng.params, logits=pool.logits,
                    caches=pool.caches, pos=pool.pos, rng=self._rng,
                    n_steps=self.chunk, greedy=True, enc_out=None,
                    fa_heads=None, duo_layers=None,
                    unroll=eng.decode_unroll)
            eng._note_decode_dispatch(dk)
            eng.dispatch_count += 1
            if eng.mesh is not None:
                # pin the decode outputs back to the pool shardings so
                # the next tick's inputs key the SAME executable even
                # if the compiler chose different output shardings
                # (no-op copy when they already match)
                caches, logits = eng._commit_state(caches, logits)
            pool.logits, pool.caches = logits, caches
            pool.advance(self.chunk)
            toks_np = eng.fetch(toks, "tokens")  # (capacity, chunk)
            # non-finite sentinel: one reduced (capacity,) bool per tick.
            # Fault isolation is slot-granular — a poisoned row retires
            # as ``failed`` (its garbage chunk discarded) while sibling
            # rows proceed untouched; every decode op is row-independent
            # so their streams are bitwise those of an unfaulted run.
            finite = eng.fetch(
                jnp.all(jnp.isfinite(pool.logits), axis=-1), "finite")
            now = self.clock()
            if tracer is not None:
                # residency spans for the slots this chunk decoded; the
                # timestamp pair brackets dispatch→host-sync, taken
                # around the token read that happens anyway
                for slot, res in pool.active.items():
                    tid = pi * 1000 + slot
                    tracer.name_thread(TR.PID_SLOTS, tid,
                                       f"g{pi}/slot{slot}", sort_index=tid)
                    tracer.complete(f"rid{res.req.rid}", TR.PID_SLOTS,
                                    tid, t_decode, now, cat="slot")
            for slot in sorted(pool.active):
                inf = pool.active[slot]
                if not finite[slot]:
                    self._retire(inf, SLO.STATUS_FAILED, now,
                                 pool=pool, slot=slot)
                    continue
                if not inf.generated:
                    inf.metrics.first_token_t = now
                take = min(self.chunk,
                           inf.req.n_steps - len(inf.generated))
                eos = getattr(inf.req, "eos_id", None)
                new = _trim_eos(toks_np[slot, :take], eos).tolist()
                eos_hit = len(new) < take or (new and new[-1] == eos)
                inf.generated.extend(new)
                self.tokens_generated += len(new)
                if eos_hit or len(inf.generated) >= inf.req.n_steps:
                    self._retire(inf, SLO.STATUS_OK, now,
                                 pool=pool, slot=slot)

    # -- memory ledger ------------------------------------------------------
    def _ledger_entries(self) -> List[TM.PoolLedgerEntry]:
        """One ledger row per slot pool, from static byte figures the
        pools computed at create() — pure host arithmetic, no device
        reads.  ``queued_match`` marks pools whose geometry matches
        some waiting request whose routing is already known (a finished
        or in-flight chunked job, or a cached monolithic-fallback key);
        empty slots in pools matching NO queued work are *fragmented*
        bytes — capacity stranded on geometries the queue doesn't
        currently want.  Waiters that have not routed yet have no
        geometry to match and deliberately don't count."""
        queued = set()
        for inf in self.waiting:
            if inf.job is not None and inf.job.caches is not None:
                queued.add(KC.slot_geometry(inf.job.caches))
            elif inf.cached_key is not None:
                queued.add(inf.cached_key)
        entries = []
        for key, pool in self.pools.items():
            pid = self._tm_pool_ids.setdefault(key, len(self._tm_pool_ids))
            entries.append(TM.PoolLedgerEntry(
                pool=f"g{pid}", capacity=pool.capacity,
                occupied=len(pool.active),
                slot_payload_bytes=pool.slot_payload_bytes,
                slot_overhead_bytes=pool.slot_overhead_bytes,
                aux_bytes=pool.aux_bytes,
                queued_match=key in queued))
        return entries

    def ledger_snapshot(self) -> Optional[TM.LedgerSnapshot]:
        """Append the current device-memory picture to the engine's
        ledger and return it (None when the ledger is disabled)."""
        eng = self.engine
        led = eng.ledger
        if led is None:
            return None
        store = eng.prefix_store
        return led.update(
            t=self.clock(), tick=self.ticks,
            pools=self._ledger_entries(),
            prefix_device_bytes=(store.device_bytes
                                 if store is not None else 0),
            prefix_host_bytes=(store.host_bytes
                               if store is not None else 0))

    def _tm_tick(self, tok0: int, counts: Dict[str, int]) -> None:
        """End-of-tick telemetry: delta counters, gauge refresh, the
        scheduler-track counter samples, and this tick's flight-recorder
        record.  ``counts`` are the tick span's.  Everything read here
        is host state the tick already materialized."""
        eng = self.engine
        now = self.clock()
        reg = eng.telemetry
        cap = sum(p.capacity for p in self.pools.values())
        reg.counter("serve_ticks_total").inc()
        reg.counter("serve_tokens_generated_total").inc(
            self.tokens_generated - tok0)
        reg.counter("serve_dispatches_total").inc(counts["dispatches"])
        reg.counter("serve_host_syncs_total").inc(counts["syncs"])
        reg.counter("serve_prompt_tokens_total").inc(counts["prompt_tokens"])
        reg.counter("flux_sa_transitions_total",
                    "sparsity-dial rung changes, either direction").inc(
            self.load.transitions - self._tm_transitions)
        self._tm_transitions = self.load.transitions
        # sparsity-rung transition events: the flight recorder's tick
        # stream shows exactly when (and in which direction) the dial
        # moved, next to the queue/batch state that drove it
        if eng.sa_level != self._tm_sa_level:
            self._tm_events.append(
                f"sa_level:{self._tm_sa_level}->{eng.sa_level}")
            self._tm_sa_level = eng.sa_level
        store = eng.prefix_store
        hits = misses = 0
        if store is not None:
            hits = store.hits - self._tm_prefix[0]
            misses = store.misses - self._tm_prefix[1]
            self._tm_prefix = (store.hits, store.misses)
        # snapshot the ledger BEFORE the gauge refresh so the exported
        # ledger gauges describe this tick, not the previous one
        snap = self.ledger_snapshot() if eng.ledger is not None else None
        eng._refresh_gauges()
        tracer = eng.tracer
        if tracer is not None:
            tracer.name_thread(TR.PID_SCHEDULER, 0, "ticks", sort_index=0)
            tracer.name_thread(TR.PID_SCHEDULER, 1, "decode", sort_index=1)
            tracer.counter("queue_depth", now,
                           {"waiting": len(self.waiting)})
            tracer.counter("slots", now,
                           {"active": self.n_active(), "capacity": cap})
            tracer.counter("sparsity", now,
                           {"sa_level": eng.sa_level,
                            "pressure": self.load.pressure})
            if snap is not None:
                # memory timeline: Perfetto step-plots the ledger tiers
                tracer.counter("ledger_bytes", now,
                               {"device": snap.device_bytes,
                                "pool_live": snap.pool_live_bytes,
                                "fragmentation": snap.fragmentation_bytes})
        fr = eng.flight_recorder
        if fr is not None:
            batch = {
                f"g{self._tm_pool_ids.setdefault(k, len(self._tm_pool_ids))}":
                p.occupancy() for k, p in self.pools.items()}
            fr.record(TM.TickRecord(
                tick=self.ticks, t=now,
                queue_depth=len(self.waiting),
                n_active=self.n_active(), capacity=cap,
                batch_by_geometry=batch,
                prefill_chunks=counts["prefill_chunks"],
                dispatch_delta=counts["dispatches"],
                sa_level=eng.sa_level, pressure=self.load.pressure,
                prefix_device_bytes=(store.device_bytes
                                     if store is not None else 0),
                prefix_host_bytes=(store.host_bytes
                                   if store is not None else 0),
                prefix_hits=hits, prefix_misses=misses,
                ledger_device_bytes=(snap.device_bytes
                                     if snap is not None else 0),
                ledger_fragmentation_bytes=(snap.fragmentation_bytes
                                            if snap is not None else 0),
                mesh=eng.mesh_shape(),
                events=tuple(self._tm_events)))
        self._tm_events = []

    def drain(self) -> Dict[int, FinishedRequest]:
        """Tick until every submitted request has retired (finished,
        shed, expired, cancelled, or quarantined), then close the
        scheduler: further ``submit`` calls raise instead of queueing
        on a scheduler nothing will ever tick again."""
        guard = 0
        while self.waiting or any(p.active for p in self.pools.values()):
            before = (self.tokens_generated, self.n_active(),
                      len(self.finished), self.prefill_chunk_ticks)
            self.tick()
            progressed = before != (self.tokens_generated, self.n_active(),
                                    len(self.finished),
                                    self.prefill_chunk_ticks)
            guard = 0 if progressed else guard + 1
            if guard > 10_000:
                raise RuntimeError(
                    "scheduler made no progress (no tokens, admissions or "
                    "completions) for 10k ticks — a request can neither "
                    "finish nor admit (check slots_per_bucket and "
                    "priorities)")
        self.closed = True
        return {f.rid: f for f in self.finished}

    # -- introspection ------------------------------------------------------
    def n_active(self) -> int:
        return sum(len(p.active) for p in self.pools.values())

    def n_geometries(self) -> int:
        return len(self.pools)
