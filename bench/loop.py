"""One run of one cell: set-up, the open-loop window, the check against
the reference.

Set-up draws the weights from the seed, builds ``ServeEngine`` and its
continuous scheduler with the configuration's serving settings (on a
cell of ``chips`` > 1, tensor-parallel over a ``(1, chips)``
``("data", "model")`` mesh of the first ``chips`` devices), and
warms every shape the cell's traffic can produce by serving warm-up
requests through the same scheduler.  The window then submits each
request when it is due and calls ``engine.step()`` until ``seconds``
have passed; every time is taken on the host at a ``step()`` return and
counted from when the request was due, not from its submission.  The
window ends at the first ``step()`` return after ``seconds``; requests
still queued or decoding then are left where they are, and the check
reads the requests the window finished.
"""
from __future__ import annotations

import gc
import math
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, model, spec, stats, traffic, work
from bench.spec import ROOT, Cell

OUT = os.path.join(ROOT, ".bench_out")


class CompileClock:
    """Backend compilations and their seconds, per phase, from JAX's
    monitoring events; persistent-cache hits and misses."""

    def __init__(self):
        self.phase = "setup"
        self.count: Dict[str, int] = {}
        self.seconds: Dict[str, float] = {}
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count[self.phase] = self.count.get(self.phase, 0) + 1
            self.seconds[self.phase] = self.seconds.get(self.phase, 0.0) + secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


@dataclass
class ReqLog:
    rid: int
    due: float                 # host clock
    prompt: np.ndarray
    n_out: int
    submit: float = math.nan
    token_t: List[float] = field(default_factory=list)
    served: Optional[np.ndarray] = None
    status: Optional[str] = None    # None: not retired by the close
    routing: Optional[Tuple[Any, ...]] = None
    prefilled: int = 0              # prompt tokens through prefill by the close


@dataclass
class Tick:
    """Work of one ``step()`` as the host saw it."""
    t0: float
    t1: float
    chunk: Optional[Tuple[int, int, int]]   # (rid, start, size)
    lives: List[int]                        # per decoded slot, after insert


@dataclass
class Run:
    """Everything a metric reader may read."""
    cell: Cell
    geometry: work.Geometry
    logs: Dict[int, ReqLog]
    ticks: List[Tick]
    window: Tuple[float, float]
    seconds: float
    setup_s: float
    compiles: Dict[str, int]
    lateness: List[float]
    device: Dict[str, Any]
    memory: Dict[str, int]
    peaks: Optional[Dict[str, float]]
    trace: Any = None           # trace.Reduced, traced runs only
    traced_ticks: List[Tick] = field(default_factory=list)
    device_id: int = 0          # the cell's first device: its plane is read

    def failed(self) -> int:
        """Requests that retired in the window other than served."""
        return sum(lg.status not in (None, "ok")
                   for lg in self.logs.values())


def _annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


def _warm_lengths(arrivals, chunk: int) -> List[int]:
    """Prompt lengths whose chunk plans hold every (first chunk, later
    chunk) size the traffic's plans hold."""
    from repro.serve.engine import chunk_plan
    firsts, later = set(), set()
    for a in arrivals:
        plan = chunk_plan(len(a.tokens), chunk)
        firsts.add(plan[0][1])
        later.update(size for _, size in plan[1:])
    out = []
    for first in sorted(firsts, reverse=True):
        rest = sorted((s for s in later if s < first or first == chunk),
                      reverse=True)
        out.append(first + sum(rest))
    covered = {s for n in out for _, s in chunk_plan(n, chunk)[1:]}
    covered |= {chunk_plan(n, chunk)[0][1] for n in out}
    if not (later | firsts) <= covered:
        raise AssertionError("warm-up misses chunk sizes "
                             f"{sorted((later | firsts) - covered)}")
    return out


class _Server:
    """Drives the engine: submits due requests, steps, records."""

    def __init__(self, engine, arrivals, t0: float, logs, ticks, lateness):
        self.engine, self.sched = engine, engine.scheduler()
        self.arrivals, self.t0, self.i = arrivals, t0, 0
        self.logs, self.ticks, self.lateness = logs, ticks, lateness
        self.seen: Dict[int, int] = {}

    def _next_chunk(self) -> Optional[Tuple[int, int, int]]:
        """The prefill chunk the next tick streams: the scheduler gives
        its one chunk per tick to the oldest waiting request whose
        prefill is not done."""
        from repro.serve.engine import chunk_plan
        for inf in sorted(self.sched.waiting,
                          key=lambda x: x.metrics.arrival_t):
            job = inf.job
            if job is None:
                start, size = chunk_plan(len(inf.req.tokens),
                                         self.engine.prefill_chunk)[0]
                return inf.req.rid, start, size
            if not job.done:
                start, size = job.plan[job.idx]
                return inf.req.rid, start, size
        return None

    def _record(self, done, t: float) -> List[int]:
        lives = []
        now_counts: Dict[int, int] = {}
        for pool in self.sched.pools.values():
            for inf in pool.active.values():
                now_counts[inf.req.rid] = len(inf.generated)
        for f in done:
            now_counts[f.rid] = len(f.tokens)
            lg = self.logs.get(f.rid)
            if lg is not None:
                lg.served, lg.status = np.asarray(f.tokens), f.status
                lg.routing = f.routing
        for rid, n in now_counts.items():
            new = n - self.seen.get(rid, 0)
            if new <= 0:
                continue
            self.seen[rid] = n
            lg = self.logs.get(rid)
            if lg is not None:
                lg.token_t.extend([t] * new)
                lives.append(len(lg.prompt) + n)
        return lives

    def pump(self, until: Callable[[float], bool]) -> None:
        from repro.serve import Request
        while True:
            now = time.perf_counter()
            if until(now):
                return
            with _annotate("bench.generator"):
                while (self.i < len(self.arrivals)
                       and self.t0 + self.arrivals[self.i].due <= now):
                    a = self.arrivals[self.i]
                    self.engine.submit(Request(rid=a.rid, tokens=a.tokens,
                                               n_steps=a.n_out))
                    lg = self.logs[a.rid]
                    lg.submit = time.perf_counter()
                    self.lateness.append(lg.submit - lg.due)
                    self.i += 1
            if self.sched.waiting or self.sched.n_active():
                chunk = self._next_chunk()
                before = self.sched.prefill_chunk_ticks
                t_a = time.perf_counter()
                with _annotate("bench.step"):
                    done = self.engine.step()
                t_b = time.perf_counter()
                with _annotate("bench.metrics"):
                    lives = self._record(done, t_b)
                    if self.sched.prefill_chunk_ticks - before != (
                            chunk is not None):
                        chunk = (-1, -1, -1)   # the guess missed: unknown
                    self.ticks.append(Tick(t_a, t_b, chunk, lives))
            else:
                with _annotate("bench.idle"):
                    due = (self.t0 + self.arrivals[self.i].due
                           if self.i < len(self.arrivals) else math.inf)
                    time.sleep(min(max(due - time.perf_counter(), 0), 0.005))

    def note_prefill(self) -> Dict[str, int]:
        """Set each request's prompt tokens through prefill so far, and
        count the queue: requests not started, in prefill, decoding."""
        jobs = {inf.req.rid: inf.job for inf in self.sched.waiting}
        queue = {"not_started": 0, "in_prefill": 0,
                 "decoding": self.sched.n_active()}
        for lg in self.logs.values():
            if lg.rid in jobs:
                job = jobs[lg.rid]
                if job is None:
                    queue["not_started"] += 1
                    lg.prefilled = 0
                else:
                    queue["in_prefill"] += 1
                    lg.prefilled = (len(lg.prompt) if job.done
                                    else job.plan[job.idx][0])
            elif not math.isnan(lg.submit):
                lg.prefilled = len(lg.prompt)
        return queue


def _warm(engine, arrivals, mix, chunk: int, vocab: int, seed: int) -> None:
    """Serve one request per needed chunk plan (two tokens each), each
    opening with the run's system prompt, so that every executable of
    the window compiles now."""
    from repro.serve import Request
    rng = np.random.default_rng([seed, 1])
    system = arrivals[0].tokens[:mix["system_prompt"]]
    for k, n in enumerate(_warm_lengths(arrivals, chunk)):
        head = system[:n]
        toks = np.concatenate([head, rng.integers(
            0, vocab, n - len(head), dtype=np.int32)])
        engine.submit(Request(rid=-(k + 1), tokens=toks, n_steps=2))
    sched = engine.scheduler()
    while sched.waiting or sched.n_active():
        engine.step()
    # Admission slices each chunk out of the prompt with an eager
    # device op, compiled per prompt length: warm every length the
    # traffic holds, as the scheduler will slice it.
    from repro.serve.engine import chunk_plan
    for n in sorted({len(a.tokens) for a in arrivals}):
        toks = jnp.asarray(np.zeros(n, np.int32))[None]
        for start, size in chunk_plan(n, chunk):
            toks[:, start:start + size].block_until_ready()


def build_engine(cell: Cell, seed: int, mesh=None):
    """(cfg, routing pattern, weights, engine with its scheduler) for the
    cell, the weights drawn from ``seed``, born sharded over ``mesh``
    and served tensor-parallel on it where one is given."""
    from repro.kernels.decode_attention import make_kernel_decode_attn
    from repro.serve import ServeEngine
    conf, point, serving = cell.config, cell.point, cell.config["serving"]
    cfg = model.model_config(conf)
    pattern = model.routing_pattern(conf, cfg.num_layers)
    params = model.make_weights(cfg, seed, mesh)
    jax.block_until_ready(params)
    engine = ServeEngine(
        params, cfg, max_len=point["max_len"],
        prefill_chunk=serving["prefill_chunk"],
        routing_override=pattern,
        decode_attn=make_kernel_decode_attn(
            block_k=serving["block_k"], min_len=2 * serving["block_k"]),
        decode_unroll=1, mesh=mesh)
    engine.scheduler(slots_per_bucket=point["slots"],
                     chunk=serving["decode_steps_per_tick"],
                     prefill_chunks_per_tick=serving[
                         "prefill_chunks_per_tick"])
    return cfg, pattern, params, engine


def serve(engine, arrivals, seconds: float, clock: CompileClock,
          trace_dir: Optional[str] = None):
    """The open-loop window.  Returns (the server, logs by rid, ticks,
    lateness, window start, window end)."""
    from bench import trace as TR
    logs: Dict[int, ReqLog] = {}
    ticks: List[Tick] = []
    lateness: List[float] = []
    if trace_dir is not None:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no per-call Python events
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    for a in arrivals:
        logs[a.rid] = ReqLog(a.rid, t0 + a.due, a.tokens, a.n_out)
    srv = _Server(engine, arrivals, t0, logs, ticks, lateness)
    end = t0 + seconds
    clock.phase = "window"
    with _annotate(TR.WINDOW):
        srv.pump(lambda now: now >= end)
    t_end = time.perf_counter()
    clock.phase = "check"
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return srv, logs, ticks, lateness, t0, t_end


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, log: Callable[[str], None] = print,
             engine_hook: Optional[Callable[[Any], None]] = None,
             control: bool = False
             ) -> Tuple[Run, Dict[str, float], Dict[str, float]]:
    """Run the cell once.  Returns (the run's record, the numbers
    compared, their limits); ``control`` adds the float8 control's
    reading (``control.py``)."""
    from repro.core import modes as M
    from bench import trace as TR
    from bench.peaks import peaks as peak_table

    clock = CompileClock()
    conf, point, serving = cell.config, cell.point, cell.config["serving"]
    devices = jax.devices()[:cell.chips]
    dev = devices[0]
    pk = peak_table(dev.device_kind) if dev.platform == "tpu" else None
    mesh = None
    if cell.chips > 1:
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(1, cell.chips)
    cfg, pattern, params, engine = build_engine(cell, seed, mesh)
    if engine_hook is not None:
        engine_hook(engine)
    arrivals = traffic.generate(cell.traffic, rate=point["rate_per_s"],
                                seconds=seconds, vocab=cfg.vocab_size,
                                seed=seed)
    trace_dir = os.path.join(OUT, "trace", cell.name) if trace else None
    with M.chunk_attention_backend("auto", block=serving["block_k"]):
        _warm(engine, arrivals, cell.traffic, serving["prefill_chunk"],
              cfg.vocab_size, seed)
        setup_s = time.perf_counter() - t_start
        srv, logs, ticks, lateness, t0, t_end = serve(
            engine, arrivals, seconds, clock, trace_dir)
    queue = srv.note_prefill()
    # the fullest of the cell's devices, against its own limit
    mem = max((d.memory_stats() or {} for d in devices),
              key=lambda m: m.get("peak_bytes_in_use", 0))
    memory = {"peak": int(mem.get("peak_bytes_in_use", 0)),
              "limit": int(mem.get("bytes_limit", 0))}
    engine.scheduler().pools.clear()
    del engine, srv
    gc.collect()

    count = getattr(spec.reference(conf), "matmul_params", None)
    geometry = work.geometry(cfg, pattern, cell.chips,
                             count(conf) if count else None)
    run = Run(cell=cell, geometry=geometry, logs=logs,
              ticks=ticks, window=(t0, t_end), seconds=seconds,
              setup_s=setup_s, compiles=dict(clock.count),
              lateness=lateness,
              device={"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices()), "chips": cell.chips},
              memory=memory, peaks=pk, device_id=dev.id)
    log(f"compiles: setup={clock.count.get('setup', 0)} "
        f"({clock.seconds.get('setup', 0.0):.1f}s) "
        f"in_window={clock.count.get('window', 0)} | persistent cache "
        f"hits={clock.hits} misses={clock.misses}")
    log(f"generator lateness: p95={stats.pct(lateness, 95) * 1e3:.3f}ms "
        f"max={max(lateness, default=0.0) * 1e3:.3f}ms "
        f"over {len(lateness)} submits")
    log("at the close: " + " ".join(f"{k}={v}" for k, v in queue.items())
        + f" finished={sum(lg.status == 'ok' for lg in logs.values())}")

    finished = [(lg.prompt, lg.served) for lg in logs.values()
                if lg.status == "ok" and lg.served is not None
                and len(lg.served)]
    picked = check.sample(finished, seed=seed, **point["check"])
    t_c = time.perf_counter()
    numbers = check.compare(params, conf, pattern, finished, picked,
                            point["max_len"], control=control)
    log(f"reference check: {len(picked)} requests, "
        f"{int(numbers['checked_tokens'])} served tokens, "
        f"{time.perf_counter() - t_c:.1f}s")
    numbers["pattern_mismatch_layers"] = float(max(
        (sum(a != b for a, b in zip(lg.routing or (), pattern))
         for lg in logs.values() if lg.status == "ok"), default=0))
    numbers["failed_requests"] = float(run.failed())
    numbers["none_checked"] = float(not picked)
    limits = dict(point["limits"], failed_requests=0, none_checked=0)
    if trace:
        t_r = time.perf_counter()
        from bench.readers import NEEDLES
        ops, spans, execs = TR.load(trace_dir, dev.id)
        run.trace = TR.reduce(ops, spans, execs, NEEDLES)
        run.traced_ticks = [t for t in ticks if t.t0 >= t0 and t.t1 <= t_end]
        idle = ", ".join(f"{k} {v:.3f}s" for k, v in sorted(
            run.trace.idle_by_host.items(), key=lambda x: -x[1]))
        log(f"trace reduced in {time.perf_counter() - t_r:.1f}s: "
            f"{len(ops)} device ops, window {run.trace.window_s:.3f}s, "
            f"busy {run.trace.busy_s:.3f}s; idle while the host was in "
            f"{idle}")
    return run, numbers, limits
