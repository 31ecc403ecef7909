"""The serving tick's spans on the profiler's clock (DESIGN.md
§Observability).

A few ticks of a tiny config are served under ``jax.profiler`` and the
recorded ``.xplane.pb`` is read back: every tick is one ``serve.tick``
span carrying its counts, its phases nest inside it, each prefill chunk
span names the chunk the scheduler planned, and the ``serve.wait``
children of a tick are as many as its ``syncs``.  ``engine.fetch`` is
the only blocking read of the tick path.  A profiler session changes
no token, dispatch or executable, and the Chrome export keeps its
event names.
"""
import glob
import json
import os
import sys
import types
import warnings

import jax
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.models import model as MD
from repro.serve import Request, ServeEngine
from repro.serve import tracing as TR
from repro.serve.engine import chunk_plan

N_REQ = 4
STEPS = 3


def _setup():
    cfg = smoke_variant(get_config("phi3-mini-3.8b"))
    return cfg, MD.init_params(jax.random.key(0), cfg)


def _prompt(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)


def _lengths():
    return [12 + 9 * i for i in range(N_REQ)]


def _serve(cfg, params, **engine_kw):
    """Serve N_REQ requests to the end, one prefill chunk a tick."""
    eng = ServeEngine(params, cfg, max_len=64, prefill_chunk=16,
                      **engine_kw)
    sched = eng.scheduler(slots_per_bucket=2, chunk=2,
                          prefill_chunks_per_tick=1)
    for i, n in enumerate(_lengths()):
        sched.submit(Request(rid=i, tokens=_prompt(cfg, n, seed=i),
                             n_steps=STEPS))
    return eng, sched, sched.drain()


def _events(trace_dir):
    """{name: [(start, end, stats)]} of the serve.* spans of a trace."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    with warnings.catch_warnings():
                        warnings.simplefilter("ignore", DeprecationWarning)
                        stats = dict(ev.stats)
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.end_ns, stats))
    return {k: sorted(v, key=lambda e: e[0]) for k, v in out.items()}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One drain under a profiler session: (engine, scheduler, result,
    serve.* events, the same drain served with no session)."""
    cfg, params = _setup()
    plain = _serve(cfg, params)
    d = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(d)
    try:
        eng, sched, res = _serve(cfg, params)
    finally:
        jax.profiler.stop_trace()
    return eng, sched, res, _events(d), plain


def _inside(ev, outer):
    return outer[0] <= ev[0] and ev[1] <= outer[1]


def _tick_of(ev, ticks):
    hits = [t for t in ticks if _inside(ev, t)]
    assert len(hits) == 1, ev
    return hits[0]


def test_one_tick_span_per_tick_with_its_counts(traced):
    eng, sched, res, evs, _ = traced
    ticks = evs["serve.tick"]
    assert len(ticks) == sched.ticks
    assert [t[2]["tick"] for t in ticks] == list(range(1, sched.ticks + 1))
    keys = {"tick", "prefill_chunks", "prompt_tokens", "decoded", "syncs",
            "dispatches"}
    for t in ticks:
        assert keys <= set(t[2]), t[2]
    assert sum(t[2]["prefill_chunks"] for t in ticks) == \
        sched.prefill_chunk_ticks
    assert sum(t[2]["prompt_tokens"] for t in ticks) == sum(_lengths())
    assert sum(t[2]["dispatches"] for t in ticks) == eng.dispatch_count
    assert sum(t[2]["syncs"] for t in ticks) == eng.host_syncs
    # every request decodes STEPS tokens two a tick: two ticks each
    assert sum(t[2]["decoded"] for t in ticks) == 2 * N_REQ
    assert all(len(f.tokens) == STEPS for f in res.values())


def test_phases_nest_inside_their_tick(traced):
    _, sched, _, evs, _ = traced
    ticks = evs["serve.tick"]
    for name in ("serve.queue", "serve.prefill", "serve.admit"):
        assert len(evs[name]) == sched.ticks, name
        assert {_tick_of(e, ticks)[2]["tick"] for e in evs[name]} == \
            set(range(1, sched.ticks + 1)), name
    for e in evs["serve.prefill_chunk"]:
        _tick_of(e, ticks)
        assert any(_inside(e, p) for p in evs["serve.prefill"])
    for e in evs["serve.decode"] + evs["serve.wait"]:
        _tick_of(e, ticks)
    # one decode span per pool decoded; the tick counts its slots
    for t in ticks:
        pools = [e for e in evs["serve.decode"] if _inside(e, t)]
        assert sum(len(str(e[2]["rids"]).split()) for e in pools) == \
            t[2]["decoded"]
    assert sum(e[2]["admitted"] for e in evs["serve.admit"]) == N_REQ


def test_prefill_chunk_spans_carry_the_planned_chunks(traced):
    _, _, _, evs, _ = traced
    got = [(e[2]["rid"], e[2]["start"], e[2]["size"])
           for e in evs["serve.prefill_chunk"]]
    # one chunk a tick, requests in arrival order
    want = [(rid, start, size) for rid, n in enumerate(_lengths())
            for start, size in chunk_plan(n, 16)]
    assert got == want


def test_wait_spans_count_the_tick_syncs(traced):
    _, _, _, evs, _ = traced
    for t in evs["serve.tick"]:
        waits = [e for e in evs["serve.wait"] if _inside(e, t)]
        assert len(waits) == t[2]["syncs"], t[2]
    assert {e[2]["what"] for e in evs["serve.wait"]} == {
        "routing", "p_fa", "tokens", "finite"}


def test_decode_spans_name_the_pool_and_its_requests(traced):
    _, _, res, evs, _ = traced
    seen = {}
    for e in evs["serve.decode"]:
        assert isinstance(e[2]["pool"], int)
        for rid in str(e[2]["rids"]).split():
            seen[int(rid)] = seen.get(int(rid), 0) + 1
    assert seen == {rid: 2 for rid in res}


def test_profiler_session_changes_no_token_dispatch_or_executable(traced):
    eng, _, res, _, (eng0, _, res0) = traced
    assert set(res0) == set(res)
    for rid in res0:
        assert np.array_equal(res0[rid].tokens, res[rid].tokens), rid
    assert eng0.dispatch_count == eng.dispatch_count
    assert eng0.host_syncs == eng.host_syncs
    assert eng0.decode_cache_size() == eng.decode_cache_size()
    assert eng0.prefill_chunk_cache_size() == eng.prefill_chunk_cache_size()
    assert eng0._decode_keys == eng._decode_keys


def _count_reads(monkeypatch):
    """Count np.asarray of a jax.Array called from the serving modules;
    returns the list every such call appends to."""
    calls = []

    def asarray(x, *a, **kw):
        if isinstance(x, jax.Array):
            calls.append(sys._getframe(1).f_code.co_name)
        return np.asarray(x, *a, **kw)

    proxy = types.SimpleNamespace(**{k: getattr(np, k) for k in dir(np)
                                     if not k.startswith("__")})
    proxy.asarray = asarray
    for name, mod in list(sys.modules.items()):
        if name.startswith("repro.serve.") and getattr(mod, "np", None) is np:
            monkeypatch.setattr(mod, "np", proxy)
    return calls


def test_fetch_is_the_only_blocking_read_of_the_tick(monkeypatch):
    """Reads known per tick: a routing chunk reads the router's
    decisions and p_fa, a later chunk reads nothing, a pool's decode
    reads its tokens and its finite sentinel."""
    cfg, params = _setup()
    eng = ServeEngine(params, cfg, max_len=64, prefill_chunk=16)
    sched = eng.scheduler(slots_per_bucket=2, chunk=2,
                          prefill_chunks_per_tick=1)
    sched.submit(Request(rid=0, tokens=_prompt(cfg, 40, 0), n_steps=6))
    calls = _count_reads(monkeypatch)
    # the routing chunk; a chunk; the last chunk, the admission and a
    # first decode; two more decodes of two steps
    expected = [2, 0, 2, 2, 2]
    got = []
    while sched.waiting or sched.n_active():
        s0, c0 = eng.host_syncs, len(calls)
        sched.tick()
        got.append(eng.host_syncs - s0)
        assert set(calls[c0:]) <= {"fetch"}, calls[c0:]
        assert len(calls) - c0 == got[-1]
    assert got == expected


def test_host_counters_reach_the_registry():
    cfg, params = _setup()
    eng, sched, _ = _serve(cfg, params, telemetry=True)
    reg = eng.telemetry
    assert reg.counter("serve_host_syncs_total").value == eng.host_syncs
    assert reg.counter("serve_prompt_tokens_total").value == sum(_lengths())


def test_chrome_export_keeps_its_event_names(tmp_path):
    cfg, params = _setup()
    eng, sched, res = _serve(cfg, params, telemetry=True)
    path = tmp_path / "trace.json"
    eng.export_trace(str(path))
    obj = json.loads(path.read_text())
    TR.validate_trace(obj)
    xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    ticks = [e for e in xs if e["pid"] == TR.PID_SCHEDULER
             and e["tid"] == 0]
    assert {e["name"] for e in ticks} == {"tick"}
    assert [e["args"]["tick"] for e in ticks] == \
        list(range(1, sched.ticks + 1))
    for e in ticks:
        assert {"prefill_chunks", "dispatches", "syncs"} <= set(e["args"])
    decodes = {e["name"] for e in xs if e["pid"] == TR.PID_SCHEDULER
               and e["tid"] == 1}
    assert decodes and all(n.startswith("decode g") for n in decodes)
    chunks = [e for e in xs if e["name"] == "prefill_chunk"]
    assert len(chunks) == sched.prefill_chunk_ticks
    assert {e["pid"] for e in chunks} == {TR.PID_REQUESTS}
    assert {e["tid"] for e in chunks} == set(res)
    assert all(e["cat"] == "phase" and "args" not in e for e in chunks)


def test_span_handle_writes_counts_to_the_chrome_event():
    clock = iter([1.0, 1.5]).__next__
    tracer = TR.SpanTracer()
    n0 = len(tracer.events)
    with TR.span("serve.x", tracer, event=("x", TR.PID_SCHEDULER, 0,
                                           "serve", {"a": 1}),
                 clock=clock, rid=3) as sp:
        sp.set(b=2)
    ev, = tracer.events[n0:]
    assert (ev["name"], ev["ts"], ev["dur"]) == ("x", 1e6, 0.5e6)
    assert ev["args"] == {"a": 1, "b": 2}
    # no tracer: the profiler annotation alone, and nothing recorded
    with TR.span("serve.x", None, event=("x", 3, 0, "serve", None),
                 rid=3) as sp:
        sp.set(b=2)
    assert sp.counts == {"b": 2}
    assert len(tracer.events) == n0 + 1
