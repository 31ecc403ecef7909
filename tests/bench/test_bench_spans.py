"""The scheduler layer's metrics from the program's `serve.*` spans:
idle inside ticks, host time less waits, syncs per tick."""
import time

import jax
import jax.numpy as jnp
import pytest

import bench_tiny
from bench import loop
from bench import run as R
from bench import spans as S
from bench import trace as TR


def _span(name, a, b, **stats):
    return S.HostSpan(name, a, b, stats)


def _synthetic(with_ticks=True):
    host = [_span(TR.WINDOW, 0.0, 10.0),
            _span("bench.step", 0.9, 3.1), _span("bench.idle", 3.1, 3.9),
            _span("bench.step", 3.9, 8.1)]
    if with_ticks:
        host += [_span(S.TICK, 1.0, 3.0, syncs=2),
                 _span("serve.decode", 1.8, 2.9, pool=0, rids="1 2"),
                 _span(S.WAIT, 2.0, 2.5, what="tokens"),
                 _span(S.TICK, 4.0, 8.0, syncs=3),
                 _span(S.WAIT, 5.0, 6.0, what="routing"),
                 _span(S.WAIT, 7.0, 7.5, what="tokens")]
    ops = [(0.5, 1.5), (2.2, 2.8), (5.0, 6.5), (6.0, 7.0), (12.0, 13.0)]
    return S.from_events(host, ops)


def test_idle_inside_ticks():
    sp = _synthetic()
    # busy [1, 1.5] and [2.2, 2.8] in the first tick, [5, 7] in the second
    assert sp.busy_in(1.0, 3.0) == pytest.approx(1.1)
    assert sp.busy_in(4.0, 8.0) == pytest.approx(2.0)
    assert S.tick_idle_share(sp) == pytest.approx(100 * (0.9 + 2.0) / 6.0)


def test_host_time_is_the_tick_less_its_waits():
    # (2 - 0.5) and (4 - 1.5) seconds
    assert S.tick_host_ms(_synthetic()) == pytest.approx(2000.0)


def test_syncs_per_tick_is_their_mean():
    assert S.host_syncs_per_tick(_synthetic()) == pytest.approx(2.5)


def test_no_tick_span_reads_none():
    sp = _synthetic(with_ticks=False)
    assert S.tick_idle_share(sp) is None
    assert S.tick_host_ms(sp) is None
    assert S.host_syncs_per_tick(sp) is None
    for f in (S.tick_idle_share, S.tick_host_ms, S.host_syncs_per_tick):
        assert f(None) is None
    assert S.from_events([_span("bench.step", 0.0, 1.0)], []) is None


def test_ticks_are_clipped_to_the_window():
    host = [_span(TR.WINDOW, 2.0, 10.0), _span(S.TICK, 1.0, 4.0, syncs=4),
            _span(S.TICK, 11.0, 12.0, syncs=9)]
    sp = S.from_events(host, [(1.0, 3.0)])
    assert [(t.start, t.end) for t in sp.ticks()] == [(2.0, 4.0)]
    assert S.tick_idle_share(sp) == pytest.approx(50.0)
    assert S.host_syncs_per_tick(sp) == pytest.approx(4.0)


def test_idle_by_innermost_span_adds_up_to_the_window_idle():
    sp = _synthetic()
    idle = S.idle_by_span(sp)
    # busy: [0.5, 1.5] from 0.5 on, [2.2, 2.8], [5, 7] → 3.6 s of 10
    assert sum(idle.values()) == pytest.approx(10.0 - 3.6)
    assert idle["bench.idle"] == pytest.approx(0.8)
    assert idle[S.WAIT] == pytest.approx(0.2 + 0.0 + 0.5)
    assert idle["serve.decode"] == pytest.approx(0.2 + 0.1)
    assert idle[S.TICK] == pytest.approx(0.3 + 0.1 + 1.0 + 0.5)
    assert idle[TR.WINDOW] == pytest.approx(0.5 + 1.9)


def test_innermost_pieces_tile_nested_spans():
    spans = [_span("a", 0.0, 10.0), _span("b", 1.0, 4.0),
             _span("c", 2.0, 3.0), _span("d", 5.0, 6.0)]
    assert S.innermost(spans) == [
        (0.0, 1.0, "a"), (1.0, 2.0, "b"), (2.0, 3.0, "c"), (3.0, 4.0, "b"),
        (4.0, 5.0, "a"), (5.0, 6.0, "d"), (6.0, 10.0, "a")]


def test_union_merges_overlaps():
    import numpy as np
    got = S.union(np.array([3.0, 0.0, 1.0, 6.0]),
                  np.array([4.0, 2.0, 1.5, 7.0]))
    assert got.tolist() == [[0.0, 2.0], [3.0, 4.0], [6.0, 7.0]]


def test_recorded_trace_without_serve_spans_reads_none(tmp_path):
    """A trace of a program with no `serve.*` spans (the harness's own
    spans only) loads, and every scheduler metric reads None."""
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(TR.WINDOW):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    sp = S.load(str(tmp_path))
    assert sp is not None and len(sp.busy)
    assert S.tick_idle_share(sp) is None
    assert S.host_syncs_per_tick(sp) is None


def test_traced_tiny_run_reports_the_scheduler_metrics():
    """The whole reader path on a CPU trace of the tiny cell: the three
    metrics are in the result line, and the window's idle splits by
    span into what the device trace's idle share says."""
    cell = bench_tiny.tiny_cell(name="phi3-spans")
    run, numbers, limits = loop.run_cell(
        cell, seed=bench_tiny.SEED, seconds=1.5, trace=True,
        t_start=time.perf_counter(), log=lambda s: None)
    out = R.result_line(run, numbers, limits, True, log=lambda s: None)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert 0.0 <= m["tick_idle_share"] <= 100.0
    assert m["tick_host_ms"] > 0
    # a tick reads its pool's tokens and sentinel, plus the routing
    # reads of a request's first chunk
    assert 1.0 <= m["host_syncs_per_tick"] <= 4.0
    sp = S.of_run(run)
    rep = S.report(sp)
    assert rep["ticks"] == len(run.traced_ticks)
    assert sum(rep["idle_by_span_s"].values()) == pytest.approx(
        rep["idle_s"])
    assert rep["idle_s"] / rep["window_s"] == pytest.approx(
        run.trace.idle_share, abs=0.01)
