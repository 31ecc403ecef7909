"""Trace reduction: busy/idle, time per op and executable, named gaps."""
import glob
import os

import pytest

import bench_tiny  # noqa: F401  (puts the repo root on sys.path)
from bench import trace as TR


def test_reduce_synthetic_trace():
    spans = [TR.Span("bench.window", 10.0, 20.0),
             TR.Span("bench.step", 10.0, 14.0),
             TR.Span("bench.metrics", 14.0, 15.0),
             TR.Span("bench.idle", 15.0, 18.0),
             TR.Span("bench.step", 18.0, 20.0)]
    execs = [TR.Op("decode#1", "decode#1", 9.0, 12.5),
             TR.Op("prefill#2", "prefill#2", 18.5, 19.0)]
    kernel = 'custom-call(), custom_call_target="tpu_custom_call"'
    ops = [TR.Op("%while.1", "decode#1", 9.0, 12.0),    # clipped at 10
           TR.Op("%k.2", "decode#1", 11.0, 11.5, "%k.2 = " + kernel),
           TR.Op("%f.3", "decode#1", 12.0, 12.5),
           TR.Op("%copy.3", "prefill#2", 18.5, 19.0),
           TR.Op("%copy.3", "prefill#2", 25.0, 26.0)]  # outside
    red = TR.reduce(ops, spans, execs, needles=("tpu_custom_call",))
    assert red.window_s == pytest.approx(10.0)
    assert red.busy_s == pytest.approx(2.5 + 0.5)
    assert red.idle_share == pytest.approx(0.7)
    # the kernel is nested in the loop: the loop's self time excludes it
    assert red.op_self[("decode#1", "%while.1")] == pytest.approx(1.5)
    assert red.op_self[("decode#1", "%k.2")] == pytest.approx(0.5)
    assert red.op_self[("prefill#2", "%copy.3")] == pytest.approx(0.5)
    assert red.op_module == pytest.approx(
        {("decode#1", "tpu_custom_call"): 0.5})
    assert red.modules == pytest.approx({"decode#1": 2.5, "prefill#2": 0.5})
    assert [m for m, _, _ in red.execs] == ["decode#1", "prefill#2"]
    # gaps 12.5-18.5 (midpoint 15.5: idle) and 19-20 (step)
    assert red.gaps[0] == ("bench.idle", pytest.approx(6.0))
    assert red.gaps[1] == ("bench.step", pytest.approx(1.0))
    assert sum(red.idle_by_host.values()) == pytest.approx(7.0)


def test_reduce_needs_one_window():
    with pytest.raises(ValueError, match="bench.window"):
        TR.reduce([], [TR.Span("bench.step", 0.0, 1.0)])


def test_names_as_a_tpu_trace_gives_them():
    assert TR.module_name("jit__unknown(17188307128140714569)") == \
        "_unknown#17188307128140714569"
    assert TR.module_name("jit_prefill_chunk") == "prefill_chunk"
    assert TR.short_name("%fusion.3 = bf16[8]{0} fusion(%p), kind=kLoop") \
        == "%fusion.3"


def test_ops_join_the_executable_run_that_holds_them():
    execs = [TR.Op("a#1", "a#1", 0.0, 1.0), TR.Op("b#2", "b#2", 2.0, 3.0)]
    ops = [TR.Op("%x", "", 0.5, 0.6), TR.Op("%y", "", 2.1, 2.2),
           TR.Op("%z", "", 1.5, 1.6)]
    got = {o.name: o.module for o in TR._by_containment(ops, execs)}
    assert got == {"%x": "a#1", "%y": "b#2", "%z": ""}


def test_recorded_trace(tmp_path):
    """A trace recorded here (CPU backend) loads and reduces: the jitted
    op is found inside the window and the window is mostly idle."""
    import time
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(TR.WINDOW):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.idle"):
            time.sleep(0.05)
    jax.profiler.stop_trace()
    assert glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)
    ops, spans, execs = TR.load(str(tmp_path))
    red = TR.reduce(ops, spans, execs)
    assert 0.05 <= red.window_s < 5.0
    assert 0.0 < red.busy_s < red.window_s
    assert red.idle_share > 0.5
    assert any(name == "bench.idle" for name, _ in red.gaps[:1])
