"""The harness loop at a tiny size on the CPU: set-up, open-loop window
through submit/step, the check against the reference and the result
line; and the command's refusal off the TPU."""
import json
import math
import time
from functools import partial

import jax
import numpy as np
import pytest

import bench_tiny
from bench import loop
from bench import run as R


def _run(hook=None, trace=False, rate=None):
    cell = bench_tiny.tiny_cell()
    if rate is not None:
        cell.point["rate_per_s"] = rate
    run, numbers, limits = loop.run_cell(
        cell, seed=bench_tiny.SEED, seconds=1.5, trace=trace,
        t_start=time.perf_counter(), log=lambda s: None, engine_hook=hook)
    return run, numbers, limits, R.result_line(run, numbers, limits, trace,
                                               log=lambda s: None)


def test_sound_run_is_correct_and_line_keeps_the_contract():
    run, numbers, limits, out = _run()
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 12        # round(8 / s × 1.5 s)
    assert set(out["metrics"]) == {"itl_p95_ms", "total_tok_s",
                                   "output_tok_s", "setup_s"}
    for m in out["metrics"].values():
        assert m["value"] is not None and m["value"] > 0
    assert out["device"]["platform"] == "cpu"
    assert out["checks"]["logit_gap"]["limit"] == limits["logit_gap"]
    assert numbers["checked_tokens"] >= 1
    assert run.compiles.get("window", 0) == 0   # warm-up covered every shape
    json.loads(json.dumps(out))
    # finished requests were served whole, each submitted when due and
    # answered after it
    done = [lg for lg in run.logs.values() if lg.status == "ok"]
    assert done and numbers["none_checked"] == 0
    for lg in done:
        assert len(lg.token_t) == lg.n_out
        assert lg.token_t[0] >= lg.submit >= lg.due


def test_window_counts_prompt_and_output_tokens():
    """Past what the slots serve, the window closes on requests queued,
    in prefill and decoding: each counts the prompt tokens it had
    through prefill by then, and total_tok_s every token of the
    window."""
    run, numbers, _, out = _run(rate=400.0)
    logs = list(run.logs.values())
    started = [lg for lg in logs if lg.prefilled]
    assert 0 < len(started) < len(logs)
    from repro.serve.engine import chunk_plan
    chunk = run.cell.config["serving"]["prefill_chunk"]
    for lg in logs:
        # whole prompts, or a prompt's chunks done so far
        starts = {st for st, _ in chunk_plan(len(lg.prompt), chunk)}
        assert lg.prefilled == len(lg.prompt) or lg.prefilled in starts
        if lg.token_t:
            assert lg.prefilled == len(lg.prompt)
    t0, t_end = run.window
    tokens = sum(lg.prefilled + len(lg.token_t) for lg in logs)
    assert out["metrics"]["total_tok_s"]["value"] == pytest.approx(
        tokens / (t_end - t0))
    assert out["correct"] is True and out["failed"] == 0


def test_traced_run_reports_per_layer_metrics():
    run, _, _, out = _run(trace=True)
    assert out["correct"] is True
    assert "device_idle_share" in out["metrics"]
    assert out["device"]["window_s"] >= 1.4 and out["device"]["busy_s"] > 0
    assert len(out["breakdown"]["device_ops"]) <= 10
    assert list(out)[-1] == "checks"


def _decode_without_donation(engine):
    from repro.models import model as MD
    return jax.jit(partial(MD.decode_many, cfg=engine.cfg),
                   static_argnames=("n_steps", "greedy", "duo_layers",
                                    "unroll"))


def test_stale_state_fails_the_check():
    """Each decode step returns its caches unchanged."""
    def hook(engine):
        plain = _decode_without_donation(engine)

        def stale(**kw):
            toks, logits, _ = plain(**kw)
            return toks, logits, kw["caches"]
        stale._cache_size = plain._cache_size   # the engine's guard reads it
        engine._decode_many = stale
    _, numbers, limits, out = _run(hook)
    assert out["correct"] is False
    assert numbers["logit_gap"] > limits["logit_gap"]


def test_altered_token_fails_the_check():
    """Every served token is replaced where decode produces it."""
    def hook(engine):
        plain = _decode_without_donation(engine)
        vocab = engine.cfg.vocab_size

        def altered(**kw):
            toks, logits, caches = plain(**kw)
            return (toks + 1) % vocab, logits, caches
        altered._cache_size = plain._cache_size
        engine._decode_many = altered
    _, numbers, limits, out = _run(hook)
    assert out["correct"] is False
    assert numbers["logit_gap"] > limits["logit_gap"]


def test_command_refuses_without_a_tpu(capsys):
    rc = R.main(["--workload", "phi3-chat", "--seed", "1",
                 "--seconds", "1", "--trace", "0"])
    cap = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in cap.err
    assert "{" not in cap.out


def test_command_refuses_an_unknown_workload(capsys):
    rc = R.main(["--workload", "no-such-cell", "--seed", "1",
                 "--seconds", "1"])
    assert rc != 0 and "no-such-cell" in capsys.readouterr().err


def test_percentiles_count_missing_requests_as_infinite():
    from bench import stats
    assert stats.pct([1.0, 2.0, 3.0], 50) == 2.0
    assert stats.pct([1.0, 2.0, math.inf], 90) == math.inf
    assert stats.pct([1.0, 2.0, 3.0, 4.0], 90) == pytest.approx(3.7)
    assert np.isnan(stats.pct([], 50))
