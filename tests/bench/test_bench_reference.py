"""The plain reference against a direct numpy forward, and its float8
control against the served tokens, at a size the CPU holds."""
import time

import jax
import numpy as np
import pytest

import bench_tiny
from bench import check, loop, model, reference


def _numpy_forward(params, conf, pattern, tokens):
    """Every position's logits, one matrix product at a time."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    d, eps, theta = conf["head_dim"], conf["rms_norm_eps"], conf["rope_theta"]
    sink, local = conf["flux"]["sink"], conf["flux"]["local"]
    n = len(tokens)
    pos = np.arange(n)

    def rms(x, s):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * s

    def rope(x):
        inv = 1.0 / theta ** (np.arange(0, d, 2) / d)
        a = pos[:, None, None] * inv
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return np.concatenate([x1 * np.cos(a) - x2 * np.sin(a),
                               x2 * np.cos(a) + x1 * np.sin(a)], -1)

    h = p["embed"][tokens]
    for layer, mode in enumerate(pattern):
        w = jax.tree.map(lambda a: a[layer], p["trunk"][0])
        x = rms(h, w["norm1"]["scale"])
        q = rope((x @ w["attn"]["wq"]).reshape(n, hq, d))
        k = rope((x @ w["attn"]["wk"]).reshape(n, hkv, d))
        v = (x @ w["attn"]["wv"]).reshape(n, hkv, d)
        k, v = np.repeat(k, hq // hkv, 1), np.repeat(v, hq // hkv, 1)
        s = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
        i, j = pos[:, None], pos[None, :]
        vis = j <= i
        if mode == "sa":
            vis &= (j < sink) | (i - j < local)
        s = np.where(vis, s, -np.inf)
        a = np.exp(s - s.max(-1, keepdims=True))
        a /= a.sum(-1, keepdims=True)
        o = np.einsum("hqk,khd->qhd", a, v).reshape(n, hq * d)
        h = h + o @ w["attn"]["wo"]
        x2 = rms(h, w["norm2"]["scale"])
        g = x2 @ w["ffn"]["gate"]
        h = h + (g / (1 + np.exp(-g)) * (x2 @ w["ffn"]["up"])) @ w["ffn"]["down"]
    x = rms(h, p["final_norm"]["scale"])
    return x @ (p["embed"].T if conf["tie_word_embeddings"] else p["out_w"])


@pytest.fixture(scope="module")
def tiny():
    cell = bench_tiny.tiny_cell()
    cfg = model.model_config(cell.config)
    pattern = model.routing_pattern(cell.config, cfg.num_layers)
    params = model.make_weights(cfg, bench_tiny.SEED)
    return cell, pattern, params


def test_reference_matches_numpy(tiny):
    cell, pattern, params = tiny
    # 40 tokens outgrow the SA window (sink 4 + local 28)
    tokens = np.random.default_rng(0).integers(0, 128, 40).astype(np.int32)
    want = _numpy_forward(params, cell.config, pattern, tokens)
    got = reference.logits(params, cell.config, pattern, tokens, 3,
                           cell.point["max_len"])
    assert got.shape == (37, 128)
    np.testing.assert_allclose(got, want[3:], rtol=1e-4, atol=1e-4)
    # the SA window matters: full attention gives other logits
    full = _numpy_forward(params, cell.config, ("fa", "fa"), tokens)
    assert np.abs(full[-1] - want[-1]).max() > 1e-3


def test_float8_control_fails_where_the_served_tokens_pass(tiny):
    """The control (the reference in float8) puts other tokens first by
    more than the limit; the served tokens stay within it."""
    cell, pattern, params = tiny
    run, numbers, limits = loop.run_cell(
        cell, seed=bench_tiny.SEED, seconds=1.5, trace=False,
        t_start=time.perf_counter(), log=lambda s: None)
    finished = [(lg.prompt, lg.served) for lg in run.logs.values()
                if lg.status == "ok"]
    picked = check.sample(finished, seed=bench_tiny.SEED,
                          **cell.point["check"])
    out = check.compare(params, cell.config, pattern, finished, picked,
                        cell.point["max_len"], control=True)
    assert out["logit_gap"] == numbers["logit_gap"]
    assert out["logit_gap"] <= limits["logit_gap"]
    assert out["control_logit_gap"] > 3 * limits["logit_gap"]
    # through the verdict that decides the cell: the program's numbers
    # read correct, the control's in their place do not
    from bench.run import result_line
    verdict = [result_line(run, nums, limits, False, log=lambda s: None)
               ["correct"] for nums in (numbers, check.as_control(
                   dict(numbers, control_logit_gap=out["control_logit_gap"])))]
    assert verdict == [True, False]


def test_sample_takes_the_longest_first():
    fin = [(np.zeros(5), np.zeros(3)), (np.zeros(50), np.zeros(9)),
           (np.zeros(7), np.zeros(4))]
    picked = check.sample(fin, served_tokens=10, max_requests=3, seed=1)
    assert picked[0] == 1 and len(picked) == 2
    assert check.sample([], served_tokens=1, max_requests=1, seed=1) == []
