"""The one traffic generator: reads a mix file's parameters.

Every seed serves one schedule: prompt lengths, output lengths and
arrival gaps are drawn from the mix's ``set_seed``, a random stream
each.  A window of ``seconds`` at ``rate`` holds round(rate × seconds)
requests, the first due at once; the drawn gaps are scaled so that the
last is due one mean gap (1 / rate) before the close, so arrivals span
the whole window.  ``--seed`` draws the token ids only.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import numpy as np


@dataclass(frozen=True)
class Arrival:
    rid: int
    due: float            # seconds after the window opens
    tokens: np.ndarray    # (prompt_len,) int32, system prompt first
    n_out: int            # tokens to generate (greedy)


def _stream(mix: Dict[str, Any], i: int) -> np.random.Generator:
    return np.random.default_rng([mix["set_seed"], i])


def _lengths(rng, spec: Dict[str, Any], n: int) -> np.ndarray:
    z = rng.standard_normal(n)
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def _gaps(rng, arrivals: Dict[str, Any], n: int) -> np.ndarray:
    """n inter-arrival gaps of unit mean: exponential (Poisson
    arrivals) or gamma with the given coefficient of variation."""
    if arrivals["process"] == "poisson":
        return rng.exponential(1.0, n)
    if arrivals["process"] == "gamma":
        shape = 1.0 / arrivals["cv"] ** 2
        return rng.gamma(shape, 1.0 / shape, n)
    raise ValueError(f"unknown arrival process {arrivals['process']!r}")


def schedule(mix: Dict[str, Any], *, rate: float, seconds: float
             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(prompt totals, output lengths, due times) of the window's
    requests; the same for every seed."""
    n = max(1, round(rate * seconds))
    due = np.concatenate([[0.0], np.cumsum(
        _gaps(_stream(mix, 2), mix["arrivals"], n - 1))])
    if n > 1:
        due *= (seconds - 1.0 / rate) / due[-1]
    prompt = _lengths(_stream(mix, 0), mix["prompt"], n)
    if mix["prompt"]["counts"] == "user":
        prompt = prompt + mix["system_prompt"]
    return prompt, _lengths(_stream(mix, 1), mix["output"], n), due


def generate(mix: Dict[str, Any], *, rate: float, seconds: float,
             vocab: int, seed: int) -> List[Arrival]:
    """The requests of one run, in due order, their ids drawn from
    ``seed``: one system prompt shared by all, then each one's own."""
    prompt, out, due = schedule(mix, rate=rate, seconds=seconds)
    rng = np.random.default_rng(seed)
    system = rng.integers(0, vocab, mix["system_prompt"], dtype=np.int32)
    return [Arrival(i, float(due[i]), np.concatenate([system, rng.integers(
        0, vocab, int(prompt[i]) - len(system), dtype=np.int32)]),
        int(out[i])) for i in range(len(due))]
