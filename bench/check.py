"""How ``correct`` is decided: the served tokens against the reference.

After the window, a sample drawn from the seed of the requests it
finished, the longest among them, is run through the configuration's
plain reference (``spec.reference``: ``bench/reference.py`` unless the
configuration names its own) over prompt + served tokens.  For every
served token the number compared is how far its reference logit lies
below the reference's best logit at that position; the widest such gap
over the sample is held to the cell's limit.  Greedy decoding serves each
position's argmax of the program's own bfloat16 logits, so a sound
program only loses near-ties; a wrong token, a stale cache or a
dropped layer loses by far more.  The routed pattern each request
reports is compared with the cell's pattern layer by layer.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench import spec


def sample(finished: Sequence[Tuple[np.ndarray, np.ndarray]], *,
           served_tokens: int, max_requests: int, seed: int) -> List[int]:
    """Indices into ``finished`` ((prompt, served) pairs): the longest
    request, then others in an order drawn from ``seed`` until the
    sample holds ``served_tokens`` served tokens or ``max_requests``."""
    if not finished:
        return []
    sizes = [len(p) + len(s) for p, s in finished]
    longest = int(np.argmax(sizes))
    order = [longest] + [int(i) for i in np.random.default_rng(seed)
                         .permutation(len(finished)) if i != longest]
    picked, n = [], 0
    for i in order:
        if len(picked) >= max_requests or n >= served_tokens:
            break
        picked.append(i)
        n += len(finished[i][1])
    return picked


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per position: reference best logit minus the logit of ``tokens``."""
    rows = np.arange(len(tokens))
    return ref_logits.max(axis=-1) - ref_logits[rows, tokens]


def compare(params, conf: Dict, pattern: Sequence[str],
            finished: Sequence[Tuple[np.ndarray, np.ndarray]],
            picked: Sequence[int], max_len: int, *,
            control: bool = False) -> Dict[str, float]:
    """Widest gap of the served tokens (and, with ``control``, of the
    tokens the float8 control puts first at the same positions)."""
    reference = spec.reference(conf)
    served_gap, control_gap, n_tok = 0.0, 0.0, 0
    for i in picked:
        prompt, served = finished[i]
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        first = len(prompt) - 1
        ref = reference.logits(params, conf, pattern, seq, first, max_len)
        served_gap = max(served_gap, float(gaps(ref, served).max()))
        n_tok += len(served)
        if control:
            low = reference.logits(params, conf, pattern, seq, first,
                                   max_len, fp8=True)
            control_gap = max(control_gap,
                              float(gaps(ref, low.argmax(-1)).max()))
    out = {"logit_gap": served_gap, "checked_tokens": float(n_tok),
           "checked_requests": float(len(picked))}
    if control:
        out["control_logit_gap"] = control_gap
    return out


def as_control(numbers: Dict[str, float]) -> Dict[str, float]:
    """The numbers compared, with the control's tokens in the place of
    the served ones: what the cell's limits would read if the program
    served what the float8 control puts first."""
    return dict(numbers, logit_gap=numbers["control_logit_gap"])
