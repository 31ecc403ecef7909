"""Shared arithmetic of the per-layer metric readers in ``metrics/``.

A reader returns None when its run holds nothing to read (no trace,
no kernel call, no chunk of the kind); the harness then leaves the
metric out of the line.  It never returns 0 for a share.
"""
from __future__ import annotations

from collections import Counter
from typing import Iterable, List, Optional, Sequence, Tuple

from bench import work

# A Pallas kernel's op in a TPU trace is a custom call to Mosaic; the
# executable it runs in tells the decode kernel from the prefill one.
KERNEL = 'custom_call_target="tpu_custom_call"'
NEEDLES = (KERNEL,)
UNNAMED = "_unknown#"


def executables(run) -> Tuple[Optional[str], List[str]]:
    """(the decode executable, the prefill executables) of the traced
    window.  The engine's jits all trace as ``jit__unknown``; each tick
    dispatches its prefill work first and its decode step last, so the
    decode executable is the unnamed one that most often ends a tick
    with decode work, and every other unnamed one is prefill."""
    tr = run.trace
    shift = tr.window_start - run.window[0]
    unnamed = [x for x in tr.execs if x[0].startswith(UNNAMED)]
    votes: Counter = Counter()
    for t in run.traced_ticks:
        if not t.lives:
            continue
        inside = [m for m, s, e in unnamed
                  if t.t0 + shift <= s and e <= t.t1 + shift]
        if inside:
            votes[inside[-1]] += 1
    if not votes:
        return None, sorted({m for m, _, _ in unnamed})
    decode = votes.most_common(1)[0][0]
    return decode, sorted({m for m, _, _ in unnamed} - {decode})


def breakdown_ops(run, n: int = 10) -> List[List]:
    """The n device ops with the most self time, each named by its
    executable (decode, prefill or its jit name) and its HLO name."""
    decode, prefill = executables(run)

    def label(mod: str) -> str:
        if mod == decode:
            return "decode"
        if mod in prefill:
            return "prefill"
        return mod.split("#")[0] or "?"

    agg: Counter = Counter()
    for (mod, op), t in run.trace.op_self.items():
        agg[f"{label(mod)}:{op}"] += t
    return [[k, v] for k, v in agg.most_common(n)]


def module_time(run, modules: Sequence[str]) -> float:
    return sum(run.trace.modules.get(m, 0.0) for m in modules)


def kernel_time(run, modules: Sequence[str], needle: str) -> float:
    return sum(run.trace.op_module.get((m, needle), 0.0) for m in modules)


def traced_chunks(run) -> Optional[list]:
    chunks = [t.chunk for t in run.traced_ticks if t.chunk is not None]
    if any(c[0] < 0 for c in chunks):
        return None          # a tick's chunk could not be told
    return chunks


def prefill_chunk_ms(run) -> Optional[float]:
    if run.trace is None:
        return None
    chunks = traced_chunks(run)
    if not chunks:
        return None
    return 1e3 * module_time(run, executables(run)[1]) / len(chunks)


def decode_step_ms(run) -> Optional[float]:
    if run.trace is None:
        return None
    steps = sum(1 for t in run.traced_ticks if t.lives)
    decode = executables(run)[0]
    if not steps or decode is None:
        return None
    return 1e3 * module_time(run, [decode]) / steps


def decode_attn_roofline(run) -> Optional[float]:
    if run.trace is None or run.peaks is None:
        return None
    decode = executables(run)[0]
    if decode is None:
        return None
    secs = kernel_time(run, [decode], KERNEL)
    flops = nbytes = 0.0
    for t in run.traced_ticks:
        if t.lives:
            f, b = work.decode_attn(run.geometry, t.lives)
            flops, nbytes = flops + f, nbytes + b
    if secs <= 0 or flops <= 0:
        return None
    return work.roofline_share(flops, nbytes, secs, run.peaks)[0]


def prefill_attn_roofline(run) -> Optional[float]:
    """The block-sparse kernel runs on the FA layers of every chunk but
    a request's first (the routing chunk, a plain prefill)."""
    if run.trace is None or run.peaks is None:
        return None
    chunks = traced_chunks(run)
    if chunks is None:
        return None
    secs = kernel_time(run, executables(run)[1], KERNEL)
    flops = nbytes = 0.0
    for _, start, size in chunks:
        if start > 0:
            f, b = work.prefill_attn(run.geometry, start, size)
            flops, nbytes = flops + f, nbytes + b
    if secs <= 0 or flops <= 0:
        return None
    return work.roofline_share(flops, nbytes, secs, run.peaks)[0]


def _positions(run) -> Iterable[int]:
    for t in run.traced_ticks:
        if t.chunk is not None:
            _, start, size = t.chunk
            yield from range(start, start + size)
        for live in t.lives:
            yield live - 1


def step_mfu(run) -> Optional[float]:
    """Model FLOPs of every token prefilled or decoded in the traced
    window over the window's length times the bf16 peak of the cell's
    chips."""
    if run.trace is None or run.peaks is None:
        return None
    chunks = traced_chunks(run)
    if chunks is None:
        return None
    logits_rows = len(chunks) + sum(len(t.lives) for t in run.traced_ticks)
    flops = work.model_flops(run.geometry, _positions(run), logits_rows)
    if flops <= 0:
        return None
    return 100.0 * flops / (run.trace.window_s * run.geometry.chips
                            * run.peaks["bf16_flops_per_s"])


def device_idle_share(run) -> Optional[float]:
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    return 100.0 * run.trace.idle_share


def peak_hbm_share(run) -> Optional[float]:
    if not run.memory.get("limit") or not run.memory.get("peak"):
        return None
    return 100.0 * run.memory["peak"] / run.memory["limit"]
