"""The serving tick's own spans, read from a run's profiler trace beside
the device's op intervals: the scheduler layer's metrics.

The program spans each scheduler tick ``serve.tick``, with children
``serve.queue``, ``serve.prefill`` (``serve.prefill_chunk`` inside),
``serve.admit``, ``serve.decode`` and ``serve.wait`` (each blocking
device-to-host read); the tick span carries its counts as event stats
(``syncs``, ``prompt_tokens``, ...).  One pass over the newest
``.xplane.pb`` under ``<bench.loop.OUT>/trace/<cell>``, memoised per
file, collects those spans with their stats, the harness's ``bench.*``
spans (``bench.window`` brackets the traced window) and the device op
intervals of the plane ``bench/trace.py`` reads, that of the first of
the cell's devices (on the CPU backend, the events that
carry an ``hlo_module`` stat, as ``bench/trace.py`` reads them).  A
program that records no ``serve.tick`` span in the window leaves every
metric here None.

    python3 bench/spans.py .bench_out/trace/<cell>

prints the split of the window's device idle time by the innermost span
the host was in, with the three metrics.
"""
from __future__ import annotations

import bisect
import functools
import json
import os
import sys
from collections import defaultdict
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace as TR  # noqa: E402

TICK, WAIT = "serve.tick", "serve.wait"
# the annotation jax puts on a device-to-host read of an array
READ = "np.asarray(jax.Array)"


@dataclass(frozen=True)
class HostSpan:
    name: str
    start: float          # seconds on the trace's clock
    end: float
    stats: Dict[str, Any]


@dataclass
class Spans:
    window: Tuple[float, float]
    host: List[HostSpan]          # serve.* and bench.* spans, by start
    busy: np.ndarray              # (n, 2) union of device op intervals
    reads: List[float]            # start of each READ event

    def __post_init__(self):
        # device-busy seconds before each union interval's start
        self._starts = self.busy[:, 0]
        self._cum = np.concatenate(
            [[0.0], np.cumsum(self.busy[:, 1] - self.busy[:, 0])])

    def busy_before(self, t: float) -> float:
        """Device-busy seconds on the trace's clock before ``t``."""
        i = int(np.searchsorted(self._starts, t, side="right")) - 1
        if i < 0:
            return 0.0
        s, e = self.busy[i]
        return float(self._cum[i] + min(e, t) - s)

    def busy_in(self, a: float, b: float) -> float:
        return self.busy_before(b) - self.busy_before(a)

    def ticks(self) -> List[HostSpan]:
        """The ``serve.tick`` spans inside the window, clipped to it."""
        w0, w1 = self.window
        return [HostSpan(sp.name, max(sp.start, w0), min(sp.end, w1),
                         sp.stats)
                for sp in self.host
                if sp.name == TICK and sp.end > w0 and sp.start < w1]


def union(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """(n, 2) disjoint intervals, by start, covering the given ones."""
    if not len(starts):
        return np.zeros((0, 2))
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.concatenate([[True], s[1:] > reach[:-1]])
    first = np.flatnonzero(new)
    last = np.concatenate([first[1:] - 1, [len(s) - 1]])
    return np.stack([s[first], reach[last]], axis=1)


def from_events(host: Sequence[HostSpan],
                ops: Sequence[Tuple[float, float]],
                reads: Sequence[float] = ()) -> Optional[Spans]:
    """Spans of one trace from its host spans and device op intervals;
    None without exactly one ``bench.window``."""
    win = [sp for sp in host if sp.name == TR.WINDOW]
    if len(win) != 1:
        return None
    w0, w1 = win[0].start, win[0].end
    arr = np.asarray(ops, dtype=float).reshape(-1, 2)
    s, e = np.clip(arr[:, 0], w0, w1), np.clip(arr[:, 1], w0, w1)
    keep = e > s
    return Spans(window=(w0, w1),
                 host=sorted(host, key=lambda sp: (sp.start, -sp.end)),
                 busy=union(s[keep], e[keep]), reads=sorted(reads))


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime_ns: int, device_id: int) -> Optional[Spans]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device = TR.device_plane(pd.planes, device_id)
    host: List[HostSpan] = []
    ops: List[Tuple[float, float]] = []
    reads: List[float] = []
    for plane in pd.planes:
        on_device = device is not None and plane.name == device.name
        for line in plane.lines:
            if on_device:
                if line.name == "XLA Ops":
                    ops.extend((ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                               for ev in line.events)
                continue
            for ev in line.events:
                name = ev.name
                if name.startswith(("serve.", "bench.")):
                    host.append(HostSpan(name, ev.start_ns * 1e-9,
                                         ev.end_ns * 1e-9, TR._stats(ev)))
                elif name == READ:
                    reads.append(ev.start_ns * 1e-9)
                elif (device is None and not name.startswith("$")
                      and "hlo_module" in TR._stats(ev)):
                    ops.append((ev.start_ns * 1e-9, ev.end_ns * 1e-9))
    return from_events(host, ops, reads)


def load(trace_dir: str, device_id: int = 0) -> Optional[Spans]:
    """The spans of the newest trace under ``trace_dir``, beside the ops
    of TPU ``device_id``."""
    path = TR._newest(trace_dir)
    return _load(path, os.stat(path).st_mtime_ns, device_id)


def of_run(run) -> Optional[Spans]:
    """The spans of a traced run (None for an untraced one)."""
    if run.trace is None:
        return None
    from bench import loop
    return load(os.path.join(loop.OUT, "trace", run.cell.name),
                run.device_id)


# -- the scheduler layer's metrics -------------------------------------------

def tick_idle_share(sp: Optional[Spans]) -> Optional[float]:
    """Device idle time inside the window's ``serve.tick`` spans over
    their length, in percent."""
    ticks = sp.ticks() if sp is not None else []
    length = sum(t.end - t.start for t in ticks)
    if length <= 0:
        return None
    busy = sum(sp.busy_in(t.start, t.end) for t in ticks)
    return 100.0 * (length - busy) / length


def _waits_by_tick(sp: Spans, ticks: List[HostSpan]) -> List[float]:
    starts = [t.start for t in ticks]
    out = [0.0] * len(ticks)
    for w in sp.host:
        if w.name != WAIT:
            continue
        i = bisect.bisect_right(starts, w.start) - 1
        if i >= 0 and w.end <= ticks[i].end:
            out[i] += w.end - w.start
    return out


def tick_host_ms(sp: Optional[Spans]) -> Optional[float]:
    """Mean over the window's ticks of the tick's length less its
    ``serve.wait`` children: the host's own work per tick, in ms."""
    ticks = sp.ticks() if sp is not None else []
    if not ticks:
        return None
    waits = _waits_by_tick(sp, ticks)
    own = [t.end - t.start - w for t, w in zip(ticks, waits)]
    return 1e3 * sum(own) / len(own)


def host_syncs_per_tick(sp: Optional[Spans]) -> Optional[float]:
    """Mean of the ``syncs`` count the window's ticks carry."""
    syncs = [t.stats["syncs"] for t in (sp.ticks() if sp is not None
                                        else []) if "syncs" in t.stats]
    if not syncs:
        return None
    return float(sum(syncs)) / len(syncs)


# -- where the idle time goes ------------------------------------------------

def innermost(spans: Sequence[HostSpan]) -> List[Tuple[float, float, str]]:
    """(start, end, name) pieces of the spans, each piece named by the
    innermost span over it; the spans nest (one host thread)."""
    out: List[Tuple[float, float, str]] = []
    stack: List[List] = []             # [span, time covered up to]

    def pop() -> None:
        top, cur = stack.pop()
        out.append((cur, top.end, top.name))
        if stack:
            stack[-1][1] = max(stack[-1][1], top.end)

    for sp in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1][0].end <= sp.start:
            pop()
        if stack:
            out.append((stack[-1][1], sp.start, stack[-1][0].name))
        stack.append([sp, sp.start])
    while stack:
        pop()
    return [p for p in out if p[1] > p[0]]


def idle_by_span(sp: Spans) -> Dict[str, float]:
    """The window's device idle seconds by the innermost host span the
    host was in; the window's own name holds what no other span covers."""
    w0, w1 = sp.window
    idle: Dict[str, float] = defaultdict(float)
    for a, b, name in innermost(sp.host):
        a, b = max(a, w0), min(b, w1)
        if b > a:
            idle[name] += (b - a) - sp.busy_in(a, b)
    return dict(idle)


def reads_outside_waits(sp: Spans) -> Tuple[int, int]:
    """(device-to-host reads inside ticks, those not in a serve.wait),
    where the trace marks reads (the TPU backend)."""
    ticks = sp.ticks()
    waits = [w for w in sp.host if w.name == WAIT]
    w_starts = [w.start for w in waits]
    t_starts = [t.start for t in ticks]
    inside = outside = 0
    for r in sp.reads:
        i = bisect.bisect_right(t_starts, r) - 1
        if i < 0 or r >= ticks[i].end:
            continue
        inside += 1
        j = bisect.bisect_right(w_starts, r) - 1
        if j < 0 or r >= waits[j].end:
            outside += 1
    return inside, outside


def report(sp: Spans) -> Dict[str, Any]:
    w0, w1 = sp.window
    idle = idle_by_span(sp)
    serve = {k: v for k, v in idle.items() if k.startswith("serve.")}
    ticks = sp.ticks()
    return {
        "window_s": w1 - w0,
        "busy_s": sp.busy_in(w0, w1),
        "idle_s": (w1 - w0) - sp.busy_in(w0, w1),
        "ticks": len(ticks),
        "tick_s": sum(t.end - t.start for t in ticks),
        "idle_in_ticks_s": sum(serve.values()),
        "idle_by_span_s": dict(sorted(idle.items(), key=lambda x: -x[1])),
        "reads_in_ticks_and_outside_waits": reads_outside_waits(sp),
        "tick_idle_share": tick_idle_share(sp),
        "tick_host_ms": tick_host_ms(sp),
        "host_syncs_per_tick": host_syncs_per_tick(sp),
    }


def main(argv: Optional[List[str]] = None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print("usage: python3 bench/spans.py <trace dir>", file=sys.stderr)
        return 2
    sp = load(args[0])
    if sp is None:
        print("no bench.window span in the trace", file=sys.stderr)
        return 1
    print(json.dumps(report(sp)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
