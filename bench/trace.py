"""Reduces a profiler trace to device busy/idle time, time per op and
per executable, and idle gaps named by what the host was doing.

On a TPU the device plane (``/device:TPU:n``) has a line "XLA Modules",
one event per executable run, named ``jit_<name>(<program id>)``, and a
line "XLA Ops", one event per HLO instruction run, named by the whole
instruction text (``%fusion.3 = bf16[...] fusion(...), ...``).  An op
belongs to the executable run that contains it.  On the CPU backend,
which has no device plane, ops are the events that carry an
``hlo_module`` stat.  The host's part is the benchmark's own
``jax.profiler.TraceAnnotation`` spans, all named ``bench.*``;
``bench.window`` brackets the traced window.
"""
from __future__ import annotations

import glob
import os
import re
import warnings
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

WINDOW = "bench.window"


@dataclass(frozen=True)
class Op:
    name: str         # short: "%fusion.3", or an executable's name
    module: str       # executable: "<jit name>#<program id>"
    start: float      # seconds on the trace's clock
    end: float
    detail: str = ""  # the instruction text of a custom call


@dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    window_start: float = 0.0
    # (executable, op) → self time: time in the op not covered by an op
    # nested in it (a loop or a conditional holds the ops it runs)
    op_self: Dict[Tuple[str, str], float] = field(default_factory=dict)
    # (executable, needle) → time of the ops whose text holds the needle
    op_module: Dict[Tuple[str, str], float] = field(default_factory=dict)
    modules: Dict[str, float] = field(default_factory=dict)   # exec → s
    execs: List[Tuple[str, float, float]] = field(default_factory=list)
    gaps: List[Tuple[str, float]] = field(default_factory=list)  # longest first
    idle_by_host: Dict[str, float] = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def module_name(raw: str) -> str:
    """``jit_prefill_chunk(42)`` → ``prefill_chunk#42``; the program id
    tells apart executables that share a name."""
    raw = raw.strip()
    m = re.fullmatch(r"(.*)\((-?\d+)\)", raw)
    name, pid = (m.group(1), m.group(2)) if m else (raw, None)
    name = name[4:] if name.startswith("jit_") else name
    return f"{name}#{pid}" if pid is not None else name


def short_name(text: str) -> str:
    """``%fusion.3 = bf16[8] fusion(...)`` → ``%fusion.3``."""
    return text.split(" = ", 1)[0].strip()


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _self_times(ops: Sequence[Tuple[float, float, "Op"]]
                ) -> Dict[Tuple[str, str], float]:
    """Self time of each (executable, op): its span minus the spans of
    the ops nested directly inside it."""
    out: Dict[Tuple[str, str], float] = defaultdict(float)
    stack: List[List] = []      # [end, key, child time]
    for s, e, o in sorted(ops, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, key, child = stack.pop()
            out[key] -= child
        if stack:
            stack[-1][2] += e - s
        key = (o.module, o.name)
        out[key] += e - s
        stack.append([e, key, 0.0])
    while stack:
        end, key, child = stack.pop()
        out[key] -= child
    return dict(out)


def _host_lookup(spans: Sequence[Span]):
    """t → the benchmark span (other than the window) covering t: the
    harness's spans follow one another without overlap."""
    import bisect
    inner = sorted((sp for sp in spans if sp.name != WINDOW),
                   key=lambda sp: sp.start)
    starts = [sp.start for sp in inner]

    def at(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < inner[i].end:
            return inner[i].name
        return "host:other"
    return at


def reduce(ops: Sequence[Op], spans: Sequence[Span],
           execs: Sequence[Op] = (), needles: Sequence[str] = ()) -> Reduced:
    """Busy/idle, per-op and per-executable time and gaps inside the
    ``bench.window`` span; everything is clipped to the window.  An op
    whose text holds one of ``needles`` is also summed under
    (executable, needle) in ``op_module``."""
    win = [sp for sp in spans if sp.name == WINDOW]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found {len(win)}")
    w0, w1 = win[0].start, win[0].end

    def clip(items):
        return [(max(x.start, w0), min(x.end, w1), x) for x in items
                if x.end > w0 and x.start < w1]

    inside = clip(ops)
    busy = _union([(s, e) for s, e, _ in inside])
    red = Reduced(window_s=w1 - w0, busy_s=sum(e - s for s, e in busy),
                  window_start=w0, op_self=_self_times(inside))
    pair_t: Dict[Tuple[str, str], float] = defaultdict(float)
    for s, e, o in inside:
        for k in needles:
            if k in o.name or k in o.detail:
                pair_t[(o.module, k)] += e - s
    red.op_module = dict(pair_t)
    mod_t: Dict[str, float] = defaultdict(float)
    for s, e, x in clip(execs):
        mod_t[x.module] += e - s
        red.execs.append((x.module, s, e))
    red.execs.sort(key=lambda x: x[1])
    red.modules = dict(mod_t)
    gaps, t = [], w0
    for s, e in busy + [(w1, w1)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    host_at = _host_lookup(spans)
    named = [(host_at((a + b) / 2), b - a) for a, b in gaps]
    idle: Dict[str, float] = defaultdict(float)
    for name, dt in named:
        idle[name] += dt
    red.gaps = sorted(named, key=lambda x: -x[1])
    red.idle_by_host = dict(idle)
    return red


def _stats(ev) -> Dict:
    with warnings.catch_warnings():   # pybind's event_stats type warns
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(ev.stats)


def _newest(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def device_plane(planes, device_id: int = 0):
    """The plane of TPU ``device_id`` (``/device:TPU:<id>``), the only
    TPU plane of a one-chip host whatever its number, or None where the
    trace has no TPU plane (the CPU backend).  Several TPU planes but
    none of that device is an error: another chip of the host may have
    run nothing of the cell."""
    tpu = {}
    for p in planes:
        m = re.match(r"/device:TPU:(\d+)", p.name)
        if m:
            tpu.setdefault(int(m.group(1)), p)
    if not tpu:
        return None
    if len(tpu) == 1:
        return next(iter(tpu.values()))
    if device_id not in tpu:
        raise ValueError(f"no trace plane of TPU {device_id}; planes: "
                         f"{sorted(p.name for p in planes)}")
    return tpu[device_id]


def load(trace_dir: str, device_id: int = 0
         ) -> Tuple[List[Op], List[Span], List[Op]]:
    """(device ops, benchmark host spans, executable runs) of the newest
    ``.xplane.pb`` under ``trace_dir``; times in seconds.  One device
    plane is read: that of ``device_id``, the first of the cell's own
    devices (on a multi-chip cell each chip runs its share of the same
    programs), never another chip of the host."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(_newest(trace_dir))
    ops: List[Op] = []
    spans: List[Span] = []
    execs: List[Op] = []
    device = device_plane(pd.planes, device_id)
    for plane in pd.planes:
        on_device = device is not None and plane.name == device.name
        for line in plane.lines:
            kind = line.name if on_device else ""
            for ev in line.events:
                name = ev.name
                if kind == "XLA Ops":
                    ops.append(Op(short_name(name), "", ev.start_ns * 1e-9,
                                  ev.end_ns * 1e-9,
                                  name if "custom_call_target" in name
                                  else ""))
                elif name.startswith("bench."):
                    spans.append(Span(name, ev.start_ns * 1e-9,
                                      ev.end_ns * 1e-9))
                elif kind == "XLA Modules":
                    mod = module_name(name)
                    execs.append(Op(mod, mod, ev.start_ns * 1e-9,
                                    ev.end_ns * 1e-9))
                elif device is None and not name.startswith("$") and (
                        "hlo_module" in (st := _stats(ev))):
                    mod = module_name(f"{st['hlo_module']}"
                                      f"({st.get('program_id', 0)})")
                    ops.append(Op(name, mod, ev.start_ns * 1e-9,
                                  ev.end_ns * 1e-9, name))
    if device is not None:
        ops = _by_containment(ops, execs)
    else:
        execs = _execs_from_ops(ops)
    return ops, spans, execs


def _execs_from_ops(ops: List[Op], gap: float = 1e-4) -> List[Op]:
    """Executable runs where the trace has no module line: consecutive
    ops of one executable, split where another executable ran between."""
    out: List[Op] = []
    for o in sorted(ops, key=lambda x: x.start):
        if out and out[-1].module == o.module and o.start - out[-1].end < gap:
            last = out[-1]
            out[-1] = Op(last.name, last.module, last.start,
                         max(last.end, o.end))
        else:
            out.append(Op(o.module, o.module, o.start, o.end))
    return out


def _by_containment(ops: List[Op], execs: List[Op]) -> List[Op]:
    """Each op tagged with the executable run it lies in."""
    execs = sorted(execs, key=lambda x: x.start)
    out, j = [], 0
    for o in sorted(ops, key=lambda x: x.start):
        while j < len(execs) and execs[j].end <= o.start:
            j += 1
        mod = (execs[j].module if j < len(execs)
               and execs[j].start <= o.start else "")
        out.append(Op(o.name, mod, o.start, o.end, o.detail))
    return out
