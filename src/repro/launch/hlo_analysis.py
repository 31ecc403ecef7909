"""Roofline-term extraction from lowered/compiled artifacts.

compute / memory terms come from ``compiled.cost_analysis()``;
collective bytes are NOT in cost_analysis — they are summed from the
post-SPMD HLO text (every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute op's output bytes).

Hardware constants (TPU v5e): see repro.launch.mesh.
"""
from __future__ import annotations

import re
from dataclasses import asdict, dataclass
from typing import Dict, List, Optional

from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  %ag = bf16[2,16,128]{2,1,0} all-gather(%x), ...
#       %t = (f32[8,128]{1,0}, u32[]) all-reduce(...)
_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\]\S*)\s+"
    r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|"
    r"collective-permute)\(")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    total_bytes: int
    by_kind: Dict[str, int]
    op_counts: Dict[str, int]


def collective_bytes(hlo_text: str) -> CollectiveStats:
    by_kind: Dict[str, int] = {}
    counts: Dict[str, int] = {}
    for m in _OP_RE.finditer(hlo_text):
        shape_text, kind = m.group(1), m.group(2)
        kind = kind.replace("-start", "")
        b = _shape_bytes(shape_text)
        by_kind[kind] = by_kind.get(kind, 0) + b
        counts[kind] = counts.get(kind, 0) + 1
    return CollectiveStats(total_bytes=sum(by_kind.values()),
                           by_kind=by_kind, op_counts=counts)


def _cost_dict(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    return dict(ca) if ca is not None else {}


def roofline_terms(compiled, hlo_text: str, n_chips: int,
                   model_flops: Optional[float] = None,
                   memory: Optional[Dict] = None) -> Dict:
    """The three roofline terms (§Roofline) in seconds, plus sources.

    All quantities are PER DEVICE (SPMD-partitioned module; calibrated
    against hand-counted sharded matmuls — EXPERIMENTS.md §Dry-run).

      compute    — loop-aware matmul FLOPs from the HLO walker
                   (``hlo_costs``): scan bodies × trip count,
                   lax.cond branches → max.  The raw
                   ``cost_analysis()`` numbers are kept under
                   ``*_xla_raw`` but they count loop bodies ONCE.
      memory     — per-step HBM traffic proxied by
                   argument+output+temp residency (every decode step
                   reads the caches & params once; temp ≈ activation
                   traffic).
      collective — loop-aware collective output bytes over ICI.
    """
    from repro.launch.hlo_costs import loop_aware_costs

    cost = _cost_dict(compiled)
    la = loop_aware_costs(hlo_text)
    coll_raw = collective_bytes(hlo_text)
    mem = memory or {}
    hbm_traffic = sum(mem.get(k) or 0 for k in
                      ("argument_size_in_bytes", "output_size_in_bytes",
                       "temp_size_in_bytes"))
    t_compute = la.flops / PEAK_FLOPS_BF16
    t_memory = hbm_traffic / HBM_BW
    t_collective = la.coll_bytes / ICI_BW
    terms = {
        "hlo_flops_per_chip": la.flops,
        "hbm_traffic_bytes_per_chip": hbm_traffic,
        "collective_bytes_per_chip": la.coll_bytes,
        "collective_by_kind": la.coll_by_kind,
        "collective_op_counts": coll_raw.op_counts,
        "flops_xla_raw": float(cost.get("flops", 0.0)),
        "bytes_xla_raw": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes_unrolled_once": coll_raw.total_bytes,
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "n_chips": n_chips,
    }
    terms["bottleneck"] = max(
        ("compute", t_compute), ("memory", t_memory),
        ("collective", t_collective), key=lambda kv: kv[1])[0]
    if model_flops is not None:
        terms["model_flops"] = model_flops
        total = la.flops * n_chips
        terms["useful_flop_ratio"] = model_flops / total if total else None
    return terms


def memory_summary(compiled) -> Dict[str, Optional[int]]:
    ma = compiled.memory_analysis()
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        out[k] = int(getattr(ma, k)) if ma is not None and hasattr(ma, k) \
            else None
    return out
