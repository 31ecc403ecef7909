"""Loop-aware HLO cost analysis.

``compiled.cost_analysis()`` counts every computation ONCE — a
``lax.scan`` body's FLOPs are not multiplied by the trip count, and
both branches of a ``lax.cond`` are summed (calibrated in
EXPERIMENTS.md §Dry-run).  For a framework whose trunk is a scan over
layer periods and whose attention is a scan over query blocks, that
undercounts compute by orders of magnitude and silently miscounts
collectives inside the loop.

This module re-derives matmul FLOPs and collective bytes by walking the
post-optimization HLO text:

  * per-computation local costs (dot ops → 2·M·N·K; collective ops →
    output bytes),
  * ``fusion``/``call`` sites add the called computation's cost,
  * ``while`` sites multiply the body by the trip count inferred from
    the loop condition's compare-against-constant,
  * ``conditional`` sites take the MAX across branches (one branch
    executes at runtime — exactly the flux hard-routing semantics).

Elementwise FLOPs are ignored (dots dominate the compute roofline term
on the MXU); HBM traffic is taken from memory_analysis + the
analytical model instead (see hlo_analysis.roofline_terms).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "s4": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "u4": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

# header lines start at column 0: ``%name (args) -> type {`` — the arg
# list may contain nested parens (tuple types), so match loosely and
# require the trailing "{".
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(")
_SHAPE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DEF = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\S+(?:\s*\([^)]*\))?)")
_CALLS = re.compile(r"(?:calls|to_apply)=%([\w.\-]+)")
_WHILE = re.compile(r"condition=%([\w.\-]+),\s*body=%([\w.\-]+)")
_COND_BRANCHES = re.compile(
    r"(?:true_computation=%([\w.\-]+),\s*false_computation=%([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\})")
_CONST = re.compile(r"constant\((\d+)\)")
_DOT_DIMS = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
# dot's lhs operand, in both HLO print dialects: untyped ``dot(%op, …)``
# and typed ``dot(f32[64,64]{1,0} %op, …)`` (the type carries the shape).
_DOT_LHS = re.compile(r" dot\((?:([a-z0-9]+)\[([0-9,]*)\]\S*\s+)?%([\w.\-]+)")
_OPERANDS = re.compile(r"\(([^)]*)\)")

_COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                     "all-to-all", "collective-permute")


def _parse_shape(text: str) -> List[Tuple[str, List[int]]]:
    out = []
    for dt, dims in _SHAPE.findall(text):
        if dt in _DTYPE_BYTES:
            out.append((dt, [int(d) for d in dims.split(",") if d]))
    return out


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _parse_shape(text):
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_elems(text: str) -> int:
    shapes = _parse_shape(text)
    if not shapes:
        return 0
    n = 1
    for d in shapes[0][1]:
        n *= d
    return n


@dataclass
class Cost:
    flops: float = 0.0
    coll_bytes: float = 0.0
    coll_by_kind: Dict[str, float] = field(default_factory=dict)

    def __iadd__(self, o: "Cost"):
        self.flops += o.flops
        self.coll_bytes += o.coll_bytes
        for k, v in o.coll_by_kind.items():
            self.coll_by_kind[k] = self.coll_by_kind.get(k, 0.0) + v
        return self

    def scaled(self, f: float) -> "Cost":
        return Cost(self.flops * f, self.coll_bytes * f,
                    {k: v * f for k, v in self.coll_by_kind.items()})


@dataclass
class _Line:
    name: str
    result_type: str
    op_text: str


class HloCostModel:
    def __init__(self, hlo_text: str):
        self.computations: Dict[str, List[str]] = {}
        self.entry: Optional[str] = None
        self._split(hlo_text)
        self._memo: Dict[str, Cost] = {}

    # -- parsing -----------------------------------------------------------
    def _split(self, text: str) -> None:
        cur = None
        buf: List[str] = []
        for line in text.splitlines():
            m = _COMP_HDR.match(line)
            if m and line.rstrip().endswith("{"):
                cur = m.group(1)
                if line.startswith("ENTRY"):
                    self.entry = cur
                buf = []
                self.computations[cur] = buf
            elif cur is not None:
                if line.startswith("}"):
                    cur = None
                else:
                    buf.append(line)

    # -- trip count --------------------------------------------------------
    def trip_count(self, cond_comp: str) -> int:
        """Largest integer constant in the loop condition (jax scans
        count 0..N-1 with an LT compare); 1 if none found."""
        best = 1
        seen = set()

        def walk(name):
            if name in seen or name not in self.computations:
                return
            seen.add(name)
            for line in self.computations[name]:
                for c in _CONST.findall(line):
                    best_local = int(c)
                    nonlocal best
                    best = max(best, best_local)
                for called in _CALLS.findall(line):
                    walk(called)

        walk(cond_comp)
        return best

    # -- per-line costs ------------------------------------------------------
    def _line_cost(self, line: str) -> Cost:
        c = Cost()
        m = _DEF.match(line)
        if not m:
            return c
        body = line[m.end(1):]
        # dot flops: 2 · prod(result dims) · K  (K = contracted size)
        if re.search(r"=\s*\S+\s+dot\(", line) or " dot(" in line:
            result_elems = _shape_elems(line.split("=", 1)[1])
            k = self._dot_contracted_size(line)
            c.flops += 2.0 * result_elems * k
        for kind in _COLLECTIVE_KINDS:
            if re.search(rf"\s{kind}(-start)?\(", line):
                b = _shape_bytes(line.split("=", 1)[1].split("(", 1)[0])
                c.coll_bytes += b
                c.coll_by_kind[kind] = c.coll_by_kind.get(kind, 0) + b
                break
        return c

    def _dot_contracted_size(self, line: str) -> int:
        m = _DOT_DIMS.search(line)
        if not m:
            return 1
        dims = [int(d) for d in m.group(1).split(",") if d]
        lhs = _DOT_LHS.search(line)
        shape = None
        if lhs is not None:
            if lhs.group(2) is not None:  # typed operand: shape inline
                shape = [int(d) for d in lhs.group(2).split(",") if d]
            else:                         # untyped: look up the def
                shape = self._operand_shapes.get(lhs.group(3))
        if shape is None:
            return 1
        k = 1
        for d in dims:
            if d < len(shape):
                k *= shape[d]
        return k

    # -- computation cost ----------------------------------------------------
    def computation_cost(self, name: str) -> Cost:
        if name in self._memo:
            return self._memo[name]
        self._memo[name] = Cost()  # cycle guard
        total = Cost()
        lines = self.computations.get(name, [])
        # operand shape env for dot contraction sizing
        self._operand_shapes = getattr(self, "_operand_shapes", {})
        for line in lines:
            m = _DEF.match(line)
            if m:
                shapes = _parse_shape(line.split("=", 1)[1].split("(")[0])
                if shapes:
                    self._operand_shapes[m.group(1)] = shapes[0][1]
        for line in lines:
            total += self._line_cost(line)
            w = _WHILE.search(line)
            if w and " while(" in line:
                cond, body = w.group(1), w.group(2)
                trips = self.trip_count(cond)
                total += self.computation_cost(body).scaled(trips)
                total += self.computation_cost(cond).scaled(trips)
                continue
            cb = _COND_BRANCHES.search(line)
            if cb and " conditional(" in line:
                branches = ([cb.group(1), cb.group(2)] if cb.group(1)
                            else [b.strip().lstrip("%") for b in
                                  cb.group(3).split(",")])
                costs = [self.computation_cost(b) for b in branches if b]
                if costs:
                    # one branch runs at runtime → max (hard routing)
                    best = max(costs, key=lambda x: x.flops)
                    total += best
                continue
            for called in _CALLS.findall(line):
                total += self.computation_cost(called)
        self._memo[name] = total
        return total

    def entry_cost(self) -> Cost:
        if self.entry is None:
            return Cost()
        return self.computation_cost(self.entry)


def loop_aware_costs(hlo_text: str) -> Cost:
    return HloCostModel(hlo_text).entry_cost()


# ---------------------------------------------------------------------------
# Pooled-decode expressed-cost report
# ---------------------------------------------------------------------------
#
# ``compiled.cost_analysis()`` on a pooled decode tick reports the cost
# the *program text* expresses, and the dense pooled path expresses a
# full (B, Hkv, L, D) KV read every tick regardless of how short the
# live prefixes are — the mask hides padding from the *result*, not from
# the roofline.  The Pallas kernel's per-row trip count
# (ceil(len_b / block_k), dead grid steps collapsed onto a repeat fetch
# by the index-map clamp) makes the expressed bytes/FLOPs track the
# LIVE prefix instead.  This section computes both analytically so the
# scaling claim is auditable without a TPU: sweep mean live length at a
# fixed buffer capacity and the dense column stays flat while the
# kernel column grows linearly.
#
# Counting conventions (deliberately conservative for the kernel):
#   * dense KV bytes    = B · Hkv · L_buf · (Dk + Dv) · dtype_bytes
#     (each batch row streams the whole buffer once; heads broadcast)
#   * kernel KV bytes   = Hkv · Σ_b ceil(min(len_b, L_buf)/bk) · bk
#                         · (Dk + Dv) · dtype_bytes
#     (the grid iterates B·Hkv rows, each with its G = Hq/Hkv query
#     heads as one tile, so a block is fetched once per kv head)
#   * FLOPs             = 2 · (same block counts) · Hq per q-row
# Tiny q/output traffic (B·Hq·(Dk+Dv)) is omitted from both columns.

def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def ragged_lengths(batch: int, live_max: int) -> List[int]:
    """Deterministic mixed-length pool: evenly spaced 1..live_max."""
    live_max = max(1, live_max)
    return [max(1, round(live_max * (i + 1) / batch))
            for i in range(batch)]


def pooled_decode_attn_cost(lengths: List[int], buffer_len: int, *,
                            n_q_heads: int, n_kv_heads: int,
                            d_k: int, d_v: int, block_k: int = 128,
                            dtype_bytes: int = 4) -> Dict[str, float]:
    """Expressed HBM bytes and MXU FLOPs for ONE pooled attention
    consult (one layer, one decode step), dense vs kernel."""
    B = len(lengths)
    row_bytes = (d_k + d_v) * dtype_bytes
    dense_bytes = B * n_kv_heads * buffer_len * row_bytes
    dense_flops = 2.0 * B * n_q_heads * buffer_len * (d_k + d_v)
    kv_cols = sum(_ceil_div(min(n, buffer_len), block_k) * block_k
                  for n in lengths)
    kernel_bytes = n_kv_heads * kv_cols * row_bytes
    kernel_flops = 2.0 * n_q_heads * kv_cols * (d_k + d_v)
    return {
        "dense_hbm_bytes": float(dense_bytes),
        "kernel_hbm_bytes": float(kernel_bytes),
        "dense_flops": dense_flops,
        "kernel_flops": kernel_flops,
        "bytes_ratio": kernel_bytes / max(dense_bytes, 1),
    }


def decode_linear_cost(n_params: int, params_bytes: int, *,
                       batch: int, n_steps: int = 1) -> Dict[str, float]:
    """Expressed non-attention cost of pooled decode: every step runs
    the full parameter set once per batch row (2·N FLOPs per token)
    and streams the params from HBM once per step (the batch shares
    one read — decode is famously parameter-bandwidth-bound)."""
    return {
        "flops": 2.0 * float(n_params) * batch * n_steps,
        "hbm_bytes": float(params_bytes) * n_steps,
    }


def pooled_decode_tick_cost(lengths: List[int],
                            layer_specs: List[Tuple],
                            *, n_steps: int = 1,
                            kernel_hits: Optional[List[bool]] = None,
                            block_k: int = 128) -> Dict:
    """Expressed attention cost of one pooled decode tick across all
    attention layers — the join the serving profiler uses.

    ``layer_specs`` holds one (buffer_len, n_q_heads, n_kv_heads, d_k,
    d_v, dtype_bytes) tuple per attention layer (the engine derives
    them from static cache shapes); ``kernel_hits[i]`` selects the
    kernel column (live-length block trips) for layers the decode
    kernel served and the dense column (full buffer sweep) for
    declined/dense layers — None means all-dense.  Returns totals plus
    the kernel-hit / kernel-decline split, each scaled by ``n_steps``.
    """
    if kernel_hits is None:
        kernel_hits = [False] * len(layer_specs)
    if len(kernel_hits) != len(layer_specs):
        raise ValueError(
            f"pooled_decode_tick_cost: {len(kernel_hits)} kernel_hits "
            f"for {len(layer_specs)} layer specs — the kernel trace and "
            f"the geometry specs must describe the same layers")
    out: Dict = {
        "flops": 0.0, "hbm_bytes": 0.0,
        "kernel_hit": {"layers": 0, "flops": 0.0, "hbm_bytes": 0.0},
        "kernel_decline": {"layers": 0, "flops": 0.0, "hbm_bytes": 0.0},
    }
    for (buf, hq, hkv, dk, dv, db), hit in zip(layer_specs, kernel_hits):
        c = pooled_decode_attn_cost(lengths, buf, n_q_heads=hq,
                                    n_kv_heads=hkv, d_k=dk, d_v=dv,
                                    block_k=block_k, dtype_bytes=db)
        fl = (c["kernel_flops"] if hit else c["dense_flops"]) * n_steps
        hb = (c["kernel_hbm_bytes"] if hit
              else c["dense_hbm_bytes"]) * n_steps
        out["flops"] += fl
        out["hbm_bytes"] += hb
        side = out["kernel_hit" if hit else "kernel_decline"]
        side["layers"] += n_steps  # layer-consults: layers × steps
        side["flops"] += fl
        side["hbm_bytes"] += hb
    return out


def pooled_decode_report(cfg, *, max_len: int, batch: int = 8,
                         block_k: int = 128, dtype_bytes: int = 4,
                         fracs=(0.125, 0.25, 0.5, 0.75, 1.0)) -> Dict:
    """Per-tick expressed-cost sweep for every decode geometry the slot
    pool routes for ``cfg`` (a ModelConfig): FullKV (buffer = max_len),
    RingKV (buffer = sink + local, when flux routing is on) and MLA
    absorbed decode (latent KV, Hkv = 1) when the config is MLA.

    Each row fixes the buffer capacity and sweeps the mean live prefix;
    dense bytes are constant down the sweep while kernel bytes scale
    with the live prefix — the acceptance check for the pooled kernel.
    """
    geoms = []
    if cfg.kv_lora_rank:
        d_k = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        geoms.append(("mla-fullkv", max_len, cfg.num_heads, 1,
                      d_k, cfg.kv_lora_rank))
    else:
        geoms.append(("fullkv", max_len, cfg.num_heads, cfg.num_kv_heads,
                      cfg.head_dim, cfg.head_dim))
    flux = getattr(cfg, "flux", None)
    if flux is not None and getattr(flux, "enabled", False):
        ring = min(flux.sink + flux.local, max_len)
        if cfg.kv_lora_rank:
            d_k = cfg.kv_lora_rank + cfg.qk_rope_head_dim
            geoms.append(("mla-ringkv", ring, cfg.num_heads, 1,
                          d_k, cfg.kv_lora_rank))
        else:
            geoms.append(("ringkv", ring, cfg.num_heads,
                          cfg.num_kv_heads, cfg.head_dim, cfg.head_dim))
    report: Dict = {"batch": batch, "block_k": block_k,
                    "dtype_bytes": dtype_bytes, "geometries": {}}
    for name, buf, hq, hkv, dk, dv in geoms:
        rows = []
        for frac in fracs:
            lens = ragged_lengths(batch, int(round(frac * buf)))
            cost = pooled_decode_attn_cost(
                lens, buf, n_q_heads=hq, n_kv_heads=hkv, d_k=dk, d_v=dv,
                block_k=block_k, dtype_bytes=dtype_bytes)
            rows.append({"live_frac": frac, "mean_len":
                         sum(lens) / len(lens), **cost})
        report["geometries"][name] = {
            "buffer_len": buf, "n_q_heads": hq, "n_kv_heads": hkv,
            "d_k": dk, "d_v": dv, "rows": rows}
    return report


def format_pooled_report(report: Dict) -> str:
    out = []
    for name, g in report["geometries"].items():
        out.append(f"{name}: buffer={g['buffer_len']} Hq={g['n_q_heads']} "
                   f"Hkv={g['n_kv_heads']} Dk={g['d_k']} Dv={g['d_v']}")
        out.append(f"  {'live':>6} {'mean_len':>9} {'dense MB':>10} "
                   f"{'kernel MB':>10} {'ratio':>7}")
        for r in g["rows"]:
            out.append(
                f"  {r['live_frac']:>6.3f} {r['mean_len']:>9.1f} "
                f"{r['dense_hbm_bytes'] / 1e6:>10.3f} "
                f"{r['kernel_hbm_bytes'] / 1e6:>10.3f} "
                f"{r['bytes_ratio']:>7.3f}")
    return "\n".join(out)


def main() -> None:
    import argparse
    import json

    from repro.configs import ALL_ARCHS, get_config, smoke_variant

    ap = argparse.ArgumentParser(
        description="Analytic expressed-cost report for pooled decode")
    ap.add_argument("--arch", choices=ALL_ARCHS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--max-len", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--block-k", type=int, default=128)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also dump the report as JSON")
    args = ap.parse_args()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    report = pooled_decode_report(cfg, max_len=args.max_len,
                                  batch=args.batch, block_k=args.block_k)
    print(format_pooled_report(report))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)


if __name__ == "__main__":
    main()
