"""Operations and bytes the served work needs, from shapes alone.

"Necessary" work is what the arithmetic requires, whatever implements
it: each live K/V byte is read once per slot, step and KV head (Hkv,
not Hq), each query and output once; FLOPs are 2·Hq·live·(Dk+Dv) per
query row.  A kernel that re-reads K/V for every query head therefore
shows a lower roofline share, never less work.

``Geometry`` describes the attention of one configuration; the counts
take per-request positions recorded on the host while serving.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Sequence, Tuple

BF16 = 2


@dataclass(frozen=True)
class Geometry:
    n_heads: int          # Hq
    n_kv_heads: int       # Hkv
    head_dim: int         # Dk = Dv
    pattern: Tuple[str, ...]   # per layer "fa" | "sa"
    sink: int
    local: int
    matmul_params: int    # per token, all layers, without embedding/head
    head_params: int      # d_model · vocab (the output projection)
    dtype_bytes: int = BF16

    @property
    def ring(self) -> int:
        return self.sink + self.local

    def visible(self, layer: int, live: int) -> int:
        """Keys a query at live-prefix length ``live`` attends to."""
        return live if self.pattern[layer] == "fa" else min(live, self.ring)


def geometry(cfg, pattern) -> Geometry:
    d, hd = cfg.d_model, cfg.head_dim
    per_layer = (d * cfg.num_heads * hd * 2 + d * cfg.num_kv_heads * hd * 2
                 + 3 * d * cfg.d_ff)
    return Geometry(cfg.num_heads, cfg.num_kv_heads, hd, tuple(pattern),
                    cfg.flux.sink, cfg.flux.local,
                    per_layer * cfg.num_layers, d * cfg.vocab_size)


def decode_attn(g: Geometry, lives: Sequence[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step's attention, all layers, for
    slots whose live lengths (after the step's insert) are ``lives``."""
    D, flops, nbytes = g.head_dim, 0.0, 0.0
    for layer in range(len(g.pattern)):
        for live in lives:
            n = g.visible(layer, live)
            flops += 2.0 * g.n_heads * n * (D + D)
            nbytes += g.n_kv_heads * n * (D + D) * g.dtype_bytes
            nbytes += 2 * g.n_heads * D * g.dtype_bytes   # q in, o out
    return flops, nbytes


def _causal_keys(start: int, size: int) -> int:
    """Σ over query rows start..start+size-1 of (row + 1)."""
    return size * start + size * (size + 1) // 2


def prefill_attn(g: Geometry, start: int, size: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of a prefill chunk's attention on the FA layers:
    ``size`` queries at [start, start+size) over the causal prefix."""
    D = g.head_dim
    n_fa = sum(p == "fa" for p in g.pattern)
    flops = 2.0 * g.n_heads * _causal_keys(start, size) * (D + D) * n_fa
    nbytes = (2 * g.n_heads * size * D
              + g.n_kv_heads * (start + size) * (D + D)) * g.dtype_bytes * n_fa
    return flops, nbytes


def model_flops(g: Geometry, positions: Iterable[int],
                logits_rows: int) -> float:
    """Model FLOPs for tokens at absolute ``positions``: 2 × matmul
    parameters each, the attention the routed pattern needs (causal on
    FA layers, at most sink + local keys on SA layers), and the output
    projection for the ``logits_rows`` rows whose logits serving needs."""
    D, total = g.head_dim, 0.0
    n_fa = sum(p == "fa" for p in g.pattern)
    n_sa = len(g.pattern) - n_fa
    for p in positions:
        live = p + 1
        keys = n_fa * live + n_sa * min(live, g.ring)
        total += 2.0 * g.matmul_params + 2.0 * g.n_heads * keys * (D + D)
    return total + 2.0 * g.head_params * logits_rows


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> Tuple[float, str]:
    """(percent of the roofline, the bound that sets it)."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
