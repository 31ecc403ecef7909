"""Operations and bytes the served work needs, from shapes alone.

"Necessary" work is what the arithmetic requires, whatever implements
it: each live K/V byte is read once per slot, step and KV head (Hkv,
not Hq), each query and output once; FLOPs are 2·Hq·live·(Dk+Dv) per
query row.  A kernel that re-reads K/V for every query head therefore
shows a lower roofline share, never less work.

``Geometry`` describes the attention of one configuration as one chip
of the cell holds it: on a cell of ``chips`` > 1 the heads are split
over the chips (tensor parallel), so the kernel time read from one
chip's trace plane is set against one chip's share of the heads.  The
counts take per-request positions recorded on the host while serving.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Optional, Sequence, Tuple

BF16 = 2


@dataclass(frozen=True)
class Geometry:
    n_heads: int          # Hq / chips: one chip's query heads
    n_kv_heads: int       # Hkv / chips, or Hkv where chips does not divide it
    head_dim: int         # Dk = Dv
    pattern: Tuple[str, ...]   # per layer "fa" | "sa"
    sink: int
    local: int
    matmul_params: int    # per token, all layers, without embedding/head
    head_params: int      # d_model · vocab (the output projection)
    dtype_bytes: int = BF16
    chips: int = 1        # chips that share each layer

    @property
    def ring(self) -> int:
        return self.sink + self.local

    def visible(self, layer: int, live: int) -> int:
        """Keys a query at live-prefix length ``live`` attends to."""
        return live if self.pattern[layer] == "fa" else min(live, self.ring)


def geometry(cfg, pattern, chips: int = 1,
             matmul_params: Optional[int] = None) -> Geometry:
    """The attention geometry of ``cfg`` as one of ``chips`` holds it:
    the query heads split over the chips, the KV heads too where
    ``chips`` divides them and otherwise whole on each chip, as the
    serving pools keep them (``repro.distributed.pool_sharding``).
    ``matmul_params`` is the count of a configuration's own reference
    (its ``matmul_params(conf)``); without it the dense block's."""
    if cfg.num_heads % chips:
        raise ValueError(f"{cfg.num_heads} query heads do not split over "
                         f"{chips} chips")
    hkv = cfg.num_kv_heads
    d, hd = cfg.d_model, cfg.head_dim
    if matmul_params is None:
        matmul_params = cfg.num_layers * (
            d * cfg.num_heads * hd * 2 + d * hkv * hd * 2 + 3 * d * cfg.d_ff)
    return Geometry(cfg.num_heads // chips,
                    hkv // chips if hkv % chips == 0 else hkv, hd,
                    tuple(pattern), cfg.flux.sink, cfg.flux.local,
                    matmul_params, d * cfg.vocab_size, chips=chips)


def decode_attn(g: Geometry, lives: Sequence[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one chip's share of one decode step's
    attention, all layers, for slots whose live lengths (after the
    step's insert) are ``lives``."""
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
    """(FLOPs, bytes) of one chip's share of a prefill chunk's attention
    on the FA layers: ``size`` queries at [start, start+size) over the
    causal prefix."""
    D = g.head_dim
    n_fa = sum(p == "fa" for p in g.pattern)
    flops = 2.0 * g.n_heads * _causal_keys(start, size) * (D + D) * n_fa
    nbytes = (2 * g.n_heads * size * D
              + g.n_kv_heads * (start + size) * (D + D)) * g.dtype_bytes * n_fa
    return flops, nbytes


def model_flops(g: Geometry, positions: Iterable[int],
                logits_rows: int) -> float:
    """Model FLOPs for tokens at absolute ``positions``, on all chips:
    2 × matmul parameters each, the attention of every query head that
    the routed pattern needs (causal on FA layers, at most sink + local
    keys on SA layers), and the output projection for the
    ``logits_rows`` rows whose logits serving needs."""
    D, total, hq = g.head_dim, 0.0, g.n_heads * g.chips
    n_fa = sum(p == "fa" for p in g.pattern)
    n_sa = len(g.pattern) - n_fa
    for p in positions:
        live = p + 1
        keys = n_fa * live + n_sa * min(live, g.ring)
        total += 2.0 * g.matmul_params + 2.0 * hq * keys * (D + D)
    return total + 2.0 * g.head_params * logits_rows


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> Tuple[float, str]:
    """(percent of the roofline, the bound that sets it)."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
