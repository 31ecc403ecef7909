# One function per paper table. Print ``name,us_per_call,derived`` CSV.
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import (bench_continued_training,  # noqa: E402
                        bench_continuous_batching, bench_data_balance,
                        bench_decode_speedup, bench_degraded_mode,
                        bench_head_vs_layer, bench_longbench_proxy,
                        bench_prefill_speedup, bench_prefix_cache,
                        bench_router_overhead, bench_ruler_proxy,
                        bench_sparsity_sweep, bench_target_sparsity,
                        roofline)
from benchmarks.common import CACHE_DIR  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

BENCHES = [
    ("Table1/LongBench-E", bench_longbench_proxy),
    ("Table2/RULER", bench_ruler_proxy),
    ("Fig1a/sparsity-collapse", bench_sparsity_sweep),
    ("Fig1b+3b/head-vs-layer-decode", bench_head_vs_layer),
    ("Fig3a/prefill-speedup", bench_prefill_speedup),
    ("Fig5/target-sparsity", bench_target_sparsity),
    ("Fig6/continued-training", bench_continued_training),
    ("Fig7/data-balance", bench_data_balance),
    ("Fig9/router-overhead", bench_router_overhead),
    ("Serving/decode-speedup", bench_decode_speedup),
    ("Serving/continuous-batching", bench_continuous_batching),
    ("Serving/prefix-cache", bench_prefix_cache),
    ("Serving/degraded-mode", bench_degraded_mode),
    ("Roofline", roofline),
]


def main() -> None:
    only = sys.argv[1] if len(sys.argv) > 1 else None
    enable_compile_cache()
    out_lines = ["name,us_per_call,derived"]
    print("name,us_per_call,derived")
    for label, mod in BENCHES:
        if only and only not in label:
            continue
        t0 = time.time()
        try:
            rows = mod.run()
        except Exception as e:  # noqa: BLE001
            print(f"{label}/ERROR,0.00,{type(e).__name__}: {e}")
            raise
        for r in rows:
            print(r.csv(), flush=True)
            out_lines.append(r.csv())
        print(f"# {label} done in {time.time() - t0:.1f}s", flush=True)
    # every BENCH_*.json a bench writes already lands under CACHE_DIR
    # (an absolute artifacts/bench/ path); the summary CSV goes to the
    # same place so CI uploads the directory as one artifact
    os.makedirs(CACHE_DIR, exist_ok=True)
    with open(os.path.join(CACHE_DIR, "results.csv"), "w") as f:
        f.write("\n".join(out_lines) + "\n")


if __name__ == "__main__":
    main()
