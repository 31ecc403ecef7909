import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
for the production meshes, prove memory/sharding coherence, and record
the roofline source numbers (EXPERIMENTS.md §Dry-run).

The lines above MUST precede any jax import — jax locks the platform
and device count at first init.  The dry-run is a CPU-only tool: it
never claims an attached accelerator.  Run:

    PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-mini-3.8b \
        --shape decode_32k [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all   # the full matrix
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.configs import ALL_ARCHS, SHAPES, get_config  # noqa: E402
from repro.distributed import logical_rules  # noqa: E402
from repro.launch import hlo_analysis as HA  # noqa: E402
from repro.launch import workloads as WL  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                         "artifacts", "dryrun")


def run_one(arch: str, shape_name: str, multi_pod: bool,
            out_dir: str = ARTIFACTS, save_hlo: bool = False,
            variant: str = "", causal_split: int = 0, **wl_kw) -> dict:
    cfg = get_config(arch)
    if causal_split:
        cfg = cfg.replace(causal_split_depth=causal_split)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape)
        + (f"_{variant}" if variant else ""),
        "n_chips": int(n_chips), "ok": False,
    }
    t0 = time.time()
    try:
        wl = WL.build_workload(cfg, shape, mesh, **wl_kw)
        record["workload"] = wl.name
        with jax.set_mesh(mesh), logical_rules(wl.rules):
            lowered = jax.jit(wl.fn, in_shardings=wl.in_shardings).lower(
                *wl.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            hlo = compiled.as_text()
        record["t_lower_s"] = round(t_lower, 1)
        record["t_compile_s"] = round(t_compile, 1)
        record["memory"] = HA.memory_summary(compiled)
        record["roofline"] = HA.roofline_terms(
            compiled, hlo, n_chips, wl.model_flops,
            memory=record["memory"])
        record["ok"] = True
        if save_hlo:
            os.makedirs(out_dir, exist_ok=True)
            with open(os.path.join(
                    out_dir, f"{arch}_{shape_name}"
                    f"_{record['mesh']}.hlo.txt"), "w") as f:
                f.write(hlo)
    except Exception as e:  # noqa: BLE001 — recorded, not swallowed
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-2000:]
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir,
                        f"{arch}_{shape_name}_{record['mesh']}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2, default=str)
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ALL_ARCHS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="full (arch × shape) matrix on this mesh")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--out", default=ARTIFACTS)
    ap.add_argument("--distributed-kv", action="store_true",
                    help="shard_map LSE-combine decode over "
                         "sequence-sharded KV (§Perf optimized variant)")
    ap.add_argument("--decode-msr", type=float, default=0.5)
    ap.add_argument("--decode-tp", action="store_true",
                    help="serving-style full-TP weight sharding "
                         "(no per-step FSDP weight gathers; §Perf)")
    ap.add_argument("--causal-split", type=int, default=0,
                    help="recursive causal split depth for expressed-"
                         "FLOP reduction (§Perf optimized variant)")
    ap.add_argument("--no-seq-shard", action="store_true",
                    help="train ablation: replicate the residual stream "
                         "instead of Megatron-SP seq sharding")
    args = ap.parse_args()

    pairs = ([(a, s) for a in ALL_ARCHS for s in SHAPES]
             if args.all else [(args.arch, args.shape)])
    n_ok = 0
    for arch, shape in pairs:
        wl_kw = {}
        variant = ""
        if SHAPES[shape].kind == "decode":
            if args.distributed_kv:
                wl_kw["distributed_kv"] = True
                variant = "distkv"
            if args.decode_tp:
                wl_kw["decode_tp"] = True
                variant = (variant + "_tp").strip("_")
            if args.decode_msr != 0.5:
                wl_kw["msr"] = args.decode_msr
                variant = (variant + f"_msr{args.decode_msr}").strip("_")
        elif args.causal_split:
            variant = f"csplit{args.causal_split}"
        if SHAPES[shape].kind == "train" and args.no_seq_shard:
            wl_kw["seq_shard"] = False
            variant = (variant + "_noseqshard").strip("_")
        r = run_one(arch, shape, args.multi_pod, args.out,
                    save_hlo=args.save_hlo, variant=variant,
                    causal_split=args.causal_split, **wl_kw)
        status = "OK " if r["ok"] else "FAIL"
        extra = ""
        if r["ok"]:
            rl = r["roofline"]
            extra = (f"compute={rl['t_compute_s']:.3e}s "
                     f"mem={rl['t_memory_s']:.3e}s "
                     f"coll={rl['t_collective_s']:.3e}s "
                     f"bottleneck={rl['bottleneck']}")
        else:
            extra = r.get("error", "")[:160]
        print(f"[{status}] {arch:24s} {shape:12s} mesh={r['mesh']:10s} "
              f"{extra}", flush=True)
        n_ok += r["ok"]
    print(f"{n_ok}/{len(pairs)} passed")
    if n_ok != len(pairs):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
