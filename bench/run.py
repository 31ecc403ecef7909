#!/usr/bin/env python3
"""Runs one benchmark cell on the chip and prints its result line.

    python3 bench/run.py --workload phi3-chat --seed 1 --seconds 40 --trace 0

The cell, its configuration, traffic mix and operating point are found
by name from ``BENCHMARK.json`` (``bench/spec.py``).  With ``--trace 0``
the result holds the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window by the
readers in ``bench/metrics/``.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` when traced), then ``checks``, each
number compared with its limit.  The run exits non-zero and prints no
result when JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _num(x: float):
    return x if math.isfinite(x) else None


def end_to_end(run) -> dict:
    """The user-facing numbers of a run, over the window: from its open
    to the first ``step()`` return after ``--seconds``."""
    from bench import stats
    logs = run.logs.values()
    t0, t_end = run.window
    itl = [b - a for lg in logs for a, b in zip(lg.token_t, lg.token_t[1:])]
    delivered = sum(len(lg.token_t) for lg in logs)
    prefilled = sum(lg.prefilled for lg in logs)
    return {
        "itl_p95_ms": 1e3 * stats.pct(itl, 95),
        "output_tok_s": delivered / (t_end - t0),
        "total_tok_s": (prefilled + delivered) / (t_end - t0),
        "setup_s": run.setup_s,
    }


def _reader(name: str):
    from bench.spec import load_module
    return load_module(os.path.join(ROOT, "bench", "metrics", name + ".py"),
                       f"bench_metric_{name}").read


def result_line(run, numbers: dict, limits: dict, trace: bool,
                log=print) -> dict:
    cell = run.cell
    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = _reader(m["name"])(run)
            if value is None:
                log(f"metric {m['name']}: nothing to read in this run")
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = end_to_end(run)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": _num(e2e[m["name"]]),
                                  "unit": m["unit"]}
    device = dict(run.device, memory_peak_bytes=run.memory["peak"])
    out = {"correct": all(numbers[k] <= v for k, v in limits.items()),
           "attempted": len(run.logs),
           "failed": run.failed(),
           "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        tr = run.trace
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        from bench.readers import breakdown_ops
        out["breakdown"] = {"device_ops": breakdown_ops(run),
                            "idle_gaps": [[n, s] for n, s in tr.gaps[:10]]}
    out["checks"] = {k: {"value": numbers[k], "limit": v}
                     for k, v in limits.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def err(msg: str) -> int:
        print(f"bench: {msg}", file=sys.stderr)
        return 2

    from bench.spec import load_cell
    try:
        cell = load_cell(args.workload)
    except (OSError, KeyError) as e:
        return err(f"cannot load workload {args.workload!r}: {e}")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return err("the program under test (src/repro) is not in this "
                   "checkout; nothing was run")

    import jax
    from bench.spec import use_compile_cache
    use_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return err(f"no TPU (JAX platform is {devices[0].platform!r}); "
                   f"nothing was run")
    if len(devices) < cell.chips:
        return err(f"{cell.name} needs {cell.chips} chips, JAX sees "
                   f"{len(devices)}")

    from bench.loop import run_cell

    def log(s: str) -> None:
        print(s, flush=True)

    log(f"cell {cell.name}: {cell.config['name']} on "
        f"{devices[0].device_kind} x{len(devices)}, seed {args.seed}, "
        f"{args.seconds:g}s window, trace {args.trace}")
    run, numbers, limits = run_cell(cell, seed=args.seed,
                                    seconds=args.seconds,
                                    trace=bool(args.trace),
                                    t_start=T_START, log=log)
    out = result_line(run, numbers, limits, bool(args.trace), log=log)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']:.6g} (limit {c['limit']:g})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
