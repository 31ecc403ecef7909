#!/usr/bin/env python3
"""Reads the check's number for the program and for its control, on
the chip, at the cell's own size and load, for several seeds in one
process (set-up is paid once per seed, compiles once).

    python3 bench/control.py --workload phi3-chat --seconds 20 --seeds 11 12 13

The control is the configuration's plain reference (``spec.reference``)
computed in float8 (``fp8=True``) put in the program's place: at each
position of the same prompts and served tokens, the token it puts
first is read against the float32 reference.  Both the program's
numbers and the control's go through the result line's own verdict
(``run.result_line`` with the cell's limits); each seed prints both
verdicts, and the control's has to read ``correct: false``.  A limit
lies between the largest reading of sound runs and the smallest
reading of the control.  The benchmark's own runs never run the
control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    from bench.spec import use_compile_cache
    use_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU; nothing was run", file=sys.stderr)
        return 2
    from bench.check import as_control
    from bench.loop import run_cell
    from bench.run import result_line
    from bench.spec import load_cell
    cell = load_cell(args.workload)
    for seed in args.seeds:
        t0 = time.perf_counter()
        run, numbers, limits = run_cell(cell, seed=seed,
                                        seconds=args.seconds, trace=False,
                                        t_start=t0, control=True)
        verdict = {who: result_line(run, nums, limits, False,
                                    log=lambda s: None)
                   for who, nums in (("program", numbers),
                                     ("control", as_control(numbers)))}
        print("control " + json.dumps({
            "workload": cell.name, "seed": seed,
            "program_correct": verdict["program"]["correct"],
            "control_correct": verdict["control"]["correct"],
            "checks": verdict["control"]["checks"], **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
