#!/usr/bin/env python3
"""The readings the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 11,12,13 [--out F]

For each seed, in one process (set-up compiles once): build the cell as
a run does, drive a short window, then compare with the reference the
program's output ("program"), the reference at the configuration's
``control`` precision in the program's place ("control") and each planted
fault of the driver's ``FAULTS``.  One JSON
line per seed and mode, to standard output and to ``--out``.  The limits
in ``bench/traffic/<mix>.json`` lie between the largest "program"
reading and the smallest reading of the control and of the faults.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--window", type=float, default=1.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import run as bench_run
    plan = bench_run.cell_plan(args.workload)
    bench_run.enable_cache()
    import jax
    devs = bench_run.devices_for(int(plan["cell"]["chips"]))
    modes = bench_run.load_driver(plan["traffic"]["driver"]).modes()
    out = open(args.out, "a") if args.out else None
    precision = plan["config"]["matmul_precision"]
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            cell = bench_run.build(plan, seed, devs)
            with jax.default_matmul_precision(precision):
                cell.setup()
                t1 = time.perf_counter()
                calls, elapsed = bench_run.window(cell, args.window)
                cell.release()
                t2 = time.perf_counter()
                readings = cell.check(modes)
            t3 = time.perf_counter()
            for mode, numbers in readings.items():
                row = {"workload": args.workload, "seed": seed, "mode": mode,
                       "numbers": numbers, "setup_s": t1 - t0,
                       "rate": calls * cell.ops_per_call / elapsed,
                       "check_s": t3 - t2}
                line = json.dumps(row)
                print(line, flush=True)
                if out:
                    out.write(line + "\n")
                    out.flush()
            del cell
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
