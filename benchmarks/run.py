"""Benchmark entry point: one module per paper figure + kernels + roofline.

  PYTHONPATH=src python -m benchmarks.run [--quick]

Each line: ``name,us_per_call,key=value;...`` CSV.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer rounds/episodes")
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)

    from repro import compile_cache
    compile_cache.enable()

    from benchmarks import (bench_ddpg, bench_kernels, bench_roofline,
                            bench_rounds, bench_sweeps, fig_avg_ms,
                            fig_cost_vs_dn, fig_cost_vs_nm, fig_ddpg_cost,
                            fig_hfl_convergence)
    rounds = 4 if args.quick else 16
    episodes = 6 if args.quick else 15
    suites = [
        ("bench_rounds",
         lambda: bench_rounds.main(["--quick"] if args.quick else [])),
        ("bench_sweeps",
         lambda: bench_sweeps.main(["--quick"] if args.quick else [])),
        ("bench_ddpg",
         lambda: bench_ddpg.main(["--quick"] if args.quick else [])),
        ("fig_hfl_convergence", lambda: fig_hfl_convergence.main(rounds)),
        ("fig_avg_ms", lambda: fig_avg_ms.main(rounds)),
        ("fig_ddpg_cost", lambda: fig_ddpg_cost.main(episodes)),
        ("fig_cost_vs_nm", fig_cost_vs_nm.main),
        ("fig_cost_vs_dn", fig_cost_vs_dn.main),
        ("bench_kernels",
         lambda: bench_kernels.main(["--quick"] if args.quick else [])),
        ("bench_roofline", bench_roofline.main),
    ]
    failed = 0
    for name, fn in suites:
        if args.only and args.only != name:
            continue
        print(f"# === {name} ===", flush=True)
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failed += 1
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
