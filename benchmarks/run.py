"""Benchmark entry point: one module per paper figure + kernels + roofline.

  PYTHONPATH=src python -m benchmarks.run [--quick]
  PYTHONPATH=src python -m benchmarks.run --profile results/profile

Each line: ``name,us_per_call,key=value;...`` CSV.  ``--profile DIR``
skips the suites and instead captures a stage-annotated device profile
(``jax.profiler.trace``) of a scanned round-engine workload — the
``hfl/associate`` … ``hfl/eval`` spans from ``repro.telemetry.spans``
segment the scan program by paper stage in TensorBoard/XProf.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def _profile(out_dir: str, quick: bool) -> int:
    import dataclasses

    from repro.configs.hfl_mnist import CONFIG
    from repro.core import engine
    from repro.telemetry import spans

    n, m = (256, 8) if quick else (1024, 16)
    cfg = dataclasses.replace(CONFIG, n_clients=n, n_edges=m,
                              clients_per_edge=4, min_samples=60,
                              max_samples=120, hidden=16, input_dim=32,
                              local_batch=16)
    spec = engine.EngineSpec(policy="gcea", scheduler="fastest")
    state, bundle, _ = engine.init_simulation(cfg, seed=0)
    rounds = 3 if quick else 5
    spans.profile_scanned(cfg, spec, state, bundle, rounds, out_dir)
    print(f"profile ({n}x{m}, {rounds} rounds) written to {out_dir}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer rounds/episodes")
    ap.add_argument("--only", default=None)
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a stage-annotated jax.profiler trace of "
                         "the scanned round engine into DIR, then exit")
    args = ap.parse_args(argv)

    from repro import compile_cache
    compile_cache.enable()
    if args.profile:
        return _profile(args.profile, args.quick)

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
