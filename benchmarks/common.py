"""Shared benchmark plumbing: CSV emission, timing statistics, provenance
stamping and the paper's simulation configs."""
from __future__ import annotations

import dataclasses
import datetime
import os
import platform
import subprocess
import time
from typing import Callable, Dict

from repro.configs.hfl_mnist import CONFIG

# A budget-friendly variant of the paper's 64-client setup for CI-speed runs;
# pass full=True for the paper-faithful topology.  mu/delta raised so τ₁=3,
# τ₂=6 give the classifier a real training signal per global round.
# 12/48 = 25% participation per round, the paper's 16/64 scarcity ratio.
SMALL = dataclasses.replace(CONFIG, n_clients=48, n_edges=4,
                            clients_per_edge=3, min_samples=100,
                            max_samples=400, hidden=64, input_dim=196,
                            mu_const=4.0, delta_const=2.0)


def emit(name: str, us_per_call: float, derived: Dict) -> str:
    kv = ";".join(f"{k}={v}" for k, v in derived.items())
    line = f"{name},{us_per_call:.1f},{kv}"
    print(line, flush=True)
    return line


def median_rps(fn: Callable, rounds: int, repeats: int = 3,
               warm: bool = True) -> float:
    """Median-of-k rounds/sec of a jax driver call.

    Single-shot driver timings are scheduler-noise limited on this host
    (BENCH_sweeps.json once recorded a *negative* dynamic-scenario
    overhead from exactly that); the median over k runs is what every
    BENCH_*.json records.  ``warm`` runs the callable once first so the
    compile never lands in a timed sample.
    """
    import jax
    if warm:
        jax.block_until_ready(fn())
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        samples.append(rounds / (time.perf_counter() - t0))
    samples.sort()
    return samples[len(samples) // 2]


def provenance() -> Dict[str, object]:
    """Recording-host identity stamped into every BENCH_*.json so the
    perf trajectory stays interpretable across machines: git sha, jax
    version, backend, device count, platform, ISO timestamp."""
    import jax
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__))).stdout.strip()
    except (OSError, subprocess.SubprocessError):
        sha = ""
    return {
        "git_sha": sha or None,
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
    }
