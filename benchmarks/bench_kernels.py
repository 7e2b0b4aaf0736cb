"""Kernel micro-benchmarks (interpret-mode correctness + XLA-oracle timing
on CPU; real timings require the TPU target).

Covers the substrate kernels (flash attention, linear recurrence) and the
PR-4 HFL kernels (``hfl_ops.score_matrix`` fused fuzzy scoring,
``hfl_ops.sic_rates`` fused NOMA SIC) — for the latter the jnp oracles are
also raced against each other (pairwise vs sorted SIC), since on CPU the
sorted jnp path is the production one and the kernel is the TPU story.

  PYTHONPATH=src python -m benchmarks.bench_kernels [--quick]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core import fuzzy, noma
from repro.kernels import hfl_ops, ops, ref


def _time_us(fn, *args, repeats: int = 5) -> float:
    jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(repeats):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / repeats * 1e6


def bench_hfl_kernels(quick: bool) -> None:
    """Interpret-mode parity + jnp-oracle timings for the HFL kernels."""
    rng = np.random.default_rng(0)
    n, m = (256, 8) if quick else (1024, 16)
    quota = 4

    gains = jnp.asarray(rng.uniform(1e-12, 1e-8, (n, m)))
    counts = jnp.asarray(rng.integers(60, 120, n), jnp.float32)
    stale = jnp.asarray(rng.integers(1, 9, n), jnp.int32)
    oracle = jax.jit(lambda g, c, s: fuzzy.score_matrix(g, c, s,
                                                        data_max=120.0))
    us = _time_us(oracle, gains, counts, stale)
    got = hfl_ops.score_matrix(gains, counts, stale, data_max=120.0,
                               interpret=True)
    err = float(jnp.max(jnp.abs(got - oracle(gains, counts, stale))))
    emit(f"hfl_score_{n}x{m}", us,
         {"interpret_maxerr": f"{err:.2e}", "rows": n * m,
          "note": "oracle-XLA time on CPU"})

    p = jnp.asarray(rng.uniform(0.01, 0.1, n))
    mask_np = np.zeros((n, m), bool)
    for j in range(m):
        mask_np[rng.choice(n, quota, replace=False), j] = True
    mask = jnp.asarray(mask_np)
    noise = noma.noise_power_w(-174.0, 1e6)

    def pairwise(p_, g_, mk_):
        def per_edge(j):
            return noma.achievable_rates(p_, g_[:, j], bandwidth_hz=1e6,
                                         noise_w=noise, mask=mk_[:, j])
        return jax.vmap(per_edge)(jnp.arange(m)).T

    f_pair = jax.jit(pairwise)
    f_sorted = jax.jit(lambda p_, g_, mk_: noma.sic_rates_matrix(
        p_, g_, mk_, bandwidth_hz=1e6, noise_w=noise))
    f_topk = jax.jit(lambda p_, g_, mk_: noma.sic_rates_matrix(
        p_, g_, mk_, bandwidth_hz=1e6, noise_w=noise, max_per_edge=quota))
    pair_us = _time_us(f_pair, p, gains, mask)
    sorted_us = _time_us(f_sorted, p, gains, mask)
    topk_us = _time_us(f_topk, p, gains, mask)
    got = hfl_ops.sic_rates(p, gains, mask, bandwidth_hz=1e6,
                            noise_w=noise, interpret=True)
    err = float(jnp.max(jnp.abs(got - f_pair(p, gains, mask))))
    emit(f"hfl_sic_{n}x{m}", pair_us,
         {"interpret_maxerr": f"{err:.2e}",
          "sorted_us": round(sorted_us, 1), "topk_us": round(topk_us, 1),
          "sorted_speedup": round(pair_us / max(sorted_us, 1e-9), 1),
          "topk_speedup": round(pair_us / max(topk_us, 1e-9), 1),
          "note": "pairwise-XLA time on CPU"})


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller shapes (CI smoke)")
    args = ap.parse_args(argv)

    key = jax.random.key(0)
    ks = jax.random.split(key, 3)
    b, s, h, kv, d = (1, 256, 4, 2, 64) if args.quick else (1, 512, 4, 2, 64)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv, d), jnp.float32)

    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    oracle = jax.jit(lambda q_, k_, v_: ref.attention_ref(q_, k_, v_,
                                                          causal=True))
    oracle(qt, kt, vt).block_until_ready()
    t0 = time.time()
    for _ in range(5):
        oracle(qt, kt, vt).block_until_ready()
    oracle_us = (time.time() - t0) / 5 * 1e6

    out = ops.flash_attention(q, k, v, causal=True, interpret=True)
    want = oracle(qt, kt, vt).transpose(0, 2, 1, 3)
    err = float(jnp.max(jnp.abs(out - want)))
    emit(f"flash_attention_{s}", oracle_us,
         {"interpret_maxerr": f"{err:.2e}",
          "flops": 4 * b * h * s * s * d, "note": "oracle-XLA time on CPU"})

    t = 512 if args.quick else 1024
    la = -jax.random.uniform(ks[0], (1, t, 256), jnp.float32, 0.01, 1.0)
    x = jax.random.normal(ks[1], (1, t, 256), jnp.float32)
    lr_oracle = jax.jit(ref.linear_recurrence_ref)
    lr_oracle(la, x).block_until_ready()
    t0 = time.time()
    for _ in range(5):
        lr_oracle(la, x).block_until_ready()
    lr_us = (time.time() - t0) / 5 * 1e6
    out = ops.linear_recurrence(la, x, interpret=True)
    err = float(jnp.max(jnp.abs(out - lr_oracle(la, x))))
    emit(f"linear_recurrence_{t}", lr_us,
         {"interpret_maxerr": f"{err:.2e}",
          "bytes": 3 * la.size * 4, "note": "oracle-XLA time on CPU"})

    bench_hfl_kernels(args.quick)


if __name__ == "__main__":
    main()
