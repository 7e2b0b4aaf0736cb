#!/usr/bin/env python3
"""Start the NOMA-HFL round engine on a TPU and check what it computes.

    python chip_smoke.py                # one chip: phases A and B
    python chip_smoke.py --four-chips   # four chips: the sharded drivers only

Phase A runs the paper's pipeline at the full width of
``configs/hfl_mnist.CONFIG`` (64 clients, 4 edges, N_m = 4, a 784-128-128-10
MLP, 200-1200 samples per client) through ``HFLSimulation``: a short DDPG
allocator training, ten scanned rounds billed by that actor, two eager
rounds from the same starting state, and round 1 again on the host's CPU
backend.  Phase B compiles the three Pallas kernels of ``kernels/hfl_ops.py``
for the chip, at the CONFIG widths and at 1024 x 16, and compares each with
its jnp reference.  ``--four-chips`` runs only the client-sharded and the
fleet-sharded drivers over a mesh of four chips against the unsharded
drivers on one.

There is no CPU fallback: without a TPU the script exits nonzero before any
work.  A failed check fails its phase, and a failed phase ends the run with
a nonzero exit.  The last line of a passing run is one JSON object naming
the device.  Each step prints its wall time split into compile (JAX's
backend-compile events; a persistent-cache hit counts as its read) and the
rest (tracing, lowering, host work and the run).  These are host-clock
times of a smoke run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import contextlib
import copy
import importlib.metadata
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ROUNDS = 10          # scanned rounds in phase A
EAGER_ROUNDS = 2     # eager rounds replayed from the same start
# Eager and scanned rounds run the same ops on the same chip; only XLA's
# fusion (and so the f32 summation order) may differ between the two
# programs.
EAGER_RTOL = 1e-3
# Round 1 on the chip against round 1 on the host CPU; the association is
# discrete and must be identical.  At default precision the TPU feeds f32
# matmuls to the MXU as bf16 (relative rounding 2^-8), in the DDPG actor
# and in all three layers of the classifier, so loss and cost agree only to
# bf16 accuracy.  Round 1 is also rerun on the chip at highest matmul
# precision, which must agree with the CPU to f32 summation order.
CPU_RTOL = {"default": 1e-2, "highest": 1e-4}

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_s = [0.0]
_cache = {"hits": 0, "misses": 0}


def _on_duration(event, secs, **_):
    if event == _COMPILE_EVENT:
        _compile_s[0] += secs


def _on_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        _cache["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _cache["misses"] += 1


@contextlib.contextmanager
def timed(label: str):
    """Print ``label``'s wall time, split into XLA compile and the rest
    (tracing, lowering, host work and the run itself)."""
    c0, t0 = _compile_s[0], time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    comp = _compile_s[0] - c0
    print(f"time {label}: wall {wall:.3f} s = compile {comp:.3f} s "
          f"+ trace/run {wall - comp:.3f} s", flush=True)


class Phase:
    """Collects a phase's checks; ``close()`` fails the phase if any
    failed, after every check has printed what it measured."""

    def __init__(self, name: str):
        self.name, self.failed = name, []

    def check(self, ok: bool, what: str) -> None:
        print(f"{'ok  ' if ok else 'FAIL'} {self.name}: {what}", flush=True)
        if not ok:
            self.failed.append(what)

    def close_to(self, what, got, want, *, rtol, atol=0.0) -> None:
        got = np.asarray(got, np.float64)
        want = np.asarray(want, np.float64)
        err = np.abs(got - want)
        rel = float(np.max(err / np.maximum(np.abs(want), 1e-30)))
        ok = got.shape == want.shape and bool(
            np.all(err <= atol + rtol * np.abs(want)))
        self.check(ok, f"{what} max|d|={float(np.max(err)):.3e} "
                       f"max rel={rel:.3e} (rtol {rtol:g}, atol {atol:g})")

    def equal(self, what, got, want) -> None:
        got, want = np.asarray(got), np.asarray(want)
        self.check(got.shape == want.shape and bool(np.all(got == want)),
                   f"{what} identical")

    def close(self) -> None:
        if self.failed:
            raise SystemExit(f"phase {self.name} failed: "
                             + "; ".join(self.failed))
        print(f"phase {self.name} passed", flush=True)


def _metrics(rows, field):
    return np.asarray([getattr(r, field) for r in rows])


def phase_a() -> None:
    """The paper's pipeline at full width through ``HFLSimulation``."""
    from repro.configs.hfl_mnist import CONFIG
    from repro.core import engine
    from repro.core.hfl import HFLSimulation

    ph = Phase("A")
    with timed("A init_simulation (host data)"):
        sim = HFLSimulation(CONFIG, policy="fcea", allocator="ddpg",
                            scheduler="pdd")
    with timed("A train_ddpg"):
        hist = sim.train_ddpg(episodes=4, steps_per_episode=16, warmup=32)
    ph.check(bool(np.all(np.isfinite(hist["episode_reward"]))),
             f"DDPG episode rewards finite {hist['episode_reward']}")
    state0 = sim.state
    start = copy.copy(sim)          # holds state0 until it runs
    with timed(f"A run_scanned {ROUNDS} rounds"):
        scanned = sim.run_scanned(ROUNDS)
    with timed(f"A run (eager) {EAGER_ROUNDS} rounds"):
        eager = start.run(EAGER_ROUNDS)

    for r in scanned:
        print(f"  round {r.round:2d} acc={r.accuracy:.4f} loss={r.loss:.5f} "
              f"cost={r.cost:.5f} assoc={r.n_associated} "
              f"z={r.z.astype(int).tolist()}", flush=True)
    for field in ("accuracy", "loss", "avg_staleness", "total_time_s",
                  "total_energy_j", "cost"):
        ph.check(bool(np.all(np.isfinite(_metrics(scanned, field)))),
                 f"scanned {field} finite")
    ph.equal("scanned round numbers", _metrics(scanned, "round"),
             np.arange(1, ROUNDS + 1))

    head = scanned[:EAGER_ROUNDS]
    for field in ("z", "n_associated", "n_available"):
        ph.equal(f"eager vs scanned {field}", _metrics(eager, field),
                 _metrics(head, field))
    for field in ("loss", "cost", "total_time_s", "total_energy_j"):
        ph.close_to(f"eager vs scanned {field}", _metrics(eager, field),
                    _metrics(head, field), rtol=EAGER_RTOL)
    ph.close_to("eager vs scanned accuracy", _metrics(eager, "accuracy"),
                _metrics(head, "accuracy"), rtol=0.0, atol=2e-3)

    cpu = jax.devices("cpu")[0]
    st, bu, actor = jax.device_put((state0, sim.bundle, sim.agent.actor),
                                   cpu)
    with timed("A round 1 on the host CPU"):
        _, m_cpu = engine.run_scanned(CONFIG, sim.spec, st, bu, 1, actor)
    ref = engine.metrics_row(jax.tree.map(np.asarray, m_cpu), 0)
    with jax.default_matmul_precision("highest"):
        with timed("A round 1 on the chip at highest precision"):
            _, m_hi = engine.run_scanned(CONFIG, sim.spec, state0,
                                         sim.bundle, 1, sim.agent.actor)
    chip = {"default": vars(scanned[0]),
            "highest": engine.metrics_row(jax.tree.map(np.asarray, m_hi),
                                          0)}
    for prec, row in chip.items():
        for field in ("z", "n_associated", "n_available"):
            ph.equal(f"chip ({prec}) vs CPU round 1 {field}", row[field],
                     ref[field])
        for field in ("loss", "cost"):
            ph.close_to(f"chip ({prec}) vs CPU round 1 {field}", row[field],
                        ref[field], rtol=CPU_RTOL[prec])
    ph.close()


def _has_kernel(ph: Phase, what: str, compiled) -> None:
    ph.check("tpu_custom_call" in compiled.as_text(),
             f"{what} lowered to tpu_custom_call")


def _sic_reference(p, g, mask, bandwidth_hz, noise_w):
    from repro.core import noma
    return jnp.stack([noma.achievable_rates(p, g[:, j],
                                            bandwidth_hz=bandwidth_hz,
                                            noise_w=noise_w, mask=mask[:, j])
                      for j in range(g.shape[1])], axis=1)


def phase_b() -> None:
    """The Pallas kernels compiled for the chip against jnp references, at
    the tolerances of the interpret-mode tests."""
    from repro.configs.hfl_mnist import CONFIG
    from repro.core import fuzzy, noma
    from repro.kernels import hfl_ops

    ph = Phase("B")
    rng = np.random.default_rng(0)
    data_max = float(CONFIG.max_samples)
    noise = noma.noise_power_w(CONFIG.noise_dbm_per_hz, CONFIG.bandwidth_hz)
    for n, m in ((CONFIG.n_clients, CONFIG.n_edges), (1024, 16)):
        k = CONFIG.clients_per_edge
        gains = jnp.asarray(rng.uniform(1e-12, 1e-8, (n, m)), jnp.float32)
        counts = jnp.asarray(rng.integers(CONFIG.min_samples,
                                          CONFIG.max_samples, n), jnp.float32)
        stale = jnp.asarray(rng.integers(1, 9, n), jnp.int32)
        cand = jnp.asarray(np.argsort(rng.random((n, m)), axis=1)[:, :k],
                           jnp.int32)
        dense = fuzzy.score_matrix(gains, counts, stale, data_max=data_max)

        with timed(f"B score_matrix {n}x{m}"):
            c = hfl_ops.score_matrix.lower(gains, counts, stale,
                                           data_max=data_max).compile()
            got = jax.block_until_ready(c(gains, counts, stale))
        _has_kernel(ph, f"score_matrix {n}x{m}", c)
        ph.close_to(f"score_matrix {n}x{m} vs fuzzy.score_matrix", got,
                    dense, rtol=1e-5, atol=2e-4)

        with timed(f"B score_candidates {n}x{m} K={k}"):
            c = hfl_ops.score_candidates.lower(
                gains, cand, counts, stale, data_max=data_max).compile()
            got = jax.block_until_ready(c(gains, cand, counts, stale))
        _has_kernel(ph, f"score_candidates {n}x{m}", c)
        ph.close_to(f"score_candidates {n}x{m} vs gathered dense scores",
                    got, jnp.take_along_axis(dense, cand, axis=1),
                    rtol=1e-5, atol=2e-4)

        p = jnp.asarray(rng.uniform(CONFIG.p_min_w, CONFIG.p_max_w, n),
                        jnp.float32)
        g = jnp.asarray(rng.uniform(0.1, 10.0, (n, m)) * 1e-9, jnp.float32)
        mask = jnp.asarray(rng.random((n, m)) < 0.5)
        with timed(f"B sic_rates {n}x{m}"):
            c = hfl_ops.sic_rates.lower(p, g, mask,
                                        bandwidth_hz=CONFIG.bandwidth_hz,
                                        noise_w=noise).compile()
            got = jax.block_until_ready(c(p, g, mask))
        _has_kernel(ph, f"sic_rates {n}x{m}", c)
        want = np.asarray(_sic_reference(p, g, mask, CONFIG.bandwidth_hz,
                                         noise))
        ph.close_to(f"sic_rates {n}x{m} vs pairwise SIC", got, want,
                    rtol=1e-5, atol=float(want.max()) * 1e-6)

    ph.close()


def phase_four_chips() -> None:
    """The client-sharded and fleet-sharded drivers over four chips against
    the unsharded drivers on one, with the tolerances of
    tests/test_client_sharding.py and tests/test_fleet_sharding.py.  At
    highest matmul precision, so a reordered cross-chip sum cannot flip an
    MXU bf16 input rounding."""
    from repro.configs.hfl_mnist import CONFIG
    from repro.core import engine

    devices = jax.devices()
    if len(devices) != 4:
        raise SystemExit(f"--four-chips needs 4 devices, found {len(devices)}")
    ph = Phase("four-chips")
    spec = engine.EngineSpec(policy="fcea", scheduler="pdd")
    rounds = 2
    with jax.default_matmul_precision("highest"):
        state, bundle, _ = engine.init_simulation(CONFIG, seed=0)
        mesh = engine.client_mesh(devices)
        ph.check(mesh.devices.size == 4, "client mesh spans 4 devices")
        with timed(f"4 run_scanned {rounds} rounds, one chip"):
            _, plain = jax.block_until_ready(
                engine.run_scanned(CONFIG, spec, state, bundle, rounds))
        with timed(f"4 run_scanned_client_sharded {rounds} rounds"):
            out, sharded = jax.block_until_ready(
                engine.run_scanned_client_sharded(CONFIG, spec, state,
                                                  bundle, rounds, mesh=mesh))
        ph.check(len(out.client_params["w1"].sharding.device_set) == 4,
                 "client params live on 4 devices")
        for f in ("loss", "cost", "accuracy", "total_energy_j"):
            ph.close_to(f"client-sharded {f}", getattr(sharded, f),
                        getattr(plain, f), rtol=2e-5, atol=1e-7)
        for f in ("n_associated", "n_available", "z"):
            ph.equal(f"client-sharded {f}", getattr(sharded, f),
                     getattr(plain, f))
        del out, sharded, plain

        pairs = [engine.init_simulation(CONFIG, seed=s)[:2]
                 for s in range(4)]
        states, bundles = engine.stack_fleet(pairs)
        del pairs
        mesh = engine.fleet_mesh(devices)
        ph.check(mesh.devices.size == 4, "fleet mesh spans 4 devices")
        with timed(f"4 run_fleet 4 seeds x {rounds} rounds, one chip"):
            _, plain = jax.block_until_ready(
                engine.run_fleet(CONFIG, spec, states, bundles, rounds))
        with timed(f"4 run_fleet_sharded 4 seeds x {rounds} rounds"):
            out, sharded = jax.block_until_ready(
                engine.run_fleet_sharded(CONFIG, spec, states, bundles,
                                         rounds, mesh=mesh))
        ph.check(len(out.client_params["w1"].sharding.device_set) == 4,
                 "fleet lanes live on 4 devices")
        for f in ("loss", "cost", "accuracy"):
            ph.close_to(f"fleet-sharded {f}", getattr(sharded, f),
                        getattr(plain, f), rtol=1e-6)
        ph.equal("fleet-sharded z", sharded.z, plain.z)
    ph.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded drivers over four chips")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    count = len(jax.devices())
    print(f"device: {dev.platform} {dev.device_kind} x{count}")
    print(f"jax {jax.__version__}, jaxlib "
          f"{importlib.metadata.version('jaxlib')}, libtpu "
          f"{importlib.metadata.version('libtpu')}")

    from repro import compile_cache
    print(f"compile cache: {compile_cache.enable()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)

    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips()
    else:
        phase_a()
        phase_b()
    print(f"total wall {time.perf_counter() - t0:.3f} s, compile "
          f"{_compile_s[0]:.3f} s, cache hits {_cache['hits']} misses "
          f"{_cache['misses']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
