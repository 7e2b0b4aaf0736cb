"""Dynamic-scenario subsystem tests (DESIGN.md §6).

(a) golden parity: scenario="static" reproduces the PR-1 engine's
    trajectories bit-for-bit (tests/golden/static_parity.json was recorded
    from the pre-scenario engine),
(b) purity: the scenario-enabled ``round_step`` lowers with no host
    callbacks,
(c) transition semantics: waypoint motion stays inside the cell,
    availability is a boolean Markov chain with the configured stationary
    rate, device classes respect the cfg bounds,
(d) the availability mask actually excludes clients from association,
    aggregation and cost,
(e) eager == scanned == fleet for dynamic scenarios too.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis or its absent-shim

from repro import scenarios
from repro.configs.hfl_mnist import CONFIG
from repro.core import engine
from repro.core.hfl import HFLSimulation

SMALL = dataclasses.replace(CONFIG, n_clients=16, n_edges=2,
                            clients_per_edge=3, min_samples=60,
                            max_samples=120, hidden=32, input_dim=64)
GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "static_parity.json")


def _advance_n(cfg, sspec, seed, rounds):
    rng = np.random.default_rng(seed)
    topo = engine.make_topology(rng, n_clients=cfg.n_clients,
                                n_edges=cfg.n_edges,
                                area_side_m=cfg.area_side_m)
    s = scenarios.init_scenario(cfg, sspec, rng, topo)
    states = [s]
    key = jax.random.key(seed)
    step = jax.jit(scenarios.advance_dynamic, static_argnums=(0,))
    for _ in range(rounds):
        key, k = jax.random.split(key)
        s = step(cfg, k, s)
        states.append(s)
    return states


# -- (a) golden static parity -------------------------------------------------

@pytest.mark.parametrize("policy,scheduler", [("fcea", "pdd"),
                                              ("gcea", "fastest")])
def test_static_matches_pr1_golden(policy, scheduler):
    """Bit-exact float equality against the recorded PR-1 trajectories.

    The goldens were recorded with JAX's non-partitionable threefry, the
    default then; under today's default every key the run draws differs,
    so the comparison runs in the mode the file was recorded under."""
    with open(GOLDEN) as fh:
        golden = json.load(fh)["trajectories"][f"{policy}-{scheduler}"]
    spec = engine.EngineSpec(policy=policy, scheduler=scheduler)
    assert spec.scenario == "static"          # the default IS the PR-1 path
    with jax.threefry_partitionable(False):
        state, bundle, _ = engine.init_simulation(SMALL, seed=0)
        _, ms = engine.run_scanned(SMALL, spec, state, bundle, 4)
    for field in ("accuracy", "loss", "cost", "total_time_s",
                  "total_energy_j", "avg_staleness"):
        got = np.asarray(getattr(ms, field), np.float64)
        np.testing.assert_array_equal(got, np.asarray(golden[field]),
                                      err_msg=field)
    np.testing.assert_array_equal(np.asarray(ms.n_associated),
                                  golden["n_associated"])
    np.testing.assert_array_equal(np.asarray(ms.z), golden["z"])


# -- (b) purity of the scenario-enabled program -------------------------------

@pytest.mark.parametrize("kind", ["static", "dynamic"])
def test_scenario_round_step_lowers_without_callbacks(kind):
    spec = engine.EngineSpec(policy="gcea", scheduler="fastest",
                             scenario=kind)
    state, bundle, _ = engine.init_simulation(
        SMALL, seed=0, scenario="full_dynamic" if kind == "dynamic" else None)
    txt = jax.jit(engine.round_step, static_argnums=(0, 1)).lower(
        SMALL, spec, state, bundle).as_text()
    assert "callback" not in txt
    assert "CustomCall" not in txt


# -- (c) transition semantics -------------------------------------------------

def test_waypoint_positions_stay_inside_cell():
    sspec = scenarios.ScenarioSpec(kind="random_waypoint",
                                   speed_max_mps=40.0, round_duration_s=10.0)
    for s in _advance_n(SMALL, sspec, seed=0, rounds=25):
        pos = np.asarray(s.pos)
        assert (pos >= 0.0).all() and (pos <= SMALL.area_side_m).all()
        # distances stay consistent with positions
        want = np.linalg.norm(pos[:, None, :] - np.asarray(s.edges)[None],
                              axis=-1)
        np.testing.assert_allclose(np.asarray(s.dist), want, rtol=1e-5)


def test_waypoint_actually_moves_clients():
    sspec = scenarios.ScenarioSpec(kind="random_waypoint", speed_min_mps=5.0)
    states = _advance_n(SMALL, sspec, seed=0, rounds=5)
    moved = np.abs(np.asarray(states[-1].pos) - np.asarray(states[0].pos))
    assert moved.max() > 1.0


def test_markov_availability_boolean_and_stationary_rate():
    big = dataclasses.replace(SMALL, n_clients=512)
    sspec = scenarios.ScenarioSpec(kind="markov_dropout", p_drop=0.3,
                                   p_return=0.2)
    states = _advance_n(big, sspec, seed=1, rounds=40)
    fractions = []
    for s in states[10:]:                       # after burn-in
        a = np.asarray(s.avail)
        assert set(np.unique(a)) <= {0.0, 1.0}
        fractions.append(a.mean())
    want = sspec.stationary_availability        # 0.2 / 0.5 = 0.4
    assert abs(np.mean(fractions) - want) < 0.05


def test_hetero_device_classes_within_bounds():
    sspec = scenarios.ScenarioSpec(kind="hetero_devices", n_device_classes=5)
    rng = np.random.default_rng(2)
    topo = engine.make_topology(rng, n_clients=64, n_edges=2,
                                area_side_m=SMALL.area_side_m)
    cfg = dataclasses.replace(SMALL, n_clients=64)
    s = scenarios.init_scenario(cfg, sspec, rng, topo)
    f = np.asarray(s.f_max_hz)
    p = np.asarray(s.p_max_w)
    assert (f >= cfg.f_min_hz).all() and (f <= cfg.f_max_hz).all()
    assert (p >= cfg.p_min_w).all() and (p <= cfg.p_max_w).all()
    assert (np.asarray(s.kappa) >= cfg.capacitance).all()
    assert len(np.unique(f)) > 1                # genuinely heterogeneous
    # device classes are persistent under the transition
    s2 = scenarios.advance_dynamic(cfg, jax.random.key(0), s)
    np.testing.assert_array_equal(np.asarray(s2.f_max_hz), f)


def test_static_transition_is_identity():
    sspec = scenarios.ScenarioSpec()
    rng = np.random.default_rng(3)
    topo = engine.make_topology(rng, n_clients=8, n_edges=2,
                                area_side_m=SMALL.area_side_m)
    cfg = dataclasses.replace(SMALL, n_clients=8)
    s = scenarios.init_scenario(cfg, sspec, rng, topo)
    s2 = scenarios.advance(cfg, "static", jax.random.key(0), s)
    for a, b in zip(jax.tree.leaves(s), jax.tree.leaves(s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and a static-parameterised DYNAMIC step leaves the world fixed too
    # (identity-by-parameterisation: speed 0, p_drop 0, p_return 1)
    s3 = scenarios.advance_dynamic(cfg, jax.random.key(0), s)
    np.testing.assert_array_equal(np.asarray(s3.pos), np.asarray(s.pos))
    np.testing.assert_array_equal(np.asarray(s3.avail), np.asarray(s.avail))
    # distances are recomputed on-device from the (unmoved) positions —
    # equal up to the f32 vs host-f64 norm rounding
    np.testing.assert_allclose(np.asarray(s3.dist), np.asarray(s.dist),
                               rtol=1e-6)


# -- hypothesis property versions --------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(0, 1000), st.floats(1.0, 50.0))
def test_prop_waypoint_in_cell(seed, speed_max):
    sspec = scenarios.ScenarioSpec(kind="random_waypoint",
                                   speed_max_mps=speed_max)
    for s in _advance_n(SMALL, sspec, seed=seed, rounds=8):
        pos = np.asarray(s.pos)
        assert (pos >= 0.0).all() and (pos <= SMALL.area_side_m).all()


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 1000), st.floats(0.05, 0.6), st.floats(0.05, 0.6))
def test_prop_availability_mask_boolean(seed, p_drop, p_return):
    sspec = scenarios.ScenarioSpec(kind="markov_dropout", p_drop=p_drop,
                                   p_return=p_return)
    for s in _advance_n(SMALL, sspec, seed=seed, rounds=6):
        a = np.asarray(s.avail)
        assert set(np.unique(a)) <= {0.0, 1.0}


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 1000), st.integers(1, 8))
def test_prop_device_classes_within_bounds(seed, n_classes):
    sspec = scenarios.ScenarioSpec(kind="hetero_devices",
                                   n_device_classes=n_classes)
    rng = np.random.default_rng(seed)
    topo = engine.make_topology(rng, n_clients=32, n_edges=2,
                                area_side_m=SMALL.area_side_m)
    cfg = dataclasses.replace(SMALL, n_clients=32)
    s = scenarios.init_scenario(cfg, sspec, rng, topo)
    f = np.asarray(s.f_max_hz)
    assert (f >= cfg.f_min_hz).all() and (f <= cfg.f_max_hz).all()


# -- (d) the mask reaches association / aggregation / cost --------------------

def test_unavailable_clients_never_associated():
    spec = engine.EngineSpec(policy="fcea", scheduler="pdd",
                             scenario="markov_dropout")
    state, bundle, _ = engine.init_simulation(
        SMALL, seed=0,
        scenario=scenarios.ScenarioSpec(kind="markov_dropout", p_drop=0.6,
                                        p_return=0.2))
    for _ in range(6):
        state, m = engine.round_step_jit(SMALL, spec, state, bundle)
        avail = np.asarray(state.scenario.avail)
        # re-derive this round's association to inspect it: the metrics
        # count must also never exceed the available population
        assert int(m.n_associated) <= int(m.n_available)
        assert int(m.n_available) == int(avail.sum())


def test_all_clients_dropped_keeps_global_model():
    """Degenerate world: nobody is available — the global model must ride
    through unchanged (Eq. 17 guard) and the round must not NaN."""
    sspec = scenarios.ScenarioSpec(kind="markov_dropout", p_drop=1.0,
                                   p_return=0.0)
    spec = engine.EngineSpec(policy="gcea", scheduler="fastest",
                             scenario="dynamic")
    state, bundle, _ = engine.init_simulation(SMALL, seed=0, scenario=sspec)
    s1, m = engine.round_step_jit(SMALL, spec, state, bundle)
    assert int(m.n_available) == 0 and int(m.n_associated) == 0
    for a, b in zip(jax.tree.leaves(state.global_params),
                    jax.tree.leaves(s1.global_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.isfinite(float(m.cost))


def test_hetero_devices_raise_energy_cost():
    """Weaker devices (higher κ at the same clamped f) change Eq. 23a."""
    spec_s = engine.EngineSpec(policy="gcea", scheduler="fastest")
    spec_d = engine.EngineSpec(policy="gcea", scheduler="fastest",
                               scenario="dynamic")
    st0, bu, _ = engine.init_simulation(SMALL, seed=0)
    _, ms = engine.run_scanned(SMALL, spec_s, st0, bu, 3)
    sspec = scenarios.ScenarioSpec(kind="hetero_devices", kappa_spread=4.0)
    st1, bu1, _ = engine.init_simulation(SMALL, seed=0, scenario=sspec)
    _, md = engine.run_scanned(SMALL, spec_d, st1, bu1, 3)
    assert not np.allclose(np.asarray(ms.total_energy_j),
                           np.asarray(md.total_energy_j))


# -- (e) drivers agree under dynamic scenarios --------------------------------

def test_dynamic_eager_matches_scanned():
    rounds = 3
    kwargs = dict(seed=0, iid=True, policy="fcea", scheduler="pdd",
                  scenario="full_dynamic")
    eager = HFLSimulation(SMALL, **kwargs)
    scanned = HFLSimulation(SMALL, **kwargs)
    assert eager.spec.scenario == "dynamic"
    me = eager.run(rounds)
    ms = scanned.run_scanned(rounds)
    for a, b in zip(me, ms):
        assert a.n_associated == b.n_associated
        assert a.n_available == b.n_available
        np.testing.assert_array_equal(a.z, b.z)
        np.testing.assert_allclose(a.loss, b.loss, rtol=1e-5)
        np.testing.assert_allclose(a.cost, b.cost, rtol=1e-5)


def test_dynamic_fleet_matches_sequential():
    spec = engine.EngineSpec(policy="gcea", scheduler="fastest",
                             scenario="dynamic")
    pairs = [engine.init_simulation(SMALL, seed=s,
                                    scenario="mobile_flaky")[:2]
             for s in (0, 1)]
    states, bundles = engine.stack_fleet(pairs)
    _, fleet = engine.run_fleet(SMALL, spec, states, bundles, 2)
    for i, (st_i, bu_i) in enumerate(pairs):
        _, seq = engine.run_scanned(SMALL, spec, st_i, bu_i, 2)
        np.testing.assert_allclose(np.asarray(fleet.cost[i]),
                                   np.asarray(seq.cost), rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(fleet.n_available[i]),
                                      np.asarray(seq.n_available))


def test_ddpg_allocator_runs_under_dynamic_scenario():
    """Regression: the actor must train on the scenario-sliced (3N,)
    observation so the engine's DDPG path doesn't shape-mismatch."""
    sim = HFLSimulation(SMALL, seed=0, policy="gcea", scheduler="fastest",
                        allocator="ddpg", scenario="full_dynamic")
    sim.train_ddpg(episodes=1, steps_per_episode=3, warmup=2, hidden=16)
    assert sim.agent_cfg.state_dim == 3 * SMALL.n_clients
    m = sim.run_round()
    assert np.isfinite(m.cost)


# -- spec plumbing ------------------------------------------------------------

def test_preset_and_kind_validation():
    assert scenarios.preset("static").engine_kind() == "static"
    assert scenarios.preset("full_dynamic").engine_kind() == "dynamic"
    assert scenarios.preset(
        "random_waypoint+markov_dropout").engine_kind() == "dynamic"
    with pytest.raises(ValueError):
        scenarios.preset("warp_drive").parts
    with pytest.raises(ValueError):
        scenarios.advance(SMALL, "warp_drive", jax.random.key(0), None)


def test_register_custom_transition_end_to_end():
    """The documented extension path: a registered custom kind must flow
    through preset/init_simulation/EngineSpec into round_step."""
    kind = "_test_blackout"

    def blackout(cfg, key, s):
        return s._replace(avail=s.avail * 0.0)

    scenarios.register_transition(kind, blackout)
    try:
        sspec = scenarios.preset(kind)
        assert sspec.is_dynamic and sspec.parts == ()
        assert sspec.engine_kind() == kind
        spec = engine.EngineSpec(policy="gcea", scheduler="fastest",
                                 scenario=kind)
        state, bundle, _ = engine.init_simulation(SMALL, seed=0,
                                                  scenario=kind)
        _, m = engine.round_step_jit(SMALL, spec, state, bundle)
        assert int(m.n_available) == 0          # the custom world acted
    finally:
        del scenarios.TRANSITIONS[kind]


def test_env_respects_noma_switch():
    """train_ddpg's env must bill the simulation's NOMA/OMA uplink."""
    from repro.core import env as env_mod
    n, m = SMALL.n_clients, SMALL.n_edges
    rng = np.random.default_rng(1)
    assoc = np.zeros((n, m), np.float32)
    assoc[np.arange(n), rng.integers(0, m, n)] = 1.0
    dist = jnp.asarray(rng.uniform(50.0, 300.0, (n, m)))
    counts = jnp.asarray(rng.integers(60, 120, n), jnp.float32)
    rewards = {}
    for noma in (True, False):
        e = env_mod.NomaHflEnv(SMALL, jnp.asarray(assoc), jnp.ones((m,)),
                               dist, counts, noma_enabled=noma)
        s0, _ = e.reset(jax.random.key(0))
        _, _, r, _ = e.step(s0, jnp.full((2 * n,), 0.5))
        rewards[noma] = float(r)
    assert rewards[True] != rewards[False]


def test_env_availability_evolves_during_training():
    """With (p_drop, p_return) the env's availability chain runs BETWEEN
    slots, so the actor's third obs block actually varies (and dropped
    clients are not billed)."""
    from repro.core import env as env_mod
    n, m = SMALL.n_clients, SMALL.n_edges
    rng = np.random.default_rng(2)
    assoc = np.zeros((n, m), np.float32)
    assoc[np.arange(n), rng.integers(0, m, n)] = 1.0
    dist = jnp.asarray(rng.uniform(50.0, 300.0, (n, m)))
    counts = jnp.asarray(rng.integers(60, 120, n), jnp.float32)
    e = env_mod.NomaHflEnv(SMALL, jnp.asarray(assoc), jnp.ones((m,)),
                           dist, counts,
                           p_drop=jnp.full((n,), 0.5),
                           p_return=jnp.full((n,), 0.5))
    assert e.state_dim == 3 * n
    s, obs = e.reset(jax.random.key(0))
    assert obs.shape == (3 * n,)
    seen = set()
    for _ in range(6):
        s, obs, r, _ = e.step(s, jnp.full((2 * n,), 0.5))
        assert np.isfinite(float(r))
        seen.add(tuple(np.asarray(s.avail).tolist()))
        # dropped clients vanish from ALL observation blocks, exactly as
        # the engine's masked assoc makes them vanish at deployment
        a = np.asarray(s.avail)
        o = np.asarray(obs).reshape(3, n)
        assert (o[:, a == 0.0] == 0.0).all()
    assert len(seen) > 1                      # the chain really moves


def test_all_part_mixtures_registered():
    """Every kind string ScenarioSpec.parts accepts must resolve to a
    transition — including the 3-part mixture, in any order."""
    import itertools
    parts = ("random_waypoint", "markov_dropout", "hetero_devices")
    for r in (1, 2, 3):
        for combo in itertools.permutations(parts, r):
            kind = "+".join(combo)
            assert scenarios.preset(kind).is_dynamic
            assert kind in scenarios.TRANSITIONS, kind


def test_env_bills_scenario_cost_surface():
    """The DDPG env must charge the engine's bill: per-device κ raises the
    reward's energy term and the device caps clamp the decoded action."""
    from repro.core import env as env_mod
    n, m = SMALL.n_clients, SMALL.n_edges
    rng = np.random.default_rng(0)
    assoc = np.zeros((n, m), np.float32)
    assoc[np.arange(n), rng.integers(0, m, n)] = 1.0
    dist = jnp.asarray(rng.uniform(50.0, 300.0, (n, m)))
    counts = jnp.asarray(rng.integers(60, 120, n), jnp.float32)
    kappa = jnp.full((n,), SMALL.capacitance * 5.0)
    f_cap = jnp.full((n,), SMALL.f_min_hz)
    common = dict(fading_rho=0.9)
    e_plain = env_mod.NomaHflEnv(SMALL, jnp.asarray(assoc),
                                 jnp.ones((m,)), dist, counts, **common)
    e_scen = env_mod.NomaHflEnv(SMALL, jnp.asarray(assoc),
                                jnp.ones((m,)), dist, counts,
                                kappa=kappa, f_max_hz=f_cap, **common)
    act = jnp.full((2 * n,), 1.0)                  # max p, max f requested
    _, f_plain = e_plain.decode_action(act)
    _, f_scen = e_scen.decode_action(act)
    assert float(jnp.max(f_scen)) == SMALL.f_min_hz   # clamped to the cap
    assert float(jnp.max(f_plain)) == pytest.approx(SMALL.f_max_hz,
                                                    rel=1e-6)
    key = jax.random.key(0)
    s0, _ = e_plain.reset(key)
    _, _, r_plain, _ = e_plain.step(s0, jnp.full((2 * n,), 0.5))
    s1, _ = e_scen.reset(key)
    _, _, r_scen, _ = e_scen.step(s1, jnp.full((2 * n,), 0.5))
    assert float(r_plain) != float(r_scen)


def test_flash_crowd_returns_in_waves():
    """flash_crowd (DESIGN.md §11): between bursts the up-set only decays
    (no lone returns); on a burst EVERY previously-dropped client comes
    back at once — the all-or-nothing wave property."""
    big = dataclasses.replace(SMALL, n_clients=256)
    sspec = scenarios.ScenarioSpec(kind="flash_crowd", p_drop=0.3,
                                   p_return=0.2)
    rng = np.random.default_rng(3)
    topo = engine.make_topology(rng, n_clients=big.n_clients,
                                n_edges=big.n_edges,
                                area_side_m=big.area_side_m)
    s = scenarios.init_scenario(big, sspec, rng, topo)
    step = jax.jit(scenarios.advance, static_argnums=(0, 1))
    key = jax.random.key(3)
    bursts = quiets = 0
    for _ in range(60):
        before = np.asarray(s.avail) > 0
        key, k = jax.random.split(key)
        s = step(big, "flash_crowd", k, s)
        after = np.asarray(s.avail)
        assert set(np.unique(after)) <= {0.0, 1.0}
        returned = (~before) & (after > 0)
        n_down = int((~before).sum())
        if n_down and returned.sum() == n_down:
            bursts += 1
        else:
            assert returned.sum() == 0          # no lone returns
            quiets += 1
    assert bursts >= 1 and quiets >= 1          # the sawtooth really runs
    # the preset registers through the normal registry machinery
    assert "flash_crowd" in scenarios.TRANSITIONS
    assert scenarios.preset("flash_crowd").is_dynamic
