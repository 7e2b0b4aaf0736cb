"""Training-stage implementation tests (DESIGN.md §13).

(a) PRNG lattice: the batched ``_batch_index_lattice`` draws exactly
    the index sequences of the nested split/fold_in reference loop —
    the stream-layout contract the PR-10 goldens were re-recorded on,
(b) impl bit-parity: ``train_impl="batched"`` (what "auto" resolves to)
    and ``train_impl="vmap"`` produce bit-identical trajectories and
    final params under the sync AND buffered engines, faults on or off,
(c) Pallas: ``local_sgd_step`` (interpret mode on CPU) matches the
    batched path to float tolerance at the kernel and the round level,
(d) warm-start: warm assignment == cold assignment bit-for-bit (the
    blocking-pair fallback guards exactness), the deferred-acceptance
    sweep count under ``random_waypoint`` mobility drops (median warm
    ≤ median cold, asserted from ``RoundTrace.assoc_sweeps``), and the
    cold carry keeps the warm leaf structurally absent,
(e) minibatch gather: ``_minibatches`` reads by (client, row) pairs
    exactly the elements the two-step cohort-slab gather picks (pad
    lanes and a fleet vmap included), and no traced round program holds
    an array with ``cap`` rows per client besides the data buffers.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.hfl_mnist import CONFIG
from repro.core import ddpg, engine
from repro.faults import FaultSpec
from repro.kernels import hfl_ops
from repro.models.mlp import MLPClassifier

SMALL = dataclasses.replace(CONFIG, n_clients=16, n_edges=2,
                            clients_per_edge=3, min_samples=60,
                            max_samples=120, hidden=32, input_dim=64)
ROUNDS = 4


def _spec(**kw):
    return engine.EngineSpec(policy="gcea", scheduler="fastest", **kw)


def _tree_equal(a, b, msg=""):
    fa, ta = jax.tree_util.tree_flatten(a)
    fb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb, msg
    for la, lb in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=msg)


# -- (a) batched PRNG lattice vs the nested reference loop -------------------

def test_lattice_matches_nested_splits():
    """One split + fold_in lattice == the per-iteration nested draws."""
    key = jax.random.key(7)
    tau2, tau1, k_lanes, batch = 3, 2, 5, 8
    gid = jnp.asarray([0, 3, 3, 9, 15], jnp.int32)
    counts = jnp.asarray([60, 0, 120, 77, 61], jnp.int32)
    got = np.asarray(engine._batch_index_lattice(
        key, tau2, tau1, gid, counts, batch))
    assert got.shape == (tau2, tau1, k_lanes, batch)
    k_t = jax.random.split(key, tau2)
    for t in range(tau2):
        for i in range(tau1):
            for j in range(k_lanes):
                kc = jax.random.fold_in(
                    jax.random.fold_in(k_t[t], i), int(gid[j]))
                want = jax.random.randint(
                    kc, (batch,), 0, max(int(counts[j]), 1))
                np.testing.assert_array_equal(got[t, i, j],
                                              np.asarray(want))


def test_lattice_indices_in_range():
    key = jax.random.key(0)
    counts = jnp.asarray([1, 60, 120], jnp.int32)
    idx = np.asarray(engine._batch_index_lattice(
        key, 4, 3, jnp.arange(3, dtype=jnp.int32), counts, 16))
    assert (idx >= 0).all()
    assert (idx < np.asarray(counts)[None, None, :, None]).all()


def test_unknown_train_impl_raises():
    with pytest.raises(ValueError, match="train_impl"):
        engine._train_impl_for(_spec(train_impl="fused"))
    assert engine._train_impl_for(_spec()) == "batched"   # auto default


# -- (b) batched vs vmap bit-parity across engines ---------------------------

@pytest.mark.parametrize("mode,faulted", [("sync", False), ("sync", True),
                                          ("buffered", False),
                                          ("buffered", True)])
def test_batched_bit_equal_vmap(mode, faulted):
    """scan-of-batched-GEMMs and vmap-of-scans are the same XLA math —
    bit-for-bit, under both engines, with and without the fault layer."""
    kw = dict(engine_mode=mode)
    if mode == "buffered":
        kw.update(n_tiers=2, retier_every=3, timeout_s=5.0)
    if faulted:
        kw["faults"] = FaultSpec(edge_p_kill=0.0, edge_p_respawn=0.0,
                                 uplink_p_loss=0.2)
    outs = {}
    for impl in ("batched", "vmap"):
        state, bundle, _ = engine.init_simulation(SMALL, seed=0)
        st, ms = engine.run_scanned(SMALL, _spec(train_impl=impl, **kw),
                                    state, bundle, ROUNDS)
        outs[impl] = (st.global_params, st.client_params, ms)
    _tree_equal(outs["batched"][0], outs["vmap"][0], "global_params")
    _tree_equal(outs["batched"][1], outs["vmap"][1], "client_params")
    _tree_equal(outs["batched"][2], outs["vmap"][2], "metrics")


def test_vmap_matches_goldens_via_auto():
    """"auto" resolves to "batched"; a vmap run of the same spec must be
    bit-equal — i.e. the vmap path also reproduces the committed goldens
    (test_scenarios pins auto against them directly)."""
    state, bundle, _ = engine.init_simulation(SMALL, seed=0)
    _, ms_auto = engine.run_scanned(SMALL, _spec(), state, bundle, ROUNDS)
    _, ms_vmap = engine.run_scanned(SMALL, _spec(train_impl="vmap"),
                                    state, bundle, ROUNDS)
    _tree_equal(ms_auto, ms_vmap, "auto-vs-vmap metrics")


# -- (c) Pallas local_sgd_step parity ----------------------------------------

def test_local_sgd_step_kernel_parity():
    """The fused kernel == τ₁ hand-stepped SGD on the same minibatches
    (interpret mode; float tolerance — softmax vs logsumexp op order)."""
    rng = np.random.default_rng(3)
    k_lanes, tau1, batch, dim, hid, ncls = 4, 3, 8, 16, 12, 5
    model = MLPClassifier(dim, hid, ncls)
    p0 = model.init(jax.random.key(1))
    params = jax.tree.map(
        lambda l: jnp.stack([l + 0.01 * i for i in range(k_lanes)]), p0)
    bx = jnp.asarray(rng.normal(size=(tau1, k_lanes, batch, dim)),
                     jnp.float32)
    by = jnp.asarray(rng.integers(0, ncls, size=(tau1, k_lanes, batch)),
                     jnp.int32)
    got = hfl_ops.local_sgd_step(params, bx, by, lr=0.1, interpret=True)

    def one(params, xs, ys):
        def step(p, xy):
            g = jax.grad(model.loss)(p, xy)
            return jax.tree.map(lambda a, b: a - 0.1 * b, p, g), None
        p, _ = jax.lax.scan(step, params, (xs, ys))
        return p
    want = jax.vmap(one, in_axes=(0, 1, 1))(params, bx, by)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]),
                                   np.asarray(want[k]),
                                   rtol=2e-5, atol=2e-6, err_msg=k)


def test_pallas_round_close_to_batched():
    state, bundle, _ = engine.init_simulation(SMALL, seed=0)
    _, ms_b = engine.run_scanned(SMALL, _spec(train_impl="batched"),
                                 state, bundle, 2)
    _, ms_p = engine.run_scanned(SMALL, _spec(train_impl="pallas"),
                                 state, bundle, 2)
    np.testing.assert_allclose(np.asarray(ms_p.loss),
                               np.asarray(ms_b.loss), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ms_p.accuracy),
                               np.asarray(ms_b.accuracy), atol=1e-3)


# -- (d) warm-started association --------------------------------------------

def test_warm_leaf_structural_absence():
    state, bundle, _ = engine.init_simulation(SMALL, seed=0)
    cold = engine.ensure_carry(SMALL, _spec(), state)
    assert cold.warm is None
    warm = engine.ensure_carry(SMALL, _spec(warm_start=True), state)
    assert warm.warm is not None
    np.testing.assert_array_equal(np.asarray(warm.warm),
                                  np.full(SMALL.n_clients, -1, np.int32))
    # a stale warm leaf is STRIPPED when the flag is off — the cold
    # carry (and with it the golden program) is structurally unchanged
    stripped = engine.ensure_carry(SMALL, _spec(), warm)
    assert stripped.warm is None


@pytest.mark.parametrize("candidates_k", [None, 2])
def test_warm_equals_cold(candidates_k):
    """Seeded deferred acceptance lands on the SAME matching: the
    blocking-pair check falls back to the cold resolver whenever the
    seeded fixpoint could diverge, so trajectories are bit-equal."""
    outs = {}
    for warm in (False, True):
        spec = _spec(scenario="dynamic", warm_start=warm,
                     candidates_k=candidates_k)
        state, bundle, _ = engine.init_simulation(
            SMALL, seed=0, scenario="random_waypoint")
        st, ms = engine.run_scanned(SMALL, spec, state, bundle, 6)
        outs[warm] = (st.global_params, ms)
    _tree_equal(outs[False][0], outs[True][0], "global_params")
    _tree_equal(outs[False][1], outs[True][1], "metrics")


def test_warm_start_reduces_sweeps_under_mobility():
    """The point of the seed: under random_waypoint mobility last
    round's matching is nearly stable, so the seeded resolver converges
    in fewer deferred-acceptance sweeps (RoundTrace.assoc_sweeps)."""
    sweeps = {}
    for warm in (False, True):
        spec = _spec(scenario="dynamic", warm_start=warm, telemetry=True)
        state, bundle, _ = engine.init_simulation(
            SMALL, seed=0, scenario="random_waypoint")
        _, (_, tr) = engine.run_scanned(SMALL, spec, state, bundle, 8)
        sweeps[warm] = np.asarray(tr.assoc_sweeps)
    # round 0 has no seed yet — compare the steady-state tail
    assert np.median(sweeps[True][1:]) <= np.median(sweeps[False][1:])
    assert sweeps[True][1:].mean() < sweeps[False][1:].mean()


def test_warm_start_requires_parallel_resolver():
    from repro.core import association
    with pytest.raises(ValueError, match="parallel"):
        association.associate_jax(
            "gcea", scores=None, gains=jnp.ones((16, 2)),
            dist=jnp.ones((16, 2)) * 10.0, quota=3,
            coverage_radius_m=100.0, key=jax.random.key(0),
            resolver="serial", seed=jnp.full((16,), -1, jnp.int32))


# -- (e) minibatches gathered by (client, row), no cohort slab ----------------

def _two_step_minibatches(x, y, safe, idx):
    """The former gather: the (K, cap, D) cohort slab, then each step's
    (K, B) rows out of it with ``take_along_axis``."""
    sel_x, sel_y = x[safe], y[safe]
    bx = jax.vmap(jax.vmap(lambda ix: jnp.take_along_axis(
        sel_x, ix[:, :, None], axis=1)))(idx)
    by = jax.vmap(jax.vmap(lambda ix: jnp.take_along_axis(
        sel_y, ix, axis=1)))(idx)
    return bx, by


def _gather_case(seed, n=12, cap=37, dim=5, tau2=3, tau1=2, k_lanes=6,
                 batch=4, admitted=4):
    """A random bundle and ``_train_cohort``'s lane selection with
    ``k_lanes - admitted`` pad lanes (sel_idx == n)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, cap, dim)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(n, cap)), jnp.int32)
    counts = jnp.asarray(rng.integers(1, cap + 1, size=n), jnp.float32)
    admit = np.sort(rng.choice(n, admitted, replace=False))
    sel_idx = jnp.asarray(np.r_[admit, [n] * (k_lanes - admitted)],
                          jnp.int32)
    safe = jnp.minimum(sel_idx, n - 1)
    idx = engine._batch_index_lattice(jax.random.key(seed), tau2, tau1,
                                      safe, counts[safe], batch)
    bundle = engine.RoundBundle(dist=None, x=x, y=y, counts=counts,
                                test_x=None, test_y=None)
    return bundle, safe, idx


def test_minibatch_gather_equals_slab_gather():
    bundle, safe, idx = _gather_case(0)
    assert int(safe[-1]) == bundle.x.shape[0] - 1       # a pad lane
    bx, by = engine._minibatches(bundle, safe, idx)
    want_x, want_y = _two_step_minibatches(bundle.x, bundle.y, safe, idx)
    assert bx.shape == idx.shape + (bundle.x.shape[2],)
    assert by.shape == idx.shape
    np.testing.assert_array_equal(np.asarray(bx), np.asarray(want_x))
    np.testing.assert_array_equal(np.asarray(by), np.asarray(want_y))


def test_minibatch_gather_equals_slab_gather_under_fleet_vmap():
    cases = [_gather_case(s) for s in (1, 2, 3)]
    bundles, safes, idxs = (jax.tree.map(lambda *l: jnp.stack(l), *part)
                            for part in zip(*cases))
    bx, by = jax.vmap(engine._minibatches)(bundles, safes, idxs)
    want_x, want_y = jax.vmap(_two_step_minibatches)(
        bundles.x, bundles.y, safes, idxs)
    np.testing.assert_array_equal(np.asarray(bx), np.asarray(want_x))
    np.testing.assert_array_equal(np.asarray(by), np.asarray(want_y))


def test_minibatch_gather_reads_lane_padded_rows():
    """A feature axis zero-padded to whole lanes (``lane_padded``, as
    ``shard_clients`` places it) gives the same minibatches, read to the
    model's width."""
    bundle, safe, idx = _gather_case(4)
    padded = bundle._replace(x=engine.lane_padded(bundle.x))
    assert padded.x.shape[-1] == engine.LANES
    dim = bundle.x.shape[-1]
    bx, by = engine._minibatches(padded, safe, idx, dim)
    want_x, want_y = engine._minibatches(bundle, safe, idx, dim)
    np.testing.assert_array_equal(np.asarray(bx), np.asarray(want_x))
    np.testing.assert_array_equal(np.asarray(by), np.asarray(want_y))


# a cap no other dimension of the SMALL world shares
SLAB = dataclasses.replace(SMALL, n_clients=24, max_samples=97)


def _walk_avals(jaxpr):
    """Every output aval of every equation, sub-programs included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield eqn.primitive.name, v.aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk_avals(sub)


def _slab_intermediates(fn, *args):
    cap = SLAB.max_samples
    closed = jax.make_jaxpr(fn)(*args)
    return [(name, aval.shape) for name, aval in _walk_avals(closed.jaxpr)
            if cap in getattr(aval, "shape", ())]


@pytest.mark.parametrize("impl", ["batched", "vmap", "pallas"])
def test_run_scanned_builds_no_cohort_slab(impl):
    """Only ``bundle.x`` / ``bundle.y`` (program inputs) have ``cap`` rows
    per client: no (K, cap, D) slab and no chunk of one is ever traced."""
    spec = engine.EngineSpec(policy="fcea", scheduler="pdd",
                             train_impl=impl)
    state, bundle, _ = engine.init_simulation(SLAB, seed=0)
    assert bundle.x.shape[1] == SLAB.max_samples
    assert engine.quota_for(SLAB, spec) * SLAB.n_edges < SLAB.n_clients
    found = _slab_intermediates(
        lambda s, b: engine.run_scanned(SLAB, spec, s, b, 2), state, bundle)
    assert found == []


def test_run_fleet_actors_builds_no_cohort_slab():
    spec = engine.EngineSpec(policy="fcea", allocator="ddpg",
                             scheduler="pdd")
    sims = [engine.init_simulation(SLAB, seed=s)[:2] for s in (0, 1)]
    states, bundles = engine.stack_fleet(sims)
    dcfg = ddpg.allocator_config(SLAB, spec, hidden=8)
    actors = jax.tree.map(
        lambda *l: jnp.stack(l),
        *[ddpg.init_ddpg(jax.random.key(s), dcfg).actor for s in (0, 1)])
    found = _slab_intermediates(
        lambda s, b, a: engine.run_fleet_actors(SLAB, spec, s, b, 2, a),
        states, bundles, actors)
    assert found == []
