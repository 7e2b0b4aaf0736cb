"""Training-stage tests (DESIGN.md §13).

(a) PRNG lattice: the batched ``_batch_index_lattice`` draws exactly
    the index sequences of the nested split/fold_in reference loop —
    the stream-layout contract the PR-10 goldens were re-recorded on,
(b) the engine's step: ``engine._cohort_fit`` (a τ₁ scan of
    ``jax.grad(model.loss)`` vmapped over the K lanes) is bit-equal to
    the MLP's cohort step written out as (K, B, D)-batched einsums, the
    oracle below, under the sync AND buffered engines, faults on or off;
    it trains any ``(init, loss)`` client model as a per-lane loop of
    SGD steps would, each lane on its own, under a fleet vmap too,
(c) warm-start: warm assignment == cold assignment bit-for-bit (the
    blocking-pair fallback guards exactness), the deferred-acceptance
    sweep count under ``random_waypoint`` mobility drops (median warm
    ≤ median cold, asserted from ``RoundTrace.assoc_sweeps``), and the
    cold carry keeps the warm leaf structurally absent,
(d) minibatch gather: ``_minibatches`` reads by (client, row) pairs
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
from repro.models import layers
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


# -- (b) the engine's step: the einsum oracle, any client model -------------

def _einsum_cohort_fit(model, lr):
    """The MLP's cohort step written out by hand: one τ₁ ``lax.scan``
    whose body takes the gradient of the K lanes' summed losses, each
    layer a (K, B, D)-batched einsum.  Lane k's gradient of the sum is
    its own Eq. 11 loss gradient."""
    def cohort_loss(p, bx, by):
        h = jax.nn.relu(jnp.einsum("kbd,kdh->kbh", bx, p["w1"])
                        + p["b1"][:, None, :])
        h = jax.nn.relu(jnp.einsum("kbh,khj->kbj", h, p["w2"])
                        + p["b2"][:, None, :])
        logits = jnp.einsum("kbh,khv->kbv", h, p["w3"]) + p["b3"][:, None, :]
        return jnp.sum(jax.vmap(layers.softmax_cross_entropy)(logits, by))

    def fit(params, bx, by):
        def step(p, batch):                          # (K, B, D), (K, B)
            g = jax.grad(cohort_loss)(p, *batch)
            return jax.tree.map(lambda w, gw: w - lr * gw, p, g), None

        params, _ = jax.lax.scan(step, params, (bx, by))
        return params

    return fit


@pytest.mark.parametrize("mode,faulted", [("sync", False), ("sync", True),
                                          ("buffered", False),
                                          ("buffered", True)])
def test_batched_bit_equal_vmap(mode, faulted, monkeypatch):
    """The engine's vmapped per-lane step and the einsum cohort step are
    the same XLA math — bit-for-bit, under both engines, with and without
    the fault layer.  The caches are cleared between the two runs, so the
    second traces ``run_scanned`` anew with the oracle in place."""
    kw = dict(engine_mode=mode)
    if mode == "buffered":
        kw.update(n_tiers=2, retier_every=3, timeout_s=5.0)
    if faulted:
        kw["faults"] = FaultSpec(edge_p_kill=0.0, edge_p_respawn=0.0,
                                 uplink_p_loss=0.2)
    outs = {}
    for step in ("engine", "einsum"):
        if step == "einsum":
            monkeypatch.setattr(engine, "_cohort_fit", _einsum_cohort_fit)
        jax.clear_caches()
        state, bundle, _ = engine.init_simulation(SMALL, seed=0)
        st, ms = engine.run_scanned(SMALL, _spec(**kw), state, bundle,
                                    ROUNDS)
        outs[step] = (st.global_params, st.client_params, ms)
    jax.clear_caches()
    _tree_equal(outs["engine"][0], outs["einsum"][0], "global_params")
    _tree_equal(outs["engine"][1], outs["einsum"][1], "client_params")
    _tree_equal(outs["engine"][2], outs["einsum"][2], "metrics")


class _Linear:
    """A softmax-linear classifier: ``{"w": (D, C), "b": (C,)}``."""

    def __init__(self, dim, n_classes):
        self.dim, self.n_classes = dim, n_classes

    def init(self, key):
        return {"w": 0.1 * jax.random.normal(key, (self.dim, self.n_classes)),
                "b": jnp.zeros((self.n_classes,))}

    def loss(self, params, batch):
        x, y = batch
        return layers.softmax_cross_entropy(x @ params["w"] + params["b"], y)


class _TanhNet:
    """Two tanh layers whose params are a (dict, list) pair: another tree
    of leaves than the MLP's flat dict, and no output bias."""

    def __init__(self, dim, hidden, n_classes):
        self.dim, self.hidden, self.n_classes = dim, hidden, n_classes

    def init(self, key):
        k1, k2 = jax.random.split(key)
        return ({"kernel": 0.3 * jax.random.normal(k1, (self.dim,
                                                        self.hidden)),
                 "bias": jnp.full((self.hidden,), 0.05)},
                [0.3 * jax.random.normal(k2, (self.hidden, self.n_classes))])

    def loss(self, params, batch):
        (hid, (out,)), (x, y) = params, batch
        h = jnp.tanh(x @ hid["kernel"] + hid["bias"])
        return layers.softmax_cross_entropy(h @ out, y)


DIM, HID, NCLS, LANES, TAU1, BATCH, LR = 12, 9, 5, 4, 3, 8, 0.1
MODELS = {"mlp": lambda: MLPClassifier(DIM, HID, NCLS),
          "linear": lambda: _Linear(DIM, NCLS),
          "tanh_net": lambda: _TanhNet(DIM, HID, NCLS)}


def _lanes_case(model, seed=0):
    """K lanes of ``model`` (each from its own init key) and τ₁ steps of
    minibatches, laid out as ``_train_cohort`` hands them to the fit:
    params (K, …), bx (τ₁, K, B, D), by (τ₁, K, B)."""
    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.key(seed), LANES)
    params = jax.vmap(model.init)(keys)
    bx = jnp.asarray(rng.normal(size=(TAU1, LANES, BATCH, DIM)), jnp.float32)
    by = jnp.asarray(rng.integers(0, NCLS, (TAU1, LANES, BATCH)), jnp.int32)
    return params, bx, by


def _lane(tree, k):
    return jax.tree.map(lambda l: l[k], tree)


def _assert_close(have, want):
    """Equal within 1e-6 of each element, or of the leaf's largest one
    where an element sits near zero (float reassociation, not a fault)."""
    have, want = np.asarray(have), np.asarray(want)
    assert have.shape == want.shape and have.dtype == want.dtype
    np.testing.assert_allclose(have, want, rtol=1e-6,
                               atol=1e-6 * float(np.max(np.abs(want))))


@pytest.mark.parametrize("name", sorted(MODELS))
def test_cohort_fit_trains_any_model(name):
    """Each lane comes out as τ₁ plain SGD steps of ``jax.grad(model.loss)``
    on its own minibatches would leave it, in a Python loop."""
    model = MODELS[name]()
    params, bx, by = _lanes_case(model)
    got = jax.jit(engine._cohort_fit(model, LR))(params, bx, by)
    assert jax.tree.structure(got) == jax.tree.structure(params)
    grad = jax.jit(jax.grad(model.loss))
    for k in range(LANES):
        p = _lane(params, k)
        for i in range(TAU1):
            g = grad(p, (bx[i, k], by[i, k]))
            p = jax.tree.map(lambda w, gw: w - LR * gw, p, g)
        for want, have in zip(jax.tree.leaves(p),
                              jax.tree.leaves(_lane(got, k))):
            _assert_close(have, want)


@pytest.mark.parametrize("name", sorted(MODELS))
def test_cohort_fit_lanes_are_independent(name):
    """Another lane's minibatches and params never reach a lane: changing
    lane 0's leaves every other lane's result bit for bit."""
    model = MODELS[name]()
    params, bx, by = _lanes_case(model)
    fit = jax.jit(engine._cohort_fit(model, LR))
    base = fit(params, bx, by)
    params2 = jax.tree.map(lambda l: l.at[0].multiply(3.0), params)
    moved = fit(params2, bx.at[:, 0].add(1.0), by.at[:, 0].set(0))
    assert not np.array_equal(np.asarray(jax.tree.leaves(moved)[0][0]),
                              np.asarray(jax.tree.leaves(base)[0][0]))
    for k in range(1, LANES):
        _tree_equal(_lane(moved, k), _lane(base, k), f"lane {k}")


@pytest.mark.parametrize("name", sorted(MODELS))
def test_cohort_fit_under_fleet_vmap(name):
    """``run_fleet`` vmaps the round over seeds, so the step runs with a
    fleet axis ahead of the lanes: each fleet member gets what it gets
    alone."""
    model = MODELS[name]()
    cases = [_lanes_case(model, seed) for seed in (1, 2, 3)]
    fleet = jax.tree.map(lambda *l: jnp.stack(l), *cases)
    fit = engine._cohort_fit(model, LR)
    got = jax.jit(jax.vmap(fit))(*fleet)
    for f, case in enumerate(cases):
        want = jax.jit(fit)(*case)
        for have, w in zip(jax.tree.leaves(_lane(got, f)),
                           jax.tree.leaves(want)):
            _assert_close(have, w)


# -- (c) warm-started association --------------------------------------------

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


# -- (d) minibatches gathered by (client, row), no cohort slab ----------------

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


def test_run_scanned_builds_no_cohort_slab():
    """Only ``bundle.x`` / ``bundle.y`` (program inputs) have ``cap`` rows
    per client: no (K, cap, D) slab and no chunk of one is ever traced."""
    spec = engine.EngineSpec(policy="fcea", scheduler="pdd")
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
