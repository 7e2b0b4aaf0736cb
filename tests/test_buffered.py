"""Semi-async buffered round engine tests (DESIGN.md §11).

(a) the static mode switch: ``engine_mode="sync"`` (explicit or default)
    keeps the buffer STRUCTURALLY absent and reproduces the committed
    golden trajectories bit-for-bit — the buffered refactor must not
    perturb the barrier engine at all,
(b) buffered micro-step semantics: the fill-or-timeout trigger fires at
    EXACTLY (fill ≥ buffer_fill) ∨ (clock ≥ last_agg + timeout_s),
    reconstructed per-step from the telemetry trace,
(c) landing semantics: a drained client's Eq. 20 counter resets to 1 and
    its in-flight flag clears, so it re-enters the market fresh,
(d) buffer algebra properties (via the _hyp shim — these collect as
    skips when hypothesis is absent): the effective merge weights
    w_n / Σw sum to 1 (the merge is scale-invariant in the raw weights),
    the staleness discount lies in (0, 1] and decays monotonically, and
    the Eq. 20 counter saturates at ``STALENESS_MAX``,
(e) the buffered carry composes with the client-axis padding and the
    sweep grid's engine-mode axis.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st
from repro import sweeps
from repro.configs.hfl_mnist import CONFIG
from repro.core import aggregation, engine, staleness

SMALL = dataclasses.replace(CONFIG, n_clients=16, n_edges=2,
                            clients_per_edge=3, min_samples=60,
                            max_samples=120, hidden=32, input_dim=64)
GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "static_parity.json")
ROUNDS = 4

SPEC_BUF = engine.EngineSpec(policy="gcea", scheduler="fastest",
                             engine_mode="buffered", n_tiers=2,
                             retier_every=3, timeout_s=5.0)


# -- (a) sync mode: structural absence + golden bit-parity -------------------

@pytest.mark.parametrize("policy,scheduler", [("fcea", "pdd"),
                                              ("gcea", "fastest")])
def test_sync_mode_bit_equal_golden(policy, scheduler):
    """An EXPLICIT engine_mode="sync" spec reproduces the goldens
    bit-for-bit (they were recorded before the buffer existed, with the
    non-partitionable threefry that was JAX's default then; today's
    default draws other keys, so the run uses the recording's mode)."""
    with open(GOLDEN) as fh:
        golden = json.load(fh)["trajectories"][f"{policy}-{scheduler}"]
    spec = engine.EngineSpec(policy=policy, scheduler=scheduler,
                             engine_mode="sync")
    with jax.threefry_partitionable(False):
        state, bundle, _ = engine.init_simulation(SMALL, seed=0)
        final, ms = engine.run_scanned(SMALL, spec, state, bundle, ROUNDS)
    for field in ("accuracy", "loss", "cost", "total_time_s",
                  "total_energy_j", "avg_staleness"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ms, field), np.float64),
            np.asarray(golden[field]), err_msg=field)
    assert final.buffer is None                 # structurally absent


def test_sync_strips_an_attached_buffer():
    spec_sync = engine.EngineSpec(policy="gcea", scheduler="fastest")
    state, bundle, _ = engine.init_simulation(SMALL, seed=0)
    with_buf = engine.ensure_buffer(SMALL, SPEC_BUF, state)
    assert isinstance(with_buf.buffer, engine.BufferState)
    stripped = engine.ensure_buffer(SMALL, spec_sync, with_buf)
    assert stripped.buffer is None
    # and an already-normalised state passes through untouched
    assert engine.ensure_buffer(SMALL, spec_sync, state) is state
    assert engine.ensure_buffer(SMALL, SPEC_BUF, with_buf) is with_buf


def test_unknown_engine_mode_raises():
    spec = engine.EngineSpec(engine_mode="psync")
    state, bundle, _ = engine.init_simulation(SMALL, seed=0)
    with pytest.raises(ValueError, match="engine_mode"):
        engine.round_step(SMALL, spec, state, bundle)


# -- (b) the fill-or-timeout trigger, reconstructed exactly ------------------

def test_trigger_fires_at_exactly_fill_or_timeout():
    """Replay the virtual clock from (dt, fill, cause) telemetry and check
    the trigger bit matches (fill ≥ target) ∨ (clock ≥ deadline) at EVERY
    micro-step — no early, late or spurious merges."""
    spec = dataclasses.replace(SPEC_BUF, telemetry=True)
    state, bundle, _ = engine.init_simulation(SMALL, seed=0)
    steps = 24
    final, (ms, tr) = engine.run_scanned(SMALL, spec, state, bundle, steps)
    target = engine.buffer_fill_for(SMALL, spec)
    dt = np.asarray(ms.total_time_s, np.float64)
    fill = np.asarray(tr.buffer_fill)
    cause = np.asarray(tr.trigger_cause)
    fired = np.asarray(ms.z)[:, 0] > 0          # merge applied this step

    clock, last_agg = 0.0, 0.0
    eps = 1e-4
    n_merges = 0
    for i in range(steps):
        clock += dt[i]
        deadline = last_agg + spec.timeout_s
        by_fill = fill[i] >= target
        by_time = clock >= deadline - eps
        want_fired = by_fill or by_time
        # cause 0 = no trigger, 1 = fill, 2 = timeout (fill wins ties)
        want_cause = 0 if not want_fired else (1 if by_fill else 2)
        assert cause[i] == want_cause, f"step {i}"
        if want_fired:
            last_agg = clock
            if fill[i] > 0:
                n_merges += 1
        # the metrics z bit is the APPLIED merge (trigger ∧ non-empty)
        assert fired[i] == (want_fired and fill[i] > 0), f"step {i}"
    assert float(final.buffer.clock_s) == pytest.approx(clock, rel=1e-5)
    assert int(final.buffer.version) == n_merges
    assert n_merges >= 1                        # the run actually merged


def test_buffered_progresses_and_keeps_carry_invariants():
    state, bundle, _ = engine.init_simulation(SMALL, seed=0)
    final, ms = engine.run_scanned(SMALL, SPEC_BUF, state, bundle, 16)
    buf = final.buffer
    assert isinstance(buf, engine.BufferState)
    assert float(buf.clock_s) > 0.0
    assert int(buf.step) == 16
    assert np.all(np.asarray(ms.total_time_s) >= 0.0)       # clock monotone
    assert int(buf.fill) >= 0 and float(buf.weight_sum) >= 0.0
    # tiers always index a valid TiFL bucket
    assert np.all((np.asarray(buf.tier) >= 0)
                  & (np.asarray(buf.tier) < SPEC_BUF.n_tiers))
    # micro-step metrics count the admitted cohort, never more than quota·M
    cap = engine.quota_for(SMALL, SPEC_BUF) * SMALL.n_edges
    assert np.all(np.asarray(ms.n_associated) <= cap)


# -- (c) drained clients re-enter fresh --------------------------------------

def test_drained_client_resets_staleness_and_in_flight():
    state, bundle, _ = engine.init_simulation(SMALL, seed=0)
    state = engine.ensure_buffer(SMALL, SPEC_BUF, state)
    n = SMALL.n_clients
    # client 1: in flight, tier 1 (not admitted at step 0), finishing
    # immediately; client 3: in flight, finishing far in the future
    in_flight = jnp.zeros((n,), bool).at[1].set(True).at[3].set(True)
    finish = jnp.zeros((n,)).at[1].set(1e-4).at[3].set(1e6)
    tier = jnp.zeros((n,), jnp.int32).at[1].set(1).at[3].set(1)
    buf = state.buffer._replace(in_flight=in_flight, finish_s=finish,
                                tier=tier)
    state = state._replace(buffer=buf,
                           staleness=jnp.full((n,), 7, jnp.int32))
    new_state, ms = engine.round_step(SMALL, SPEC_BUF, state, bundle)
    stale = np.asarray(new_state.staleness)
    nbuf = new_state.buffer
    assert stale[1] == 1                        # landed -> reset (Eq. 20)
    assert not bool(nbuf.in_flight[1])          # drained -> idle again
    assert stale[3] == 8                        # still flying -> +1
    assert bool(nbuf.in_flight[3])
    assert int(nbuf.fill) >= 1                  # the landing was buffered


# -- (d) buffer algebra properties (skip without hypothesis) -----------------

@given(st.floats(0.1, 50.0), st.floats(0.1, 50.0), st.floats(0.1, 50.0),
       st.floats(0.01, 100.0))
@settings(max_examples=25, deadline=None)
def test_merge_weights_sum_to_one(w1, w2, w3, scale):
    """The applied step is Σwδ/Σw: rescaling every raw weight by a common
    factor changes nothing, and identical deltas merge to exactly that
    delta — i.e. the effective weights sum to 1."""
    g = {"w": jnp.zeros((3,)), "b": jnp.zeros(())}
    weights = jnp.asarray([w1, w2, w3], jnp.float32)
    v = jnp.asarray([1.0, -2.0, 0.5])
    deltas = {"w": jnp.tile(v[None], (3, 1)), "b": jnp.ones((3,))}
    fired = jnp.asarray(True)

    ds, ws = aggregation.buffer_accumulate(
        aggregation.buffer_zeros(g), jnp.zeros(()), deltas, weights)
    out = aggregation.buffer_apply(g, ds, ws, 1.0, fired)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(v),
                               rtol=1e-5)
    np.testing.assert_allclose(float(out["b"]), 1.0, rtol=1e-5)
    # scale-invariance of the raw weights
    ds2, ws2 = aggregation.buffer_accumulate(
        aggregation.buffer_zeros(g), jnp.zeros(()), deltas,
        weights * jnp.float32(scale))
    out2 = aggregation.buffer_apply(g, ds2, ws2, 1.0, fired)
    np.testing.assert_allclose(np.asarray(out2["w"]),
                               np.asarray(out["w"]), rtol=1e-4)


@given(st.integers(1, 10**7), st.integers(0, 10**7))
@settings(max_examples=50, deadline=None)
def test_staleness_weight_bounded_and_monotone(age, bump):
    w = float(staleness.buffer_weight(jnp.asarray(age)))
    w2 = float(staleness.buffer_weight(jnp.asarray(age + bump)))
    assert 0.0 < w <= 1.0
    assert w2 <= w + 1e-7                       # older is never up-weighted
    if age == 1:
        assert w == pytest.approx(1.0)          # fresh update undiscounted


@given(st.integers(1, 2**30))
@settings(max_examples=50, deadline=None)
def test_update_staleness_saturates(a):
    stale = jnp.asarray([a], jnp.int32)
    out = int(staleness.update_staleness(stale,
                                         jnp.asarray([False]))[0])
    assert out == min(a + 1, staleness.STALENESS_MAX)
    assert int(staleness.update_staleness(
        jnp.asarray([staleness.STALENESS_MAX], jnp.int32),
        jnp.asarray([False]))[0]) == staleness.STALENESS_MAX


def test_buffer_age_saturates_and_floors():
    ver = jnp.asarray(5, jnp.int32)
    assert int(staleness.buffer_age(ver, jnp.asarray(5, jnp.int32))) == 1
    assert int(staleness.buffer_age(ver, jnp.asarray(9, jnp.int32))) == 1
    big = jnp.asarray(staleness.STALENESS_MAX + 7, jnp.int32)
    assert int(staleness.buffer_age(big, jnp.asarray(0, jnp.int32))) \
        == staleness.STALENESS_MAX


# -- (e) composition: padding + the sweep grid's engine-mode axis ------------

def test_pad_clients_pads_the_buffer_too():
    state, bundle, _ = engine.init_simulation(SMALL, seed=0)
    state = engine.ensure_buffer(SMALL, SPEC_BUF, state)
    cfg2, state2, bundle2 = engine.pad_clients(SMALL, state, bundle, 10)
    assert cfg2.n_clients == 20
    buf = state2.buffer
    assert buf.finish_s.shape == (20,) and buf.tier.shape == (20,)
    assert not np.any(np.asarray(buf.in_flight[SMALL.n_clients:]))
    # the padded world still steps (inert clients never associate)
    _, ms = engine.run_scanned(cfg2, SPEC_BUF, state2, bundle2, 3)
    assert np.all(np.asarray(ms.n_associated) <= np.asarray(ms.n_available)
                  + 0)


def test_sweep_engine_mode_axis_and_cell_ids(tmp_path):
    grid = sweeps.SweepGrid(name="bt", scenarios=("static",),
                            policies=("gcea",), schedulers=("fastest",),
                            seeds=(0,), n_rounds=2,
                            engine_modes=("sync", "buffered"))
    cells = sweeps.expand_grid(grid)
    ids = {c.cell_id for c in cells}
    assert ids == {"static__gcea__mid__fastest__noma__s0",
                   "static__gcea__mid__fastest__noma__s0__buffered"}
    summary = sweeps.run_sweep(SMALL, grid, out_dir=str(tmp_path))
    assert summary["n_cells"] == 2
    assert summary["n_compiles"] == 2           # one per engine mode
    assert set(summary["final"]) == ids


def test_stream_scanned_accepts_buffered_spec():
    """The streaming drivers must normalise the carry too: a buffered
    spec entering ``stream_scanned`` with ``state.buffer is None`` would
    otherwise change the scan-carry structure mid-scan."""
    from repro.telemetry import sink

    spec = dataclasses.replace(SPEC_BUF, telemetry=True)
    state, bundle, _ = engine.init_simulation(SMALL, seed=0)
    assert state.buffer is None                    # the hazardous input
    mem = sink.MemorySink()
    final, ms, tr = sink.stream_scanned(SMALL, spec, state, bundle, 3, mem)
    assert len(mem.records) == 3
    assert final.buffer is not None
    assert int(final.buffer.step) == 3
    # the streamed trace carries the buffered leaves
    assert np.asarray(tr.buffer_fill).shape == (3,)
