"""In-scan telemetry tests (DESIGN.md §10).

(a) the static flag: telemetry off keeps the engine output structurally
    identical (a plain ``RoundMetrics``) and BIT-equal to the PR-1
    goldens; telemetry on changes only the output arity — the metrics
    half stays bit-equal to the same goldens,
(b) ``RoundTrace`` shape/dtype invariants under ``run_scanned``,
    ``run_fleet`` and the client-sharded driver,
(c) the Eq. 23a decomposition identity: the three energy terms sum
    exactly to ``RoundMetrics.total_energy_j`` and the time terms
    upper-bound ``total_time_s``,
(d) streaming: a JSONL sink written by ``stream_scanned`` parses back to
    the same stacked pytree the pure collect mode returns,
(e) the sweep runner persists ``<cell>.trace.json`` beside the metrics,
(f) the stage and part scopes: each part sits under its stage in the
    compiled module's metadata, and the scopes change nothing else.
"""
import contextlib
import dataclasses
import json
import os
import re
import sys

import jax
import numpy as np
import pytest

from repro import sweeps
from repro.configs.hfl_mnist import CONFIG
from repro.core import engine
from repro.telemetry import RoundTrace, STALE_BIN_EDGES, sink, spans, trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from bench import tracing  # noqa: E402  (reads the scopes off a chip trace)

SMALL = dataclasses.replace(CONFIG, n_clients=16, n_edges=2,
                            clients_per_edge=3, min_samples=60,
                            max_samples=120, hidden=32, input_dim=64)
GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "static_parity.json")
ROUNDS = 4

INT_LEAVES = {"round", "assoc_sweeps", "edge_load", "pdd_iters",
              "sic_depth", "stale_hist", "buffer_fill", "trigger_cause",
              "tier_active", "tier_occupancy", "dead_edges",
              "orphaned_clients", "uplink_retries", "uplink_dropped",
              "quarantined"}


def _leaf_shapes(m):
    """Expected trailing (per-round) shape of every RoundTrace leaf."""
    return {"edge_load": (m,), "z_relaxed": (m,),
            "stale_hist": (len(STALE_BIN_EDGES),)}


def _check_trace(tr, lead, m):
    assert isinstance(tr, RoundTrace)
    trailing = _leaf_shapes(m)
    for name, leaf in tr._asdict().items():
        leaf = np.asarray(leaf)
        want = lead + trailing.get(name, ())
        assert leaf.shape == want, f"{name}: {leaf.shape} != {want}"
        if name in INT_LEAVES:
            assert np.issubdtype(leaf.dtype, np.integer), name
        else:
            assert leaf.dtype == np.float32, name


# -- (a) static flag: structural absence + golden bit-parity -----------------

@pytest.mark.parametrize("policy,scheduler", [("fcea", "pdd"),
                                              ("gcea", "fastest")])
def test_telemetry_off_is_structurally_absent(policy, scheduler):
    spec = engine.EngineSpec(policy=policy, scheduler=scheduler)
    assert not spec.telemetry                       # off by default
    state, bundle, _ = engine.init_simulation(SMALL, seed=0)
    _, out = engine.run_scanned(SMALL, spec, state, bundle, 2)
    assert isinstance(out, engine.RoundMetrics)     # no trace half at all
    ms, tr = engine.split_output(spec, out)
    assert ms is out and tr is None


@pytest.mark.parametrize("policy,scheduler", [("fcea", "pdd"),
                                              ("gcea", "fastest")])
def test_telemetry_on_metrics_bit_equal_golden(policy, scheduler):
    """Turning the flag on must not perturb a single metrics bit.  Run
    with the non-partitionable threefry the goldens were recorded under
    (JAX's default then; today's default draws other keys)."""
    with open(GOLDEN) as fh:
        golden = json.load(fh)["trajectories"][f"{policy}-{scheduler}"]
    spec = engine.EngineSpec(policy=policy, scheduler=scheduler,
                             telemetry=True)
    with jax.threefry_partitionable(False):
        state, bundle, _ = engine.init_simulation(SMALL, seed=0)
        _, (ms, tr) = engine.run_scanned(SMALL, spec, state, bundle, ROUNDS)
    for field in ("accuracy", "loss", "cost", "total_time_s",
                  "total_energy_j", "avg_staleness"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ms, field), np.float64),
            np.asarray(golden[field]), err_msg=field)
    _check_trace(tr, (ROUNDS,), SMALL.n_edges)
    # scheduler internals match the spec: PDD iterates, "fastest" doesn't
    if scheduler == "pdd":
        assert np.all(np.asarray(tr.pdd_iters) > 0)
    else:
        assert np.all(np.asarray(tr.pdd_iters) == 0)


# -- (b) shape/dtype invariants under every driver ---------------------------

def test_trace_shapes_scanned_and_fleet():
    spec = engine.EngineSpec(policy="fcea", scheduler="pdd", telemetry=True)
    seeds = (0, 1)
    pairs = [engine.init_simulation(SMALL, seed=s)[:2] for s in seeds]
    _, ms, tr = sink.collect_scanned(SMALL, spec, *pairs[0], 3)
    _check_trace(tr, (3,), SMALL.n_edges)
    states, bundles = engine.stack_fleet(pairs)
    _, msf, trf = sink.collect_fleet(SMALL, spec, states, bundles, 3)
    _check_trace(trf, (len(seeds), 3), SMALL.n_edges)
    # fleet lane 0 == the single-sim run (same world, same program)
    for name in RoundTrace._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(trf, name))[0],
            np.asarray(getattr(tr, name)), rtol=1e-5, err_msg=name)


def test_trace_shapes_client_sharded():
    spec = engine.EngineSpec(policy="gcea", scheduler="fastest",
                             telemetry=True)
    state, bundle, _ = engine.init_simulation(SMALL, seed=0)
    _, out = engine.run_scanned_client_sharded(SMALL, spec, state, bundle, 2)
    ms, tr = engine.split_output(spec, out)
    _check_trace(tr, (2,), SMALL.n_edges)
    # N=16 on the 1-device CPU mesh needs no padding: bit-equal to plain
    _, out2 = engine.run_scanned(SMALL, spec, state, bundle, 2)
    np.testing.assert_array_equal(np.asarray(tr.edge_load),
                                  np.asarray(out2[1].edge_load))


def test_trace_candidate_frontier_fields():
    spec = engine.EngineSpec(policy="gcea", scheduler="fastest",
                             candidates_k=2, telemetry=True)
    state, bundle, _ = engine.init_simulation(SMALL, seed=0)
    _, ms, tr = sink.collect_scanned(SMALL, spec, state, bundle, 3)
    _check_trace(tr, (3,), SMALL.n_edges)
    vf = np.asarray(tr.frontier_valid_frac)
    sat = np.asarray(tr.frontier_saturation)
    assert np.all((vf >= 0) & (vf <= 1)) and np.all((sat >= 0) & (sat <= 1))
    assert np.all(np.asarray(tr.assoc_sweeps) >= 1)


# -- (c) Eq. 23a decomposition identity --------------------------------------

@pytest.mark.parametrize("policy,scheduler", [("fcea", "pdd"),
                                              ("gcea", "fastest")])
def test_cost_decomposition_identity(policy, scheduler):
    spec = engine.EngineSpec(policy=policy, scheduler=scheduler,
                             telemetry=True)
    state, bundle, _ = engine.init_simulation(SMALL, seed=0)
    _, ms, tr = sink.collect_scanned(SMALL, spec, state, bundle, ROUNDS)
    energy = (np.asarray(tr.energy_local_j) + np.asarray(tr.energy_uplink_j)
              + np.asarray(tr.energy_cloud_j))
    np.testing.assert_allclose(energy, np.asarray(ms.total_energy_j),
                               rtol=1e-5)
    tsum = (np.asarray(tr.time_local_s) + np.asarray(tr.time_uplink_s)
            + np.asarray(tr.time_cloud_s))
    assert np.all(tsum >= np.asarray(ms.total_time_s) - 1e-5)
    # the SIC decode depth is the max edge occupancy, capped by the quota
    assert np.all(np.asarray(tr.sic_depth)
                  == np.asarray(tr.edge_load).max(axis=1))
    assert np.all(np.asarray(tr.sic_depth) <= SMALL.clients_per_edge)


def test_staleness_histogram_counts_every_client():
    stale = np.array([1, 1, 2, 3, 5, 7, 9, 20], np.int32)
    hist = np.asarray(trace.staleness_histogram(stale))
    assert hist.sum() == stale.size
    assert hist[0] == 2 and hist[-1] == 1          # A_n=1 pair; A_n=20


# -- (d) streaming sinks: JSONL round-trip -----------------------------------

class _Tee:
    def __init__(self, *sinks):
        self.sinks = sinks

    def emit(self, tr):
        for s in self.sinks:
            s.emit(tr)


def test_jsonl_sink_roundtrip(tmp_path):
    spec = engine.EngineSpec(policy="gcea", scheduler="fastest",
                             candidates_k=2, telemetry=True)
    state, bundle, _ = engine.init_simulation(SMALL, seed=0)
    path = str(tmp_path / "rounds.jsonl")
    mem = sink.MemorySink()
    with sink.JsonlSink(path) as js:
        _, ms, tr = sink.stream_scanned(SMALL, spec, state, bundle, ROUNDS,
                                        _Tee(mem, js))
    assert len(mem.records) == ROUNDS
    parsed = sink.load_jsonl(path)
    stacked = mem.stacked()
    for name in RoundTrace._fields:
        want = np.asarray(getattr(tr, name))
        np.testing.assert_allclose(parsed[name], want, rtol=1e-6,
                                   err_msg=name)
        np.testing.assert_array_equal(np.asarray(getattr(stacked, name)),
                                      want, err_msg=name)
    # the stream is a tee: the returned pytree is the collect-mode result
    _, ms2, tr2 = sink.collect_scanned(SMALL, spec, state, bundle, ROUNDS)
    np.testing.assert_array_equal(np.asarray(tr.edge_load),
                                  np.asarray(tr2.edge_load))


def test_stream_requires_telemetry():
    spec = engine.EngineSpec(policy="gcea", scheduler="fastest")
    state, bundle, _ = engine.init_simulation(SMALL, seed=0)
    with pytest.raises(ValueError, match="telemetry"):
        sink.stream_scanned(SMALL, spec, state, bundle, 1,
                            sink.MemorySink())


def test_emit_stacked_bridges_fleet_traces():
    spec = engine.EngineSpec(policy="fcea", scheduler="pdd", telemetry=True)
    pairs = [engine.init_simulation(SMALL, seed=s)[:2] for s in (0, 1)]
    states, bundles = engine.stack_fleet(pairs)
    _, ms, tr = sink.collect_fleet(SMALL, spec, states, bundles, 2)
    mem = sink.MemorySink()
    sink.emit_stacked(tr, mem, fleet_axes=1)
    assert len(mem.records) == 2 * 2               # (seed, round) pairs
    assert all(r.edge_load.shape == (SMALL.n_edges,) for r in mem.records)


# -- (e) the sweep runner persists traces ------------------------------------

def test_sweep_writes_trace_json(tmp_path):
    grid = sweeps.SweepGrid(name="tt", scenarios=("static",),
                            policies=("gcea",), schedulers=("fastest",),
                            seeds=(0,), n_rounds=2, telemetry=True)
    summary = sweeps.run_sweep(SMALL, grid, out_dir=str(tmp_path))
    sweep_dir = os.path.join(str(tmp_path), "sweep_tt")
    traces = [f for f in os.listdir(sweep_dir) if f.endswith(".trace.json")]
    assert len(traces) == summary["n_cells"] == 1
    with open(os.path.join(sweep_dir, traces[0])) as fh:
        payload = json.load(fh)
    assert payload["n_rounds"] == 2
    tr = payload["trace"]
    assert set(tr) == set(RoundTrace._fields)
    assert len(tr["time_local_s"]) == 2
    assert len(tr["edge_load"][0]) == SMALL.n_edges


# -- (f) stage and part scopes ----------------------------------------------

# the stage each part sits under; the round loop sits under none
HOME = {"cohort": "train", "sgd": "train", "agg": "train",
        "pdd": "schedule", "round_loop": None}


def _compiled_text(spec, rounds=2):
    """The compiled module of a fresh scan (a new function each call, so
    nothing of an earlier trace is reused)."""
    state, bundle, _ = engine.init_simulation(SMALL, seed=0)
    fn = jax.jit(lambda s, b: engine._scan_rounds(SMALL, spec, s, b,
                                                  rounds, None))
    return fn.lower(state, bundle).compile().as_text()


def _without_metadata(text):
    text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n.*?\n\n", "\n", text, flags=re.S)
    return re.sub(r", metadata=\{[^}]*\}", "", text)


def test_part_scopes_nest_under_their_stage():
    assert set(HOME) == set(spans.PARTS)
    spec = engine.EngineSpec(policy="fcea", scheduler="pdd")
    seen = set()
    for scope in tracing.hlo_scopes(_compiled_text(spec)).values():
        if not scope.startswith("jit("):
            continue            # reduction bodies carry paths of their own
        comps = scope.replace("(", "/").replace(")", "/").split("/")
        for i, c in enumerate(comps):
            if c in HOME:
                outer = [s for s in comps[:i] if s in spans.STAGES]
                assert (outer[-1] if outer else None) == HOME[c], scope
                seen.add(c)
        inner = [c for c in comps if c in HOME]
        if inner and HOME[inner[-1]]:
            assert tracing.stage_of(scope) == HOME[inner[-1]], scope
        elif inner and not set(comps) & set(spans.STAGES):
            assert tracing.stage_of(scope) == "other", scope
    assert seen == set(spans.PARTS)


def test_part_scopes_change_metadata_only(monkeypatch):
    spec = engine.EngineSpec(policy="fcea", scheduler="pdd")
    scoped = _compiled_text(spec)
    assert '/round_loop/' in scoped
    monkeypatch.setattr(engine, "_part",
                        lambda name: contextlib.nullcontext())
    bare = _compiled_text(spec)
    assert '/round_loop/' not in bare
    assert _without_metadata(scoped) == _without_metadata(bare)


def test_unknown_scope_names_are_refused():
    with pytest.raises(ValueError):
        spans.part("gather")
    with pytest.raises(ValueError):
        spans.stage("cohort")
