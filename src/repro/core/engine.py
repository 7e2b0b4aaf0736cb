"""Pure-functional HFL round engine (DESIGN.md §2).

The paper's global round (fade → fuzzy-score → associate → allocate →
τ₂·τ₁ training → schedule → cloud aggregate, §II-§IV) as ONE pure function:

    round_step(cfg, spec, state, bundle) -> (state', RoundMetrics)

* ``RoundState``  — everything that evolves across rounds, as a pytree:
  stacked global/client params, channel gains, staleness, the PRNG key and
  the round index.
* ``RoundBundle`` — everything that is fixed for one scenario but differs
  between scenarios (topology distances, the federated dataset): traced
  arrays, so a *batch* of scenarios is just a stacked bundle.
* ``cfg``/``spec`` — hashable static configuration; they select code paths
  at trace time (association policy, allocator, scheduler, NOMA vs OMA).

Because ``round_step`` is end-to-end jittable (association included — see
``association.resolve_jax``), two compiled drivers come for free:

* ``run_scanned``  — ``lax.scan`` over rounds: an entire experiment is one
  XLA program (no per-round dispatch, no host sync);
* ``run_fleet``    — ``vmap`` over a batch of independent simulations for
  multi-seed / multi-scenario sweeps, on top of the scanned driver.

The legacy ``HFLSimulation`` class survives as a thin stateful wrapper in
``repro.core.hfl``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (aggregation, association, candidates, cost, env,
                        fuzzy, noma, pdd, staleness)
from repro.core.candidates import CandidateSet
from repro import telemetry
from repro.telemetry.spans import part as _part
from repro.telemetry.spans import stage as _stage
from repro.data import federated
from repro.faults import guard as fault_guard
from repro.faults import inject as fault_inject
from repro.faults.spec import FaultSpec, FaultState, init_faults
from repro.models.mlp import MLPClassifier
from repro import scenarios
from repro.scenarios import ScenarioSpec, ScenarioState

Params = Any


# ---------------------------------------------------------------------------
# Static spec + pytrees
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """Static (hashable) per-simulation switches; a jit static argument."""
    policy: str = "fcea"            # fcea | gcea | rcea
    allocator: str = "mid"          # mid | rra | fpa | fca | ddpg
    scheduler: str = "pdd"          # pdd | fastest
    noma_enabled: bool = True
    fading_rho: float = 0.9
    oma_quota_factor: float = 0.5
    # scenario transition KIND only (a trace-time switch into
    # scenarios.TRANSITIONS) — the scenario's numbers live in the
    # ScenarioState arrays, so different parameterisations share a compile.
    scenario: str = "static"
    # hot-path implementation switches (DESIGN.md §8).  All of them pick
    # between bit-compatible (resolver) or float-summation-order-compatible
    # (sic_impl, pallas_score) implementations of the SAME math:
    # * resolver — "parallel" sweep deferred-acceptance (default) vs the
    #   legacy "serial" one-pop-per-step while-loop, kept for A/B;
    # * sic_impl — "auto" (sorted cumulative-interference from N ≥ 64,
    #   bit-stable pairwise below) | "pairwise" | "sorted" | "pallas";
    # * pallas_score — route fcea fuzzy scoring through the fused
    #   kernels.hfl_ops.score_matrix kernel (interpret-mode on CPU).
    resolver: str = "parallel"
    sic_impl: str = "auto"
    pallas_score: bool = False
    # (N, K) candidate frontier (DESIGN.md §9): score/associate/bill only
    # each client's K nearest edges instead of all M.  ``None`` = dense
    # (the golden-pinned PR-4 path, bit-for-bit); K ≥ the max in-coverage
    # degree is bit-identical to dense by the §9 parity contract, smaller
    # K prunes the market (feasibility invariants still hold).
    candidates_k: Optional[int] = None
    # in-scan telemetry (DESIGN.md §10): with it on, ``round_step`` returns
    # ``(state', (RoundMetrics, telemetry.RoundTrace))`` — the per-stage
    # Eq. 23a decomposition plus association/scheduler internals riding the
    # scan outputs.  Off (the default) the trace is STRUCTURALLY absent:
    # the lowered program and every output are bit-identical to the
    # telemetry-less engine (golden parity holds un-re-recorded).
    telemetry: bool = False
    # semi-async buffered round engine (DESIGN.md §11).  "sync" is the
    # paper's semi-synchronous barrier — bit-for-bit today's program, with
    # the aggregation buffer STRUCTURALLY absent from the carry.
    # "buffered" turns ``round_step`` into a MICRO-step: each scan step
    # admits one TiFL-style speed-tier cohort through the same fuzzy/
    # candidate/association pipeline, trains it, and lands its
    # staleness-weighted model deltas in a FedBuff aggregation buffer at
    # their per-client Eq. 13/15 virtual finish times; the cloud applies
    # the buffered merge when ``buffer_fill`` updates landed OR
    # ``timeout_s`` of virtual time elapsed since the last aggregation —
    # round throughput becomes buffer-drain rate instead of
    # min-over-clients.
    engine_mode: str = "sync"       # sync | buffered
    buffer_fill: int = 0            # 0 = auto: (quota · M) // 2
    timeout_s: float = 10.0         # virtual seconds between forced merges
    n_tiers: int = 4                # TiFL speed tiers (1 = no tiering)
    retier_every: int = 8           # micro-steps between quantile retiers
    buffer_lr: float = 1.0          # server step on the merged mean delta
    # fault injection & graceful degradation (DESIGN.md §12).  ``None``
    # (the default) keeps every fault path STRUCTURALLY absent — no
    # FaultState rides the carry, no fault op is traced, and every golden
    # trajectory stays bit-exact un-re-recorded (the telemetry/engine_mode
    # discipline).  Set a ``FaultSpec`` to turn on edge churn, SINR-tied
    # uplink loss with retry/backoff (buffered mode), mid-round crashes,
    # delta poisoning, and the update-quarantine guard.
    faults: Optional[FaultSpec] = None
    # warm-started association (DESIGN.md §13.4): carry the previous
    # round's assigned vector in ``RoundState.warm`` and seed the
    # deferred-acceptance sweeps from it — under mobility the seed is
    # nearly stable, so the resolver converges in a sweep or two, with a
    # blocking-pair check + cold-resolver fallback guarding exactness.
    # Off (the default) the warm leaf is STRUCTURALLY absent and no seed
    # reaches the resolver: the cold program is bit-identical.
    warm_start: bool = False


class RoundBundle(NamedTuple):
    """Per-scenario constants (traced; leading batch axis under vmap)."""
    dist: jnp.ndarray        # (N, M) client-edge distances
    x: jnp.ndarray           # (N, cap, dim) padded client data
    y: jnp.ndarray           # (N, cap) labels
    counts: jnp.ndarray      # (N,) float32 — D_n
    test_x: jnp.ndarray      # (T, dim)
    test_y: jnp.ndarray      # (T,)


class BufferState(NamedTuple):
    """The buffered engine's extra scan carry (DESIGN.md §11): the FedBuff
    aggregation buffer + the per-client in-flight bookkeeping + the TiFL
    tier table.  Lives in ``RoundState.buffer`` on the buffered path and
    is ``None`` (structurally absent — zero leaves, zero program bytes)
    in ``engine_mode="sync"``."""
    pending_delta: Params    # (N, ...) trained-minus-pulled model deltas
    finish_s: jnp.ndarray    # (N,) f32 absolute virtual completion times
    in_flight: jnp.ndarray   # (N,) bool — admitted, not yet landed
    pulled_ver: jnp.ndarray  # (N,) int32 global version at admission
    obs_s: jnp.ndarray       # (N,) f32 EMA of measured finish durations
    tier: jnp.ndarray        # (N,) int32 TiFL speed tier (0 = fastest)
    delta_sum: Params        # global-shaped Σ w·Δ accumulator
    weight_sum: jnp.ndarray  # () f32 Σ w over buffered updates
    fill: jnp.ndarray        # () int32 updates landed since last trigger
    version: jnp.ndarray     # () int32 cloud aggregation count
    clock_s: jnp.ndarray     # () f32 virtual wall clock
    last_agg_s: jnp.ndarray  # () f32 clock at the last trigger
    step: jnp.ndarray        # () int32 micro-step counter


class RoundState(NamedTuple):
    """Everything that evolves across global rounds."""
    global_params: Params    # cloud model
    client_params: Params    # stacked (N, ...) client models
    gains: jnp.ndarray       # (N, M) current |h|²
    staleness: jnp.ndarray   # (N,) int32 — A_n
    key: jnp.ndarray         # PRNG key
    round_idx: jnp.ndarray   # () int32
    scenario: ScenarioState  # per-round world state (DESIGN.md §6)
    buffer: Any = None       # BufferState | None (DESIGN.md §11)
    faults: Any = None       # FaultState | None (DESIGN.md §12)
    warm: Any = None         # (N,) int32 prev assigned | None (§13.4)


class RoundMetrics(NamedTuple):
    """Per-round observables (jnp leaves; stacked along rounds by scan)."""
    round: jnp.ndarray
    accuracy: jnp.ndarray
    loss: jnp.ndarray
    avg_staleness: jnp.ndarray
    total_time_s: jnp.ndarray
    total_energy_j: jnp.ndarray
    cost: jnp.ndarray
    n_associated: jnp.ndarray
    n_available: jnp.ndarray
    z: jnp.ndarray           # (M,)


# ---------------------------------------------------------------------------
# Topology (paper §V: 500 m square, cloud at centre, 4 edges at midpoints
# of the corner-to-centre lines, clients uniform)
# ---------------------------------------------------------------------------

def make_topology(rng: np.random.Generator, *, n_clients: int, n_edges: int,
                  area_side_m: float) -> Dict[str, np.ndarray]:
    half = area_side_m / 2.0
    cloud = np.array([half, half])
    corners = np.array([[0.0, 0.0], [0.0, area_side_m],
                        [area_side_m, 0.0], [area_side_m, area_side_m]])
    mids = (corners + cloud) / 2.0
    if n_edges <= 4:
        edges = mids[:n_edges]
    else:  # extra edges uniformly placed
        extra = rng.uniform(0.0, area_side_m, (n_edges - 4, 2))
        edges = np.concatenate([mids, extra], axis=0)
    clients = rng.uniform(0.0, area_side_m, (n_clients, 2))
    dist = np.linalg.norm(clients[:, None, :] - edges[None, :, :], axis=-1)
    return {"cloud": cloud, "edges": edges, "clients": clients, "dist": dist}


def coverage_radius(cfg) -> float:
    """Generous enough that every client can reach ≥ 1 edge."""
    return cfg.area_side_m * 0.75


def quota_for(cfg, spec: EngineSpec) -> int:
    """OMA admits fewer clients per edge: each needs an orthogonal channel
    slice (paper §V-B — 'insufficient orchestrated clients')."""
    if spec.noma_enabled:
        return cfg.clients_per_edge
    return max(1, int(cfg.clients_per_edge * spec.oma_quota_factor))


def buffer_fill_for(cfg, spec: EngineSpec) -> int:
    """The fill half of the fill-or-timeout trigger.  ``buffer_fill=0``
    resolves to half the per-micro-step admission capacity (quota · M),
    so in steady state the trigger fires well before a whole cohort's
    straggler tail lands."""
    if spec.buffer_fill > 0:
        return int(spec.buffer_fill)
    return max(1, (quota_for(cfg, spec) * cfg.n_edges) // 2)


def init_buffer(cfg, spec: EngineSpec, state: "RoundState") -> BufferState:
    """A fresh (empty) aggregation buffer shaped for ``state``'s models.
    Tiers start round-robin over clients (balanced cohorts before any
    finish time has been observed); the first quantile retier replaces
    them with measured-speed tiers."""
    n = cfg.n_clients
    f32, i32 = jnp.float32, jnp.int32
    return BufferState(
        pending_delta=jax.tree.map(jnp.zeros_like, state.client_params),
        finish_s=jnp.zeros((n,), f32),
        in_flight=jnp.zeros((n,), bool),
        pulled_ver=jnp.zeros((n,), i32),
        obs_s=jnp.zeros((n,), f32),
        tier=jnp.arange(n, dtype=i32) % max(1, int(spec.n_tiers)),
        delta_sum=aggregation.buffer_zeros(state.global_params),
        weight_sum=jnp.zeros((), f32),
        fill=jnp.zeros((), i32),
        version=jnp.zeros((), i32),
        clock_s=jnp.zeros((), f32),
        last_agg_s=jnp.zeros((), f32),
        step=jnp.zeros((), i32))


def ensure_buffer(cfg, spec: EngineSpec, state: "RoundState") -> "RoundState":
    """Normalise ``state.buffer`` to the spec's engine mode: attach a
    fresh buffer for ``engine_mode="buffered"`` (keeping one that is
    already there, e.g. mid-scan), strip it for "sync" so the sync carry
    — and with it every golden program — stays structurally identical to
    the pre-buffer engine.  The check is on the pytree STRUCTURE (None or
    not), so it is trace-time static and jit-safe."""
    if spec.engine_mode == "buffered":
        if state.buffer is None:
            return state._replace(buffer=init_buffer(cfg, spec, state))
        return state
    if spec.engine_mode != "sync":
        raise ValueError(f"unknown engine_mode {spec.engine_mode!r}; "
                         f"choose 'sync' or 'buffered'")
    if state.buffer is not None:
        return state._replace(buffer=None)
    return state


def ensure_faults(cfg, spec: EngineSpec, state: "RoundState") -> "RoundState":
    """Normalise ``state.faults`` to the spec: attach a fresh
    ``FaultState`` when ``spec.faults`` is set (keeping one already there,
    e.g. mid-scan or restored from a checkpoint), strip it when faults are
    off so the no-fault carry — and with it every golden program — stays
    structurally identical to the pre-fault engine.  Like
    ``ensure_buffer``, the check is on pytree STRUCTURE (None or not), so
    it is trace-time static and jit-safe."""
    if spec.faults is not None:
        if state.faults is None:
            return state._replace(faults=init_faults(cfg))
        return state
    if state.faults is not None:
        return state._replace(faults=None)
    return state


def init_warm(cfg) -> jnp.ndarray:
    """A fresh warm-start seed: every client unassigned (−1), so the first
    warm round degenerates to the cold resolver's empty start."""
    return jnp.full((cfg.n_clients,), -1, jnp.int32)


def ensure_warm(cfg, spec: EngineSpec, state: "RoundState") -> "RoundState":
    """Normalise ``state.warm`` to the spec: attach the unassigned seed
    when ``spec.warm_start`` is on (keeping one already there, e.g.
    mid-scan or restored from a checkpoint), strip it when off so the
    cold carry — and with it every golden program — stays structurally
    identical to the pre-warm engine.  Same pytree-STRUCTURE check as
    ``ensure_buffer``/``ensure_faults``: trace-time static, jit-safe."""
    if spec.warm_start:
        if state.warm is None:
            return state._replace(warm=init_warm(cfg))
        return state
    if state.warm is not None:
        return state._replace(warm=None)
    return state


def ensure_carry(cfg, spec: EngineSpec, state: "RoundState") -> "RoundState":
    """Normalise the FULL scan carry to the spec's optional subsystems
    (aggregation buffer + fault state + warm-association seed) — the one
    entry point drivers use."""
    return ensure_warm(
        cfg, spec, ensure_faults(cfg, spec, ensure_buffer(cfg, spec, state)))


# ---------------------------------------------------------------------------
# Initialisation (host side: numpy RNG builds the scenario once)
# ---------------------------------------------------------------------------

def client_model(cfg) -> MLPClassifier:
    """The model every client trains: ``init(key)``, ``loss(params,
    (x, y))``, ``accuracy(params, x, y)``."""
    return MLPClassifier(cfg.input_dim, cfg.hidden, cfg.n_classes)


def init_simulation(cfg, *, seed: int = 0, iid: bool = True,
                    scenario: "ScenarioSpec | str | None" = None
                    ) -> Tuple[RoundState, RoundBundle, Dict[str, Any]]:
    """Build one scenario: returns (state, bundle, aux) where aux carries
    the host-side objects (topo dict, FederatedData, model, numpy rng).

    ``scenario`` (a ScenarioSpec, preset name or kind string) parameterises
    the dynamic world; its numpy draws happen AFTER topology + data, so the
    same seed yields the same federation under every scenario."""
    sspec = scenarios.preset(scenario)
    rng = np.random.default_rng(seed)
    key = jax.random.key(seed)
    topo = make_topology(rng, n_clients=cfg.n_clients, n_edges=cfg.n_edges,
                         area_side_m=cfg.area_side_m)
    data = federated.make_federated(
        rng, n_clients=cfg.n_clients, dim=cfg.input_dim,
        n_classes=cfg.n_classes, iid=iid,
        min_samples=cfg.min_samples, max_samples=cfg.max_samples,
        dirichlet_alpha=cfg.dirichlet_alpha,
        noise=getattr(cfg, "data_noise", 1.2))
    model = client_model(cfg)
    key, k_init = jax.random.split(key)
    global_params = model.init(k_init)
    dist = jnp.asarray(topo["dist"])
    key, k_gain = jax.random.split(key)
    gains = noma.rayleigh_gains(k_gain, dist,
                                path_loss_exponent=cfg.path_loss_exponent)
    state = RoundState(
        global_params=global_params,
        client_params=aggregation.replicate(global_params, cfg.n_clients),
        gains=gains,
        staleness=staleness.init_staleness(cfg.n_clients),
        key=key,
        round_idx=jnp.asarray(0, jnp.int32),
        scenario=scenarios.init_scenario(cfg, sspec, rng, topo))
    bundle = RoundBundle(
        dist=dist,
        x=jnp.asarray(data.x),
        y=jnp.asarray(data.y),
        counts=jnp.asarray(data.counts, jnp.float32),
        test_x=jnp.asarray(data.test_x),
        test_y=jnp.asarray(data.test_y))
    aux = {"topo": topo, "data": data, "model": model, "rng": rng,
           "scenario_spec": sspec}
    return state, bundle, aux


def stack_fleet(states_and_bundles) -> Tuple[RoundState, RoundBundle]:
    """Stack per-seed (state, bundle) pairs along a new leading fleet axis
    so ``run_fleet`` can vmap over them."""
    states = [s for s, _ in states_and_bundles]
    bundles = [b for _, b in states_and_bundles]
    stack = lambda *ls: jnp.stack(ls)
    return (jax.tree.map(stack, *states), jax.tree.map(stack, *bundles))


# ---------------------------------------------------------------------------
# Round pieces (pure)
# ---------------------------------------------------------------------------

def _batch_index_lattice(key, tau2: int, tau1: int, gid: jnp.ndarray,
                         counts: jnp.ndarray, batch_size: int) -> jnp.ndarray:
    """Every minibatch index of the round in ONE batched draw
    (DESIGN.md §13.2): the key for (edge-iteration t, local step i,
    client c) is ``fold_in(fold_in(split(key, τ₂)[t], i), c)`` with ``c``
    the client's GLOBAL index.

    One outer split + a fold_in lattice replaces nested per-iteration
    ``jax.random.split`` calls — no O(N) key fan-out inside the scan, and
    the drawn index stream is a pure function of (round key, t, i, global
    id): identical between the dense and gathered cohort paths, and
    independent of which OTHER clients were admitted.  Pad lanes repeat a
    real client's id (their draws are discarded with the lane).

    gid/counts: (K,) global ids + per-lane sample counts.
    Returns idx (τ₂, τ₁, K, B) int32 into each lane's data buffer.
    """
    k_t = jax.random.split(key, tau2)
    hi = jnp.maximum(counts, 1)

    def one(kt, i, c, cnt):
        kc = jax.random.fold_in(jax.random.fold_in(kt, i), c)
        return jax.random.randint(kc, (batch_size,), 0, cnt)

    per_c = jax.vmap(one, in_axes=(None, None, 0, 0))
    per_i = jax.vmap(per_c, in_axes=(None, 0, None, None))
    per_t = jax.vmap(per_i, in_axes=(0, None, None, None))
    return per_t(k_t, jnp.arange(tau1, dtype=jnp.int32), gid, hi)


def _minibatches(bundle: RoundBundle, safe, idx, dim: Optional[int] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The round's minibatches, read straight from the data buffers by
    (client, row) pairs: lane k's row ``idx[..., k, b]`` of client
    ``safe[k]``.  ``safe`` (K,), ``idx`` (τ₂, τ₁, K, B) → ``bx``
    (τ₂, τ₁, K, B, D), ``by`` (τ₂, τ₁, K, B).  These are exactly the
    elements ``take_along_axis(bundle.x[safe], idx)`` picks, without the
    (K, cap, D) copy of the lanes' whole buffers.  Rows wider than the
    model's ``dim`` features (``lane_padded``) are gathered whole and cut
    to ``dim`` after: a TPU gathers whole rows in one op, but part rows
    one at a time, in a loop."""
    lane = safe[:, None]                             # broadcasts over B
    bx = bundle.x[lane, idx]
    if dim is not None and dim != bx.shape[-1]:
        bx = bx[..., :dim]
    return bx, bundle.y[lane, idx]


def _cohort_fit(model, lr: float):
    """One edge-iteration of τ₁ local-SGD steps over the stacked K-lane
    cohort: ``fit(params_K, bx, by) -> params_K`` with ``bx`` (τ₁, K, B, D)
    and ``by`` (τ₁, K, B) the iteration's minibatches, gathered by
    ``_train_cohort`` before the τ₂ scan (the fit never sees a data
    buffer).

    Generic over the client model: each lane runs a τ₁ ``lax.scan`` of
    ``jax.grad(model.loss)`` and the SGD update, vmapped over the K lanes
    (DESIGN.md §13.1).  XLA batches the lanes' products into single
    batched ``dot_general``s, so nothing lowers to K small matmuls.
    """
    def one_client(params, xs, ys):                  # xs (tau1, B, D)
        def step(p, batch):
            with _part("sgd"):
                g = jax.grad(model.loss)(p, batch)
                return jax.tree.map(lambda w, gw: w - lr * gw, p, g), None

        params, _ = jax.lax.scan(step, params, (xs, ys))
        return params

    return jax.vmap(one_client, in_axes=(0, 1, 1))


def _associate(cfg, spec: EngineSpec, key, gains, dist, counts, stale,
               avail: Optional[jnp.ndarray] = None,
               cand: Optional[CandidateSet] = None,
               with_sweeps: bool = False,
               seed: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Association, fully in JAX.  ``avail`` (N,) masks unavailable
    clients out of coverage (scenario dropout).

    Dense (``cand=None``): returns the (N, M) one-hot.  Candidate mode
    (DESIGN.md §9): fuzzy scoring and the resolver sweeps run on the
    (N, K) frontier (``avail`` is already folded into ``cand.valid`` by
    the builder) and the COMPACT assigned vector (N,) comes back.
    ``with_sweeps`` (telemetry) makes the result a (result, sweep-count)
    pair — the counter already sits in the resolver's while state."""
    scores = None
    if spec.policy == "fcea":
        if cand is not None:
            if spec.pallas_score:
                from repro.kernels import hfl_ops    # cycle-free lazy import
                scores = hfl_ops.score_candidates(
                    gains, cand.idx, counts, stale,
                    data_max=float(cfg.max_samples))
            else:
                scores = fuzzy.score_candidates(
                    gains, cand, counts, stale,
                    data_max=float(cfg.max_samples))
        elif spec.pallas_score:
            from repro.kernels import hfl_ops        # cycle-free lazy import
            scores = hfl_ops.score_matrix(gains, counts, stale,
                                          data_max=float(cfg.max_samples))
        else:
            scores = fuzzy.score_matrix(gains, counts, stale,
                                        data_max=float(cfg.max_samples))
    if cand is not None:
        return association.associate_candidates(
            spec.policy, scores=scores, gains=gains, cand=cand,
            quota=quota_for(cfg, spec), key=key, n_edges=cfg.n_edges,
            return_sweeps=with_sweeps, seed=seed)
    return association.associate_jax(
        spec.policy, scores=scores, gains=gains, dist=dist,
        quota=quota_for(cfg, spec),
        coverage_radius_m=coverage_radius(cfg), key=key, avail=avail,
        resolver=spec.resolver, return_sweeps=with_sweeps, seed=seed)


def _next_warm(spec: EngineSpec, assoc, assigned) -> Optional[jnp.ndarray]:
    """The warm seed the NEXT round's resolver starts from: this round's
    assigned vector (N,) int32, or None with warm-start off (the leaf —
    and every op deriving it — stays structurally absent)."""
    if not spec.warm_start:
        return None
    if assigned is not None:              # candidate path: already compact
        return assigned.astype(jnp.int32)
    sel = jnp.sum(assoc, axis=1) > 0
    return jnp.where(sel, jnp.argmax(assoc, axis=1).astype(jnp.int32),
                     jnp.asarray(-1, jnp.int32))


def _build_candidates(cfg, spec: EngineSpec, dist,
                      avail: Optional[jnp.ndarray],
                      edge_up: Optional[jnp.ndarray] = None
                      ) -> Optional[CandidateSet]:
    """The per-round (N, K) frontier, or None on the dense path.
    ``edge_up`` (fault-layer churn) invalidates dead edges while keeping
    the frontier's distances physical."""
    if spec.candidates_k is None:
        return None
    return candidates.build_candidates(
        dist, spec.candidates_k, coverage_radius_m=coverage_radius(cfg),
        avail=avail, edge_up=edge_up)


def _grid_allocate(cfg, spec: EngineSpec, assoc, gains, counts, dist,
                   scen: Optional[ScenarioState], fixed_axis: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The paper's FPA/FCA benchmarks (§V-D): one action axis pinned at
    its maximum, the other grid-optimised against the SAME Eq. 23a bill
    the engine charges — literally ``env.grid_best_action``, the one
    implementation the env baselines use, over the allocator-stage
    surface (z = 1; ``assoc`` is already availability-masked upstream)."""
    params = env.make_env_params(
        cfg, assoc, jnp.ones((cfg.n_edges,)), dist, counts,
        kappa=scen.kappa if scen is not None else None,
        p_max_w=scen.p_max_w if scen is not None else None,
        f_max_hz=scen.f_max_hz if scen is not None else None)
    a = env.grid_best_action(cfg, params, gains, fixed_axis=fixed_axis,
                             fixed_frac=1.0,
                             noma_enabled=spec.noma_enabled)
    return env.env_decode_action(cfg, params, a)


def _allocate(cfg, spec: EngineSpec, key, assoc, gains, counts,
              actor_params, scen: Optional[ScenarioState], dist,
              assigned: Optional[jnp.ndarray] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(p_w (N,), f_hz (N,)) per the configured allocator (§IV-C).
    ``dist`` (N, M) feeds the fpa/fca grid search's EnvParams; on the
    candidate path ``assigned`` (N,) lets the DDPG observation gather its
    own-edge gains instead of the (N, M) one-hot product."""
    n = cfg.n_clients
    mid_p = jnp.full((n,), 0.5 * (cfg.p_min_w + cfg.p_max_w))
    mid_f = jnp.full((n,), 0.5 * (cfg.f_min_hz + cfg.f_max_hz))
    if spec.allocator == "ddpg" and actor_params is not None:
        from repro.core import ddpg                 # cycle-free lazy import
        # in a dynamic scenario the observation gains an availability slice
        avail = None if scen is None else scen.avail
        if assigned is not None:
            obs = env.observe_assigned(
                assigned, candidates.own_edge_gather(assigned, gains),
                counts, avail=avail)
        else:
            obs = env.observe(assoc, gains, counts, avail=avail)
        act = ddpg.actor_apply(actor_params, obs)
        return env.decode_action(cfg, act, n)
    if spec.allocator == "rra":
        a = jax.random.uniform(key, (2, n))
        p = cfg.p_min_w + a[0] * (cfg.p_max_w - cfg.p_min_w)
        f = cfg.f_min_hz + a[1] * (cfg.f_max_hz - cfg.f_min_hz)
        return p, f
    if spec.allocator == "fpa":     # power pinned at p_max, f optimised
        return _grid_allocate(cfg, spec, assoc, gains, counts, dist, scen,
                              fixed_axis=0)
    if spec.allocator == "fca":     # frequency pinned at f_max, p optimised
        return _grid_allocate(cfg, spec, assoc, gains, counts, dist, scen,
                              fixed_axis=1)
    # "mid" (and ddpg before an agent exists): midpoint defaults
    return mid_p, mid_f


def associate_snapshot(cfg, spec: EngineSpec, state: RoundState,
                       bundle: RoundBundle) -> jnp.ndarray:
    """One-off (N, M) association on the CURRENT state, without advancing
    it: the same key slot and inputs ``round_step`` consumes, taken
    pre-transition (a dynamic ``round_step`` advances the scenario and
    fades the channel first, so its deployed association is one world
    step ahead of this snapshot).  THE single definition of the
    snapshot — the DDPG trainer's episode MDP and the wrapper's
    ``HFLSimulation._associate`` both read it, so the two consumers
    cannot drift from each other."""
    dynamic = spec.scenario != "static"
    scen = state.scenario
    dist = scen.dist if dynamic else bundle.dist
    avail = scen.avail if dynamic else None
    edge_up = (state.faults.edge_up
               if spec.faults is not None and state.faults is not None
               else None)
    cand = _build_candidates(cfg, spec, dist, avail, edge_up)
    if edge_up is not None and cand is None:
        # dense path: route around the CURRENT dead edges the same way the
        # round does (masked distance field)
        dist = fault_inject.masked_dist(dist, edge_up)
    out = _associate(cfg, spec, round_keys(spec, state.key)[3],
                     state.gains, dist, bundle.counts, state.staleness,
                     avail, cand, seed=state.warm)
    if cand is not None:      # compact assigned vector -> the (N, M) view
        out = candidates.assigned_one_hot(out, cfg.n_edges)
    return out


def _schedule_traced(cfg, spec: EngineSpec, rc_all: cost.RoundCost
                     ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, ...]]:
    """Semi-synchronous edge-selection mask z (M,) from ONE cost eval,
    plus the scheduler internals ``(iterations, residual, z_relaxed)``
    the telemetry trace records (zeros / the final z for the "fastest"
    baseline).  The internals ride along for free — ``pdd_schedule``
    already returns the full ``PDDResult``, so a telemetry-off caller
    that keeps only z leaves them to dead-code elimination.

    The PDD problem must optimise EXACTLY the Eq. 23a surface the engine
    bills: its per-edge time is ``t_cloud + U_m`` with
    ``U_m = τ₂ · max_{n∈N_m} t_n`` — the τ₂-scaled edge-iteration time of
    Eq. 13, not one bare client iteration.  With that U, the PDD objective
    at its own z equals ``apply_schedule(cfg, rc_all, z).cost`` identically
    (the regression test in tests/test_pdd.py pins it).
    """
    quota = max(1, int(round(cfg.semi_sync_fraction * cfg.n_edges)))
    if spec.scheduler == "pdd":
        with _part("pdd"):
            t_cloud = jnp.full((cfg.n_edges,),
                               cfg.edge_model_size_bits / cfg.edge_rate_bps)
            U = rc_all.per_edge_time_s - t_cloud
            res = pdd.pdd_schedule(rc_all.per_edge_energy_j, t_cloud, U,
                                   lam_t=cfg.lambda_t, lam_e=cfg.lambda_e,
                                   quota=quota)
        return res.z_binary, (res.iterations, res.residual, res.z)
    with _part("pdd"):
        z = pdd.semi_sync_fastest(rc_all.per_edge_time_s, quota)
    return z, (jnp.asarray(0, jnp.int32), jnp.asarray(0.0, jnp.float32),
               z.astype(jnp.float32))


def _schedule(cfg, spec: EngineSpec, rc_all: cost.RoundCost) -> jnp.ndarray:
    """The z-only view of ``_schedule_traced``."""
    return _schedule_traced(cfg, spec, rc_all)[0]


def _train_cohort(cfg, spec: EngineSpec, model, key,
                  state: RoundState, bundle: RoundBundle, assoc
                  ) -> Tuple[Params, Params]:
    """τ₂ × (τ₁ local SGD + edge aggregation) as a lax.scan (Eqs. 11, 13)
    — the per-cohort training stage shared by the sync round (which cloud-
    aggregates the result, ``_train``) and the buffered micro-step (which
    buffers the cohort's per-client deltas instead, DESIGN.md §11).
    Returns ``(client_params, edge_params)``.

    At most ``quota · M`` clients are ever admitted (a static bound), so
    the whole stage runs COMPACT (DESIGN.md §13): the admitted clients'
    params are gathered ONCE into a fixed K = min(N, quota·M) lane
    buffer before the scan, and so are the round's minibatches — the
    fold_in lattice draws every (τ₂, τ₁, K, B) row index and one gather
    by (client, row) pairs reads exactly those rows of ``bundle.x`` /
    ``bundle.y``, never a lane's whole data buffer.  Every edge iteration
    trains/aggregates/broadcasts on the (K, …) stack — model updates by
    ``_cohort_fit``, the lanes' products batched over K — and the result
    scatters back ONCE after the scan.  Unadmitted clients keep their
    params (exactly the old dense semantics); per-iteration work is
    O(quota·M) with no O(N) key splits, gathers or aggregation einsums
    left inside the scan.
    """
    counts = bundle.counts
    n = cfg.n_clients
    k_sel = min(n, quota_for(cfg, spec) * cfg.n_edges)
    selected = jnp.sum(assoc, axis=1) > 0

    # admitted-lane selection, hoisted OUT of the scan (it only depends on
    # ``assoc``): indices padded with n (dropped on the final scatter),
    # clamped for the gathers.  Pad lanes repeat client n−1's data and
    # draws — they train garbage that carries ZERO aggregation weight
    # (``lane_ok``) and never scatters back.
    with _part("cohort"):
        sel_idx = jnp.nonzero(selected, size=k_sel, fill_value=n)[0]
        safe = jnp.minimum(sel_idx, n - 1)
        lane_ok = (sel_idx < n).astype(assoc.dtype)            # (K,)
        sel_counts = counts[safe]
        sel_assoc = assoc[safe] * lane_ok[:, None]             # (K, M)
        # every τ₂·τ₁ minibatch of the round from ONE batched PRNG draw,
        # gathered ONCE straight from the data buffers
        idx = _batch_index_lattice(key, cfg.tau2, cfg.tau1, safe,
                                   sel_counts, cfg.local_batch)
        bx, by = _minibatches(bundle, safe, idx, cfg.input_dim)
    fit = _cohort_fit(model, cfg.lr)

    # admitted lanes start from the global model
    with _part("agg"):
        edge_params = aggregation.replicate(state.global_params, cfg.n_edges)
    with _part("cohort"):
        lane_params = jax.tree.map(lambda l: l[safe], state.client_params)
    with _part("agg"):
        lane_params = aggregation.broadcast_to_clients(
            None, sel_assoc, edge_params, lane_params)

    def edge_iter(carry, batch_t):
        lane_p, _ = carry
        lane_p = fit(lane_p, *batch_t)
        with _part("agg"):
            edge_p = aggregation.edge_aggregate(lane_p, sel_assoc, sel_counts)
            lane_p = aggregation.broadcast_to_clients(None, sel_assoc, edge_p,
                                                      lane_p)
        return (lane_p, edge_p), None

    (lane_params, edge_params), _ = jax.lax.scan(
        edge_iter, (lane_params, edge_params), (bx, by))
    # pad lanes target index n -> dropped; real lanes overwrite
    with _part("agg"):
        client_params = jax.tree.map(
            lambda old, new: old.at[sel_idx].set(new, mode="drop"),
            state.client_params, lane_params)
    return client_params, edge_params


def _train(cfg, spec: EngineSpec, model, key,
           state: RoundState, bundle: RoundBundle, assoc, z
           ) -> Tuple[Params, Params]:
    """``_train_cohort`` followed by the semi-synchronous cloud
    aggregation (Eq. 17) — the sync engine's training stage."""
    client_params, edge_params = _train_cohort(cfg, spec, model, key,
                                               state, bundle, assoc)
    counts = bundle.counts
    with _part("agg"):
        edge_data = jnp.sum(assoc * counts[:, None], axis=0)      # (M,)
        z_eff = z * (edge_data > 0).astype(z.dtype)
        agg = aggregation.cloud_aggregate(edge_params, z_eff, edge_data)
        # keep the old global model when no selected edge has data
        # (branchless version of the eager `if` — Eq. 17 degenerate case)
        has_data = jnp.sum(z_eff * edge_data) > 0
        global_params = jax.tree.map(
            lambda a, g: jnp.where(has_data, a, g), agg, state.global_params)
    return global_params, client_params


def _train_faulty(cfg, spec: EngineSpec, model, key,
                  state: RoundState, bundle: RoundBundle, assoc, z, gains,
                  edge_up, k_crash, k_loss, k_poison
                  ) -> Tuple[Params, Params, Tuple[jnp.ndarray, ...]]:
    """The sync training stage under faults (DESIGN.md §12.2).

    Training is unchanged (``_train_cohort``), but the cloud epilogue
    moves to DELTA space: each selected client's update is its trained
    model minus the global it pulled.  The transmitted copy then runs the
    fault gauntlet — mid-round crash (compute billed, delta lost),
    SINR-tied uplink loss (sync has no buffer to retry from: a lost
    upload is simply dropped this round), optional poisoning, and the
    quarantine guard — and only the surviving, guard-cleaned deltas reach
    ``faulted_cloud_aggregate``.  Client LOCAL params are never poisoned:
    poisoning models a corrupted transmission, not corrupted training.

    Returns ``(global', client_params, (ok, crashed, lost, n_rej))`` —
    ``ok`` is the surviving-client mask the staleness update consumes.
    """
    fsp = spec.faults
    client_params, _ = _train_cohort(cfg, spec, model, key, state, bundle,
                                     assoc)
    selected = jnp.sum(assoc, axis=1) > 0
    crashed = fault_inject.draw_crashes(fsp, k_crash, selected)
    lost = fault_inject.draw_losses(fsp, k_loss, gains, edge_up,
                                    selected & ~crashed)
    delivered = selected & ~crashed & ~lost
    deltas = jax.tree.map(lambda c, g: c - g[None], client_params,
                          state.global_params)
    deltas, _ = fault_inject.poison_deltas(fsp, k_poison, deltas, delivered)
    clean, ok, n_rej = fault_guard.quarantine(deltas, delivered,
                                              fsp.quarantine_clip)
    with _part("agg"):
        assoc_eff = assoc * ok.astype(assoc.dtype)[:, None]
        global_params = aggregation.faulted_cloud_aggregate(
            state.global_params, clean, assoc_eff, bundle.counts, z)
    return global_params, client_params, (ok, crashed, lost, n_rej)


# ---------------------------------------------------------------------------
# The round step + compiled drivers
# ---------------------------------------------------------------------------

def round_keys(spec: EngineSpec, key) -> Tuple[jnp.ndarray, ...]:
    """THE round's PRNG layout: (carry, scenario?, fade, assoc, alloc, train).

    The scenario key exists only on dynamic paths — the static path keeps
    the PR-1 5-way split bit-for-bit (golden parity depends on it).  Both
    ``round_step`` and the wrapper's association snapshot derive their keys
    from here, so the layout lives in exactly one place.
    """
    if spec.scenario != "static":
        return jax.random.split(key, 6)
    key, k_fade, k_assoc, k_alloc, k_train = jax.random.split(key, 5)
    return key, None, k_fade, k_assoc, k_alloc, k_train


def _buffered_step(cfg, spec: EngineSpec, state: RoundState,
                   bundle: RoundBundle,
                   actor_params: Optional[Params] = None
                   ) -> Tuple[RoundState, RoundMetrics]:
    """One buffered MICRO-step (DESIGN.md §11) — the semi-async engine's
    scan body.  Same shape contract as the sync ``round_step``: it returns
    ``(state', RoundMetrics)`` (or the telemetry pair), but the step
    semantics are event-driven:

    1. gate the market to the idle clients of the current TiFL speed tier
       and run the UNCHANGED fuzzy/candidate/association/allocation
       pipeline on that cohort;
    2. train the admitted cohort (``_train_cohort``) and park its
       per-client model deltas as in-flight with Eq. 13/15 virtual finish
       times;
    3. advance the virtual clock to the next completion event (or the
       timeout deadline), land every finished update in the FedBuff
       buffer with staleness weight w(a)=a^{-1/2} · D_n;
    4. fire the cloud merge when the buffer holds ``buffer_fill`` updates
       OR ``timeout_s`` elapsed since the last merge;
    5. every ``retier_every`` micro-steps, recompute quantile speed tiers
       from the per-client duration EMA (TiFL).

    ``metrics.total_time_s`` is the virtual-clock advance dt (not a
    barrier max), ``metrics.z`` broadcasts the trigger bit, and
    ``metrics.round`` counts micro-steps.
    """
    model = client_model(cfg)
    buf: BufferState = state.buffer
    n = cfg.n_clients
    f32, i32 = jnp.float32, jnp.int32
    n_tiers = max(1, int(spec.n_tiers))

    # 0. scenario transition + fading — identical preamble to the sync
    #    round (same round_keys layout, so the per-step PRNG stream is
    #    comparable across engines).
    dynamic = spec.scenario != "static"
    key, k_scen, k_fade, k_assoc, k_alloc, k_train = round_keys(spec,
                                                                state.key)
    if dynamic:
        scen = scenarios.advance(cfg, spec.scenario, k_scen, state.scenario)
        dist, avail = scen.dist, scen.avail
    else:
        scen = state.scenario
        dist, avail = bundle.dist, jnp.ones((n,), f32)
    gains = noma.evolve_gains(k_fade, state.gains, dist,
                              path_loss_exponent=cfg.path_loss_exponent,
                              rho=spec.fading_rho)

    # 0b. fault layer (DESIGN.md §12): the fault stream folds off the fade
    #     key (the no-fault PRNG layout is untouched); edge churn advances
    #     the live-edge mask, and the ASSOCIATION view of the distance
    #     field pushes dead edges out of coverage so the unchanged
    #     pipeline routes the orphaned clients to the survivors.
    fsp = spec.faults
    if fsp is not None:
        k_edge, k_loss, k_crash, k_poison = jax.random.split(
            fault_inject.fault_key(k_fade), 4)
        edge_up = fault_inject.advance_edges(fsp, k_edge,
                                             state.faults.edge_up)
        dist_assoc = fault_inject.masked_dist(dist, edge_up)
    else:
        edge_up = None
        dist_assoc = dist

    # 1. TiFL cohort gate: only idle clients of the scheduled tier enter
    #    the association market this micro-step, so every cohort is
    #    speed-coherent and the buffer drains in waves instead of one
    #    straggler-paced front.
    cur_tier = jnp.mod(buf.step, n_tiers)
    eligible = ((~buf.in_flight) & (buf.tier == cur_tier)).astype(f32) \
        * avail
    with _stage("associate"):
        cand = _build_candidates(cfg, spec, dist, eligible, edge_up)
        sweeps = None
        if cand is not None:
            out = _associate(cfg, spec, k_assoc, gains, dist,
                             bundle.counts, state.staleness, eligible,
                             cand, with_sweeps=spec.telemetry,
                             seed=state.warm)
            assigned = out
            if spec.telemetry:
                assigned, sweeps = out
            assoc = candidates.assigned_one_hot(
                assigned, cfg.n_edges).astype(f32)
        else:
            assigned = None
            assoc = _associate(cfg, spec, k_assoc, gains, dist_assoc,
                               bundle.counts, state.staleness, eligible,
                               with_sweeps=spec.telemetry, seed=state.warm)
            if spec.telemetry:
                assoc, sweeps = assoc
            assoc = assoc.astype(f32) * eligible[:, None]
    new_warm = _next_warm(spec, assoc, assigned)
    with _stage("allocate"):
        p, f = _allocate(cfg, spec, k_alloc, assoc, gains, bundle.counts,
                         actor_params, scen if dynamic else None, dist,
                         assigned)
        if dynamic:
            p = jnp.minimum(p, scen.p_max_w)
            f = jnp.minimum(f, scen.f_max_hz)

    # 2. per-client Eq. 13/15 surface at z=1 — the buffered engine never
    #    schedules edges (no barrier to prune); it reads the per-client
    #    time/energy columns for finish times and the cohort bill.
    with _stage("schedule"):
        rc_all = cost.round_cost(cfg, power_w=p, f_hz=f, gains=gains,
                                 assoc=assoc, z=jnp.ones((cfg.n_edges,)),
                                 n_samples=bundle.counts,
                                 noma_enabled=spec.noma_enabled,
                                 capacitance=scen.kappa if dynamic else None,
                                 sic_impl=spec.sic_impl,
                                 sic_max_per_edge=quota_for(cfg, spec),
                                 assigned=assigned)
    admitted = jnp.sum(assoc, axis=1) > 0                    # (N,) bool
    if fsp is not None:
        # mid-round crash: the cohort bill still charges the admitted
        # client (the energy was spent) but its update never takes flight.
        crashed = fault_inject.draw_crashes(fsp, k_crash, admitted)
        flying = admitted & ~crashed
    else:
        crashed = None
        flying = admitted

    # 3. train the cohort from the CURRENT global model and park its
    #    deltas in flight.  The admitted client's update is its trained
    #    edge model minus the global it pulled (anchored NOW, while the
    #    pull version is current) — it lands in the buffer later, at its
    #    virtual finish time, possibly several merges stale.
    with _stage("train"):
        client_params, _ = _train_cohort(cfg, spec, model, k_train, state,
                                         bundle, assoc)

    def _mask(m, leaf):
        return m.reshape((-1,) + (1,) * (leaf.ndim - 1))

    pending = jax.tree.map(
        lambda pd, c, g: jnp.where(_mask(flying, c), c - g[None], pd),
        buf.pending_delta, client_params, state.global_params)
    if fsp is not None:
        # poisoning corrupts the TRANSMITTED copy (the in-flight delta),
        # never the client's local params; a new attempt resets the
        # upload's retry ledger.
        pending, _ = fault_inject.poison_deltas(fsp, k_poison, pending,
                                                flying)
        attempts0 = jnp.where(flying, 0, state.faults.attempts)
    # modelled wall duration: τ₂ edge iterations + the edge→cloud hop
    dur = cfg.tau2 * rc_all.client_time_s \
        + cfg.edge_model_size_bits / cfg.edge_rate_bps
    finish = jnp.where(flying, buf.clock_s + dur, buf.finish_s)
    in_flight = buf.in_flight | flying
    pulled = jnp.where(flying, buf.version, buf.pulled_ver)
    obs = jnp.where(flying,
                    jnp.where(buf.obs_s > 0.0,
                              0.5 * buf.obs_s + 0.5 * dur, dur),
                    buf.obs_s)

    # 4. event-driven clock: jump to the earliest in-flight completion or
    #    the timeout deadline, whichever is sooner (never backwards).
    inf = jnp.asarray(jnp.finfo(jnp.float32).max, f32)
    next_fin = jnp.min(jnp.where(in_flight, finish, inf))
    deadline = buf.last_agg_s + jnp.asarray(spec.timeout_s, f32)
    target = jnp.where(jnp.any(in_flight),
                       jnp.minimum(next_fin, deadline), deadline)
    clock = jnp.maximum(buf.clock_s, target)
    dt = clock - buf.clock_s

    # 5. land every completed update with its staleness weight
    eps = jnp.asarray(1e-5, f32)
    landed = in_flight & (finish <= clock + eps)
    if fsp is not None:
        # 5b. uplink loss + retry/backoff (DESIGN.md §12.2): a completed
        #     upload is lost with its SINR-tied probability; a lost upload
        #     with attempts left re-enters flight at an exponentially
        #     backed-off finish time, otherwise it is dropped and counted.
        #     Delivered updates then pass the quarantine guard, and ONLY
        #     the guard-cleaned tree reaches the accumulator (the raw
        #     pending delta stays in the carry for any retry to re-send).
        landed_raw = landed
        lost = fault_inject.draw_losses(fsp, k_loss, gains, edge_up,
                                        landed_raw)
        can_retry = lost & (attempts0 < int(fsp.max_attempts))
        dropped = lost & ~can_retry
        delivered = landed_raw & ~lost
        finish = jnp.where(can_retry,
                           clock + fault_inject.backoff_s(fsp, attempts0),
                           finish)
        attempts = jnp.where(can_retry, attempts0 + 1, attempts0)
        clean, okd, n_rej = fault_guard.quarantine(
            pending, delivered, fsp.quarantine_clip)
        landed = okd
        land_tree = clean
    else:
        land_tree = pending
    age = staleness.buffer_age(buf.version, pulled)
    w = jnp.where(landed,
                  staleness.buffer_weight(age) * bundle.counts, 0.0)
    delta_sum, weight_sum = aggregation.buffer_accumulate(
        buf.delta_sum, buf.weight_sum, land_tree, w)
    fill = buf.fill + jnp.sum(landed, dtype=i32)
    if fsp is not None:
        in_flight = (in_flight & ~landed_raw) | can_retry
    else:
        in_flight = in_flight & ~landed

    # 6. fill-or-timeout trigger → staleness-weighted buffered merge.
    #    ``applied`` (merge actually changed the model) gates the version
    #    bump and the cloud-hop energy; ``fired`` alone resets the timer,
    #    so an empty timeout does not freeze the clock.  Under faults the
    #    merge additionally waits for ``min_participation`` buffered
    #    updates (a churn-starved buffer keeps accumulating across timeout
    #    resets); at the default 1 the guard is value-identical to the
    #    guard-less trigger (fill == 0 ⇒ the buffer is empty).
    fill_target = buffer_fill_for(cfg, spec)
    timed_out = clock >= deadline - eps
    fired = (fill >= fill_target) | timed_out
    if fsp is not None:
        do_merge = fired & (fill >= max(1, int(fsp.min_participation)))
    else:
        do_merge = fired
    applied = do_merge & (weight_sum > 0.0)
    global_params = aggregation.buffer_apply(
        state.global_params, delta_sum, weight_sum, spec.buffer_lr,
        do_merge)
    delta_sum = jax.tree.map(
        lambda d: jnp.where(do_merge, jnp.zeros_like(d), d), delta_sum)
    weight_sum = jnp.where(do_merge, 0.0, weight_sum)
    fill_after = jnp.where(do_merge, 0, fill)
    version = buf.version + applied.astype(i32)
    last_agg = jnp.where(fired, clock, buf.last_agg_s)

    # 7. TiFL retier cadence: quantile tiers over the duration EMA
    #    (rank · n_tiers // N ∈ [0, n_tiers)); unmeasured clients sort
    #    first, i.e. optimistically fast.
    step1 = buf.step + 1
    do_retier = jnp.mod(step1, max(1, int(spec.retier_every))) == 0
    rank = jnp.argsort(jnp.argsort(obs))
    tier = jnp.where(do_retier,
                     ((rank * n_tiers) // n).astype(i32), buf.tier)

    # 8. Eq. 20 per micro-step: landing in the buffer is this engine's
    #    "orchestrated" event — landed clients reset to 1, everyone else
    #    saturating-increments, so a drained client re-enters fresh.
    new_stale = staleness.update_staleness(state.staleness, landed)

    rc = cost.cohort_cost(cfg, rc_all, admitted, dt, applied)
    round_idx = state.round_idx + 1
    with _stage("eval"):
        accuracy = model.accuracy(global_params, bundle.test_x,
                                  bundle.test_y)
        loss = model.loss(global_params, (bundle.test_x, bundle.test_y))
    metrics = RoundMetrics(
        round=round_idx,
        accuracy=accuracy,
        loss=loss,
        avg_staleness=jnp.mean(new_stale.astype(f32)),
        total_time_s=dt,
        total_energy_j=rc.total_energy_j,
        cost=rc.cost,
        n_associated=jnp.sum(admitted.astype(i32)),
        n_available=jnp.sum((eligible > 0).astype(i32)),
        z=applied.astype(f32) * jnp.ones((cfg.n_edges,)))
    new_buf = BufferState(
        pending_delta=pending, finish_s=finish, in_flight=in_flight,
        pulled_ver=pulled, obs_s=obs, tier=tier, delta_sum=delta_sum,
        weight_sum=weight_sum, fill=fill_after, version=version,
        clock_s=clock, last_agg_s=last_agg, step=step1)
    new_faults = None
    fault_tr = None
    if fsp is not None:
        flt: FaultState = state.faults
        n_retry = jnp.sum(can_retry, dtype=i32)
        n_drop = jnp.sum(dropped, dtype=i32) + jnp.sum(crashed, dtype=i32)
        n_crash = jnp.sum(crashed, dtype=i32)
        new_faults = FaultState(
            edge_up=edge_up, attempts=attempts,
            n_retries=flt.n_retries + n_retry,
            n_dropped=flt.n_dropped + n_drop,
            n_quarantined=flt.n_quarantined + n_rej,
            n_crashed=flt.n_crashed + n_crash)
        fault_tr = (jnp.sum((edge_up <= 0).astype(i32)),
                    fault_inject.orphan_count(dist, edge_up,
                                              coverage_radius(cfg), avail),
                    n_retry, n_drop, n_rej)
    new_state = RoundState(global_params, client_params, gains, new_stale,
                           key, round_idx, scen, new_buf, new_faults,
                           new_warm)
    if spec.telemetry:
        cause = jnp.where(fired,
                          jnp.where(fill >= fill_target, 1, 2),
                          0).astype(i32)
        tr = telemetry.round_trace(
            cfg, spec, round_idx=round_idx, rc_all=rc_all,
            z=metrics.z, assoc=assoc, power_w=p, f_hz=f,
            counts=bundle.counts, staleness=new_stale,
            capacitance=scen.kappa if dynamic else None,
            sweeps=sweeps, sched=None, cand=cand, assigned=assigned,
            dist=dist, avail=avail if dynamic else None,
            coverage_radius_m=coverage_radius(cfg),
            buffer=(fill, cause, cur_tier,
                    jnp.sum((eligible > 0).astype(i32))),
            faults=fault_tr)
        return new_state, (metrics, tr)
    return new_state, metrics


def round_step(cfg, spec: EngineSpec, state: RoundState,
               bundle: RoundBundle, actor_params: Optional[Params] = None
               ) -> Tuple[RoundState, RoundMetrics]:
    """One pure global round; jit/scan/vmap to taste.

    Returns ``(state', RoundMetrics)`` — or, with ``spec.telemetry``,
    ``(state', (RoundMetrics, telemetry.RoundTrace))``; ``split_output``
    normalises the two shapes for generic callers.

    With ``spec.engine_mode="buffered"`` the step is a semi-async
    MICRO-step (``_buffered_step``); "sync" (the default) is the paper's
    semi-synchronous barrier round, bit-for-bit the pre-buffer program
    (``ensure_carry`` keeps the buffer and fault state structurally
    absent)."""
    state = ensure_carry(cfg, spec, state)
    if spec.engine_mode == "buffered":
        return _buffered_step(cfg, spec, state, bundle, actor_params)
    model = client_model(cfg)

    # 0. scenario transition (DESIGN.md §6).  The static kind keeps the
    #    PR-1 key-split and data flow bit-for-bit (no scenario key is
    #    consumed, distances come from the bundle) — the parity tests
    #    pin this against golden trajectories.
    dynamic = spec.scenario != "static"
    key, k_scen, k_fade, k_assoc, k_alloc, k_train = round_keys(spec,
                                                                state.key)
    if dynamic:
        scen = scenarios.advance(cfg, spec.scenario, k_scen, state.scenario)
        dist, avail = scen.dist, scen.avail
    else:
        scen = state.scenario
        dist, avail = bundle.dist, None

    # 1. channel fading (distances may have just moved)
    gains = noma.evolve_gains(k_fade, state.gains, dist,
                              path_loss_exponent=cfg.path_loss_exponent,
                              rho=spec.fading_rho)
    # 1b. fault layer (DESIGN.md §12): fold the fault stream off the fade
    #     key (no split consumed from the round layout), advance the edge
    #     churn, and push dead edges out of the ASSOCIATION view of the
    #     distance field — the unchanged pipeline routes their orphaned
    #     clients to the surviving frontier.  Gains, allocation and the
    #     Eq. 23a bill keep the PHYSICAL distances.
    fsp = spec.faults
    if fsp is not None:
        k_edge, k_loss, k_crash, k_poison = jax.random.split(
            fault_inject.fault_key(k_fade), 4)
        edge_up = fault_inject.advance_edges(fsp, k_edge,
                                             state.faults.edge_up)
        dist_assoc = fault_inject.masked_dist(dist, edge_up)
    else:
        edge_up = None
        dist_assoc = dist
    # 2. fuzzy scoring + association (pure JAX — no host loop);
    #    unavailable clients are out of coverage this round.  With
    #    ``spec.candidates_k`` set, the (N, K) frontier is built once here
    #    and scoring/resolution/billing all run on it (DESIGN.md §9);
    #    the (N, M) one-hot is reconstructed only for the training/
    #    aggregation stage's cheap masked reductions.
    sweeps = None
    with _stage("associate"):
        cand = _build_candidates(cfg, spec, dist, avail, edge_up)
        if cand is not None:
            out = _associate(cfg, spec, k_assoc, gains, dist,
                             bundle.counts, state.staleness, avail, cand,
                             with_sweeps=spec.telemetry, seed=state.warm)
            assigned = out
            if spec.telemetry:
                assigned, sweeps = out
            assoc = candidates.assigned_one_hot(
                assigned, cfg.n_edges).astype(jnp.float32)
            # ``cand.valid`` already excludes dropped clients — no avail mask
        else:
            assigned = None
            assoc = _associate(cfg, spec, k_assoc, gains, dist_assoc,
                               bundle.counts, state.staleness, avail,
                               with_sweeps=spec.telemetry, seed=state.warm)
            if spec.telemetry:
                assoc, sweeps = assoc
            assoc = assoc.astype(jnp.float32)
            if dynamic:
                # explicit Eq. 11/17/23a mask: even a policy that ignored
                # ``avail`` cannot train on, aggregate or bill a dropped
                # client
                assoc = assoc * avail[:, None]
    new_warm = _next_warm(spec, assoc, assigned)
    # 3. resource allocation, clamped to the device class caps
    with _stage("allocate"):
        p, f = _allocate(cfg, spec, k_alloc, assoc, gains, bundle.counts,
                         actor_params, scen if dynamic else None, dist,
                         assigned)
        if dynamic:
            p = jnp.minimum(p, scen.p_max_w)
            f = jnp.minimum(f, scen.f_max_hz)
    # 4. ONE cost evaluation at z=1, reused by the scheduler and the final
    #    masked round cost (Eqs. 18-19 depend on z only through a mask)
    with _stage("schedule"):
        rc_all = cost.round_cost(cfg, power_w=p, f_hz=f, gains=gains,
                                 assoc=assoc, z=jnp.ones((cfg.n_edges,)),
                                 n_samples=bundle.counts,
                                 noma_enabled=spec.noma_enabled,
                                 capacitance=scen.kappa if dynamic else None,
                                 sic_impl=spec.sic_impl,
                                 sic_max_per_edge=quota_for(cfg, spec),
                                 assigned=assigned)
        if spec.telemetry:
            z, sched = _schedule_traced(cfg, spec, rc_all)
        else:
            z = _schedule(cfg, spec, rc_all)
        if fsp is not None:
            # a dead edge cannot be scheduled: association already routed
            # around it, this removes it from the Eq. 18/19 bill too
            z = z * (edge_up > 0).astype(z.dtype)
        rc = cost.apply_schedule(cfg, rc_all, z)
    # 5. τ₂·τ₁ training + hierarchical aggregation
    with _stage("train"):
        if fsp is not None:
            global_params, client_params, fev = _train_faulty(
                cfg, spec, model, k_train, state, bundle, assoc, z, gains,
                edge_up, k_crash, k_loss, k_poison)
            ok_clients, crashed, lost, n_rej = fev
        else:
            global_params, client_params = _train(cfg, spec, model,
                                                  k_train, state, bundle,
                                                  assoc, z)
    # 6. staleness (Eq. 20): reset only for clients whose edge was selected
    #    (and, under faults, whose update actually survived to aggregation)
    selected = jnp.sum(assoc, axis=1) > 0
    orchestrated = ok_clients if fsp is not None else selected
    effective = orchestrated & (z > 0)[jnp.argmax(assoc, axis=1)]
    new_stale = staleness.update_staleness(state.staleness, effective)

    round_idx = state.round_idx + 1
    n_avail = (jnp.sum(avail > 0, dtype=jnp.int32) if dynamic
               else jnp.asarray(cfg.n_clients, jnp.int32))
    with _stage("eval"):
        accuracy = model.accuracy(global_params, bundle.test_x,
                                  bundle.test_y)
        loss = model.loss(global_params, (bundle.test_x, bundle.test_y))
    metrics = RoundMetrics(
        round=round_idx,
        accuracy=accuracy,
        loss=loss,
        avg_staleness=jnp.mean(new_stale.astype(jnp.float32)),
        total_time_s=rc.total_time_s,
        total_energy_j=rc.total_energy_j,
        cost=rc.cost,
        n_associated=jnp.sum(selected.astype(jnp.int32)),
        n_available=n_avail,
        z=z)
    new_faults = None
    fault_tr = None
    if fsp is not None:
        flt: FaultState = state.faults
        i32 = jnp.int32
        n_drop = (jnp.sum(lost, dtype=i32)
                  + jnp.sum(crashed, dtype=i32))
        new_faults = FaultState(
            edge_up=edge_up, attempts=flt.attempts,
            n_retries=flt.n_retries,      # sync has no buffer to retry from
            n_dropped=flt.n_dropped + n_drop,
            n_quarantined=flt.n_quarantined + n_rej,
            n_crashed=flt.n_crashed + jnp.sum(crashed, dtype=i32))
        fault_tr = (jnp.sum((edge_up <= 0).astype(i32)),
                    fault_inject.orphan_count(dist, edge_up,
                                              coverage_radius(cfg), avail),
                    jnp.zeros((), i32), n_drop, n_rej)
    new_state = RoundState(global_params, client_params, gains, new_stale,
                           key, round_idx, scen, None, new_faults, new_warm)
    if spec.telemetry:
        tr = telemetry.round_trace(
            cfg, spec, round_idx=round_idx, rc_all=rc_all, z=z,
            assoc=assoc, power_w=p, f_hz=f, counts=bundle.counts,
            staleness=new_stale,
            capacitance=scen.kappa if dynamic else None,
            sweeps=sweeps, sched=sched, cand=cand, assigned=assigned,
            dist=dist, avail=avail,
            coverage_radius_m=coverage_radius(cfg), faults=fault_tr)
        return new_state, (metrics, tr)
    return new_state, metrics


round_step_jit = jax.jit(round_step, static_argnums=(0, 1))


def _scan_rounds(cfg, spec, state, bundle, n_rounds, actor_params):
    # normalise the carry BEFORE the scan so its pytree structure is
    # fixed: buffered runs enter with the aggregation buffer attached,
    # faulted runs with the fault state attached, everything else with
    # both structurally absent (a no-op on a plain sync state — golden
    # programs are untouched).
    state = ensure_carry(cfg, spec, state)

    def step(s, _):
        return round_step(cfg, spec, s, bundle, actor_params)

    with _part("round_loop"):
        return jax.lax.scan(step, state, None, length=n_rounds)


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def run_scanned(cfg, spec: EngineSpec, state: RoundState,
                bundle: RoundBundle, n_rounds: int,
                actor_params: Optional[Params] = None
                ) -> Tuple[RoundState, RoundMetrics]:
    """A whole experiment as ONE XLA program: ``lax.scan`` over rounds.
    Returned metrics leaves have a leading (n_rounds,) axis (with
    ``spec.telemetry`` the per-round output is the (metrics, trace) pair
    — see ``split_output``)."""
    return _scan_rounds(cfg, spec, state, bundle, n_rounds, actor_params)


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def run_fleet(cfg, spec: EngineSpec, states: RoundState,
              bundles: RoundBundle, n_rounds: int,
              actor_params: Optional[Params] = None
              ) -> Tuple[RoundState, RoundMetrics]:
    """``vmap`` of the scanned driver over a fleet of independent
    simulations (stacked states/bundles from ``stack_fleet``).  Metrics
    leaves gain a leading (n_seeds, n_rounds, ...) shape."""
    return jax.vmap(
        lambda s, b: _scan_rounds(cfg, spec, s, b, n_rounds, actor_params)
    )(states, bundles)


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def run_fleet_actors(cfg, spec: EngineSpec, states: RoundState,
                     bundles: RoundBundle, n_rounds: int,
                     actor_params: Params
                     ) -> Tuple[RoundState, RoundMetrics]:
    """``run_fleet`` with a PER-SIMULATION actor: ``actor_params`` leaves
    carry a leading fleet axis (one trained actor per stacked cell), so a
    sweep can bill every ddpg cell with the actor trained on ITS OWN
    world while still running the whole group as one vmapped program."""
    return jax.vmap(
        lambda s, b, a: _scan_rounds(cfg, spec, s, b, n_rounds, a)
    )(states, bundles, actor_params)


# ---------------------------------------------------------------------------
# Fleet-axis sharding (DESIGN.md §8.3): the stacked simulations of a fleet
# are embarrassingly parallel, so a 1-D device mesh over the LEADING fleet
# axis scales `run_fleet` across devices with zero cross-device collectives
# (GSPMD partitions the vmap; every lane's program is untouched).
# ---------------------------------------------------------------------------

def fleet_mesh(devices=None) -> "jax.sharding.Mesh":
    """1-D ``("fleet",)`` mesh over ``devices`` (default: all of them).
    On CPU, spawn placeholder devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=K`` *before* jax
    imports (see tests/test_fleet_sharding.py)."""
    devices = jax.devices() if devices is None else list(devices)
    return jax.sharding.Mesh(np.asarray(devices), ("fleet",))


def shard_fleet(tree, mesh: "jax.sharding.Mesh"):
    """Place a stacked pytree with its leading axis split over the mesh."""
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("fleet"))
    return jax.device_put(tree, sharding)


def run_fleet_sharded(cfg, spec: EngineSpec, states: RoundState,
                      bundles: RoundBundle, n_rounds: int,
                      actor_params: Optional[Params] = None, *,
                      mesh: "jax.sharding.Mesh | None" = None,
                      per_sim_actors: bool = False
                      ) -> Tuple[RoundState, RoundMetrics]:
    """``run_fleet`` (or ``run_fleet_actors`` when ``per_sim_actors``)
    with the fleet axis sharded over ``mesh`` (default: all devices).

    A fleet whose size is not a multiple of the device count is padded by
    replicating the last simulation (the pad lanes compute and are then
    sliced off — wasted work only on the ragged remainder).  Per-lane
    results are identical to the unsharded drivers: partitioning an
    embarrassingly-parallel vmap axis changes placement, not math
    (asserted by the multi-device parity test)."""
    mesh = fleet_mesh() if mesh is None else mesh
    n_dev = int(mesh.devices.size)
    fleet = jax.tree.leaves(states)[0].shape[0]
    pad = (-fleet) % n_dev

    def _pad(leaf):
        reps = jnp.repeat(leaf[-1:], pad, axis=0)
        return jnp.concatenate([leaf, reps], axis=0)

    if pad:
        states = jax.tree.map(_pad, states)
        bundles = jax.tree.map(_pad, bundles)
        if per_sim_actors:
            actor_params = jax.tree.map(_pad, actor_params)
    states, bundles = shard_fleet((states, bundles), mesh)
    if per_sim_actors:
        actor_params = shard_fleet(actor_params, mesh)
        out, ms = run_fleet_actors(cfg, spec, states, bundles, n_rounds,
                                   actor_params)
    else:
        out, ms = run_fleet(cfg, spec, states, bundles, n_rounds,
                            actor_params)
    if pad:
        out = jax.tree.map(lambda l: l[:fleet], out)
        ms = jax.tree.map(lambda l: l[:fleet], ms)
    return out, ms


# ---------------------------------------------------------------------------
# Client-axis sharding (DESIGN.md §9.3): split N over a 1-D ("clients",)
# mesh for N ≫ 10⁴ single-simulation scale.  Unlike the fleet axis, the
# client axis is NOT embarrassingly parallel — association, aggregation and
# the Eq. 23 bill all reduce over clients — but on the candidate layout
# every per-client stage (candidate build, fuzzy frontier scoring, local
# SGD, the resolver's elementwise sweep work) is row-local over N, and the
# cross-client terms are exactly the per-edge/global reductions GSPMD
# lowers to collectives of (M,)- or scalar-sized partials.  We device_put
# the N-leading leaves P("clients") and let GSPMD partition the jitted
# round program; nothing in round_step needs to change.
# ---------------------------------------------------------------------------

def client_mesh(devices=None) -> "jax.sharding.Mesh":
    """1-D ``("clients",)`` mesh over ``devices`` (default: all of them).
    On CPU, spawn placeholder devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=K`` *before* jax
    imports (see tests/test_client_sharding.py)."""
    devices = jax.devices() if devices is None else list(devices)
    return jax.sharding.Mesh(np.asarray(devices), ("clients",))


def _client_shardings(state: RoundState, bundle: RoundBundle,
                      mesh: "jax.sharding.Mesh"):
    """Per-leaf placement: N-leading leaves split over ``("clients",)``,
    everything else (global model, PRNG key, edge positions, test set)
    replicated."""
    P = jax.sharding.PartitionSpec
    cl = jax.sharding.NamedSharding(mesh, P("clients"))
    rep = jax.sharding.NamedSharding(mesh, P())
    scen_sh = ScenarioState(
        pos=cl, waypoint=cl, speed=cl, avail=cl, p_drop=cl, p_return=cl,
        f_max_hz=cl, p_max_w=cl, kappa=cl, edges=rep, dist=cl)
    buf_sh = None
    if state.buffer is not None:
        buf: BufferState = state.buffer
        # per-client leaves split over ("clients",); the global-shaped
        # delta accumulator and the scalar trigger state replicated —
        # exactly the global-model layout, so the buffered merge lowers
        # to the same all-reduce shape as the sync cloud aggregation.
        buf_sh = BufferState(
            pending_delta=jax.tree.map(lambda _: cl, buf.pending_delta),
            finish_s=cl, in_flight=cl, pulled_ver=cl, obs_s=cl, tier=cl,
            delta_sum=jax.tree.map(lambda _: rep, buf.delta_sum),
            weight_sum=rep, fill=rep, version=rep, clock_s=rep,
            last_agg_s=rep, step=rep)
    flt_sh = None
    if state.faults is not None:
        # the retry ledger is per-client; the (M,) edge mask and the
        # scalar counters are replicated like the rest of the edge state
        flt_sh = FaultState(edge_up=rep, attempts=cl, n_retries=rep,
                            n_dropped=rep, n_quarantined=rep,
                            n_crashed=rep)
    state_sh = RoundState(
        global_params=jax.tree.map(lambda _: rep, state.global_params),
        client_params=jax.tree.map(lambda _: cl, state.client_params),
        gains=cl, staleness=cl, key=rep, round_idx=rep, scenario=scen_sh,
        buffer=buf_sh, faults=flt_sh,
        warm=cl if state.warm is not None else None)
    bundle_sh = RoundBundle(dist=cl, x=cl, y=cl, counts=cl,
                            test_x=rep, test_y=rep)
    return state_sh, bundle_sh


def shard_clients(state: RoundState, bundle: RoundBundle,
                  mesh: "jax.sharding.Mesh | None" = None
                  ) -> Tuple[RoundState, RoundBundle]:
    """Place one simulation with its client axis split over ``mesh``, the
    feature axis of ``bundle.x`` zero-padded to whole lanes
    (``lane_padded``).  Requires ``cfg.n_clients`` divisible by the device
    count — pad a ragged N with ``pad_clients`` first."""
    mesh = client_mesh() if mesh is None else mesh
    state_sh, bundle_sh = _client_shardings(state, bundle, mesh)
    bundle = jax.device_put(bundle, bundle_sh)
    return (jax.device_put(state, state_sh),
            bundle._replace(x=lane_padded(bundle.x)))


# A TPU tiles an array's minor dimension in lanes of 128.
LANES = 128


def lane_padded(x):
    """``x`` with its last (feature) axis zero-padded to a multiple of
    ``LANES``, placed as ``x`` is.  A TPU lays out an f32[N, cap, D] it is
    handed so as to pad least (DESIGN.md §9.3): where D is not a multiple
    of 128, the client or the sample axis goes minor, and the minibatch
    gather, which reads whole sample rows, then re-lays all of ``x`` at the
    entry of every call.  With D a multiple of 128 the rows lie contiguous and
    the gather reads them in place; ``_minibatches`` drops the padding."""
    pad = (-x.shape[-1]) % LANES
    return x if pad == 0 else _pad_features(x, pad)


@functools.partial(jax.jit, static_argnums=1)
def _pad_features(x, pad: int):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def pad_clients(cfg, state: RoundState, bundle: RoundBundle, multiple: int):
    """Pad N up to a multiple of ``multiple`` with INERT clients: parked
    far outside every coverage disk (static distances and, under
    mobility, positions — speed 0 keeps them parked), unavailable with a
    sticky dropout chain, zero data counts.  They can never associate, so
    they never train into an aggregate, never earn a rate and never bill
    a joule (invariants pinned in tests/test_client_sharding.py).

    Returns ``(cfg', state', bundle')`` with ``cfg.n_clients`` grown —
    note a padded world is a DIFFERENT experiment from the unpadded one
    (the per-round PRNG fans out over N, and per-round aggregates like
    ``avg_staleness`` average over the padded axis); the parity guarantee
    is sharded == unsharded on the SAME padded world.  A ddpg actor's
    observation dim is 2N/3N — train it on the padded shape."""
    n = cfg.n_clients
    pad = (-n) % int(multiple)
    if pad == 0:
        return cfg, state, bundle
    far = cfg.area_side_m * 1e3

    def rep_last(leaf):
        return jnp.concatenate([leaf, jnp.repeat(leaf[-1:], pad, axis=0)],
                               axis=0)

    def const(leaf, value):
        tail = jnp.full((pad,) + leaf.shape[1:], value, leaf.dtype)
        return jnp.concatenate([leaf, tail], axis=0)

    scen = state.scenario
    scen = scen._replace(
        pos=const(scen.pos, far), waypoint=const(scen.waypoint, far),
        speed=const(scen.speed, 0.0), avail=const(scen.avail, 0.0),
        p_drop=const(scen.p_drop, 1.0), p_return=const(scen.p_return, 0.0),
        f_max_hz=rep_last(scen.f_max_hz), p_max_w=rep_last(scen.p_max_w),
        kappa=rep_last(scen.kappa), dist=const(scen.dist, far))
    state = state._replace(
        client_params=jax.tree.map(rep_last, state.client_params),
        gains=rep_last(state.gains),
        staleness=const(state.staleness, 0),
        scenario=scen)
    if state.buffer is not None:
        buf = state.buffer
        # padded clients are idle forever: zero pending delta, tier 0 —
        # being unavailable they never associate, so they never land.
        state = state._replace(buffer=buf._replace(
            pending_delta=jax.tree.map(lambda l: const(l, 0.0),
                                       buf.pending_delta),
            finish_s=const(buf.finish_s, 0.0),
            in_flight=const(buf.in_flight, False),
            pulled_ver=const(buf.pulled_ver, 0),
            obs_s=const(buf.obs_s, 0.0),
            tier=const(buf.tier, 0)))
    if state.faults is not None:
        # inert clients never admit, so their retry ledger stays zero
        state = state._replace(faults=state.faults._replace(
            attempts=const(state.faults.attempts, 0)))
    if state.warm is not None:
        # inert clients are never assigned, so their seed stays -1
        state = state._replace(warm=const(state.warm, -1))
    bundle = bundle._replace(
        dist=const(bundle.dist, far), x=rep_last(bundle.x),
        y=rep_last(bundle.y), counts=const(bundle.counts, 0.0))
    return dataclasses.replace(cfg, n_clients=n + pad), state, bundle


def run_scanned_client_sharded(cfg, spec: EngineSpec, state: RoundState,
                               bundle: RoundBundle, n_rounds: int,
                               actor_params: Optional[Params] = None, *,
                               mesh: "jax.sharding.Mesh | None" = None
                               ) -> Tuple[RoundState, RoundMetrics]:
    """``run_scanned`` with the client axis sharded over ``mesh`` (default:
    all devices), padding a ragged N with inert clients first.  Returns
    the padded-world results — slice client-axis leaves to
    ``cfg.n_clients`` yourself if you need the original N view."""
    mesh = client_mesh() if mesh is None else mesh
    cfg, state, bundle = pad_clients(cfg, state, bundle,
                                     int(mesh.devices.size))
    state, bundle = shard_clients(state, bundle, mesh)
    return run_scanned(cfg, spec, state, bundle, n_rounds, actor_params)


def split_output(spec: EngineSpec, out):
    """Normalise a driver's per-round output to ``(metrics, trace)``.

    Telemetry off: ``out`` IS the ``RoundMetrics`` pytree → ``(out, None)``.
    Telemetry on: ``out`` is the ``(RoundMetrics, RoundTrace)`` pair the
    engine emitted → returned as-is.  The split is static (it follows the
    spec flag), so generic callers — the sweep runner, benches, tests —
    handle both engine shapes with one line."""
    if spec.telemetry:
        return out
    return out, None


def metrics_row(metrics: RoundMetrics, i: Optional[int] = None):
    """Host-side view: pull round ``i`` (or a scalar metrics) to floats."""
    pick = (lambda l: l[i]) if i is not None else (lambda l: l)
    return {
        "round": int(pick(metrics.round)),
        "accuracy": float(pick(metrics.accuracy)),
        "loss": float(pick(metrics.loss)),
        "avg_staleness": float(pick(metrics.avg_staleness)),
        "total_time_s": float(pick(metrics.total_time_s)),
        "total_energy_j": float(pick(metrics.total_energy_j)),
        "cost": float(pick(metrics.cost)),
        "n_associated": int(pick(metrics.n_associated)),
        "n_available": int(pick(metrics.n_available)),
        "z": np.asarray(pick(metrics.z)),
    }
