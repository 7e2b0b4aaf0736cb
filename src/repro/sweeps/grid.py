"""Declarative scenario × policy × allocator sweep runner (DESIGN.md §6.3).

A ``SweepGrid`` names the axes of an experiment grid — scenarios (preset
names or ``ScenarioSpec``s), association policies, allocators, schedulers,
NOMA on/off, seeds — and ``run_sweep`` executes the full cross product with
the MINIMUM number of XLA compiles:

* axes that are trace-time code paths (policy / allocator / scheduler /
  NOMA / scenario *kind*) partition the grid into static-spec groups;
* everything else (scenario parameterisation, seeds) is DATA: every cell
  of a group is stacked along the fleet axis (``stack_fleet``) and the
  whole group runs as one vmapped ``run_fleet`` call — one compile, no
  matter how many scenarios × seeds ride in it.

Because every built-in dynamic scenario normalises to the single "dynamic"
transition kind (scenarios are arrays, not code — DESIGN.md §6.1), a sweep
over N scenarios × S seeds under one policy is exactly ONE compile (plus
one for a static-scenario row if present).

Per-cell metric trajectories are persisted as JSON under
``results/sweep_<name>/`` — the machinery for the paper's Figs. 8-12
protocol under moving, flaky, heterogeneous clients.

    PYTHONPATH=src python -m repro.sweeps.grid --quick   # demo sweep
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import scenarios
from repro.core import engine
from repro.faults import FaultSpec


@dataclasses.dataclass(frozen=True)
class SweepCell:
    """One point of the grid (hashable; carries the RESOLVED scenario spec
    so custom parameterisations survive the trip through the runner)."""
    scenario: str                  # display label (preset name / kind)
    sspec: scenarios.ScenarioSpec
    policy: str
    allocator: str
    scheduler: str
    noma_enabled: bool
    seed: int
    engine_mode: str = "sync"      # sync | buffered (DESIGN.md §11)

    @property
    def cell_id(self) -> str:
        noma = "noma" if self.noma_enabled else "oma"
        # the sync id keeps the historical shape so existing result files
        # and tooling line up; buffered cells get an explicit suffix
        mode = "" if self.engine_mode == "sync" else f"__{self.engine_mode}"
        return (f"{self.scenario}__{self.policy}__{self.allocator}"
                f"__{self.scheduler}__{noma}__s{self.seed}{mode}")


@dataclasses.dataclass
class SweepGrid:
    """The declarative grid: every field is an axis of the cross product.

    ``scenarios`` entries may be preset names / kind strings, ScenarioSpec
    instances, or ``(label, ScenarioSpec)`` pairs — use a pair to give a
    custom parameterisation a distinct cell label.
    """
    name: str
    scenarios: Sequence[Any] = ("static",)
    policies: Sequence[str] = ("fcea",)
    allocators: Sequence[str] = ("mid",)
    schedulers: Sequence[str] = ("pdd",)
    noma: Sequence[bool] = (True,)
    seeds: Sequence[int] = (0,)
    n_rounds: int = 10
    iid: bool = True
    # (N, K) candidate frontier for every cell (DESIGN.md §9): None =
    # dense; K ≥ the max in-coverage degree is bit-identical to dense (at
    # sizes where the dense path runs its sorted SIC), so flipping this on
    # a sweep changes speed, not results
    candidates_k: "int | None" = None
    # dense-path SIC formulation (EngineSpec.sic_impl); the candidate
    # path's compact SIC is the sorted/top-k formulation regardless
    sic_impl: str = "auto"
    # in-scan telemetry (DESIGN.md §10): every cell also persists its
    # per-round RoundTrace as ``<cell_id>.trace.json`` beside the metrics
    telemetry: bool = False
    # engine-mode axis (DESIGN.md §11): "sync" is the paper's barrier
    # round; "buffered" runs the same n_rounds as semi-async MICRO-steps.
    # The buffer_* fields parameterise every buffered cell's trigger.
    engine_modes: Sequence[str] = ("sync",)
    buffer_fill: int = 0           # 0 = auto ((quota · M) // 2)
    timeout_s: float = 10.0
    n_tiers: int = 4
    retier_every: int = 8
    # fault injection (DESIGN.md §12): a FaultSpec turns every cell into
    # a chaos cell (edge churn, uplink loss, quarantine...); None keeps
    # the fault layer structurally absent
    faults: "FaultSpec | None" = None
    # per-group DDPG training budget (used when the grid has
    # allocator="ddpg" cells and no pre-trained actor is supplied)
    ddpg_episodes: int = 12
    ddpg_steps: int = 40
    ddpg_warmup: int = 64
    ddpg_hidden: int = 64


def _resolve_scenario(entry: Any) -> Tuple[str, scenarios.ScenarioSpec]:
    """(label, spec) for a grid scenario entry, preserving its parameters."""
    if isinstance(entry, tuple):
        label, spec = entry
        return str(label), scenarios.preset(spec)
    if isinstance(entry, scenarios.ScenarioSpec):
        return entry.kind, entry
    return str(entry), scenarios.preset(entry)


def expand_grid(grid: SweepGrid) -> List[SweepCell]:
    cells = [SweepCell(label, sspec, po, al, sch, nm, sd, em)
             for label, sspec in map(_resolve_scenario, grid.scenarios)
             for po in grid.policies for al in grid.allocators
             for sch in grid.schedulers for nm in grid.noma
             for sd in grid.seeds for em in grid.engine_modes]
    ids = [c.cell_id for c in cells]
    if len(set(ids)) != len(ids):
        dupes = sorted({i for i in ids if ids.count(i) > 1})
        raise ValueError(
            f"ambiguous sweep cells {dupes}: two scenario entries share a "
            f"label — use (label, ScenarioSpec) pairs to disambiguate")
    return cells


def _spec_for(cell: SweepCell, grid: SweepGrid) -> engine.EngineSpec:
    return engine.EngineSpec(policy=cell.policy, allocator=cell.allocator,
                             scheduler=cell.scheduler,
                             noma_enabled=cell.noma_enabled,
                             scenario=cell.sspec.engine_kind(),
                             candidates_k=grid.candidates_k,
                             sic_impl=grid.sic_impl,
                             telemetry=grid.telemetry,
                             engine_mode=cell.engine_mode,
                             buffer_fill=grid.buffer_fill,
                             timeout_s=grid.timeout_s,
                             n_tiers=grid.n_tiers,
                             retier_every=grid.retier_every,
                             faults=grid.faults)


def _group_cells(cells: Sequence[SweepCell], grid: SweepGrid
                 ) -> Dict[engine.EngineSpec, List[SweepCell]]:
    groups: Dict[engine.EngineSpec, List[SweepCell]] = {}
    for cell in cells:
        groups.setdefault(_spec_for(cell, grid), []).append(cell)
    return groups


def run_sweep(cfg, grid: SweepGrid, *, out_dir: str = "results",
              write_json: bool = True, actor_params=None,
              mesh=None) -> Dict[str, Any]:
    """Execute the grid; returns (and persists) a summary + per-cell rows.

    One ``run_fleet`` call — hence one compile — per static-spec group;
    inside a group all scenarios × seeds run vmapped in a single program.
    Pass ``mesh`` (e.g. ``engine.fleet_mesh()``) to shard every group's
    fleet axis across devices (DESIGN.md §8.3) — per-cell results are
    identical to the unsharded run, only placement changes.

    ``allocator="ddpg"`` cells need a trained actor.  By default every
    ddpg CELL trains its own actor on its own world (scenario × seed) via
    the scanned ``ddpg.train_allocator`` (budgeted by the grid's
    ``ddpg_*`` fields; one training compile serves the whole group), and
    the stacked actors ride the fleet vmap (``run_fleet_actors``) — a
    dynamic group trains on the (3N,) scenario-sliced observation, a
    static group on (2N,), so mixed grids just work and no cell is ever
    billed with an actor trained on a different scenario.  Pass
    ``actor_params`` (a pre-trained actor pytree) to use one shared actor
    for every ddpg cell instead; then the grid must not mix observation
    shapes.
    """
    cells = expand_grid(grid)
    ddpg_cells = [c for c in cells if c.allocator == "ddpg"]
    if ddpg_cells and actor_params is not None:
        if len({c.sspec.engine_kind() == "static" for c in ddpg_cells}) > 1:
            raise ValueError(
                "ddpg cells mix static (2N,) and dynamic (3N,) observation "
                "shapes — one actor cannot serve both; split the grid or "
                "drop actor_params to train per group")
    groups = _group_cells(cells, grid)
    sweep_dir = os.path.join(out_dir, f"sweep_{grid.name}")
    if write_json:
        os.makedirs(sweep_dir, exist_ok=True)

    per_cell: Dict[str, Dict[str, list]] = {}
    timings: List[Dict[str, Any]] = []
    # cells differing only in policy/allocator/scheduler/NOMA share the
    # exact same (seed, scenario) world — init it once, not once per cell
    init_cache: Dict[Tuple[int, scenarios.ScenarioSpec], tuple] = {}

    def _init(c: SweepCell):
        k = (c.seed, c.sspec)
        if k not in init_cache:
            init_cache[k] = engine.init_simulation(cfg, seed=c.seed,
                                                   iid=grid.iid,
                                                   scenario=c.sspec)[:2]
        return init_cache[k]

    failed: Dict[str, str] = {}

    def _run_group(spec: engine.EngineSpec, members: List[SweepCell]) -> None:
        pairs = [_init(c) for c in members]
        states, bundles = engine.stack_fleet(pairs)
        cell_actors, train_s = None, 0.0
        if spec.allocator == "ddpg" and actor_params is None:
            # train ONE actor PER CELL on that cell's own world, all the
            # cells of the group vmapped into a single XLA program
            # (train_allocator_fleet), then ride the stacked actors
            # through the fleet vmap: every ddpg row in the persisted
            # JSON ran an actor trained on exactly the scenario × seed it
            # reports
            from repro.core import ddpg
            t0 = time.perf_counter()
            # fold a tag into each seed root so the training stream is
            # decorrelated from init_simulation(seed)'s world-init stream
            # (same root key, children 0/1 already spent on model/gains)
            keys = jnp.stack([jax.random.fold_in(jax.random.key(c.seed),
                                                 7919) for c in members])
            agents, _ = ddpg.train_allocator_fleet(
                cfg, spec, states, bundles, None, keys,
                episodes=grid.ddpg_episodes,
                steps_per_episode=grid.ddpg_steps,
                warmup=grid.ddpg_warmup, hidden=grid.ddpg_hidden)
            cell_actors = jax.block_until_ready(agents.actor)
            train_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        if mesh is not None:
            _, out = engine.run_fleet_sharded(
                cfg, spec, states, bundles, grid.n_rounds,
                cell_actors if cell_actors is not None else actor_params,
                mesh=mesh, per_sim_actors=cell_actors is not None)
        elif cell_actors is not None:
            _, out = engine.run_fleet_actors(cfg, spec, states, bundles,
                                             grid.n_rounds, cell_actors)
        else:
            _, out = engine.run_fleet(cfg, spec, states, bundles,
                                      grid.n_rounds, actor_params)
        ms, traces = engine.split_output(spec, out)
        jax.block_until_ready(ms.cost)
        dt = time.perf_counter() - t0
        timing = {"spec": dataclasses.asdict(spec),
                  "n_cells": len(members), "wall_s": round(dt, 4)}
        if spec.allocator == "ddpg":
            timing["ddpg_trained"] = actor_params is None
            timing["ddpg_train_s"] = round(train_s, 4)
            timing["ddpg_actors"] = (len(members) if actor_params is None
                                     else "shared")
        timings.append(timing)
        # one device->host transfer per metrics leaf for the WHOLE group
        host = {k: np.asarray(v) for k, v in ms._asdict().items()}
        tr_host = (None if traces is None else
                   {k: np.asarray(v) for k, v in traces._asdict().items()})
        for i, cell in enumerate(members):
            rows = {k: v[i].tolist() for k, v in host.items()}
            per_cell[cell.cell_id] = rows
            if write_json:
                payload = {"cell": dataclasses.asdict(cell),
                           "spec": dataclasses.asdict(spec),
                           "n_rounds": grid.n_rounds,
                           "metrics": rows}
                with open(os.path.join(sweep_dir,
                                       f"{cell.cell_id}.json"), "w") as fh:
                    json.dump(payload, fh, indent=1)
                if tr_host is not None:
                    # the per-stage Eq. 23a decomposition + association/
                    # scheduler internals, beside the metrics JSON
                    tp = {"cell": dataclasses.asdict(cell),
                          "n_rounds": grid.n_rounds,
                          "trace": {k: v[i].tolist()
                                    for k, v in tr_host.items()}}
                    with open(os.path.join(
                            sweep_dir,
                            f"{cell.cell_id}.trace.json"), "w") as fh:
                        json.dump(tp, fh, indent=1)

    for spec, members in groups.items():
        # one crashed group (a divergent chaos cell, an OOM'd compile)
        # must not take down the rest of the sweep: record the failure
        # against every member cell and keep going
        try:
            _run_group(spec, members)
        except Exception as exc:  # noqa: BLE001
            for cell in members:
                failed[cell.cell_id] = repr(exc)
            timings.append({"spec": dataclasses.asdict(spec),
                            "n_cells": len(members),
                            "error": repr(exc)})

    summary = {
        "name": grid.name,
        "n_cells": len(cells),
        "n_compiles": len(groups),     # one vmapped run_fleet per group
        "n_rounds": grid.n_rounds,
        "axes": {"scenarios": [_resolve_scenario(s)[0]
                               for s in grid.scenarios],
                 "policies": list(grid.policies),
                 "allocators": list(grid.allocators),
                 "schedulers": list(grid.schedulers),
                 "noma": list(grid.noma),
                 "seeds": list(grid.seeds),
                 "engine_modes": list(grid.engine_modes)},
        "groups": timings,
        "final": summarize(per_cell),
        "failed_cells": failed,
    }
    if write_json:
        with open(os.path.join(sweep_dir, "summary.json"), "w") as fh:
            json.dump(summary, fh, indent=1)
    summary["cells"] = per_cell
    return summary


def summarize(per_cell: Dict[str, Dict[str, list]]) -> Dict[str, dict]:
    """Final-round view per cell: the numbers the paper's figures plot."""
    out = {}
    for cid, rows in per_cell.items():
        out[cid] = {"accuracy": rows["accuracy"][-1],
                    "loss": rows["loss"][-1],
                    "cost": rows["cost"][-1],
                    "mean_cost": float(np.mean(rows["cost"])),
                    "n_associated": rows["n_associated"][-1],
                    "n_available": rows["n_available"][-1]}
    return out


def main(argv=None) -> None:
    import argparse
    import dataclasses as dc

    from repro import compile_cache
    from repro.configs.hfl_mnist import CONFIG

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="results")
    ap.add_argument("--sharded", action="store_true",
                    help="shard each group's fleet axis over all devices")
    ap.add_argument("--candidates", type=int, default=None, metavar="K",
                    help="run every cell on the (N, K) candidate frontier")
    ap.add_argument("--telemetry", action="store_true",
                    help="persist per-round RoundTrace JSON beside each "
                         "cell's metrics")
    ap.add_argument("--buffered", action="store_true",
                    help="add the semi-async buffered engine as a second "
                         "engine-mode axis value (DESIGN.md §11)")
    ap.add_argument("--faults", action="store_true",
                    help="run the chaos-smoke grid instead: the buffered "
                         "engine under edge churn + SINR-tied uplink loss "
                         "with telemetry on (DESIGN.md §12)")
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = dc.replace(CONFIG, n_clients=32, n_edges=4, min_samples=60,
                     max_samples=120, hidden=32, input_dim=64)
    if args.faults:
        grid = SweepGrid(
            name="chaos",
            scenarios=("static", "markov_dropout"),
            policies=("gcea",),
            seeds=(0,) if args.quick else (0, 1),
            n_rounds=3 if args.quick else 10,
            candidates_k=args.candidates,
            telemetry=True,
            engine_modes=("buffered",),
            faults=FaultSpec(edge_p_kill=0.2, edge_p_respawn=0.5,
                             uplink_p_loss=0.1, uplink_loss_slope=0.2))
    else:
        grid = SweepGrid(
            name="demo",
            scenarios=("static", "random_waypoint", "markov_dropout",
                       "hetero_devices", "full_dynamic", "flash_crowd"),
            policies=("fcea", "gcea"),
            seeds=(0,) if args.quick else (0, 1),
            n_rounds=3 if args.quick else 10,
            candidates_k=args.candidates,
            telemetry=args.telemetry,
            engine_modes=("sync", "buffered") if args.buffered else ("sync",))
    summary = run_sweep(cfg, grid, out_dir=args.out,
                        mesh=engine.fleet_mesh() if args.sharded else None)
    print(json.dumps({k: summary[k] for k in
                      ("name", "n_cells", "n_compiles", "groups")}, indent=1))
    for cid, row in summary["final"].items():
        print(f"{cid}: acc={row['accuracy']:.3f} "
              f"cost={row['mean_cost']:.3f} avail={row['n_available']}")
    if summary["failed_cells"]:
        # run_sweep has already written summary.json with the failures
        for cid, err in summary["failed_cells"].items():
            print(f"FAILED {cid}: {err}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
