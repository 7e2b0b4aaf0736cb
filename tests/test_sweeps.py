"""Sweep-grid runner tests (DESIGN.md §6.3).

The acceptance shape: a run_fleet sweep over ≥3 scenarios × 2 association
policies completes in a single vmapped compile PER static-spec group (all
dynamic scenarios share one group per policy) and writes per-cell JSON
trajectories under the results directory.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro import scenarios, sweeps
from repro.configs.hfl_mnist import CONFIG
from repro.core import engine

SMALL = dataclasses.replace(CONFIG, n_clients=16, n_edges=2,
                            clients_per_edge=3, min_samples=60,
                            max_samples=120, hidden=32, input_dim=64)


def _grid(**over):
    base = dict(name="t",
                scenarios=("random_waypoint", "markov_dropout",
                           "hetero_devices"),
                policies=("fcea", "gcea"), seeds=(0,), n_rounds=2)
    base.update(over)
    return sweeps.SweepGrid(**base)


def test_expand_grid_cross_product():
    grid = _grid(seeds=(0, 1))
    cells = sweeps.expand_grid(grid)
    assert len(cells) == 3 * 2 * 2
    assert len({c.cell_id for c in cells}) == len(cells)


def test_dynamic_scenarios_share_one_compile_per_policy(tmp_path):
    """3 dynamic scenarios × 2 policies -> exactly 2 vmapped compiles."""
    grid = _grid()
    before = engine.run_fleet._cache_size()
    summary = sweeps.run_sweep(SMALL, grid, out_dir=str(tmp_path))
    after = engine.run_fleet._cache_size()
    assert summary["n_cells"] == 6
    assert summary["n_compiles"] == 2              # one per policy
    # the jit cache grew by at most one entry per policy group — the three
    # scenarios of a group really do share a single vmapped program
    assert after - before <= 2
    for g in summary["groups"]:
        assert g["n_cells"] == 3                   # scenarios ride the vmap
        assert g["spec"]["scenario"] == "dynamic"


def test_sweep_writes_per_cell_json(tmp_path):
    grid = _grid(scenarios=("static", "full_dynamic"), policies=("gcea",),
                 schedulers=("fastest",))
    summary = sweeps.run_sweep(SMALL, grid, out_dir=str(tmp_path))
    sweep_dir = os.path.join(str(tmp_path), "sweep_t")
    files = sorted(os.listdir(sweep_dir))
    assert "summary.json" in files
    cell_files = [f for f in files if f != "summary.json"]
    assert len(cell_files) == summary["n_cells"] == 2
    for f in cell_files:
        with open(os.path.join(sweep_dir, f)) as fh:
            payload = json.load(fh)
        assert payload["n_rounds"] == 2
        for field in ("accuracy", "loss", "cost", "n_available", "z"):
            assert len(payload["metrics"][field]) == 2
        assert np.isfinite(payload["metrics"]["cost"]).all()


def test_sweep_cell_matches_direct_run(tmp_path):
    """A sweep cell's trajectory equals a standalone run_scanned with the
    same scenario + seed (the grid machinery adds nothing but batching)."""
    grid = _grid(scenarios=("mobile_flaky",), policies=("fcea",),
                 n_rounds=3)
    summary = sweeps.run_sweep(SMALL, grid, out_dir=str(tmp_path),
                               write_json=False)
    (cid, rows), = summary["cells"].items()
    spec = engine.EngineSpec(policy="fcea", scenario="dynamic")
    state, bundle, _ = engine.init_simulation(SMALL, seed=0,
                                              scenario="mobile_flaky")
    _, ms = engine.run_scanned(SMALL, spec, state, bundle, 3)
    np.testing.assert_allclose(rows["cost"], np.asarray(ms.cost), rtol=1e-5)
    np.testing.assert_array_equal(rows["n_available"],
                                  np.asarray(ms.n_available))


def test_custom_scenario_spec_parameters_survive(tmp_path):
    """Regression: a ScenarioSpec passed into the grid must run with ITS
    parameters, not a preset rebuilt from its kind label."""
    blackout = scenarios.ScenarioSpec(kind="markov_dropout", p_drop=1.0,
                                      p_return=0.0)
    grid = _grid(scenarios=(("blackout", blackout),), policies=("gcea",),
                 schedulers=("fastest",), n_rounds=2)
    summary = sweeps.run_sweep(SMALL, grid, out_dir=str(tmp_path),
                               write_json=False)
    (cid, rows), = summary["cells"].items()
    assert cid.startswith("blackout__")
    # p_drop=1, p_return=0: everyone is gone from round 1 onward — the
    # default markov_dropout preset would keep most clients available
    assert rows["n_available"] == [0, 0]


def test_ddpg_group_trains_its_own_actor(tmp_path):
    """The per-cell DDPG path: with no pre-trained actor, every ddpg cell
    trains its own actor on its own world (one vmapped
    ``train_allocator_fleet`` program per group) and the stacked actors
    ride the fleet vmap — no silent fallback to the midpoint allocator,
    no error."""
    grid = _grid(scenarios=("full_dynamic",), policies=("gcea",),
                 schedulers=("fastest",), allocators=("ddpg", "mid"),
                 seeds=(0, 1), ddpg_episodes=1, ddpg_steps=4,
                 ddpg_warmup=2, ddpg_hidden=16)
    summary = sweeps.run_sweep(SMALL, grid, out_dir=str(tmp_path))
    assert summary["n_cells"] == 4
    trained = [g for g in summary["groups"]
               if g["spec"]["allocator"] == "ddpg"]
    assert len(trained) == 1
    assert trained[0]["ddpg_trained"] is True
    assert trained[0]["ddpg_train_s"] > 0
    for cid, row in summary["final"].items():
        assert np.isfinite(row["mean_cost"])
    # both allocators really ran: the ddpg and mid trajectories differ
    costs = {cid: summary["cells"][cid]["cost"]
             for cid in summary["cells"]}
    ddpg_cells = [v for c, v in sorted(costs.items()) if "__ddpg__" in c]
    mid_cells = [v for c, v in sorted(costs.items()) if "__mid__" in c]
    assert len(ddpg_cells) == len(mid_cells) == 2
    assert ddpg_cells[0] != mid_cells[0]


def test_ddpg_cells_train_on_their_own_world(tmp_path):
    """Honest columns: every ddpg cell's actor is trained on that cell's
    own scenario × seed — two seeds must yield DIFFERENT ddpg
    trajectories than a single shared actor would explain, and the group
    timing records one actor per cell."""
    grid = _grid(scenarios=("full_dynamic",), policies=("gcea",),
                 schedulers=("fastest",), allocators=("ddpg",),
                 seeds=(0, 1), ddpg_episodes=1, ddpg_steps=4,
                 ddpg_warmup=2, ddpg_hidden=16)
    summary = sweeps.run_sweep(SMALL, grid, write_json=False)
    (g,) = summary["groups"]
    assert g["ddpg_actors"] == 2
    costs = [summary["cells"][c]["cost"] for c in sorted(summary["cells"])]
    assert costs[0] != costs[1]


def test_ddpg_static_and_dynamic_groups_each_train(tmp_path):
    """Mixed observation shapes are fine WITHOUT a shared actor: the
    static group trains a (2N,) actor, the dynamic group a (3N,) one."""
    grid = _grid(scenarios=("static", "full_dynamic"), policies=("gcea",),
                 schedulers=("fastest",), allocators=("ddpg",),
                 ddpg_episodes=1, ddpg_steps=4, ddpg_warmup=2,
                 ddpg_hidden=16)
    summary = sweeps.run_sweep(SMALL, grid, write_json=False)
    assert summary["n_cells"] == 2
    assert all(g["ddpg_trained"] for g in summary["groups"])
    assert len(summary["groups"]) == 2      # one compile+actor per kind


def test_ddpg_cells_reject_mixed_observation_shapes_with_shared_actor():
    """One PRE-TRAINED actor cannot serve both static (2N,) and dynamic
    (3N,) observations — that path must still refuse."""
    grid = _grid(scenarios=("static", "full_dynamic"), allocators=("ddpg",))
    with pytest.raises(ValueError, match="observation"):
        sweeps.run_sweep(SMALL, grid, write_json=False,
                         actor_params={"w": np.zeros((1,))})


def test_duplicate_scenario_labels_rejected():
    spec_a = scenarios.ScenarioSpec(kind="markov_dropout", p_drop=0.1)
    spec_b = scenarios.ScenarioSpec(kind="markov_dropout", p_drop=0.9)
    with pytest.raises(ValueError, match="ambiguous"):
        sweeps.expand_grid(_grid(scenarios=(spec_a, spec_b)))


def test_render_tables_sweep_mode(tmp_path):
    """results/render_tables.py renders a run_sweep summary.json into the
    Figs. 8-12 cost/accuracy markdown tables."""
    import importlib.util
    grid = _grid(scenarios=("static", "markov_dropout"), policies=("gcea",),
                 schedulers=("fastest",), seeds=(0, 1))
    sweeps.run_sweep(SMALL, grid, out_dir=str(tmp_path))
    path = os.path.join(os.path.dirname(__file__), "..", "results",
                        "render_tables.py")
    spec = importlib.util.spec_from_file_location("render_tables", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    report = mod.sweep_report(os.path.join(str(tmp_path), "sweep_t"))
    assert "Final accuracy" in report
    assert "Mean round cost" in report
    assert "gcea/mid/fastest/noma" in report
    # one row per scenario, mean ± std over the two seeds
    assert "| static |" in report and "| markov_dropout |" in report
    assert "±" in report


def test_sweep_candidates_k_matches_dense():
    """A sweep on the (N, K ≥ degree) frontier bills identical metrics —
    flipping ``candidates_k`` changes speed, not results (DESIGN.md §9)."""
    import dataclasses as dc
    grid = _grid(scenarios=("static", "markov_dropout"), policies=("fcea",),
                 schedulers=("fastest",), seeds=(0,))
    # the compact SIC is the sorted formulation — pin the dense cells to
    # it so the bills compare bit-for-bit at this (tiny) N too
    grid = dc.replace(grid, sic_impl="sorted")
    dense = sweeps.run_sweep(SMALL, grid, write_json=False)
    kgrid = dc.replace(grid, candidates_k=SMALL.n_edges)
    cand = sweeps.run_sweep(SMALL, kgrid, write_json=False)
    assert dense["cells"].keys() == cand["cells"].keys()
    for cid in dense["cells"]:
        for metric in ("accuracy", "cost", "n_associated"):
            np.testing.assert_array_equal(
                np.asarray(dense["cells"][cid][metric]),
                np.asarray(cand["cells"][cid][metric]),
                err_msg=f"{cid}:{metric}")


def test_render_tables_plot_mode(tmp_path):
    """``plot`` mode writes one PNG per metric from the per-cell
    trajectory files next to summary.json (the Figs. 8-12 figure view)."""
    import importlib.util
    pytest.importorskip("matplotlib")
    grid = _grid(scenarios=("static", "markov_dropout"), policies=("gcea",),
                 schedulers=("fastest",), seeds=(0, 1))
    sweeps.run_sweep(SMALL, grid, out_dir=str(tmp_path))
    path = os.path.join(os.path.dirname(__file__), "..", "results",
                        "render_tables.py")
    spec = importlib.util.spec_from_file_location("render_tables", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.plot_report(os.path.join(str(tmp_path), "sweep_t"),
                          str(tmp_path / "figs"))
    assert len(out) == 2                      # accuracy + cost panels
    for p in out:
        assert os.path.exists(p) and os.path.getsize(p) > 0
        assert p.endswith(".png")


def test_same_seed_same_data_across_scenarios():
    """Scenario draws happen after topology+data: the federation is
    identical under every scenario, so sweep columns are comparable."""
    _, b_static, _ = engine.init_simulation(SMALL, seed=3)
    _, b_dyn, _ = engine.init_simulation(SMALL, seed=3,
                                         scenario="full_dynamic")
    np.testing.assert_array_equal(np.asarray(b_static.counts),
                                  np.asarray(b_dyn.counts))
    np.testing.assert_array_equal(np.asarray(b_static.x),
                                  np.asarray(b_dyn.x))
    np.testing.assert_array_equal(np.asarray(b_static.dist),
                                  np.asarray(b_dyn.dist))


def test_grid_main_exits_nonzero_when_a_cell_fails(tmp_path, monkeypatch):
    """One crashed group fails the whole command, after summary.json has
    recorded which cells failed."""
    from repro import compile_cache
    from repro.sweeps import grid as grid_mod

    real_run_sweep, real_run_fleet = grid_mod.run_sweep, engine.run_fleet

    def run_fleet(cfg, spec, *args, **kw):
        if spec.policy == "gcea":
            raise RuntimeError("injected group failure")
        return real_run_fleet(cfg, spec, *args, **kw)

    def small_sweep(cfg, grid, *, out_dir, mesh=None):
        return real_run_sweep(SMALL, _grid(scenarios=("static",),
                                           schedulers=("fastest",)),
                              out_dir=out_dir, mesh=mesh)

    monkeypatch.setattr(compile_cache, "enable", lambda: None)
    monkeypatch.setattr(engine, "run_fleet", run_fleet)
    monkeypatch.setattr(grid_mod, "run_sweep", small_sweep)
    with pytest.raises(SystemExit) as exc:
        grid_mod.main(["--quick", "--out", str(tmp_path)])
    assert exc.value.code == 1
    with open(os.path.join(str(tmp_path), "sweep_t", "summary.json")) as fh:
        summary = json.load(fh)
    assert summary["failed_cells"]
    assert all("gcea" in cid for cid in summary["failed_cells"])
    assert summary["final"]                    # the fcea cell still ran
