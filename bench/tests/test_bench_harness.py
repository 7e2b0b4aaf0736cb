"""CPU checks of the benchmark harness: shape-derived counts, the trace
reduction, the on-device data generator, discovery by name, and the
refusal to run without a TPU."""
from __future__ import annotations

import dataclasses
import gzip
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from bench import flops, tracing  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def _config(name="hfl-mnist", **over):
    from bench import cells
    with open(ROOT / "bench" / "configs" / f"{name}.json") as fh:
        conf = json.load(fh)
    return dataclasses.replace(cells.hfl_config(conf), **over)


# ---------------------------------------------------------------------------
# Operations and bytes from shapes
# ---------------------------------------------------------------------------

def test_counts_match_hand_counts_at_the_paper_config():
    cfg = _config()
    assert flops.client_params(cfg) == 118_282
    assert (cfg.tau1, cfg.tau2) == (1, 3)
    assert flops.admitted(cfg) == 16
    # K x tau2*tau1 x B x 6|theta| = 16 x 3 x 32 x 6 x 118,282
    assert flops.train_flops(cfg, 1, 3) == 1_090_086_912
    # 2|theta| x 2,000 test samples
    assert flops.eval_flops(cfg, 2000) == 473_128_000
    assert flops.round_flops(cfg, 1, 3, 2000) == 1_090_086_912 + 473_128_000
    # per lane and step: 3|theta| parameter words + B (D + 1) input words
    per_step = (3 * 118_282 + 32 * 785) * 4
    assert flops.train_bytes(cfg, 1, 3) == 16 * 3 * per_step


def test_ddpg_counts_match_hand_counts():
    # actor 128-64-64-128, critic 256-64-64-1 at N = 64, hidden 64
    actor = 128 * 64 + 64 + 64 * 64 + 64 + 64 * 128 + 128
    critic = 256 * 64 + 64 + 64 * 64 + 64 + 64 * 1 + 1
    assert flops.mlp_params((128, 64, 64, 128)) == actor
    assert flops.mlp_params((256, 64, 64, 1)) == critic
    update = 64 * (8 * actor + 12 * critic)
    assert flops.ddpg_step_flops(64, 64, 64) == update + 2 * actor
    # 480 steps, updates from step 64 on (417 of them), every step acts
    call = flops.ddpg_call_flops(64, 64, 64, 12, 40, 64)
    assert call == 417 * update + 480 * 2 * actor


def test_least_time_names_its_bound():
    peak = flops.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    t, bound = flops.least_time(197e12, 1.0, peak)
    assert (t, bound) == (1.0, "compute")
    t, bound = flops.least_time(1.0, 819e9, peak)
    assert (t, bound) == (1.0, "memory")


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        flops.peaks("TPU v99")


# ---------------------------------------------------------------------------
# Trace reduction
# ---------------------------------------------------------------------------

def _op(name, start, dur, scope=""):
    return {"name": name, "start": float(start), "dur": float(dur),
            "scope": scope or name}


def test_busy_is_the_union_and_idle_the_rest():
    ops = [_op("a", 0, 100, "jit(f)/train/dot"),
           _op("b", 50, 100, "jit(f)/train/add"),      # overlaps a
           _op("c", 300, 100, "jit(f)/eval/reduce")]
    host = [{"name": "bench/dispatch", "start": 0.0, "dur": 500.0}]
    red = tracing.reduce_events({"devices": {"/device:TPU:0": ops},
                                 "host": host})
    assert red["window_s"] == pytest.approx(500e-9)
    assert red["busy_s"] == pytest.approx(250e-9)
    gaps = sorted(g[1] for g in red["idle_gaps"])
    assert gaps == pytest.approx([100e-9, 150e-9])
    assert all(g[0] == "bench/dispatch" for g in red["idle_gaps"])


def test_self_time_removes_nested_ops_and_scopes_attribute():
    # a while op spanning its body: only its own time counts
    ops = [_op("while", 0, 100, "jit(f)/while"),
           _op("f1", 10, 30, "jit(f)/while/body/associate/fusion"),
           _op("f2", 50, 40, "jit(f)/while/body/schedule/pdd/while")]
    red = tracing.reduce_events({"devices": {"d0": ops}, "host": []})
    st = red["stage_s"]
    assert st["associate"] == pytest.approx(30e-9)
    assert st["schedule"] == pytest.approx(40e-9)
    assert st["other"] == pytest.approx(30e-9)
    assert red["busy_s"] == pytest.approx(100e-9)


def test_innermost_stage_wins():
    assert tracing.stage_of("jit(run)/train/eval/dot") == "eval"
    assert tracing.stage_of("jit(run)/while/body/fusion.3") == "other"
    assert tracing.stage_of("jvp(train)/dot_general") == "train"


def test_collectives_are_timed_and_averaged_over_devices():
    d0 = [_op("all-reduce.1", 0, 40, "jit(f)/train/psum"),
          _op("fusion.2", 40, 60, "jit(f)/train/dot")]
    d1 = [_op("all-gather.7", 0, 20, "jit(f)/associate/gather"),
          _op("fusion.2", 20, 80, "jit(f)/train/dot")]
    red = tracing.reduce_events({"devices": {"d0": d0, "d1": d1},
                                 "host": []})
    assert red["n_devices"] == 2
    assert red["collective_s"] == pytest.approx((40 + 20) / 2 * 1e-9)
    assert red["busy_s"] == pytest.approx(100e-9)


def test_recorded_chip_trace_reduces():
    """The first 6,000 XLA ops of a traced window of the paper deployment
    (two lanes) on a TPU v5e, as ``load_events`` gives them: scope paths
    from the compiled module's metadata, HLO text cut short."""
    with gzip.open(DATA / "v5e_fleet_events.json.gz", "rt") as fh:
        events = json.load(fh)
    red = tracing.reduce_events(events)
    assert red["n_devices"] == 1
    assert 0.0 < red["busy_s"] <= red["window_s"]
    stages = red["stage_s"]
    for name in ("associate", "allocate", "schedule", "train", "eval"):
        assert stages.get(name, 0.0) > 0.0, name
    total = sum(stages.values())
    assert total == pytest.approx(red["busy_s"], rel=0.02)
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10


# ---------------------------------------------------------------------------
# The data generator
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fleet():
    import jax
    from bench import datagen
    cfg = _config(n_clients=24)
    w = datagen.make_fleet(datagen.root_key(2 ** 40 + 17), cfg, 2, 8)
    return cfg, jax.tree.map(np.asarray, w._replace(key=None))


def test_datagen_shapes(fleet):
    cfg, w = fleet
    n, m, cap, d = 24, cfg.n_edges, cfg.max_samples, cfg.input_dim
    assert w.x.shape == (2, n, cap, d) and w.x.dtype == np.float32
    assert w.y.shape == (2, n, cap) and w.y.dtype == np.int32
    assert w.dist.shape == w.gains.shape == (2, n, m)
    assert w.test_x.shape == (2, 2000, d)
    assert w.params["w1"].shape == (2, d, cfg.hidden)
    assert w.actor["w0"].shape == (2, 2 * n, 8)


def test_datagen_counts_labels_and_padding(fleet):
    cfg, w = fleet
    counts = w.counts.astype(int)
    assert counts.min() >= cfg.min_samples
    assert counts.max() <= cfg.max_samples
    slot = np.arange(cfg.max_samples)
    valid = slot[None, None, :] < counts[..., None]
    assert np.all(w.y[~valid] == 0)
    assert np.all(w.x[~valid] == 0.0)
    assert np.all((w.y >= 0) & (w.y < cfg.n_classes))
    x_valid = w.x[valid]
    assert np.all((x_valid > 0.0) & (x_valid < 1.0))
    # edges 0-3 sit at the midpoints of the corner-to-centre lines
    side = cfg.area_side_m
    assert np.allclose(w.edges[0, :4], [[side / 4, side / 4],
                                        [side / 4, 3 * side / 4],
                                        [3 * side / 4, side / 4],
                                        [3 * side / 4, 3 * side / 4]])


def test_datagen_label_skew_follows_dirichlet_alpha(fleet):
    cfg, w = fleet
    shares = []
    for lane in range(2):
        for c in range(24):
            ys = w.y[lane, c, :int(w.counts[lane, c])]
            hist = np.bincount(ys, minlength=cfg.n_classes)
            shares.append(hist.max() / hist.sum())
    # Dir(0.5) over 10 classes: the largest class holds ~40% on average;
    # an IID draw of >= 200 samples would hold ~14%
    assert np.mean(shares) > 0.3


def test_datagen_is_a_function_of_the_seed():
    import jax
    from bench import datagen
    cfg = _config(n_clients=8)
    a = datagen.make_fleet(datagen.root_key(3 * 2 ** 33 + 5), cfg, 1, 4,
                           with_data=False)
    b = datagen.make_fleet(datagen.root_key(3 * 2 ** 33 + 5), cfg, 1, 4,
                           with_data=False)
    c = datagen.make_fleet(datagen.root_key(5), cfg, 1, 4, with_data=False)
    data = jax.random.key_data
    a, b = a._replace(key=data(a.key)), b._replace(key=data(b.key))
    same = jax.tree.map(lambda u, v: bool(np.array_equal(u, v)), a, b)
    assert all(jax.tree.leaves(same))
    assert not np.array_equal(np.asarray(a.dist), np.asarray(c.dist))


# ---------------------------------------------------------------------------
# Discovery by name
# ---------------------------------------------------------------------------

def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


NEW_DRIVER = """
from bench import cells


class Driver(cells.Cell):
    unit = "rounds"
    MODELS = {"scenario": ("static", "full_dynamic")}
    FAULTS = ("answer",)
"""


def test_new_config_traffic_driver_and_metric_are_found_by_name(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    conf = json.loads((tmp_path / "bench/configs/hfl-mnist.json").read_text())
    conf["name"] = "hfl-new"
    conf["pipeline"] = dict(conf["pipeline"], scenario="full_dynamic")
    (tmp_path / "bench/configs/hfl-new.json").write_text(json.dumps(conf))
    (tmp_path / "bench/traffic/new-mix.json").write_text(json.dumps(
        {"driver": "new_driver", "lanes": 2, "rounds_per_call": 1,
         "actor_hidden": 8, "trace_seconds": 1.0, "limits": {}}))
    (tmp_path / "bench/drivers/new_driver.py").write_text(NEW_DRIVER)
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx['units']\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "hfl-new", "source": "x",
                             "file": "bench/configs/hfl-new.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new-cell", "config": "hfl-new",
                               "traffic": "new-mix", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "new_metric", "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "device", "moves": "rounds_per_s",
                               "workloads": ["new-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    run = _load(tmp_path / "bench" / "run.py", "bench_run_copy")
    plan = run.cell_plan("new-cell")
    assert plan["config"]["name"] == "hfl-new"
    assert plan["traffic"]["lanes"] == 2
    assert [m["name"] for m in plan["per_layer"]] == ["new_metric"]
    assert run.load_reader("new_metric")({"units": 3}) == 6.0
    cell = run.build(plan, 7)
    assert type(cell).__module__ == "bench_drivers_new_driver"
    assert cell.spec.scenario == "full_dynamic"
    assert type(cell).modes() == ("program", "control", "answer")
    after = {p: p.read_bytes() for p in before}
    assert after == before


MODEL_DRIVER = """
import jax.numpy as jnp

from bench import cells


class Driver(cells.Cell):
    unit = "rounds"
    ops_per_call = 2
    MODELS = {"scenario": ("static",)}

    @classmethod
    def config_of(cls, conf):
        model = conf["client_model"]
        return cells.hfl_config(dict(
            conf, input_dim=model["hidden_size"], hidden=model["hidden_size"],
            n_classes=model["vocab_size"]))

    def setup(self):
        model = self.conf["client_model"]
        self.experts = model["num_experts"]
        self.x = jnp.ones((model["hidden_size"],), jnp.float32)
        self.dispatch().block_until_ready()

    def dispatch(self):
        return self.x * 2.0

    def failed(self):
        return 0

    def check(self, modes=("program",)):
        return {m: {"token_gap": 0.0} for m in modes}

    def flops_per_op(self):
        return 1.0

    def train_counts(self):
        return 1.0, 1.0

    def counters(self):
        return {"expert_load_max": float(self.experts) / 4.0}

    def part_counts(self):
        # 1 us of the chip's bf16 peak a round
        return {"train/moe": (197e6, 1.0)}
"""

MOE_ROOFLINE = """
from bench import flops


def read(ctx):
    t = ctx["part_s"].get("train/moe", 0.0)
    if "train/moe" not in ctx["part_counts"] or t <= 0.0:
        return None
    least, _ = flops.least_time(*ctx["part_counts"]["train/moe"],
                                ctx["peak"])
    return least * ctx["units"] / t * 100.0
"""


def test_client_model_config_with_counters_and_part_counts_needs_no_bench_edit(
        tmp_path, monkeypatch):
    """A configuration that describes its client model in a block of its
    own (no MLP widths), its driver (``config_of``, ``counters``,
    ``part_counts``), a part the program names and readers of that part's
    counts and of the counter: new files and entries only, driven through
    a whole traced run of the harness (the look for a chip skipped, the
    trace's events given)."""
    from bench import cells, flops as bench_flops
    from repro.telemetry import spans
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    conf = json.loads((tmp_path / "bench/configs/hfl-mnist.json").read_text())
    for key in ("input_dim", "hidden", "n_classes"):
        del conf[key]
    conf["name"] = "hfl-moe"
    conf["client_model"] = {"hidden_size": 64, "vocab_size": 512,
                            "num_experts": 32, "num_experts_per_tok": 4}
    (tmp_path / "bench/configs/hfl-moe.json").write_text(json.dumps(conf))
    (tmp_path / "bench/traffic/moe-mix.json").write_text(json.dumps(
        {"driver": "moe_driver", "trace_seconds": 0.2,
         "limits": {"token_gap": 0.0}}))
    (tmp_path / "bench/drivers/moe_driver.py").write_text(MODEL_DRIVER)
    (tmp_path / "bench/metrics/moe_roofline.py").write_text(MOE_ROOFLINE)
    (tmp_path / "bench/metrics/expert_load_max.py").write_text(
        "def read(ctx):\n    return ctx.get('expert_load_max')\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "hfl-moe", "source": "x",
                             "file": "bench/configs/hfl-moe.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "moe-cell", "config": "hfl-moe",
                               "traffic": "moe-mix", "chips": 1, "why": "x"})
    for name, unit in (("moe_roofline", "%"), ("expert_load_max", "tokens")):
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": "lower",
            "source": "device_trace", "layer": "train", "moves":
            "rounds_per_s", "workloads": ["moe-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    # what the program's later change would bring: a part it scopes, and
    # a trace whose ops carry it (1 ms of it, 1 ms of plain SGD)
    monkeypatch.setattr(spans, "PARTS", spans.PARTS + ("moe",))
    body = "jit(run)/round_loop/while/body/closed_call/train/sgd"
    events = {"devices": {"/device:TPU:0": [
        {"name": "fusion.1", "start": 0.0, "dur": 1e6,
         "scope": f"{body}/moe/dot_general"},
        {"name": "fusion.2", "start": 1e6, "dur": 1e6,
         "scope": f"{body}/dot_general"}]},
        "host": [{"name": "bench/dispatch", "start": 0.0, "dur": 4e6}]}
    monkeypatch.setattr(tracing, "load_events", lambda *_: events)
    v5e = bench_flops.peaks("TPU v5 lite")
    monkeypatch.setattr(bench_flops, "peaks", lambda kind: v5e)

    run = _load(tmp_path / "bench" / "run.py", "bench_run_moe_copy")
    monkeypatch.setattr(run, "enable_cache", lambda: None)
    plan = run.cell_plan("moe-cell")
    with pytest.raises(KeyError, match="input_dim"):
        cells.hfl_config(plan["config"])
    cell = run.build(plan, 7)
    assert (cell.cfg.input_dim, cell.cfg.n_classes) == (64, 512)
    assert cell.conf["client_model"]["num_experts"] == 32
    result, info = run.run("moe-cell", 2 ** 31 + 5, 1.0, True,
                           require_tpu=False, plan=plan)
    assert result["correct"] and info["in_window"] == 0
    units = result["attempted"]
    assert units > 0
    assert result["metrics"] == {
        "moe_roofline": {"value": pytest.approx(1e-6 * units / 1e-3 * 100),
                         "unit": "%"},
        "expert_load_max": {"value": 8.0, "unit": "tokens"}}
    # the breakdown stays keyed by stage
    assert [k for k, _ in result["breakdown"]["device_ops"]] == \
        ["train/fusion.1", "train/fusion.2"]
    after = {p: p.read_bytes() for p in before}
    assert after == before


@pytest.mark.parametrize("workload", ["mnist-fleet16", "mnist-ddpg32",
                                      "xdev1024-dense", "xdev4096-clients4"])
@pytest.mark.parametrize("field,value", [("scenario", "full_dynamic"),
                                         ("scenario", "flash_crowd"),
                                         ("engine_mode", "buffered")])
def test_driver_refuses_what_its_reference_does_not_model(workload, field,
                                                          value):
    from bench import run
    from bench_plans import plan_of
    plan = plan_of(workload)
    if field == "scenario":
        plan["config"] = dict(plan["config"], pipeline=dict(
            plan["config"]["pipeline"], scenario=value))
    else:
        plan["traffic"] = dict(plan["traffic"], engine=dict(
            plan["traffic"].get("engine", {}), **{field: value}))
    with pytest.raises(ValueError, match=f"{field}.*{value}"):
        run.build(plan, 11)


def test_scenario_state_follows_the_configuration():
    import jax
    from bench import cells, datagen
    from repro.core import engine
    cfg = _config(n_clients=8)
    w = datagen.make_single(datagen.root_key(2 ** 35 + 3), cfg, 4)
    spec = engine.EngineSpec(scenario="full_dynamic")
    state, _ = cells.program_inputs(cfg, spec, w, None, 2 ** 35 + 3)
    assert np.all(np.asarray(state.scenario.speed) > 0.0)
    again, _ = cells.program_inputs(cfg, spec, w, None, 2 ** 35 + 3)
    same = jax.tree.map(lambda a, b: bool(np.array_equal(a, b)),
                        state.scenario, again.scenario)
    assert all(jax.tree.leaves(same))
    static, _ = cells.program_inputs(cfg, engine.EngineSpec(), w, None, 5)
    assert np.all(np.asarray(static.scenario.speed) == 0.0)


# ---------------------------------------------------------------------------
# Refusals
# ---------------------------------------------------------------------------

def _bench_cmd(cwd: Path, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mnist-fleet16",
         "--seed", str(2 ** 31 + 12345), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    return not any(line.startswith("{") and '"correct"' in line
                   for line in stdout.splitlines())


def test_run_refuses_without_a_tpu():
    out = _bench_cmd(ROOT)
    assert out.returncode != 0
    assert _no_result(out.stdout)
    assert "TPU" in out.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    out = _bench_cmd(tmp_path)
    assert out.returncode != 0
    assert _no_result(out.stdout)
