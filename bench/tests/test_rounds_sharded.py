"""The client-sharded rounds driver (``drivers/rounds_sharded.py``) on four
placeholder CPU devices at a small size, and the ``collective_ms`` reader.

The multi-device cases run in one subprocess: the placeholder-device
``XLA_FLAGS`` must be set before jax imports and must not leak into this
test process.  It draws the world shard by shard and compares it with
``datagen``'s one-device draw, reads how much of ``x`` each device holds,
reads the driver's counters after a short window, and makes whole runs
of ``bench/run.py`` (the harness's look for a chip skipped) of the
program, the control and each planted fault, with the cell's limits; the
tests below read its report."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import run, tracing  # noqa: E402

CELL = "xdev4096-clients4"
SHARDS = 4
N_CLIENTS = 96
SEED = 2 ** 33 + 41
# The cell's configuration at a size a test run holds; every limit but one
# is the cell's own.  On the CPU the program matches the reference to
# rounding (element gap 0.0) while the control reads a third of what it
# reads on the chip (element gap 0.0016 against 0.0031-0.0058), about the
# chip-set limit: the control's number gets a limit of its own here, as in
# test_bench_checks.py.
SMALL_CONFIG = {"n_clients": N_CLIENTS, "n_edges": 8}
SMALL_LIMITS = {"model_elem_gap": 0.00025}
FAULTS = run.load_driver(run.cell_plan(CELL)["traffic"]["driver"]).FAULTS

_SCRIPT = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(shards)d"
sys.path[:0] = [%(root)r, %(root)r + "/src"]
import jax
import numpy as np
from bench import cells, datagen, run
from bench.drivers import rounds_sharded

run.enable_cache = lambda: None
plan = run.cell_plan(%(cell)r)
plan["config"] = dict(plan["config"], **%(config)r)
plan["traffic"]["limits"] = dict(plan["traffic"]["limits"], **%(limits)r)
report = {"devices": len(jax.devices())}

cfg = cells.hfl_config(plan["config"])
hidden = plan["traffic"]["actor_hidden"]
root = datagen.root_key(%(seed)d)
mesh = rounds_sharded.client_mesh(cfg.n_clients, jax.devices())
sharded = rounds_sharded.sharded_world(root, cfg, hidden, mesh)
whole = datagen.make_single(root, cfg, hidden)
data = lambda w: jax.tree.map(np.asarray, w._replace(
    key=jax.random.key_data(w.key)))
same = jax.tree.map(lambda a, b: a.dtype == b.dtype and a.shape == b.shape
                    and bool(np.array_equal(a, b)), data(sharded), data(whole))
report["draw_equal"] = {f: bool(all(jax.tree.leaves(getattr(same, f))))
                        for f in datagen.World._fields}
report["x_devices"] = sorted(d.id for d in sharded.x.sharding.device_set)
report["x_shards"] = [list(s.data.shape) for s in
                      sharded.x.addressable_shards]
compiled = rounds_sharded.sharded_world.lower(root, cfg, hidden, mesh).compile()
report["draw_out_bytes"] = compiled.memory_analysis().output_size_in_bytes
first = jax.devices()[0]
report["first_device_bytes"] = sum(
    s.data.nbytes for a in jax.tree.leaves(sharded._replace(
        key=jax.random.key_data(sharded.key)))
    for s in a.addressable_shards if s.device == first)
del sharded, whole
try:
    rounds_sharded.client_mesh(%(shards)d * 24 + 1, jax.devices())
except ValueError as e:
    report["uneven"] = str(e)

with jax.default_matmul_precision(plan["config"]["matmul_precision"]):
    cell = run.build(plan, %(seed)d)
    cell.setup()
    run.window(cell, 0.1)
    gains = cell.state.gains.sharding
    report["state_devices"] = len(gains.device_set)
    report["state_replicated"] = gains.is_fully_replicated
    report["counters"] = cell.counters()
del cell

modes = {}
for mode in ("program", "control") + %(faults)r:
    result, info = run.run(%(cell)r, %(seed)d, 0.2, False, require_tpu=False,
                           mode=mode, plan=plan)
    modes[mode] = {"correct": result["correct"], "checks": result["checks"],
                   "attempted": result["attempted"],
                   "failed": result["failed"],
                   "count": result["device"]["count"],
                   "in_window": info["in_window"], "last": list(result)[-1]}
report["modes"] = modes
print("REPORT " + json.dumps(report))
"""


@pytest.fixture(scope="module")
def report():
    script = _SCRIPT % {"shards": SHARDS, "root": str(ROOT), "cell": CELL,
                        "config": SMALL_CONFIG, "limits": SMALL_LIMITS,
                        "seed": SEED, "faults": tuple(FAULTS)}
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("REPORT ")]
    return json.loads(line[-1][len("REPORT "):])


def test_sharded_draw_equals_the_one_device_draw(report):
    """Every leaf of the world, ``x`` among them, bit for bit."""
    assert report["draw_equal"] == {f: True for f in report["draw_equal"]}
    assert "x" in report["draw_equal"]


def test_no_device_holds_more_than_its_share_of_x(report):
    from bench import cells
    cfg = cells.hfl_config(dict(run.cell_plan(CELL)["config"],
                                **SMALL_CONFIG))
    share = [N_CLIENTS // SHARDS, cfg.max_samples, cfg.input_dim]
    assert report["devices"] == SHARDS
    assert report["x_devices"] == list(range(SHARDS))
    assert report["x_shards"] == [share] * SHARDS
    # the compiled draw's output on a device is what the world places
    # there: that device's share of x and of the other client-axis leaves,
    # and the replicated leaves (up to the alignment of its buffers)
    assert abs(report["draw_out_bytes"] - report["first_device_bytes"]) < 1024


def test_sound_run_is_correct(report):
    row = report["modes"]["program"]
    assert row["correct"], row["checks"]
    assert row["failed"] == 0 and row["attempted"] > 0
    assert row["in_window"] == 0
    assert row["count"] == SHARDS
    assert row["last"] == "checks"


def test_control_is_not_correct(report):
    row = report["modes"]["control"]
    assert not row["correct"], row["checks"]


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(report, fault):
    row = report["modes"][fault]
    assert not row["correct"], row["checks"]


def test_counters_read_the_sharded_state(report):
    """The driver's counter call runs from the state the window left,
    still split over the four devices."""
    assert report["state_devices"] == SHARDS
    assert not report["state_replicated"]
    assert list(report["counters"]) == ["assoc_sweeps"]
    assert report["counters"]["assoc_sweeps"] >= 1.0


def test_a_population_the_mesh_does_not_divide_is_refused(report):
    """The program would pad it with inert clients: another experiment."""
    assert "do not split evenly" in report["uneven"]


def test_driver_refuses_lanes():
    plan = run.cell_plan(CELL)
    plan["traffic"] = dict(plan["traffic"], lanes=2)
    with pytest.raises(ValueError, match="lanes"):
        run.build(plan, 11).setup()


def test_a_single_device_is_refused():
    """With one device there is no exchange between chips to measure."""
    import jax
    from bench.drivers import rounds_sharded
    with pytest.raises(ValueError, match="more than one device"):
        rounds_sharded.client_mesh(N_CLIENTS, jax.devices()[:1])


def test_the_cell_is_the_whole_population_on_four_chips():
    plan = run.cell_plan(CELL)
    assert plan["cell"]["chips"] == SHARDS
    assert plan["traffic"]["lanes"] is None
    assert plan["config"]["n_clients"] == 4096
    assert plan["config"]["n_clients"] % SHARDS == 0
    assert plan["config"]["reduced"] == []


# ---------------------------------------------------------------------------
# collective_ms
# ---------------------------------------------------------------------------

def _op(name, start, dur, scope):
    return {"name": name, "start": float(start), "dur": float(dur),
            "scope": scope}


def test_collective_ms_is_the_per_chip_mean_per_round():
    """Two devices, each with an all-reduce beside compute: the reader
    gives the chips' mean collective self time over the rounds."""
    d0 = [_op("%all-reduce.3 = f32[128,784] all-reduce(%fusion.1)", 0, 300,
              "jit(run_scanned)/round_loop/while/body/train/psum"),
          _op("fusion.1", 300, 700, "jit(run_scanned)/train/dot")]
    d1 = [_op("all-reduce-start.3", 0, 500,
              "jit(run_scanned)/round_loop/while/body/train/psum"),
          _op("fusion.1", 500, 500, "jit(run_scanned)/train/dot")]
    red = tracing.reduce_events({"devices": {"/device:TPU:0": d0,
                                             "/device:TPU:1": d1},
                                 "host": []})
    read = run.load_reader("collective_ms")
    ctx = {"unit": "rounds", "units": 4, "chips": 2,
           "collective_s": red["collective_s"]}
    assert read(ctx) == pytest.approx((300 + 500) / 2 * 1e-9 / 4 * 1e3)
    assert read(dict(ctx, chips=1)) is None
    assert read(dict(ctx, units=0)) is None


def test_collective_ms_is_listed_for_the_four_chip_cell_only():
    bench = run.load_json("BENCHMARK.json")
    for cell in bench["workloads"]:
        names = [m["name"] for m in run.cell_plan(cell["name"])["per_layer"]]
        assert ("collective_ms" in names) == (cell["name"] == CELL), \
            cell["name"]
