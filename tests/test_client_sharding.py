"""Client-axis sharding (DESIGN.md §9.3): ``shard_clients`` /
``run_scanned_client_sharded`` must reproduce the unsharded round engine
with the N axis genuinely split across devices, and ``pad_clients`` must
add only INERT clients (never associated, never billed).

Unlike the fleet axis (tests/test_fleet_sharding.py), the client axis has
cross-device reductions (aggregation, per-edge cost, fuzzy normalisation),
so multi-device float parity is pinned at tight tolerances rather than
bit-exactness; integer observables (association counts, z) stay exact.

The multi-device cases run in a SUBPROCESS: the placeholder-device
``XLA_FLAGS`` must be set before jax imports and must not leak into this
test process (same pattern as test_fleet_sharding.py).
"""
import dataclasses
import os
import subprocess
import sys

import numpy as np

from repro.configs.hfl_mnist import CONFIG
from repro.core import engine

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SMALL = dataclasses.replace(CONFIG, n_clients=16, n_edges=2,
                            clients_per_edge=3, min_samples=60,
                            max_samples=120, hidden=32, input_dim=64)


def test_single_device_sharded_matches_plain():
    """On the 1-device default mesh the sharded driver is a pass-through
    (N divisible by 1, no padding, placement-only device_put)."""
    spec = engine.EngineSpec(policy="fcea", scheduler="fastest",
                             candidates_k=2)
    state, bundle, _ = engine.init_simulation(SMALL, seed=0)
    _, plain = engine.run_scanned(SMALL, spec, state, bundle, 2)
    _, sharded = engine.run_scanned_client_sharded(SMALL, spec, state,
                                                   bundle, 2)
    for field in ("loss", "cost", "accuracy", "n_associated"):
        np.testing.assert_array_equal(
            np.asarray(getattr(plain, field)),
            np.asarray(getattr(sharded, field)), err_msg=field)


def test_client_mesh_shape():
    import jax
    mesh = engine.client_mesh()
    assert mesh.axis_names == ("clients",)
    assert int(mesh.devices.size) == len(jax.devices())


def test_pad_clients_inert():
    """Padded clients can never associate and the real clients' admitted
    set stays feasible; a multiple that divides N is a no-op."""
    state, bundle, _ = engine.init_simulation(SMALL, seed=0)
    same = engine.pad_clients(SMALL, state, bundle, 4)
    assert same[0].n_clients == SMALL.n_clients          # 16 % 4 == 0
    cfg2, st2, bu2 = engine.pad_clients(SMALL, state, bundle, 5)
    assert cfg2.n_clients == 20
    spec = engine.EngineSpec(policy="fcea", scheduler="fastest",
                             candidates_k=2)
    assoc = np.asarray(engine.associate_snapshot(cfg2, spec, st2, bu2))
    assert assoc[SMALL.n_clients:].sum() == 0            # pads never admitted
    assert (assoc.sum(axis=1) <= 1).all()
    assert (assoc.sum(axis=0) <= SMALL.clients_per_edge).all()
    # the padded world still runs end to end (dense and candidate paths)
    for s in (spec, dataclasses.replace(spec, candidates_k=None)):
        _, ms = engine.run_scanned(cfg2, s, st2, bu2, 2)
        assert np.isfinite(np.asarray(ms.cost)).all()


_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import numpy as np
import jax
from repro.configs.hfl_mnist import CONFIG
from repro.core import engine

assert len(jax.devices()) == 4
SMALL = dataclasses.replace(CONFIG, n_clients=16, n_edges=2,
                            clients_per_edge=3, min_samples=60,
                            max_samples=120, hidden=32, input_dim=64)

def check(cfg, spec, state, bundle, label):
    _, plain = engine.run_scanned(cfg, spec, state, bundle, 2)
    _, sharded = engine.run_scanned_client_sharded(cfg, spec, state,
                                                   bundle, 2)
    for f in ("loss", "cost", "accuracy", "total_energy_j"):
        np.testing.assert_allclose(np.asarray(getattr(plain, f)),
                                   np.asarray(getattr(sharded, f)),
                                   rtol=2e-5, atol=1e-7,
                                   err_msg=f"{label}:{f}")
    for f in ("n_associated", "n_available", "z"):
        np.testing.assert_array_equal(np.asarray(getattr(plain, f)),
                                      np.asarray(getattr(sharded, f)),
                                      err_msg=f"{label}:{f}")

# 16 clients over 4 devices, candidate and dense paths, static + dynamic
state, bundle, _ = engine.init_simulation(SMALL, seed=0)
for spec in (engine.EngineSpec(policy="fcea", scheduler="fastest",
                               candidates_k=2),
             engine.EngineSpec(policy="gcea", scheduler="fastest")):
    check(SMALL, spec, state, bundle, f"even:{spec.policy}")
print("EVEN_OK")

dyn = engine.EngineSpec(policy="fcea", scheduler="fastest",
                        scenario="dynamic", candidates_k=2)
st, bu, _ = engine.init_simulation(SMALL, seed=1, scenario="full_dynamic")
check(SMALL, dyn, st, bu, "dynamic")
print("DYN_OK")

# ragged N: 18 clients pad to 20 over 4 devices; the padded world's
# sharded and unsharded runs must agree, and the pads stay inert
RAG = dataclasses.replace(SMALL, n_clients=18)
state, bundle, _ = engine.init_simulation(RAG, seed=0)
spec = engine.EngineSpec(policy="fcea", scheduler="fastest",
                         candidates_k=2)
cfgp, stp, bup = engine.pad_clients(RAG, state, bundle, 4)
assert cfgp.n_clients == 20
check(cfgp, spec, stp, bup, "ragged")
assoc = np.asarray(engine.associate_snapshot(cfgp, spec, stp, bup))
assert assoc[RAG.n_clients:].sum() == 0
print("RAGGED_OK")
"""


def test_multi_device_client_sharding_parity():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for tag in ("EVEN_OK", "DYN_OK", "RAGGED_OK"):
        assert tag in out.stdout


_LAYOUT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import numpy as np
import jax
from repro.configs.hfl_mnist import CONFIG
from repro.core import engine

assert len(jax.devices()) == 4
SMALL = dataclasses.replace(CONFIG, n_clients=16, n_edges=2,
                            clients_per_edge=3, min_samples=60,
                            max_samples=120, hidden=32, input_dim=64)
RAG = dataclasses.replace(SMALL, n_clients=18)
split = jax.sharding.PartitionSpec("clients")
for label, cfg in (("even", SMALL), ("ragged", RAG)):
    state, bundle, _ = engine.init_simulation(cfg, seed=0)
    cfg, state, bundle = engine.pad_clients(cfg, state, bundle, 4)
    _, placed = engine.shard_clients(state, bundle)
    x, d = np.asarray(placed.x), cfg.input_dim
    assert x.shape == (cfg.n_clients, cfg.max_samples, engine.LANES), label
    assert placed.x.sharding.spec == split, label
    assert placed.x.format.layout.major_to_minor == (0, 1, 2), label
    assert x[..., :d].tobytes() == np.asarray(bundle.x).tobytes(), label
    assert not x[..., d:].any(), label
    for f in engine.RoundBundle._fields:
        if f != "x":
            a, b = np.asarray(getattr(bundle, f)), np.asarray(getattr(placed, f))
            assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), (label, f)
    print(label.upper() + "_OK")
"""


def test_shard_clients_places_x_row_major():
    """``shard_clients`` splits ``bundle.x`` over the clients with its
    feature axis zero-padded to whole lanes (64 → 128), row-major, the
    layout the minibatch gather reads; the data and every other bundle
    leaf keep every bit, and a padded world is placed the same way."""
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _LAYOUT_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for tag in ("EVEN_OK", "RAGGED_OK"):
        assert tag in out.stdout
