"""Compile the main path for a described TPU v5e chip, with no chip attached.

The TPU compiler is installed with JAX, so these tests catch what the chip
would refuse (block shapes Mosaic cannot tile, programs that do not fit)
without a chip.  They compile only: nothing runs, so they say nothing
about results or speed; ``chip_smoke.py`` checks those on the chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  All such tests stay in this one file for the same reason.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.hfl_mnist import CONFIG
from repro.core import engine
from repro.kernels import hfl_ops

KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip would be written to the persistent
    cache but could never be read back; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def config_shapes(one_chip):
    """(state, bundle) of the full ``CONFIG`` world as shapes on one chip."""
    state, bundle, _ = engine.init_simulation(CONFIG, seed=0)
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (state, bundle))


def _lower_kernel(name, n, m, sds):
    f32, i32 = jnp.float32, jnp.int32
    if name == "score_matrix":
        return hfl_ops.score_matrix.lower(
            sds((n, m), f32), sds((n,), f32), sds((n,), i32),
            data_max=float(CONFIG.max_samples), interpret=False)
    if name == "score_candidates":
        return hfl_ops.score_candidates.lower(
            sds((n, m), f32), sds((n, CONFIG.clients_per_edge), i32),
            sds((n,), f32), sds((n,), i32),
            data_max=float(CONFIG.max_samples), interpret=False)
    return hfl_ops.sic_rates.lower(
        sds((n,), f32), sds((n, m), f32), sds((n, m), jnp.bool_),
        bandwidth_hz=CONFIG.bandwidth_hz, noise_w=4e-15, interpret=False)


@pytest.mark.parametrize("n,m", [(CONFIG.n_clients, CONFIG.n_edges),
                                 (1024, 16)])
@pytest.mark.parametrize("name", ["score_matrix", "score_candidates",
                                  "sic_rates"])
def test_hfl_kernel_compiles_for_v5e(one_chip, name, n, m):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _lower_kernel(name, n, m, sds).compile()
    assert KERNEL in compiled.as_text()


def _compile_run_scanned(spec, config_shapes, rounds=10):
    state, bundle = config_shapes
    compiled = engine.run_scanned.lower(CONFIG, spec, state, bundle,
                                        rounds).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9                   # one v5e chip holds 16 GB
    return compiled.as_text()


@pytest.fixture(scope="module")
def default_program(config_shapes):
    """The compiled text of the default-spec round engine at full width."""
    spec = engine.EngineSpec(policy="fcea", scheduler="pdd")
    return _compile_run_scanned(spec, config_shapes)


def test_run_scanned_compiles_for_v5e(default_program):
    """The paper's round engine at full CONFIG width, default spec."""
    assert "ENTRY" in default_program


# an instruction's result: ``%name = <dtype>[<dims>]{layout} <opcode>(``
_RESULT = re.compile(r"^\s*(?:ROOT\s+)?%(\S+)\s*=\s*\w+\[([\d,]*)\]\S*\s+"
                     r"([\w-]+)\(")


def test_run_scanned_sgd_products_batch_the_lanes(default_program):
    """Every matrix product of the local steps carries the K admitted lanes
    as its leading (batch) dimension: the generic per-lane step, vmapped
    over the cohort, lowers to batched products, never to one product per
    lane or a loop over the lanes."""
    spec = engine.EngineSpec(policy="fcea", scheduler="pdd")
    lanes = min(CONFIG.n_clients,
                engine.quota_for(CONFIG, spec) * CONFIG.n_edges)
    dims = [tuple(int(d) for d in m.group(2).split(",") if d)
            for line in default_program.splitlines()
            if (m := _RESULT.match(line))
            and m.group(3) in ("convolution", "dot")
            and re.search(r'op_name="[^"]*/train/[^"]*/sgd/', line)]
    assert dims, "no matrix product under train/sgd"
    assert all(d[:1] == (lanes,) for d in dims), (lanes, dims)


def _data_rows(program, rows, width=CONFIG.input_dim):
    """(name, opcode, operands) of every instruction that makes an array of
    ``rows`` × ``width`` rows per client.  Parameters, tuple elements and
    bitcasts only name an array that another instruction made."""
    found = []
    for line in program.splitlines():
        m = _RESULT.match(line)
        if m is None or m.group(3) in ("parameter", "get-tuple-element",
                                       "bitcast"):
            continue
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        if dims[-2:] == (rows, width):
            found.append((m.group(1), m.group(3), line[m.end():]))
    return found


def test_run_scanned_reads_minibatches_not_data_slabs(default_program):
    """Training gathers its minibatches straight from ``bundle.x``: the one
    array with ``max_samples`` rows per client made inside the program is
    the entry copy that re-lays the ``bundle.x`` parameter.  No (K, cap, D)
    cohort slab, and none of the (·, 384, D) / (·, 48, D) chunks XLA cuts
    a slab gather into."""
    cap = CONFIG.max_samples
    assert re.search(rf"%bundle_x\S* = f32\[{CONFIG.n_clients},{cap},"
                     rf"{CONFIG.input_dim}\]\S* parameter\(",
                     default_program)
    made = _data_rows(default_program, cap)
    assert [(op, args.startswith("%bundle_x")) for _, op, args in made] \
        == [("copy", True)], made
    assert _data_rows(default_program, 384) == []
    assert _data_rows(default_program, 48) == []


@pytest.fixture(scope="module")
def client_split_inputs(topo):
    """The full ``CONFIG`` world as shapes on the topology's four chips,
    placed as ``shard_clients`` places it: the shardings of
    ``_client_shardings`` and ``bundle.x`` as ``lane_padded`` makes it."""
    mesh = engine.client_mesh(topo.devices)
    state, bundle, _ = engine.init_simulation(CONFIG, seed=0)
    placed = engine._client_shardings(state, bundle, mesh)
    state, bundle = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        (state, bundle), placed)
    padded = jax.eval_shape(engine.lane_padded, bundle.x)
    return state, bundle, bundle._replace(x=jax.ShapeDtypeStruct(
        padded.shape, padded.dtype, sharding=bundle.x.sharding))


def _row_writes(program):
    """Every ``dynamic-update-slice`` that writes into an array of
    ``input_dim``-wide rows: what a gather becomes when the TPU copies its
    rows one at a time, in a loop, instead of in one op."""
    return [(name, dims) for line in program.splitlines()
            if (m := _RESULT.match(line))
            for name, dims, op in [m.groups()]
            if op == "dynamic-update-slice"
            and dims.endswith(f",{CONFIG.input_dim}")]


def _x_parameter(program):
    """(name, layout) of the program's ``bundle.x`` parameter."""
    m = re.search(r"%(\S+) = f32\[[\d,]+\](\{[^}]*\}) parameter\(.*"
                  r'op_name="bundle\.x"', program)
    return m.group(1), m.group(2)


@pytest.mark.parametrize("placement", ["shard_clients", "as_made"])
def test_client_split_run_scanned_entry_copy(client_split_inputs, placement):
    """``run_scanned`` with the client axis split over four chips, 16
    clients a chip.  Placed by ``shard_clients``, ``bundle.x`` (feature
    axis padded to 896) enters row-major and no instruction makes an array
    of ``max_samples`` sample rows per client; the minibatch gather reads
    whole padded rows in one op, not part rows in a loop.  As made (784
    features), the chip's compact default puts the sample axis minor, and
    the program re-lays the whole parameter at its entry, which the
    placement exists to avoid."""
    state, as_made, placed = client_split_inputs
    bundle = placed if placement == "shard_clients" else as_made
    width = bundle.x.shape[-1]
    spec = engine.EngineSpec(policy="fcea", scheduler="pdd")
    program = _compile_run_scanned(spec, (state, bundle), rounds=3)
    name, layout = _x_parameter(program)
    made = [(op, args.startswith(f"%{name})")) for _, op, args
            in _data_rows(program, CONFIG.max_samples, width)]
    if placement == "shard_clients":
        assert width % engine.LANES == 0
        assert layout.startswith("{2,1,0"), layout
        assert made == []
        assert _data_rows(program, CONFIG.max_samples) == []
        assert _row_writes(program) == []
    else:
        assert not layout.startswith("{2,1,0"), layout
        assert made == [("copy", True)]


def test_run_scanned_pallas_association_compiles_for_v5e(config_shapes,
                                                         monkeypatch):
    """The fused scoring and SIC kernels inside the round program."""
    monkeypatch.setattr(hfl_ops, "_on_cpu", lambda: False)
    spec = dataclasses.replace(
        engine.EngineSpec(policy="fcea", scheduler="pdd"),
        pallas_score=True, sic_impl="pallas")
    assert KERNEL in _compile_run_scanned(spec, config_shapes, rounds=2)
