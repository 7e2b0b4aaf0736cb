"""Synchronous rounds of a whole client population with its client axis
split over a 1-D ``("clients",)`` mesh of the chips the harness gave the
cell: the program's ``run_scanned`` on inputs placed by
``engine.shard_clients``, which is the path of
``engine.run_scanned_client_sharded`` once the population divides the
mesh (no inert clients are padded in).

Only the draw of the world and the placement of the program's inputs
differ from ``rounds.Driver``; its entry, reference
(``reference.sync_rounds``, run on the same sharded inputs), planted
faults, ``MODELS`` and ``check`` are used as they are, with one fault
more that only a split client axis can have: the exchange between chips
left out ("no_exchange").  The draw never lets a chip hold the whole
padded client data: ``x`` is drawn shard by shard, each chip its own
clients, by ``datagen``'s per-client draw from the same keys, templates,
labels and padding, so that the world equals ``datagen``'s bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import cells, compare, datagen
from bench.drivers import rounds

# the leaves of ``datagen.World`` whose first axis is the client axis
CLIENT_AXIS = ("clients", "dist", "x", "y", "counts", "gains")


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def sharded_world(root, cfg, actor_hidden: int, mesh) -> datagen.World:
    """``datagen.make_single(root, cfg, actor_hidden)``'s world with its
    client-axis leaves split over ``mesh`` and the rest replicated; ``x``
    is drawn under ``shard_map``, each device its own N / devices
    clients, so that no device holds more."""
    parts = jax.tree.map(lambda a: a[0], datagen.lane_parts(
        root, cfg, 1, actor_hidden))
    templates, y, valid, x_keys = parts[-4:]
    split = NamedSharding(mesh, P("clients"))
    rep = NamedSharding(mesh, P())

    def shard(tmpl, kd, yy, vv):
        return jax.lax.map(
            lambda a: datagen.client_x(jax.random.wrap_key_data(a[0]), tmpl,
                                       a[1], a[2], cfg),
            (kd, yy, vv), batch_size=min(64, kd.shape[0]))

    y, valid, kd = (jax.lax.with_sharding_constraint(a, split)
                    for a in (y, valid, jax.random.key_data(x_keys)))
    x = jax.shard_map(
        shard, mesh=mesh,
        in_specs=(P(), P("clients"), P("clients"), P("clients")),
        out_specs=P("clients"))(templates, kd, y, valid)
    w = datagen.world_of(parts, x)
    return w._replace(**{f: jax.lax.with_sharding_constraint(
        getattr(w, f), split if f in CLIENT_AXIS else rep)
        for f in datagen.World._fields if f != "x"})


def client_mesh(n_clients: int, devices):
    """The program's ``("clients",)`` mesh over ``devices``; refuses a
    single device (no exchange between chips to measure) and a population
    the mesh does not divide, which the program would pad with inert
    clients (another experiment)."""
    from repro.core import engine
    if len(devices) < 2:
        raise ValueError(f"the client axis is split over more than one "
                         f"device; the cell was given {len(devices)}")
    if n_clients % len(devices):
        raise ValueError(f"{n_clients} clients do not split evenly over "
                         f"{len(devices)} devices")
    return engine.client_mesh(devices)


@functools.partial(jax.jit, static_argnums=1)
def _first_shard_only(x, shards: int):
    """``x`` with every client outside the first of ``shards`` equal shards
    holding zero samples (as the first chip sees the data when the
    exchange between chips is left out); split as ``x`` is."""
    n = x.shape[0]
    mine = jnp.arange(n) < n // shards
    return jnp.where(mine[:, None, None], x, 0.0)


def _shapes(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=a.sharding), tree)


class Driver(rounds.Driver):
    """``rounds.Driver`` over one simulation whose client axis is split
    over the cell's chips; the traffic's ``lanes`` must be null.  The state
    is chained from call to call with the shardings the program gives it
    back, which must be those it was given."""
    FAULTS = rounds.Driver.FAULTS + ("no_exchange",)

    def draw(self, root) -> datagen.World:
        if self.lanes is not None:
            raise ValueError("the client-sharded driver runs one simulation "
                             "(lanes null)")
        self.mesh = client_mesh(self.cfg.n_clients, self.devices)
        return sharded_world(root, self.cfg, self.traffic["actor_hidden"],
                             self.mesh)

    def place(self, state, bundle):
        from repro.core import engine
        state, bundle = engine.shard_clients(state, bundle, self.mesh)
        self.given = _shapes(state)
        return state, bundle

    def setup(self):
        super().setup()
        same = jax.tree.map(
            lambda a, b: a.sharding.is_equivalent_to(b.sharding, a.ndim),
            self.given, _shapes(self.state))
        if not all(jax.tree.leaves(same)):
            raise RuntimeError("the program returns its state with other "
                               "shardings than it was given: the window's "
                               "calls would compile another program")

    def check(self, modes=("program",)):
        """``rounds.Driver.check``, and the fault "no_exchange": the
        reference in the program's place, trained on the data the first
        chip would hold were the exchange between chips left out (the
        other chips' clients' samples never arrive: zero rows), so that
        the cohort's members outside the first shard train on nothing."""
        out = super().check(tuple(m for m in modes if m != "no_exchange"))
        if "no_exchange" in modes:
            whole = self.world
            self.world = whole._replace(
                x=_first_shard_only(whole.x, len(self.devices)))
            try:
                prog = self.reference_run()
            finally:
                self.world = whole
            init = self._lanes(cells.host(whole.params))
            ref = self.reference_run(z_seen=np.asarray(prog["z"]))
            out["no_exchange"] = compare.rounds_numbers(prog, ref, init)
        return out
