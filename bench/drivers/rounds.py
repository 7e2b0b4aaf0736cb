"""Synchronous rounds of the round engine, and the reference that follows
them (``reference.sync_rounds``): static scenario, synchronous engine, the
paper's fcea + ddpg + pdd pipeline."""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import numpy as np

from bench import cells, compare, counters, datagen, flops, reference


@functools.lru_cache(maxsize=None)
def _sync_rounds(radio, rounds: int, drop_half: bool, fleet: bool,
                 precision: str, seen: bool):
    """The reference's rounds, jitted once per shape of the run; ``seen``:
    whether it takes the compared run's z (``reference.sync_rounds``)."""
    run = functools.partial(reference.sync_rounds, r=radio, rounds=rounds,
                            drop_half=drop_half)
    if not seen:
        run = functools.partial(run, z_seen=None)
    return cells.traced_at(jax.vmap(run) if fleet else run, precision)


class Driver(cells.Cell):
    """Synchronous rounds: ``run_fleet_actors`` over ``lanes`` simulations
    (or ``run_scanned`` over one when ``lanes`` is null), each with its
    own seed-made actor, ``rounds_per_call`` rounds a call, the state
    chained from call to call.  The first call of set-up is the one the
    reference follows."""
    unit = "rounds"
    MODELS = {"policy": ("fcea",), "allocator": ("ddpg",),
              "scheduler": ("pdd",), "noma_enabled": (True,),
              "scenario": ("static",), "engine_mode": ("sync",),
              "faults": (None,), "telemetry": (False,)}
    FAULTS = ("unchanged", "half_batch", "answer", "bill")

    def setup(self):
        from repro.core import engine
        t = self.traffic
        self.lanes = t.get("lanes")
        self.rounds = int(t["rounds_per_call"])
        self.world = self.draw(datagen.root_key(self.seed))
        state, bundle = self.place(*cells.program_inputs(
            self.cfg, self.spec, self.world, self.lanes, self.seed))
        cfg, spec, rounds, actor = self.cfg, self.spec, self.rounds, \
            self.world.actor
        entry = (engine.run_scanned if self.lanes is None
                 else engine.run_fleet_actors)
        self.entry, self.bundle = entry, bundle
        self.fn = lambda s: entry(cfg, spec, s, bundle, rounds, actor)
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding), state)
        self.lower = lambda: entry.lower(cfg, spec, shapes, bundle, rounds,
                                         actor)
        self.ops_per_call = (self.lanes or 1) * self.rounds
        out, ms = self.fn(state)
        del state
        self.first = {"loss": ms.loss, "cost": ms.cost, "z": ms.z,
                      "staleness": out.staleness,
                      "params": out.global_params}
        self.state = out
        self.outs: List = []
        jax.block_until_ready(self.dispatch())   # every program warm
        self.outs = []

    def draw(self, root) -> datagen.World:
        """The cell's inputs and weights, drawn from the run's root key."""
        hidden = self.traffic["actor_hidden"]
        if self.lanes is None:
            return datagen.make_single(root, self.cfg, hidden)
        return datagen.make_fleet(root, self.cfg, self.lanes, hidden)

    def place(self, state, bundle):
        """The program's inputs as the entry takes them: as made."""
        return state, bundle

    def dispatch(self):
        self.state, ms = self.fn(self.state)
        self.outs.append((ms.loss, ms.cost))
        return ms.cost

    def drop_last(self):
        self.outs.pop()

    def failed(self) -> int:
        bad = 0
        for loss, cost in self.outs:
            ok = np.isfinite(np.asarray(loss)) & np.isfinite(np.asarray(cost))
            bad += int(np.sum(~ok))
        return bad

    def release(self):
        self.state = self.fn = self.outs = self.bundle = None

    def counters(self):
        """``assoc_sweeps``: deferred-acceptance sweeps a round, from one
        telemetry-on call of the window's entry from its last state."""
        return {"assoc_sweeps": counters.assoc_sweeps(
            self.entry, self.cfg, self.spec, self.state, self.bundle,
            self.rounds, self.world.actor)}

    def hlo_text(self) -> str:
        return self.lower().compile().as_text()

    def program_first(self) -> Dict:
        return self._lanes(cells.host(self.first))

    def _lanes(self, tree):
        if self.lanes is None:
            return jax.tree.map(lambda a: np.asarray(a)[None], tree)
        return tree

    def reference_run(self, drop_half=False, precision="highest",
                      z_seen=None) -> Dict:
        """The reference's rounds; ``z_seen`` (lanes, R, M): the schedule
        of the run it is compared with, followed only where its own is
        undecided (``reference.sync_rounds``)."""
        w = self.world
        args = (w.key, w.params, w.gains, w.dist, w.x, w.y, w.counts,
                w.test_x, w.test_y, w.actor)
        if z_seen is not None:
            z_seen = np.asarray(z_seen, np.float32)
            args += (z_seen[0] if self.lanes is None else z_seen,)
        out = _sync_rounds(self.radio, self.rounds, drop_half,
                           self.lanes is not None, precision,
                           z_seen is not None)(*args)
        out = cells.host(out)
        return self._lanes({"loss": out.loss, "cost": out.cost, "z": out.z,
                            "staleness": out.staleness,
                            "params": out.params, "followed": out.followed})

    def check(self, modes=("program",)) -> Dict[str, Dict[str, float]]:
        """The program's first call against the reference, which follows
        its z where its own is undecided.  Faults: "unchanged" (the call
        returns its model as it was), "half_batch" (the reference's SGD on
        half of each minibatch), "answer" (one lane's schedule z flipped
        at one edge in the first round) and "bill" (one lane's first
        Eq. 23a bill 1% high)."""
        init = self._lanes(cells.host(self.world.params))
        refs = {}
        out = {}
        for mode in modes:
            if mode == "control":
                prog = self.reference_run(**self.control)
            elif mode == "half_batch":
                prog = self.reference_run(drop_half=True)
            else:
                prog = self.program_first()
            if mode == "unchanged":
                prog = dict(prog, params=init)
            elif mode == "answer":
                z = np.array(prog["z"])
                z[0, 0, 0] = 1 - z[0, 0, 0]
                prog = dict(prog, z=z)
            elif mode == "bill":
                cost = np.array(prog["cost"])
                cost[0, 0] *= 1.01
                prog = dict(prog, cost=cost)
            seen = np.asarray(prog["z"], np.float32)
            key = seen.tobytes()
            if key not in refs:
                refs[key] = self.reference_run(z_seen=seen)
            out[mode] = compare.rounds_numbers(prog, refs[key], init)
        return out

    def flops_per_op(self):
        return flops.round_flops(self.cfg, self.radio.tau1, self.radio.tau2,
                                 datagen.TEST_SAMPLES)

    def train_counts(self):
        return (flops.train_flops(self.cfg, self.radio.tau1, self.radio.tau2),
                flops.train_bytes(self.cfg, self.radio.tau1, self.radio.tau2))
