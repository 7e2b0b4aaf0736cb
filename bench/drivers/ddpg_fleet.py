"""The DDPG allocator's training (Algorithm 2) over a fleet of agents, and
the reference that replays one call of it (``reference.ddpg_train``)."""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import cells, compare, datagen, flops, reference


@functools.lru_cache(maxsize=None)
def _ddpg_train(radio, drop_half: bool, precision: str, episodes: int,
                steps_per_episode: int, warmup: int, hidden: int):
    """The reference's allocator training over lanes, jitted once; replay
    buffer and batch as the program's ``DDPGConfig`` defaults."""
    run = functools.partial(reference.ddpg_train, r=radio, episodes=episodes,
                            steps=steps_per_episode, warmup=warmup,
                            hidden=hidden, buffer_size=4096, batch=64,
                            drop_half=drop_half)
    return cells.traced_at(jax.vmap(run), precision)


@functools.partial(jax.jit, static_argnums=(2,))
def _call_keys(root, call, lanes: int):
    """One training key per lane for window call ``call``."""
    k = jax.random.fold_in(root, call)
    return jax.vmap(lambda i: jax.random.fold_in(k, i))(jnp.arange(lanes))


class Driver(cells.Cell):
    """``ddpg.train_allocator_fleet`` over ``lanes`` cells: each call
    trains a fresh agent per lane from a new key (as each ddpg cell of a
    sweep does) for ``episodes`` x ``steps_per_episode`` steps.  After the
    window one completed call, drawn from the seed, is replayed by the
    reference."""
    unit = "steps"
    MODELS = {"policy": ("fcea",), "allocator": ("ddpg",),
              "scheduler": ("pdd",), "noma_enabled": (True,),
              "scenario": ("static",), "engine_mode": ("sync",),
              "faults": (None,), "telemetry": (False,)}
    FAULTS = ("unchanged", "half_batch", "answer")

    def setup(self):
        from repro.core import ddpg
        t = self.traffic
        self.lanes = int(t["lanes"])
        self.opts = dict(episodes=int(t["episodes"]),
                         steps_per_episode=int(t["steps_per_episode"]),
                         warmup=int(t["warmup"]), hidden=int(t["hidden"]))
        self.ops_per_call = (self.lanes * self.opts["episodes"]
                             * self.opts["steps_per_episode"])
        root = datagen.root_key(self.seed)
        # the trainer reads no client data: the fleet is drawn with the
        # data-free layout of the same world
        self.world = datagen.make_fleet(root, self.cfg, self.lanes,
                                        t["hidden"], with_data=False)
        state, bundle = cells.program_inputs(self.cfg, self.spec, self.world,
                                             self.lanes, self.seed)
        cfg, spec, opts = self.cfg, self.spec, self.opts

        @jax.jit
        def train(state, bundle, keys):
            agent, hist = ddpg.train_allocator_fleet(
                cfg, spec, state, bundle, None, keys, **opts)
            nets = {"actor": agent.actor, "critic": agent.critic,
                    "target_actor": agent.target_actor,
                    "target_critic": agent.target_critic}
            return nets, hist["episode_reward"]

        self.train = functools.partial(train, state, bundle)
        self.train_root = jax.random.fold_in(root, 0x7D9A)
        self.calls = 0
        self.kept: List = []
        jax.block_until_ready(self.dispatch())     # compile and warm
        jax.block_until_ready(self.dispatch())
        self.kept = []

    def keys(self, call: int):
        return _call_keys(self.train_root, call, self.lanes)

    def dispatch(self):
        self.calls += 1
        nets, reward = self.train(self.keys(self.calls))
        self.kept.append((self.calls, nets, reward))
        return reward

    def drop_last(self):
        self.kept.pop()

    def failed(self) -> int:
        bad = 0
        for _, nets, reward in self.kept:
            ok = np.isfinite(np.asarray(reward)).all(axis=1)
            ok &= np.asarray(jax.tree.reduce(
                lambda a, b: a & b,
                jax.tree.map(lambda l: jnp.isfinite(l).reshape(
                    l.shape[0], -1).all(axis=1), nets)))
            bad += int(np.sum(~ok)) * self.ops_per_call // self.lanes
        return bad

    def release(self):
        self.train = None

    def sample(self) -> int:
        """The completed window call the reference replays (from the seed)."""
        rng = np.random.default_rng([self.seed, 0x7D9A])
        return int(rng.integers(len(self.kept)))

    def reference_run(self, call: int, drop_half=False,
                      precision="highest") -> Dict:
        w = self.world
        out = _ddpg_train(self.radio, drop_half, precision, **self.opts)(
            self.keys(call), w.gains, w.dist, w.counts)
        return {"reward": cells.host(out.episode_reward),
                "nets": cells.host(out.nets), "init": cells.host(out.init)}

    def check(self, modes=("program",)) -> Dict[str, Dict[str, float]]:
        """The sampled call of the window against the reference.  Faults:
        "unchanged" (the call returns its initial networks),
        "half_batch" (every update's means over half of its minibatch) and
        "answer" (one lane's first episode reward off by 1%)."""
        if not self.kept:
            raise RuntimeError("no completed call to compare")
        call, nets, reward = self.kept[self.sample()]
        ref = self.reference_run(call)
        first = (self.opts["warmup"] - 1) // self.opts["steps_per_episode"]
        out = {}
        for mode in modes:
            if mode in ("control", "half_batch"):
                ctl = (self.reference_run(call, **self.control)
                       if mode == "control"
                       else self.reference_run(call, drop_half=True))
                prog = {"reward": ctl["reward"], "nets": ctl["nets"]}
            else:
                prog = {"reward": np.asarray(reward), "nets": cells.host(nets)}
            if mode == "unchanged":
                prog = dict(prog, nets=ref["init"])
            elif mode == "answer":
                r = np.array(prog["reward"], np.float64)
                r[0, 0] *= 1.01
                prog = dict(prog, reward=r)
            out[mode] = compare.ddpg_numbers(prog, ref, first)
        return out

    def flops_per_op(self):
        o = self.opts
        per_call = flops.ddpg_call_flops(
            self.cfg.n_clients, o["hidden"], 64, o["episodes"],
            o["steps_per_episode"], o["warmup"])
        return per_call / (o["episodes"] * o["steps_per_episode"])
