"""What every driver shares: the program's config and spec from a
configuration file, the program's inputs for a generated world, and the
``Cell`` interface the harness drives.

A traffic file (``traffic/<mix>.json``) names its driver under
``"driver"``; the driver is the ``Driver`` class of
``drivers/<driver>.py``, found by that name.  A driver builds the cell's
inputs from the seed, warms the program's entry at the cell's shapes, hands
the window one call at a time, and afterwards runs the reference over what
the timed entry produced.  This module and the drivers are the only code
that knows the program's API.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import datagen, reference


def hfl_config(conf: Dict):
    """The program's config object from a configuration file: every field
    of ``HFLConfig`` that the file names at its top level."""
    from repro.configs.hfl_mnist import HFLConfig
    names = {f.name for f in dataclasses.fields(HFLConfig)}
    missing = names - set(conf)
    if missing:
        raise KeyError(f"configuration {conf.get('name')!r} lacks "
                       f"{sorted(missing)}")
    return HFLConfig(**{k: conf[k] for k in names})


def engine_spec(conf: Dict, traffic: Dict):
    from repro.core import engine
    return engine.EngineSpec(**conf["pipeline"], **traffic.get("engine", {}))


def host(tree):
    return jax.tree.map(np.asarray, tree)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _replicate(params, n: int, lanes: Optional[int]):
    if lanes is None:
        return jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (n,) + l.shape), params)
    return jax.tree.map(
        lambda l: jnp.broadcast_to(l[:, None], (lanes, n) + l.shape[1:]),
        params)


def program_inputs(cfg, spec, w: datagen.World, lanes: Optional[int],
                   seed: int):
    """The program's (RoundState, RoundBundle) for the generated world: the
    round state of a fresh simulation, its scenario state made by the
    program's own initialiser for ``spec.scenario`` (host draws from the
    seed), stacked over lanes under a fleet."""
    from repro import scenarios
    from repro.core import engine
    sspec = scenarios.preset(spec.scenario)

    def scen(i):
        pick = (lambda a: np.asarray(a)) if i is None else \
            (lambda a: np.asarray(a[i]))
        topo = {"clients": pick(w.clients), "edges": pick(w.edges),
                "dist": pick(w.dist)}
        rng = np.random.default_rng([seed % 2 ** 64, i or 0])
        return scenarios.init_scenario(cfg, sspec, rng, topo)

    if lanes is None:
        sc = scen(None)
        lead = ()
    else:
        sc = jax.tree.map(lambda *ls: jnp.stack(ls),
                          *[scen(i) for i in range(lanes)])
        lead = (lanes,)
    state = engine.RoundState(
        global_params=w.params,
        client_params=_replicate(w.params, cfg.n_clients, lanes),
        gains=w.gains,
        staleness=jnp.ones(lead + (cfg.n_clients,), jnp.int32),
        key=w.key,
        round_idx=jnp.zeros(lead, jnp.int32),
        scenario=sc)
    bundle = engine.RoundBundle(dist=w.dist, x=w.x, y=w.y, counts=w.counts,
                                test_x=w.test_x, test_y=w.test_y)
    return state, bundle


def traced_at(fn, precision: str):
    """``fn`` jitted, its matrix products traced at ``precision``."""
    jitted = jax.jit(fn)

    def call(*args):
        with reference.matmul_precision(precision):
            return jitted(*args)
    return call


class Cell:
    """What the harness needs of a driver.

    ``MODELS`` names, per ``EngineSpec`` field, the values the driver's
    reference models; a cell whose configuration or traffic states another
    is refused before anything runs, since its reference would compare the
    program with something else.  ``FAULTS`` are the planted faults the
    checks must catch."""
    unit = ""                  # what one operation is: "rounds" | "steps"
    ops_per_call = 0
    MODELS: Dict[str, tuple] = {}
    FAULTS: tuple = ()

    def __init__(self, conf: Dict, traffic: Dict, seed: int, devices=()):
        """``conf``: the configuration file as loaded, kept whole as
        ``self.conf``; the program's config comes from ``config_of``, its
        ``EngineSpec`` from the configuration's pipeline and the traffic's
        ``engine``, and the control (keyword arguments of
        ``reference_run``, such as ``precision``) from ``conf["control"]``.
        ``devices``: the chips the harness gave the cell (its ``chips`` in
        ``BENCHMARK.json``)."""
        self.conf = conf
        cfg, spec = self.config_of(conf), engine_spec(conf, traffic)
        for field, allowed in self.MODELS.items():
            value = getattr(spec, field)
            if value not in allowed:
                raise ValueError(
                    f"{type(self).__module__}: the reference models "
                    f"{field} in {allowed}, not {value!r}")
        self.cfg, self.spec, self.traffic, self.seed = cfg, spec, traffic, seed
        self.control = conf["control"]
        self.devices = tuple(devices)
        self.radio = reference.radio_of(cfg, spec.fading_rho)

    @classmethod
    def config_of(cls, conf: Dict):
        """The program's config for the configuration file ``conf``: by
        default ``hfl_config``, which needs every ``HFLConfig`` field at its
        top level.  A driver whose configuration describes its client model
        otherwise overrides this."""
        return hfl_config(conf)

    @classmethod
    def modes(cls) -> tuple:
        """The modes ``check`` reads: the program, its control, the faults."""
        return ("program", "control") + tuple(cls.FAULTS)

    def setup(self) -> None:
        raise NotImplementedError

    def dispatch(self):
        """Start one call; returns what to block on for its end."""
        raise NotImplementedError

    def drop_last(self) -> None:
        """Forget the last dispatched call (it ended outside the window)."""

    def failed(self) -> int:
        raise NotImplementedError

    def release(self) -> None:
        """Free the program's state before the reference runs."""

    def check(self, modes=("program",)) -> Dict[str, Dict[str, float]]:
        """Every reading of ``compare`` per mode of ``modes()``: "program";
        "control", the reference computed as ``control`` says in the
        program's place; and the planted faults of ``FAULTS``."""
        raise NotImplementedError

    def flops_per_op(self) -> float:
        raise NotImplementedError

    def hlo_text(self) -> str:
        """The compiled text of the program the window drives (for the
        scope paths of its ops), or "" where its ops carry none."""
        return ""

    def counters(self) -> Dict[str, float]:
        """The program's own counters for the per-layer readers' context,
        by name; called once after a traced window, untimed, before
        ``release``."""
        return {}

    def part_counts(self) -> Dict[str, tuple]:
        """``{"<stage>/<part>": (flops, bytes)}`` per operation (``unit``)
        of the work a part of the program must do, for roofline readers of
        that part's time (``part_s``)."""
        return {}
