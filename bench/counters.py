"""Counters the round program keeps in its ``RoundTrace``, read by one
untimed call after a traced window.

``EngineSpec.telemetry=True`` compiles another program (the trace rides
the scan's outputs), so the counters cannot come from the window itself:
the call below runs the cell's entry once more, telemetry on, from the
state the window left.  It is made after the window's compiles are
counted, and nothing of it is timed.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def assoc_sweeps(cell) -> float:
    """Mean deferred-acceptance sweeps a round, over lanes and rounds, of
    one telemetry-on call of a rounds cell's entry (``run_scanned`` or
    ``run_fleet_actors``) from the cell's current state, on its inputs."""
    from repro.core import engine
    w = cell.world
    bundle = engine.RoundBundle(dist=w.dist, x=w.x, y=w.y, counts=w.counts,
                                test_x=w.test_x, test_y=w.test_y)
    spec = dataclasses.replace(cell.spec, telemetry=True)
    entry = (engine.run_scanned if cell.lanes is None
             else engine.run_fleet_actors)
    _, out = entry(cell.cfg, spec, cell.state, bundle, cell.rounds, w.actor)
    _, trace = engine.split_output(spec, out)
    return float(np.mean(np.asarray(trace.assoc_sweeps)))
