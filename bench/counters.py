"""Counters the round program keeps in its ``RoundTrace``, read by one
untimed call after a traced window (a driver's ``counters()``).

``EngineSpec.telemetry=True`` compiles another program (the trace rides
the scan's outputs), so the counters cannot come from the window itself:
the call below runs the cell's entry once more, telemetry on, from the
state the window left.  It is made after the window's compiles are
counted, and nothing of it is timed.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def assoc_sweeps(entry, cfg, spec, state, bundle, *args) -> float:
    """Mean deferred-acceptance sweeps a round, over lanes and rounds, of
    one telemetry-on call of a rounds entry (``run_scanned`` or
    ``run_fleet_actors``) from ``state`` on the program's ``bundle`` as
    the driver built and placed it; ``args`` follow the bundle in the
    entry's signature."""
    from repro.core import engine
    spec = dataclasses.replace(spec, telemetry=True)
    _, out = entry(cfg, spec, state, bundle, *args)
    _, trace = engine.split_output(spec, out)
    return float(np.mean(np.asarray(trace.assoc_sweeps)))
