"""Deferred-acceptance sweeps a round, the resolver's own counter
(``RoundTrace.assoc_sweeps``), from one untimed telemetry-on call after
the traced window (``bench/counters.py``)."""


def read(ctx):
    return ctx.get("assoc_sweeps")
