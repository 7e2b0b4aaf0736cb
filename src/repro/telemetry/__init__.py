"""In-scan round telemetry (DESIGN.md §10).

Three pieces, all gated by the static ``EngineSpec.telemetry`` flag so the
disabled path is structurally absent from the engine's programs:

* ``trace``  — the ``RoundTrace`` pytree of per-stage observables riding
  the scan outputs next to ``RoundMetrics`` (Eq. 23a cost decomposition,
  association/scheduler internals, SIC decode depth, staleness histogram);
* ``sink``   — host-side sinks (JSONL, in-memory) fed from inside the
  jitted drivers via ``jax.debug.callback``, plus the pure collect mode;
* ``spans``  — the ``jax.named_scope``s around the paper stages and their
  parts, which only change the compiled module's ``op_name`` metadata.
  ``bench/tracing.py`` reduces a chip trace's ops by stage through that
  metadata, and ``bench/parts.py`` by part.

``sink`` imports the engine, so it is NOT re-exported here (the engine
imports ``trace``/``spans``); import it explicitly::

    from repro.telemetry import sink
"""
from repro.telemetry import spans, trace
from repro.telemetry.trace import RoundTrace, STALE_BIN_EDGES, round_trace

__all__ = ["RoundTrace", "STALE_BIN_EDGES", "round_trace", "spans", "trace"]
