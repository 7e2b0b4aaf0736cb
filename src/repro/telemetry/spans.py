"""Named scopes for the paper stages and their parts (DESIGN.md §10.3).

``stage(name)`` wraps a round stage, and ``part(name)`` a piece of one, in
a ``jax.named_scope``.  The scope lands in the ``op_name`` metadata of the
compiled module, which is all it changes: the computation, and hence every
golden trajectory, stays as it was.  ``bench/tracing.py`` joins a device
trace's ops with that metadata and reduces their time per stage, and
``bench/parts.py`` per part.

Part names must not be a stage name or a JAX primitive name (``gather``,
``scatter``, ``while``, ``dot_general`` …): a scope path ends in the
primitive's name, so a part called ``gather`` could not be told from the
primitive.  ``round_loop`` names the scan over rounds and sits under no
stage; every other part sits under one stage.
"""
from __future__ import annotations

import jax

STAGES = ("associate", "allocate", "schedule", "train", "eval")
PARTS = ("cohort", "sgd", "agg", "pdd", "round_loop")


def stage(name: str):
    """Scope a paper stage (one of ``STAGES``)."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; known: {STAGES}")
    return jax.named_scope(name)


def part(name: str):
    """Scope a part of a stage, or the round loop (one of ``PARTS``)."""
    if name not in PARTS:
        raise ValueError(f"unknown part {name!r}; known: {PARTS}")
    return jax.named_scope(name)
