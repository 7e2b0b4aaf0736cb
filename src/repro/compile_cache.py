"""Where JAX's persistent compilation cache lives.

The program entry points (``chip_smoke.py``, ``examples/hfl_mnist_train.py``,
``python -m benchmarks.run``, ``python -m repro.sweeps.grid``) call
``enable()`` once, before their first compile.  Library modules never call
it on import, and the tests never call it: a compile for a described chip
that is not attached writes entries no later run can read back.

The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set, and a fixed
``<repo>/.jax_cache`` otherwise.  The path is part of what a later run has to
find again, so it is never built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir() -> str:
    """The cache directory ``enable()`` points JAX at."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
