"""Diagonal linear-recurrence Pallas-TPU kernel (RG-LRU / SSM scans).

Computes h_t = exp(log_a_t)·h_{t-1} + x_t along the time axis.  TPU
adaptation (DESIGN.md §3): the recurrence is *diagonal*, so channels are
embarrassingly parallel — we tile channels across the lane dimension
(block_c a multiple of 128) and the grid's parallel axes, and sweep time in
VMEM-resident blocks:

* grid = (B, nC, nT) with the time axis innermost ("arbitrary"): the carry
  h lives in a (1, block_c) VMEM scratch across the nT sweep.
* Inside a block the time loop is a `fori_loop` over block_t rows — a
  vector op per step on (block_c,) lanes, the idiomatic TPU shape for a
  scan that XLA would otherwise serialise badly.
* HBM traffic is exactly 2 reads + 1 write per element — the kernel is
  memory-bound by construction, matching the roofline analysis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _linrec_kernel(log_a_ref, x_ref, o_ref, h_ref, *, block_t: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, h):
        a = jnp.exp(log_a_ref[0, t, :].astype(jnp.float32))
        x = x_ref[0, t, :].astype(jnp.float32)
        h = a * h + x
        o_ref[0, t, :] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_t, step, h_ref[0, :])
    h_ref[0, :] = h


def linear_recurrence(log_a: jnp.ndarray, x: jnp.ndarray, *,
                      block_t: int = 256, block_c: int = 128,
                      interpret: bool = False) -> jnp.ndarray:
    """log_a, x: (B, S, C) -> h (B, S, C) fp32 carry, output in x.dtype."""
    b, s, c = x.shape
    block_t = min(block_t, s)
    block_c = min(block_c, c)
    assert s % block_t == 0 and c % block_c == 0, (s, c, block_t, block_c)
    grid = (b, c // block_c, s // block_t)

    kernel = functools.partial(_linrec_kernel, block_t=block_t)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, block_c),
                         lambda b_, ic, it: (b_, it, ic)),
            pl.BlockSpec((1, block_t, block_c),
                         lambda b_, ic, it: (b_, it, ic)),
        ],
        out_specs=pl.BlockSpec((1, block_t, block_c),
                               lambda b_, ic, it: (b_, it, ic)),
        out_shape=jax.ShapeDtypeStruct((b, s, c), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(log_a, x)
