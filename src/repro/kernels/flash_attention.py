"""Flash attention Pallas-TPU kernel (causal / sliding-window, GQA).

TPU adaptation of the paper-era flash algorithm (DESIGN.md §3: the HFL paper
itself has no kernel — this serves the substrate's big-model hot spot):

* grid = (B, H, nQ, nK) with the K-block axis innermost ("arbitrary"
  dimension semantics): the online-softmax state for one (b, h, q-block)
  lives in VMEM scratch across the nK sweep, so the (S, S) score matrix
  never exists and HBM traffic is O(S·D) per head.
* BlockSpecs tile Q/O as (1, 1, block_q, D) and K/V as (1, 1, block_k, D)
  in VMEM; the K/V index map folds the GQA group so Q head h reads KV head
  h // (H // KV) — MQA/GQA need no materialised head broadcast.
* block_q/block_k default to 128/256 — multiples of the 128-lane MXU tile
  for D ∈ {64, 128, 256}.
* Causal masking is positional inside the block; fully-above-diagonal
  K-blocks short-circuit (``@pl.when``) so the causal sweep does ~half the
  work, and sliding-window masking likewise skips blocks left of the window.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e38


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 block_q: int, block_k: int, seq_len: int, causal: bool,
                 window: int, scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_k

    # Block-level skip: causal blocks entirely above the diagonal and
    # sliding-window blocks entirely left of the window contribute nothing.
    run = True
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window:
        run = jnp.logical_and(run, k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
        allowed = k_pos < seq_len
        if causal:
            allowed = jnp.logical_and(allowed, k_pos <= q_pos)
        if window:
            allowed = jnp.logical_and(allowed, k_pos > q_pos - window)
        s = jnp.where(allowed, s, NEG_INF)

        m_prev = m_ref[:, 0]                                 # (bq,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_cur[:, None])
        p = jnp.where(allowed, p, 0.0)
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[:, 0] = m_cur

    @pl.when(ik == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 256,
                    interpret: bool = False) -> jnp.ndarray:
    """q (B, H, S, D), k/v (B, KV, S, D) -> (B, H, S, D).

    S must be a multiple of max(block_q, block_k); D should be a multiple
    of 128 on real TPUs (any D works in interpret mode).
    """
    b, h, s, d = q.shape
    kv = k.shape[1]
    group = h // kv
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    nq = s // block_q
    nk = s // block_k
    grid = (b, h, nq, nk)

    kernel = functools.partial(
        _attn_kernel, block_q=block_q, block_k=block_k, seq_len=s,
        causal=causal, window=window, scale=d ** -0.5)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, iq, ik, group=group:
                         (b_, h_ // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, iq, ik, group=group:
                         (b_, h_ // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
