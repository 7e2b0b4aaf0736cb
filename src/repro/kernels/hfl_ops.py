"""Pallas kernels for the HFL round hot path (DESIGN.md §8.2).

Fused kernels following the ``kernels/ops.py`` contract — interpret mode
on CPU (this container), compiled on a real TPU target, with pure-jnp
references (``repro.core.fuzzy.score_matrix`` and the pairwise
``repro.core.noma.sic_sinr``) that the parity tests pin:

* ``score_matrix`` — the fuzzy competency scoring of §III as ONE kernel
  per row block: triangular memberships, the 27-rule Mamdani table and
  centre-of-gravity defuzzification are fused over a block of (client,
  edge) rows, so neither the (N, M, 27) rule-strength tensor nor the
  (N, M, 201, 5) clipped-output tensor ever exists in HBM — VMEM holds
  one (201, 5, block) slab at a time.
* ``sic_rates`` — all M edges' NOMA SIC rates in ONE ``pallas_call``:
  grid (M, N/bI, N/bJ) with the j-axis innermost; each (edge, i-block)
  accumulates its cumulative interference Σ_{weaker j} p_j·|h_j|² across
  the j sweep in VMEM scratch, so the (N, N) "who is decoded after whom"
  comparison matrix is never materialised (the jnp pairwise form writes
  it out per edge — 2 GB of temps at 4096×32).  The weaker-than order is
  the same (received power, client index) order as ``noma.sic_sinr`` and
  the sorted ``noma.sic_rates_matrix``, so all three agree up to float
  summation order.

All are wired into ``engine.round_step`` behind ``EngineSpec`` toggles
(``pallas_score`` / ``sic_impl="pallas"``); the jnp paths stay the
default on CPU where interpret mode would only add overhead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fuzzy


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# Fused fuzzy scoring
# ---------------------------------------------------------------------------

# Static rule geometry.  Pallas kernels may not capture ARRAY constants,
# so triangles unroll to python-scalar literals at trace time and the CoG
# grid rides in as a replicated input block.
_RULES_FLAT = [int(r) for r in np.asarray(fuzzy.RULES).reshape(-1)]
_IN_TRIS = np.asarray(fuzzy._IN_TRIS).tolist()    # 3 × (a, b, c)
_OUT_TRIS = np.asarray(fuzzy._OUT_TRIS).tolist()  # 5 × (a, b, c)
_GRID = np.asarray(fuzzy._COG_GRID, np.float32)   # (201,)


def _tri_scalar(v: jnp.ndarray, abc) -> jnp.ndarray:
    """Membership of values ``v`` in ONE (a, b, c) triangle (scalar args
    inline as literals — no captured constants)."""
    a, b, c = abc
    up = (v - a) / max(b - a, 1e-9)
    down = (c - v) / max(c - b, 1e-9)
    return jnp.clip(jnp.minimum(up, down), 0.0, 1.0)


def _score_kernel(cq_ref, dq_ref, ms_ref, grid_ref, out_ref):
    cq, dq, ms = cq_ref[0], dq_ref[0], ms_ref[0]               # (R,)
    m_cq = [_tri_scalar(cq, t) for t in _IN_TRIS]              # 3 × (R,)
    m_dq = [_tri_scalar(dq, t) for t in _IN_TRIS]
    m_ms = [_tri_scalar(ms, t) for t in _IN_TRIS]
    # Max–Min inference, unrolled over the static 27-rule table and folded
    # straight into the 5 output-set strengths — the (R, 27) rule tensor
    # never exists, even in VMEM
    deg = [jnp.minimum(jnp.minimum(m_cq[i], m_dq[j]), m_ms[k])
           for i in range(3) for j in range(3) for k in range(3)]
    strengths = []
    for s in range(5):
        terms = [deg[r] for r in range(27) if _RULES_FLAT[r] == s]
        acc = terms[0]
        for t in terms[1:]:
            acc = jnp.maximum(acc, t)
        strengths.append(acc)
    strengths = jnp.stack(strengths)                           # (5, R)
    # Mamdani clip + aggregate + CoG over the 201-point output grid
    g = grid_ref[0]                                            # (G,)
    mu = jnp.stack([_tri_scalar(g, t) for t in _OUT_TRIS])     # (5, G)
    clipped = jnp.minimum(mu[:, :, None], strengths[:, None, :])
    agg = jnp.max(clipped, axis=0)                             # (G, R)
    num = jnp.sum(g[:, None] * agg, axis=0)
    den = jnp.maximum(jnp.sum(agg, axis=0), 1e-9)
    out_ref[0] = num / den


def _score_rows(cq: jnp.ndarray, dq: jnp.ndarray, ms: jnp.ndarray,
                block_r: int, interp: bool) -> jnp.ndarray:
    """The fused fuzzy pipeline over flat rows: (R,) cq/dq/ms -> (R,)
    NO* scores.  Shared by the dense (N·M) and candidate (N·K) callers —
    the kernel is row-shape-agnostic, only the gather differs."""
    rows = cq.shape[0]
    block_r = min(block_r, max(rows, 1))
    padded = -(-rows // block_r) * block_r
    flat = [jnp.pad(v, (0, padded - rows)).reshape(1, padded).astype(
        jnp.float32) for v in (cq, dq, ms)]
    spec = pl.BlockSpec((1, block_r), lambda i: (0, i))
    grid_spec = pl.BlockSpec((1, _GRID.size), lambda i: (0, 0))
    out = pl.pallas_call(
        _score_kernel,
        grid=(padded // block_r,),
        in_specs=[spec, spec, spec, grid_spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((1, padded), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interp,
    )(*flat, jnp.asarray(_GRID).reshape(1, -1))
    return out[0, :rows]


@functools.partial(jax.jit,
                   static_argnames=("data_max", "block_r", "interpret"))
def score_matrix(gains: jnp.ndarray, counts: jnp.ndarray,
                 staleness: jnp.ndarray, *, data_max: float,
                 block_r: int = 512,
                 interpret: bool | None = None) -> jnp.ndarray:
    """Drop-in for ``fuzzy.score_matrix`` — (N, M) competency scores.

    The Eq. 21 normalisation (global dB min/max reductions) runs as plain
    XLA; the per-row fuzzy pipeline runs as the fused kernel over the
    flattened (N·M,) rows.
    """
    interp = _on_cpu() if interpret is None else interpret
    cq, dq, ms = fuzzy.normalized_inputs(gains, counts, staleness,
                                         data_max=data_max)
    n, m = cq.shape
    flat = _score_rows(cq.reshape(-1),
                       jnp.broadcast_to(dq[:, None], (n, m)).reshape(-1),
                       jnp.broadcast_to(ms[:, None], (n, m)).reshape(-1),
                       block_r, interp)
    return flat.reshape(n, m)


@functools.partial(jax.jit,
                   static_argnames=("data_max", "block_r", "interpret"))
def score_candidates(gains: jnp.ndarray, cand_idx: jnp.ndarray,
                     counts: jnp.ndarray, staleness: jnp.ndarray, *,
                     data_max: float, block_r: int = 512,
                     interpret: bool | None = None) -> jnp.ndarray:
    """Gathered-candidate variant of ``score_matrix`` (DESIGN.md §9):
    drop-in for ``fuzzy.score_candidates`` — (N, K) competency scores for
    the candidate frontier ``cand_idx`` only.

    Same global Eq. 21 normalisation as the dense kernel (so each score
    is bit-compatible with the dense matrix entry at the same pair), but
    the fused Mamdani/CoG kernel sweeps N·K flattened rows instead of
    N·M — the pruned pairs never reach the kernel grid.
    """
    interp = _on_cpu() if interpret is None else interpret
    cq, dq, ms = fuzzy.normalized_inputs(gains, counts, staleness,
                                         data_max=data_max)
    n, k = cand_idx.shape
    cq_k = jnp.take_along_axis(cq, cand_idx, axis=1)
    flat = _score_rows(cq_k.reshape(-1),
                       jnp.broadcast_to(dq[:, None], (n, k)).reshape(-1),
                       jnp.broadcast_to(ms[:, None], (n, k)).reshape(-1),
                       block_r, interp)
    return flat.reshape(n, k)


# ---------------------------------------------------------------------------
# Fused NOMA SIC rates
# ---------------------------------------------------------------------------

def _sic_kernel(pi_ref, gi_ref, mi_ref, pj_ref, gj_ref, mj_ref, out_ref,
                intf_ref, *, block_i: int, block_j: int, noise_w: float,
                bandwidth_hz: float):
    ii = pl.program_id(1)
    ij = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(ij == 0)
    def _init():
        intf_ref[...] = jnp.zeros_like(intf_ref)

    rx_i = pi_ref[0] * gi_ref[0, 0] * mi_ref[0, 0]             # (bI,)
    rx_j = pj_ref[0] * gj_ref[0, 0] * mj_ref[0, 0]             # (bJ,)
    i_pos = ii * block_i + jax.lax.broadcasted_iota(
        jnp.int32, (block_i, block_j), 0)
    j_pos = ij * block_j + jax.lax.broadcasted_iota(
        jnp.int32, (block_i, block_j), 1)
    # decoded after me ⇔ strictly weaker received power, index tie-break —
    # the exact ``noma.sic_sinr`` order
    weaker = (rx_j[None, :] < rx_i[:, None]) | \
        ((rx_j[None, :] == rx_i[:, None]) & (j_pos > i_pos))
    intf_ref[...] += jnp.sum(jnp.where(weaker, rx_j[None, :], 0.0), axis=1)

    @pl.when(ij == nj - 1)
    def _finish():
        sinr = rx_i / (intf_ref[...] + noise_w)
        out_ref[0, 0] = bandwidth_hz * jnp.log2(1.0 + sinr) * mi_ref[0, 0]


@functools.partial(jax.jit, static_argnames=("bandwidth_hz", "noise_w",
                                             "block_n", "interpret"))
def sic_rates(power_w: jnp.ndarray, gains: jnp.ndarray, mask: jnp.ndarray,
              *, bandwidth_hz: float, noise_w: float, block_n: int = 256,
              interpret: bool | None = None) -> jnp.ndarray:
    """(N,) power, (N, M) gains, (N, M) mask -> (N, M) SIC rates; masked
    entries are zero.  One ``pallas_call`` covers every edge."""
    interp = _on_cpu() if interpret is None else interpret
    n, m = gains.shape
    block_n = min(block_n, n)
    padded = -(-n // block_n) * block_n
    pad = padded - n
    p = jnp.pad(power_w.astype(jnp.float32), (0, pad)).reshape(1, padded)
    # per-edge columns travel edge-major as (M, 1, N) rows, so every block's
    # last two dims are (1, block_n) — a layout Mosaic accepts; an
    # (N, M) array sliced into (block_n, 1) columns is refused
    g, mk = (jnp.pad(a.astype(jnp.float32).T, ((0, 0), (0, pad))).reshape(
        m, 1, padded) for a in (gains, mask))
    nb = padded // block_n

    kernel = functools.partial(_sic_kernel, block_i=block_n,
                               block_j=block_n, noise_w=noise_w,
                               bandwidth_hz=bandwidth_hz)
    p_i = pl.BlockSpec((1, block_n), lambda e, i, j: (0, i))
    p_j = pl.BlockSpec((1, block_n), lambda e, i, j: (0, j))
    row_i = pl.BlockSpec((1, 1, block_n), lambda e, i, j: (e, 0, i))
    row_j = pl.BlockSpec((1, 1, block_n), lambda e, i, j: (e, 0, j))
    out = pl.pallas_call(
        kernel,
        grid=(m, nb, nb),
        in_specs=[p_i, row_i, row_i, p_j, row_j, row_j],
        out_specs=row_i,
        out_shape=jax.ShapeDtypeStruct((m, 1, padded), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_n,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interp,
    )(p, g, mk, p, g, mk)
    return out[:, 0, :n].T
