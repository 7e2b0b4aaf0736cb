"""Plain reference of what the timed paths compute.

Written from the paper (arXiv 2311.02130 §II-§IV) and the simulator's
documented semantics, in straightforward ``jax.numpy``, importing nothing
of the program under test and taking nothing it made: every input is drawn
by ``datagen`` from the run's seed.

* ``sync_rounds``: R synchronous global rounds of one simulation — fading,
  fuzzy scoring (Table I, Eq. 21-22), edge-proposing deferred acceptance
  with nearest-edge conflict resolution, the DDPG actor's (p, f), the NOMA
  SIC bill (Eqs. 3-19, 23a), PDD edge scheduling (Alg. 1 with the M_c
  quota), tau2 x tau1 local SGD with edge and cloud aggregation (Eqs. 11,
  17), staleness (Eq. 20) and the test loss.  Of what the program made
  it may see one thing, the schedule z under comparison, and goes on with
  it only in a round whose own PDD choice a rounding of the bill can
  change (``pdd_undecided``), and only where it is a choice PDD can make.
* ``ddpg_train``: Algorithm 2 — episodes of act / environment step / replay
  store / critic and actor Adam updates / soft target updates, on the
  association of the initial state.

Every matrix product runs at ``Precision.HIGHEST`` (float32) unless
``matmul_precision`` asks for less while the reference is traced: the
control of ``compare`` traces it at "high".  Clients are handled
as an (edge, slot) table of at most ``quota`` members per edge, so memory
stays O(M * quota) in the training stage whatever N is.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import math
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# The precision of every matrix product: HIGHEST unless a control asks
# for less (``matmul_precision``) while the reference is traced.
_PRECISION = contextvars.ContextVar("reference_precision",
                                    default=jax.lax.Precision.HIGHEST)

# Paper Table I: output set of rule (channel quality, data quantity,
# staleness), each input weak/medium/strong; outputs poor..excellent = 0..4.
RULES = np.array([[[0, 0, 1], [0, 1, 2], [1, 2, 3]],
                  [[0, 1, 2], [1, 2, 3], [2, 3, 4]],
                  [[1, 2, 3], [2, 3, 4], [3, 4, 4]]])
IN_SETS = ((-50.0, 0.0, 50.0), (0.0, 50.0, 100.0), (50.0, 100.0, 150.0))
OUT_SETS = ((-25.0, 0.0, 25.0), (0.0, 25.0, 50.0), (25.0, 50.0, 75.0),
            (50.0, 75.0, 100.0), (75.0, 100.0, 125.0))
STALENESS_CAP = 1 << 20
# ``pdd_undecided``: relative scales of the bill's perturbations (a float32
# rounding is 6e-8; the program's bill departs from this one by 1e-7 to
# 2e-6), and draws per scale
UNDECIDED_SCALES = (1e-7, 1e-6, 1e-5, 1e-4)
UNDECIDED_SAMPLES = 16


class Radio(NamedTuple):
    """The scalar constants of the cost model, from the configuration."""
    tau1: int
    tau2: int
    lr: float
    batch: int
    quota: int              # N_m clients per edge
    edges_per_round: int    # M_c = round(semi_sync_fraction * M)
    radius: float           # coverage radius [m]
    data_max: float
    ple: float              # path-loss exponent
    rho: float              # Gauss-Markov fading coefficient
    bandwidth: float
    noise_w: float
    p_min: float
    p_max: float
    f_min: float
    f_max: float
    cycles: float
    kappa: float
    model_bits: float
    edge_bits: float
    edge_rate: float
    edge_power: float
    lam_t: float
    lam_e: float


def radio_of(cfg, fading_rho: float = 0.9) -> Radio:
    tau1 = max(1, round(cfg.mu_const * math.log(1.0 / cfg.local_accuracy_theta)))
    tau2 = max(1, round(cfg.delta_const * math.log(1.0 / cfg.edge_accuracy_xi)
                        / (1.0 - cfg.local_accuracy_theta)))
    return Radio(
        tau1=tau1, tau2=tau2, lr=cfg.lr, batch=cfg.local_batch,
        quota=cfg.clients_per_edge,
        edges_per_round=max(1, int(round(cfg.semi_sync_fraction
                                         * cfg.n_edges))),
        radius=0.75 * cfg.area_side_m, data_max=float(cfg.max_samples),
        ple=cfg.path_loss_exponent, rho=fading_rho,
        bandwidth=cfg.bandwidth_hz,
        noise_w=10.0 ** (cfg.noise_dbm_per_hz / 10.0) / 1000.0
        * cfg.bandwidth_hz,
        p_min=cfg.p_min_w, p_max=cfg.p_max_w, f_min=cfg.f_min_hz,
        f_max=cfg.f_max_hz, cycles=cfg.cycles_per_sample,
        kappa=cfg.capacitance, model_bits=cfg.model_size_bits,
        edge_bits=cfg.edge_model_size_bits, edge_rate=cfg.edge_rate_bps,
        edge_power=cfg.edge_power_w, lam_t=cfg.lambda_t, lam_e=cfg.lambda_e)


@contextlib.contextmanager
def matmul_precision(name: str):
    """Trace the reference with its matrix products at ``name``
    ("highest", "high" or "default")."""
    token = _PRECISION.set(jax.lax.Precision(name))
    try:
        yield
    finally:
        _PRECISION.reset(token)


def _dot(a, b):
    precision = _PRECISION.get()
    if precision == jax.lax.Precision.HIGH and a.dtype == jnp.float32:
        # three bfloat16 passes with float32 sums, as a TPU computes HIGH,
        # written out so that any backend computes the same
        def split(x):
            hi = x.astype(jnp.bfloat16)
            return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        (a_hi, a_lo), (b_hi, b_lo) = split(a), split(b)
        mm = functools.partial(jnp.matmul,
                               preferred_element_type=jnp.float32)
        return mm(a_hi, b_hi) + mm(a_hi, b_lo) + mm(a_lo, b_hi)
    return jnp.matmul(a, b, precision=precision)


def _tri(x, a, b, c):
    return jnp.clip(jnp.minimum((x - a) / (b - a), (c - x) / (c - b)),
                    0.0, 1.0)


# ---------------------------------------------------------------------------
# Channel, fuzzy scoring and association
# ---------------------------------------------------------------------------

def fade(key, gains, dist, r: Radio):
    """First-order Gauss-Markov step towards a fresh Rayleigh draw."""
    fresh = (jnp.maximum(dist, 1.0) ** (-r.ple)
             * jax.random.exponential(key, dist.shape).astype(dist.dtype))
    return r.rho * gains + (1.0 - r.rho) * fresh


def fuzzy_scores(gains, counts, staleness, r: Radio):
    """(N, M) competency NO* in [0, 100] (Eqs. 21-22, Table I)."""
    dt = gains.dtype
    db = 10.0 * jnp.log10(jnp.maximum(gains, 1e-30))
    lo, hi = jnp.min(db), jnp.max(db)
    cq = jnp.clip((db - lo) / jnp.maximum(hi - lo, 1e-9), 0.0, 1.0) * 100.0
    dq = jnp.clip(counts / r.data_max, 0.0, 1.0) * 100.0
    st = staleness.astype(dt)
    ms = jnp.clip(st / jnp.maximum(jnp.max(st), 1.0), 0.0, 1.0) * 100.0
    member = lambda v: jnp.stack([_tri(v, *s) for s in IN_SETS], -1)
    m_cq, m_dq, m_ms = member(cq), member(dq), member(ms)   # (N,M,3) (N,3)
    deg = jnp.minimum(
        jnp.minimum(m_cq[:, :, :, None, None], m_dq[:, None, None, :, None]),
        m_ms[:, None, None, None, :]).reshape(cq.shape + (27,))
    fires = jnp.asarray(RULES.reshape(-1)[:, None] == np.arange(5)[None, :])
    strength = jnp.max(jnp.where(fires, deg[..., None], 0.0), axis=-2)
    grid = jnp.linspace(0.0, 100.0, 201).astype(dt)
    mu = jnp.stack([_tri(grid, *s) for s in OUT_SETS], -1)     # (G, 5)
    agg = jnp.max(jnp.minimum(mu, strength[:, :, None, :]), axis=-1)
    return (jnp.sum(grid * agg, axis=-1)
            / jnp.maximum(jnp.sum(agg, axis=-1), 1e-9))


def deferred_acceptance(scores, dist, r: Radio):
    """Edge-proposing deferred acceptance with quota ``r.quota``: each
    edge proposes down its list (score descending, lower client index
    first) to clients in its coverage; a client keeps its nearest offer
    (lower edge index on an exact tie).  Returns assigned (N,), -1 for
    unmatched clients."""
    n, m = scores.shape
    cover = dist <= r.radius
    order = jnp.argsort(jnp.where(cover, -scores, jnp.inf), axis=0,
                        stable=True).T                          # (M, N)
    n_cover = jnp.sum(cover, axis=0)
    edge = jnp.arange(m)

    def active(s):
        held, ptr = s
        count = jnp.sum(held[:, None] == edge[None, :], axis=0)
        return (count < r.quota) & (ptr < n_cover)

    def body(s):
        held, ptr = s
        act = active(s)
        target = order[edge, jnp.minimum(ptr, n - 1)]
        offer = act[None, :] & (target[None, :] == jnp.arange(n)[:, None])
        offer = offer | (held[:, None] == edge[None, :])
        best = jnp.argmin(jnp.where(offer, dist, jnp.inf), axis=1)
        held = jnp.where(jnp.any(offer, axis=1), best, -1)
        return held, ptr + act.astype(ptr.dtype)

    held0 = jnp.full((n,), -1, jnp.int32)
    held, _ = jax.lax.while_loop(lambda s: jnp.any(active(s)), body,
                                 (held0, jnp.zeros((m,), jnp.int32)))
    return held.astype(jnp.int32)


def members_of(assigned, m: int, quota: int):
    """(M, quota) client ids per edge in ascending order, padded with N;
    and the matching validity mask."""
    n = assigned.shape[0]
    ids = jax.vmap(lambda e: jnp.nonzero(assigned == e, size=quota,
                                         fill_value=n)[0])(jnp.arange(m))
    return ids, ids < n


# ---------------------------------------------------------------------------
# Allocation and the Eq. 23a bill
# ---------------------------------------------------------------------------

def actor_apply(actor, obs):
    h = jax.nn.relu(_dot(obs, actor["w0"]) + actor["b0"])
    h = jax.nn.relu(_dot(h, actor["w1"]) + actor["b1"])
    return jax.nn.sigmoid(_dot(h, actor["w2"]) + actor["b2"])


def observe(assigned, gains, counts):
    """DDPG state: log-gain to the own edge and the data share of every
    associated client, zero for the others, as (2N,)."""
    on = assigned >= 0
    own = jnp.take_along_axis(gains, jnp.maximum(assigned, 0)[:, None],
                              axis=1)[:, 0]
    g = jnp.log10(jnp.maximum(own, 1e-20)) / 10.0 + 1.0
    d = counts / jnp.maximum(jnp.max(counts), 1.0)
    return jnp.concatenate([jnp.where(on, g, 0.0), jnp.where(on, d, 0.0)])


def decode(action, r: Radio):
    n = action.shape[0] // 2
    p = r.p_min + action[:n] * (r.p_max - r.p_min)
    f = r.f_min + action[n:] * (r.f_max - r.f_min)
    return p, f


def bill(p, f, gains, counts, assigned, r: Radio):
    """Per-edge totals at z = 1: (time (M,), energy (M,)) with the
    edge-to-cloud hop included (Eqs. 3-16)."""
    m = gains.shape[1]
    ids, ok = members_of(assigned, m, r.quota)                # (M, q)
    safe = jnp.minimum(ids, assigned.shape[0] - 1)
    rx = jnp.where(ok, p[safe] * gains[safe, jnp.arange(m)[:, None]], 0.0)
    q = ids.shape[1]
    j = jnp.arange(q)
    # SIC in descending received power: j is decoded after i when weaker,
    # or equal and of a higher client index
    after = (rx[:, None, :] < rx[:, :, None]) | (
        (rx[:, None, :] == rx[:, :, None]) & (j[None, None, :] > j[None, :, None]))
    interference = jnp.sum(jnp.where(after, rx[:, None, :], 0.0), axis=-1)
    rate = r.bandwidth * jnp.log2(1.0 + rx / (interference + r.noise_w))
    t_com = r.model_bits / jnp.maximum(rate, 1.0)
    e_com = p[safe] * t_com
    d, ff = counts[safe], f[safe]
    t_cmp = r.tau1 * r.cycles * d / ff
    e_cmp = r.tau1 * (r.kappa / 2.0) * ff ** 2 * r.cycles * d
    t_client = jnp.where(ok, t_cmp + t_com, 0.0)
    e_client = jnp.where(ok, e_cmp + e_com, 0.0)
    t_cloud = r.edge_bits / r.edge_rate
    time = r.tau2 * jnp.max(t_client, axis=1) + t_cloud
    energy = r.tau2 * jnp.sum(e_client, axis=1) + r.edge_power * t_cloud
    return time, energy


def pdd(energy, time, r: Radio, outer: int = 30, inner: int = 40):
    """Alg. 1 (penalty dual decomposition) with the sum(z) = M_c quota as
    one more penalised equality; returns the binary z (M,)."""
    m = energy.shape[0]
    dt = energy.dtype
    t_cloud = jnp.asarray(r.edge_bits / r.edge_rate, dt)
    tu = t_cloud + (time - t_cloud)
    quota = r.edges_per_round

    def inner_step(_, s):
        z, zt, q, qt, gamma, mu, w, v = s
        zt = jnp.clip((z ** 2 + q * z * v + z + qt * v) / (z ** 2 + 1.0),
                      0.0, 1.0)
        i_m = (zt / v - qt - q * (1.0 - zt) - r.lam_e * energy - gamma * tu
               - mu - (jnp.sum(z) - quota) / v)
        z = jnp.clip(i_m * v / (1.0 + (1.0 - zt) ** 2), 0.0, 1.0)
        w = jnp.max(z * tu)
        gamma = jnp.maximum(0.0, gamma + (z * tu - w)
                            / jnp.maximum(v, 1e-6) * 0.1)
        return z, zt, q, qt, gamma, mu, w, v

    def outer_step(_, s):
        z, zt, q, qt, gamma, mu, w, v = jax.lax.fori_loop(0, inner,
                                                          inner_step, s)
        q = q + z * (1.0 - zt) / v
        qt = qt + (z - zt) / v
        mu = mu + (jnp.sum(z) - quota) / v
        return z, zt, q, qt, gamma, mu, w, v * 0.8

    half = jnp.full((m,), 0.5, dt)
    zero = jnp.zeros((m,), dt)
    s = (half, half, zero, zero, zero, jnp.zeros((), dt), jnp.max(tu),
         jnp.ones((), dt))
    z = jax.lax.fori_loop(0, outer, outer_step, s)[0]
    keep = z >= jnp.sort(z)[m - quota]
    keep = keep & (jnp.cumsum(keep) <= quota)
    return keep.astype(dt)


def pdd_undecided(key, energy, time, z, r: Radio):
    """Whether ``pdd``'s choice ``z`` is undecided in float32: whether any
    of ``len(UNDECIDED_SCALES) * UNDECIDED_SAMPLES`` draws of the bill,
    each entry scaled by 1 + s * U(-1, 1) for s of ``UNDECIDED_SCALES``,
    leads it to another choice.  Its 1,200 serial iterations can end at
    different vertices for bills a rounding apart: such a choice is not an
    answer that two float32 computations of the same bill must share."""
    scales = jnp.repeat(jnp.asarray(UNDECIDED_SCALES, energy.dtype),
                        UNDECIDED_SAMPLES)
    u = jax.random.uniform(key, (scales.shape[0], 2) + energy.shape,
                           energy.dtype, -1.0, 1.0)
    zs = jax.vmap(lambda s, uu: pdd(energy * (1.0 + s * uu[0]),
                                    time * (1.0 + s * uu[1]), r))(scales, u)
    return jnp.any(zs != z[None])


def is_vertex(z, r: Radio):
    """Whether ``z`` is a choice ``pdd`` can make: 0/1 entries, exactly
    ``edges_per_round`` of them 1."""
    return (jnp.all((z == 0) | (z == 1))
            & (jnp.sum(z) == r.edges_per_round))


# ---------------------------------------------------------------------------
# Local training and aggregation
# ---------------------------------------------------------------------------

def mlp_logits(params, x):
    h = jax.nn.relu(_dot(x, params["w1"]) + params["b1"])
    h = jax.nn.relu(_dot(h, params["w2"]) + params["b2"])
    return _dot(h, params["w3"]) + params["b3"]


def cross_entropy(params, x, y):
    logits = mlp_logits(params, x)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)


def minibatches(key, client, count, r: Radio):
    """(tau2, tau1, B) sample indices of one client: the draw for edge
    iteration t, local step i is keyed fold_in(fold_in(split(key, tau2)[t],
    i), client)."""
    k_t = jax.random.split(key, r.tau2)
    hi = jnp.maximum(count, 1)
    draw = lambda kt, i: jax.random.randint(
        jax.random.fold_in(jax.random.fold_in(kt, i), client),
        (r.batch,), 0, hi)
    steps = jnp.arange(r.tau1, dtype=jnp.int32)
    return jax.vmap(lambda kt: jax.vmap(lambda i: draw(kt, i))(steps))(k_t)


def hierarchical_train(key, global_params, x, y, counts, assigned, z,
                       r: Radio, drop_half: bool = False):
    """tau2 edge iterations of tau1 SGD steps per admitted client, each
    edge iteration ending in the data-weighted edge average (Eq. 11) that
    every member adopts; then the cloud average over the scheduled edges
    that hold data (Eq. 17).  ``drop_half`` trains on the first half of
    every minibatch only (a planted fault for the checks)."""
    m = z.shape[0]
    ids, ok = members_of(assigned, m, r.quota)
    n = assigned.shape[0]
    safe = jnp.minimum(ids, n - 1)
    idx = jax.vmap(jax.vmap(lambda c: minibatches(key, c, counts[c], r)))(
        safe)                                          # (M, q, tau2, tau1, B)
    w = jnp.where(ok, counts[safe], 0.0)                # (M, q)
    if drop_half:
        idx = idx[..., : r.batch // 2]

    def sgd(p, c, ix):
        bx, by = x[c][ix], y[c][ix]
        g = jax.grad(cross_entropy)(p, bx, by)
        return jax.tree.map(lambda a, b: a - r.lr * b, p, g)

    def edge_iteration(edge_p, t):
        def client(p, c, ixs):                          # ixs (tau1, B)
            for i in range(r.tau1):
                p = sgd(p, c, ixs[i])
            return p
        per_edge = jax.vmap(jax.vmap(client, in_axes=(None, 0, 0)),
                            in_axes=(0, 0, 0))
        trained = per_edge(edge_p, safe, idx[:, :, t])
        den = jnp.maximum(jnp.sum(w, axis=1), 1e-12)

        def avg(leaf):
            wl = w.reshape(w.shape + (1,) * (leaf.ndim - 2))
            return jnp.sum(leaf * wl, axis=1) / den.reshape(
                (-1,) + (1,) * (leaf.ndim - 2))
        return jax.tree.map(avg, trained), None

    start = jax.tree.map(lambda l: jnp.broadcast_to(l, (m,) + l.shape),
                         global_params)
    edge_p, _ = jax.lax.scan(edge_iteration, start, jnp.arange(r.tau2))
    edge_data = jnp.sum(w, axis=1)
    cw = z * (edge_data > 0) * edge_data
    tot = jnp.sum(cw)

    def cloud(leaf, old):
        cl = cw.reshape((-1,) + (1,) * (leaf.ndim - 1))
        new = jnp.sum(leaf * cl, axis=0) / jnp.maximum(tot, 1e-12)
        return jnp.where(tot > 0, new, old)
    return jax.tree.map(cloud, edge_p, global_params)


# ---------------------------------------------------------------------------
# Synchronous rounds
# ---------------------------------------------------------------------------

class RoundOut(NamedTuple):
    loss: jnp.ndarray        # (R,) test loss after each round
    cost: jnp.ndarray        # (R,) Eq. 23a bill
    z: jnp.ndarray           # (R, M)
    n_associated: jnp.ndarray
    params: Dict             # global model after the R rounds
    staleness: jnp.ndarray   # (N,) after the R rounds
    followed: jnp.ndarray    # (R,) rounds whose z is the compared one's


def sync_rounds(key, params, gains, dist, x, y, counts, test_x, test_y,
                actor, z_seen=None, *, r: Radio, rounds: int,
                drop_half: bool = False) -> RoundOut:
    """``rounds`` synchronous global rounds from the initial state.

    ``z_seen`` (R, M): the schedule of the run being compared.  In a round
    whose own choice is undecided in float32 (``pdd_undecided``), and
    there only, the reference goes on with ``z_seen`` where that is a
    choice PDD can make (``is_vertex``), so that the rounds after it
    compare like with like; ``followed`` marks those rounds.  Everywhere
    else the reference keeps its own z."""
    n = dist.shape[0]
    dt = gains.dtype

    def one(carry, seen):
        key, params, gains, stale = carry
        key, k_fade, _, k_undecided, k_train = jax.random.split(key, 5)
        gains = fade(k_fade, gains, dist, r)
        assigned = deferred_acceptance(fuzzy_scores(gains, counts, stale, r),
                                       dist, r)
        p, f = decode(actor_apply(actor, observe(assigned, gains, counts)), r)
        time, energy = bill(p, f, gains, counts, assigned, r)
        z = pdd(energy, time, r)
        follow = jnp.zeros((), bool)
        if seen is not None:
            seen = seen.astype(dt)
            follow = (is_vertex(seen, r) & jnp.any(seen != z)
                      & pdd_undecided(k_undecided, energy, time, z, r))
            z = jnp.where(follow, seen, z)
        cost = (r.lam_t * jnp.max(z * time)
                + r.lam_e * jnp.sum(z * energy))
        params = hierarchical_train(k_train, params, x, y, counts, assigned,
                                    z, r, drop_half)
        on = (assigned >= 0) & (z[jnp.maximum(assigned, 0)] > 0)
        stale = jnp.where(on, 1, jnp.minimum(stale + 1, STALENESS_CAP))
        loss = cross_entropy(params, test_x, test_y)
        out = (loss, cost, z, jnp.sum(assigned >= 0), follow)
        return (key, params, gains, stale), out

    stale0 = jnp.ones((n,), jnp.int32)
    (_, params, _, stale), (loss, cost, z, n_assoc, followed) = jax.lax.scan(
        one, (key, params, gains.astype(dt), stale0), z_seen, length=rounds)
    return RoundOut(loss, cost, z, n_assoc, params, stale, followed)


# ---------------------------------------------------------------------------
# DDPG allocator training (Algorithm 2)
# ---------------------------------------------------------------------------

class DDPGOut(NamedTuple):
    nets: Dict               # actor / critic / target_actor / target_critic
    init: Dict               # the same four at initialisation
    episode_reward: jnp.ndarray   # (episodes,)


def _net_init(key, sizes, dt):
    ks = jax.random.split(key, len(sizes) - 1)
    out = {}
    for i in range(len(sizes) - 1):
        std = 1.0 / math.sqrt(sizes[i])
        out[f"w{i}"] = (std * jax.random.normal(
            ks[i], (sizes[i], sizes[i + 1]), jnp.float32)).astype(dt)
        out[f"b{i}"] = jnp.zeros((sizes[i + 1],), dt)
    return out


def _net(params, x):
    for i in range(3):
        x = _dot(x, params[f"w{i}"]) + params[f"b{i}"]
        if i < 2:
            x = jax.nn.relu(x)
    return x


def _adam(p, g, m, v, lr, step):
    """One Adam step; the bias corrections are taken in float32 from the
    integer step count whatever the parameters' dtype."""
    dt = jax.tree.leaves(p)[0].dtype
    m = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
    v = jax.tree.map(lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
    t = step.astype(jnp.float32)
    c1 = (1.0 - 0.9 ** t).astype(dt)
    c2 = (1.0 - 0.999 ** t).astype(dt)
    p = jax.tree.map(lambda a, mm, vv: a - lr * (mm / c1)
                     / (jnp.sqrt(vv / c2) + 1e-8), p, m, v)
    return p, m, v


def ddpg_train(key, gains0, dist, counts, r: Radio, *, episodes: int,
               steps: int, warmup: int, hidden: int, buffer_size: int,
               batch: int, gamma: float = 0.99, tau: float = 0.005,
               lr: float = 1e-3, sigma0: float = 0.1,
               sigma_decay: float = 0.999, drop_half: bool = False
               ) -> DDPGOut:
    """Algorithm 2 on the association of the initial state (staleness
    all 1): one agent, state (2N,), action (2N,).  ``drop_half`` takes
    each update's means over the first half of its minibatch only (a
    planted fault for the checks)."""
    dt = gains0.dtype
    n, _ = dist.shape
    stale = jnp.ones((n,), jnp.int32)
    assigned = deferred_acceptance(fuzzy_scores(gains0, counts, stale, r),
                                   dist, r)
    s_dim = a_dim = 2 * n
    key, k_agent = jax.random.split(key)
    k_a, k_c = jax.random.split(k_agent)
    actor = _net_init(k_a, (s_dim, hidden, hidden, a_dim), dt)
    critic = _net_init(k_c, (s_dim + a_dim, hidden, hidden, 1), dt)
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
    init = {"actor": actor, "critic": critic, "target_actor": actor,
            "target_critic": critic}

    def q_value(c, s, a):
        return _net(c, jnp.concatenate([s, a], -1))[..., 0]

    def reward_of(gains, action):
        p, f = decode(action, r)
        time, energy = bill(p, f, gains, counts, assigned, r)
        return -(r.lam_t * jnp.max(time) + r.lam_e * jnp.sum(energy))

    def update(k, ag):
        size = jnp.maximum(jnp.where(ag["full"], buffer_size, ag["idx"]), 1)
        j = jax.random.randint(k, (batch,), 0, size)
        if drop_half:
            j = j[: batch // 2]
        s, a, rw, s2 = (ag["buf"][name][j] for name in ("s", "a", "r", "s2"))
        target = rw + gamma * q_value(
            ag["target_critic"], s2,
            jax.nn.sigmoid(_net(ag["target_actor"], s2)))
        t = ag["step"] + 1
        gc = jax.grad(lambda c: jnp.mean((target - q_value(c, s, a)) ** 2))(
            ag["critic"])
        critic, cm, cv = _adam(ag["critic"], gc, ag["cm"], ag["cv"], lr, t)
        ga = jax.grad(lambda pa: -jnp.mean(
            q_value(critic, s, jax.nn.sigmoid(_net(pa, s)))))(ag["actor"])
        actor, am, av = _adam(ag["actor"], ga, ag["am"], ag["av"], lr, t)
        soft = lambda tt, oo: jax.tree.map(
            lambda a_, b_: (1.0 - tau) * a_ + tau * b_, tt, oo)
        new = dict(ag, actor=actor, critic=critic, am=am, av=av, cm=cm,
                   cv=cv, target_actor=soft(ag["target_actor"], actor),
                   target_critic=soft(ag["target_critic"], critic),
                   sigma=ag["sigma"] * sigma_decay, step=ag["step"] + 1)
        return new

    def step(carry, _):
        ag, gains, gkey, obs, key, t = carry
        key, k_act, k_upd = jax.random.split(key, 3)
        a = jax.nn.sigmoid(_net(ag["actor"], obs))
        noise = jax.random.normal(k_act, a.shape).astype(dt)
        a = jnp.clip(a + ag["sigma"] * noise, 0.0, 1.0)
        rw = reward_of(gains, a)
        k1, gkey = jax.random.split(gkey)
        gains = fade(k1, gains, dist, r)
        obs2 = observe(assigned, gains, counts)
        i = ag["idx"]
        buf = {name: ag["buf"][name].at[i].set(val) for name, val in
               (("s", obs), ("a", a), ("r", rw), ("s2", obs2))}
        nxt = (i + 1) % buffer_size
        ag = dict(ag, buf=buf, idx=nxt, full=ag["full"] | (nxt == 0))
        t = t + 1
        ag = jax.lax.cond(t >= warmup, lambda: update(k_upd, ag), lambda: ag)
        return (ag, gains, gkey, obs2, key, t), rw

    def episode(carry, _):
        ag, key, t = carry
        key, k_reset = jax.random.split(key)
        k1, gkey = jax.random.split(k_reset)
        gains = (jnp.maximum(dist, 1.0) ** (-r.ple)
                 * jax.random.exponential(k1, dist.shape).astype(dt))
        obs = observe(assigned, gains, counts)
        (ag, _, _, _, key, t), rewards = jax.lax.scan(
            step, (ag, gains, gkey, obs, key, t), None, length=steps)
        return (ag, key, t), jnp.mean(rewards)

    agent = {"actor": actor, "critic": critic, "target_actor": actor,
             "target_critic": critic, "am": zeros(actor), "av": zeros(actor),
             "cm": zeros(critic), "cv": zeros(critic),
             "buf": {"s": jnp.zeros((buffer_size, s_dim), dt),
                     "a": jnp.zeros((buffer_size, a_dim), dt),
                     "r": jnp.zeros((buffer_size,), dt),
                     "s2": jnp.zeros((buffer_size, s_dim), dt)},
             "idx": jnp.zeros((), jnp.int32), "full": jnp.zeros((), bool),
             "sigma": jnp.asarray(sigma0, dt), "step": jnp.zeros((), jnp.int32)}
    (agent, _, _), rewards = jax.lax.scan(
        episode, (agent, key, jnp.zeros((), jnp.int32)), None,
        length=episodes)
    nets = {k: agent[k] for k in init}
    return DDPGOut(nets, init, rewards)
