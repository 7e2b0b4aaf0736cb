"""A cell's inputs and weights, drawn on the device from the run's seed.

The semantics are a copy of the program's host generator, so that the
program receives only generated arrays and the reference can be fed the
same ones:

* topology: a square of side ``area_side_m``, the first four edge servers
  at the midpoints of the corner-to-centre lines, further edges and every
  client uniform in the square;
* data: per client a uniform D_n in [min_samples, max_samples]; labels
  from a Dirichlet(alpha) class mixture per client, floored per class and
  topped up by largest remainder so each client holds exactly D_n; each
  sample is its class template plus Gaussian noise through a sigmoid; rows
  past D_n are zero; a test set of ``test_samples`` from the same classes;
* the client model (784-128-128-10 MLP), each weight ~ N(0, 1/fan_in),
  biases zero; the DDPG actor the same way;
* initial channel gains: path loss d^-ple times Exp(1) Rayleigh power.

Every simulation of a fleet is drawn from ``fold_in(root, lane)``.  The
padded client data, the largest array, is drawn client by client under
``lax.map`` so that no second copy of it is ever live.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

TEST_SAMPLES = 2000


def root_key(seed: int):
    """A PRNG key that depends on all 64 bits of ``seed``."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed must lie in [0, 2**64), got {seed}")
    return jax.random.fold_in(jax.random.key(seed % 2 ** 32), seed // 2 ** 32)


class World(NamedTuple):
    """One simulation's inputs (a leading lane axis under a fleet)."""
    clients: jnp.ndarray     # (N, 2) positions [m]
    edges: jnp.ndarray       # (M, 2)
    dist: jnp.ndarray        # (N, M)
    x: jnp.ndarray           # (N, cap, D) f32, zero past D_n
    y: jnp.ndarray           # (N, cap) int32
    counts: jnp.ndarray      # (N,) f32 D_n
    test_x: jnp.ndarray      # (T, D)
    test_y: jnp.ndarray      # (T,)
    params: Dict             # client-model init {w1,b1,w2,b2,w3,b3}
    gains: jnp.ndarray       # (N, M) initial |h|^2
    key: jnp.ndarray         # the simulation's round key
    actor: Dict              # DDPG actor {w0,b0,w1,b1,w2,b2}


def dense_init(key, sizes, first: int = 0) -> Dict:
    """Weights ~ N(0, 1/fan_in) and zero biases, named w{i}/b{i}."""
    ks = jax.random.split(key, len(sizes) - 1)
    out = {}
    for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
        out[f"w{i + first}"] = (jax.random.normal(ks[i], (a, b), jnp.float32)
                                / jnp.sqrt(jnp.float32(a)))
        out[f"b{i + first}"] = jnp.zeros((b,), jnp.float32)
    return out


def topology(key, cfg):
    side = cfg.area_side_m
    half = side / 2.0
    corners = jnp.array([[0.0, 0.0], [0.0, side], [side, 0.0], [side, side]])
    mids = (corners + jnp.array([half, half])) / 2.0
    k_e, k_c = jax.random.split(key)
    if cfg.n_edges <= 4:
        edges = mids[:cfg.n_edges]
    else:
        extra = jax.random.uniform(k_e, (cfg.n_edges - 4, 2), jnp.float32,
                                   0.0, side)
        edges = jnp.concatenate([mids, extra], axis=0)
    clients = jax.random.uniform(k_c, (cfg.n_clients, 2), jnp.float32, 0.0,
                                 side)
    dist = jnp.linalg.norm(clients[:, None, :] - edges[None, :, :], axis=-1)
    return clients, edges, dist


def dirichlet_labels(key, counts, cfg):
    """(N, cap) labels: per client exactly floor + largest-remainder class
    counts of a Dir(alpha) mixture, in random slot order, 0 past D_n."""
    n, c, cap = cfg.n_clients, cfg.n_classes, cfg.max_samples
    k_mix, k_perm = jax.random.split(key)
    mix = jax.random.dirichlet(k_mix, jnp.full((c,), cfg.dirichlet_alpha),
                               (n,))
    quota = mix * counts[:, None].astype(jnp.float32)
    per_class = jnp.floor(quota).astype(jnp.int32)
    deficit = counts - jnp.sum(per_class, axis=1)                  # < c
    frac_rank = jnp.argsort(jnp.argsort(-(quota - jnp.floor(quota)),
                                        axis=1, stable=True), axis=1)
    per_class = per_class + (frac_rank < deficit[:, None]).astype(jnp.int32)
    bounds = jnp.cumsum(per_class, axis=1)                         # (N, C)
    slot = jnp.arange(cap, dtype=jnp.int32)
    label = jnp.sum(slot[None, :, None] >= bounds[:, None, :], axis=-1)
    valid = slot[None, :] < counts[:, None]
    u = jnp.where(valid, jax.random.uniform(k_perm, (n, cap)), 2.0)
    label = jnp.take_along_axis(label, jnp.argsort(u, axis=1), axis=1)
    return jnp.where(valid, label, 0).astype(jnp.int32), valid


def _lane_small(key, cfg, actor_hidden):
    """Everything of one simulation but the padded client data."""
    (k_topo, k_cnt, k_tmpl, k_lab, k_test, k_model, k_gain, k_state,
     k_actor, k_x) = jax.random.split(key, 10)
    clients, edges, dist = topology(k_topo, cfg)
    counts = jax.random.randint(k_cnt, (cfg.n_clients,), cfg.min_samples,
                                cfg.max_samples + 1)
    counts = jnp.maximum(counts, 1)
    templates = jax.random.normal(k_tmpl, (cfg.n_classes, cfg.input_dim))
    y, valid = dirichlet_labels(k_lab, counts, cfg)
    k_ty, k_tx = jax.random.split(k_test)
    test_y = jax.random.randint(k_ty, (TEST_SAMPLES,), 0, cfg.n_classes)
    test_x = jax.nn.sigmoid(
        templates[test_y]
        + cfg.data_noise * jax.random.normal(k_tx,
                                             (TEST_SAMPLES, cfg.input_dim)))
    params = dense_init(k_model, (cfg.input_dim, cfg.hidden, cfg.hidden,
                                  cfg.n_classes), first=1)
    pl = jnp.maximum(dist, 1.0) ** (-cfg.path_loss_exponent)
    gains = pl * jax.random.exponential(k_gain, dist.shape)
    n2 = 2 * cfg.n_clients
    actor = dense_init(k_actor, (n2, actor_hidden, actor_hidden, n2))
    x_keys = jax.random.split(k_x, cfg.n_clients)
    return (clients, edges, dist, counts.astype(jnp.float32), test_x,
            test_y.astype(jnp.int32), params, gains, k_state, actor,
            templates, y, valid, x_keys)


def client_x(key, templates, y, valid, cfg):
    """One client's (cap, D) padded samples: the template of each sample's
    class plus Gaussian noise through a sigmoid, zero past D_n."""
    z = jax.random.normal(key, (cfg.max_samples, cfg.input_dim))
    x = jax.nn.sigmoid(templates[y] + cfg.data_noise * z)
    return jnp.where(valid[:, None], x, 0.0)


def lane_parts(root, cfg, lanes: int, actor_hidden: int):
    """``_lane_small`` of every lane, each from ``fold_in(root, lane)``;
    every part carries a leading (lanes,) axis."""
    keys = jax.vmap(lambda i: jax.random.fold_in(root, i))(
        jnp.arange(lanes, dtype=jnp.uint32))
    return jax.vmap(lambda k: _lane_small(k, cfg, actor_hidden))(keys)


def world_of(parts, x) -> World:
    """The ``World`` of ``lane_parts``'s parts (with or without their lane
    axis) and the padded client data ``x``."""
    (clients, edges, dist, counts, test_x, test_y, params, gains, k_state,
     actor, _, y, _, _) = parts
    return World(clients, edges, dist, x, y, counts, test_x, test_y, params,
                 gains, k_state, actor)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _make(root, cfg, lanes: int, actor_hidden: int, single: bool,
          with_data: bool) -> World:
    parts = lane_parts(root, cfg, lanes, actor_hidden)
    templates, y, valid, x_keys = parts[-4:]
    n = cfg.n_clients
    lane_of = jnp.repeat(jnp.arange(lanes), n)

    def one_client(args):
        k, lane, yy, vv = args
        return client_x(k, templates[lane], yy, vv, cfg)

    flat = lambda a: a.reshape((lanes * n,) + a.shape[2:])
    if with_data:
        x = jax.lax.map(one_client,
                        (flat(x_keys), lane_of, flat(y), flat(valid)),
                        batch_size=min(64, lanes * n))
        x = x.reshape((lanes, n) + x.shape[1:])
    else:
        x = jnp.zeros((lanes, n, 0, cfg.input_dim), jnp.float32)
    w = world_of(parts, x)
    if single:      # a reshape inside the program: no copy of the data
        w = jax.tree.map(lambda a: a.reshape(a.shape[1:]), w)
    return w


def make_fleet(root, cfg, lanes: int, actor_hidden: int,
               with_data: bool = True) -> World:
    """``lanes`` simulations, each from ``fold_in(root, lane)``; every leaf
    carries a leading (lanes,) axis.  ``with_data=False`` leaves out the
    padded client samples (x of zero rows) for a path that reads none."""
    return _make(root, cfg, lanes, actor_hidden, False, with_data)


def make_single(root, cfg, actor_hidden: int) -> World:
    """One simulation (no lane axis): lane 0 of a fleet of one."""
    return _make(root, cfg, 1, actor_hidden, True, True)
