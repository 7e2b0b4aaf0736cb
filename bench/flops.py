"""Operations and bytes the algorithm needs, counted from shapes.

Never from the compiler's cost analysis, which moves with the
implementation: these counts are the yardstick a faster implementation
is measured against.  Model FLOPs use the 6·|theta| per trained sample and
2·|theta| per evaluated sample convention (forward 2, backward 4).
"""
from __future__ import annotations

import json
import os
from typing import Dict


def mlp_params(sizes) -> int:
    """Weights and biases of a dense stack."""
    return sum(a * b + b for a, b in zip(sizes[:-1], sizes[1:]))


def client_params(cfg) -> int:
    return mlp_params((cfg.input_dim, cfg.hidden, cfg.hidden, cfg.n_classes))


def admitted(cfg) -> int:
    """K: clients trained a round (N_m per edge, at most N)."""
    return min(cfg.n_clients, cfg.clients_per_edge * cfg.n_edges)


def train_flops(cfg, tau1: int, tau2: int) -> float:
    """K lanes x tau2*tau1 SGD steps of B samples at 6·|theta| each."""
    return (admitted(cfg) * tau2 * tau1 * cfg.local_batch * 6.0
            * client_params(cfg))


def train_bytes(cfg, tau1: int, tau2: int, itemsize: int = 4) -> float:
    """Per SGD step and lane: the parameters read by the forward and the
    backward pass and written once, and the B sampled rows of x and y."""
    theta = client_params(cfg)
    per_step = (3 * theta + cfg.local_batch * (cfg.input_dim + 1)) * itemsize
    return admitted(cfg) * tau2 * tau1 * per_step


def eval_flops(cfg, test_samples: int) -> float:
    return 2.0 * client_params(cfg) * test_samples


def round_flops(cfg, tau1: int, tau2: int, test_samples: int) -> float:
    return train_flops(cfg, tau1, tau2) + eval_flops(cfg, test_samples)


def ddpg_step_flops(n_clients: int, hidden: int, batch: int) -> float:
    """One DDPG update a step: target actor and critic forward (2 each),
    critic forward and backward (6), actor forward and backward (6) through
    the critic's forward and input gradient (4); plus the acting forward."""
    s = a = 2 * n_clients
    actor = mlp_params((s, hidden, hidden, a))
    critic = mlp_params((s + a, hidden, hidden, 1))
    update = batch * (2 * actor + 2 * critic + 6 * critic + 6 * actor
                      + 4 * critic)
    return update + 2.0 * actor


def ddpg_call_flops(n_clients: int, hidden: int, batch: int, episodes: int,
                    steps: int, warmup: int) -> float:
    """A whole training call of one lane: every step acts, the steps from
    ``warmup`` on also update."""
    total = episodes * steps
    updates = max(0, total - warmup + 1)
    s = 2 * n_clients
    act = 2.0 * mlp_params((s, hidden, hidden, s))
    return updates * (ddpg_step_flops(n_clients, hidden, batch) - act) \
        + total * act


def least_time(flops: float, nbytes: float, peak: Dict) -> tuple:
    """(seconds, bound): the larger of compute time and memory time."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def peaks(device_kind: str) -> Dict:
    """The chip's published peaks; an unknown device is an error."""
    path = os.path.join(os.path.dirname(__file__), "peaks.json")
    with open(path) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; add them with their source")
    return table[device_kind]
