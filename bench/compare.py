"""The numbers that decide ``correct``, and the judgement against limits.

Each function takes the program's outputs and the reference's, both with
a leading lane axis, as host arrays or pytrees of them, and returns every
reading it knows; a cell's traffic file names under ``limits`` the ones
that are compared.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def rel_gap(prog, ref) -> float:
    """Largest |prog - ref| / |ref| over all entries."""
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    den = np.maximum(np.abs(ref), np.finfo(np.float32).tiny)
    return float(np.max(np.abs(prog - ref) / den))


def change_gaps(prog: Dict, ref: Dict, init: Dict) -> Dict[str, float]:
    """Readings of the parameters' change from ``init``, worst lane.

    * ``worst`` / ``median``: the worst and the median leaf's gap between
      the norms of the program's and the reference's change, over the
      larger of the reference's norm of that leaf and of the median leaf;
    * ``element``: the median over every parameter of |change gap| /
      |reference change|: a relative error that a rare flip of one ReLU
      (which moves a few rows) does not reach, and a lower precision of
      every product does.

    A leaf whose reference change is under a thousandth of the median
    leaf's moves by round-off alone and is left out of all three."""
    keys = sorted(ref)
    delta = lambda tree, k: (np.asarray(tree[k], np.float64)
                             - np.asarray(init[k], np.float64))
    lanes = np.asarray(init[keys[0]]).shape[0]
    dp = [delta(prog, k).reshape(lanes, -1) for k in keys]
    dr = [delta(ref, k).reshape(lanes, -1) for k in keys]
    p = np.stack([np.linalg.norm(a, axis=1) for a in dp], 1)  # (lanes, leaves)
    r = np.stack([np.linalg.norm(a, axis=1) for a in dr], 1)
    med = np.median(r, axis=1, keepdims=True)
    moved = r >= 1e-3 * med
    gap = np.where(moved, np.abs(p - r)
                   / np.maximum(np.maximum(r, med), 1e-30), 0.0)
    element = []
    for lane in range(lanes):
        rel = [np.abs(a[lane] - b[lane]) / np.maximum(np.abs(b[lane]), 1e-30)
               for a, b, m in zip(dp, dr, moved[lane]) if m]
        element.append(np.median(np.concatenate(rel)))
    return {"worst": float(np.max(gap)),
            "median": float(np.max(np.median(gap, axis=1))),
            "element": float(np.max(element))}


def rounds_numbers(prog: Dict, ref: Dict, init: Dict) -> Dict[str, float]:
    """prog/ref: {loss (L,R), cost (L,R), z (L,R,M), staleness (L,N),
    params {leaf: (L, ...)}}, and the reference's ``followed`` (L,R): the
    rounds in which it went on with the compared z, its own being
    undecided; init: the initial global model per lane."""
    ch = change_gaps(prog["params"], ref["params"], init)
    return {
        "z_followed": float(np.sum(ref["followed"])),
        "loss_gap": rel_gap(prog["loss"], ref["loss"]),
        "cost_gap": rel_gap(prog["cost"], ref["cost"]),
        "z_mismatch": float(np.sum(np.asarray(prog["z"]) != np.asarray(
            ref["z"]))),
        "staleness_mismatch": float(np.sum(
            np.asarray(prog["staleness"]) != np.asarray(ref["staleness"]))),
        "model_gap": ch["worst"],
        "model_median_gap": ch["median"],
        "model_elem_gap": ch["element"],
    }


def ddpg_numbers(prog: Dict, ref: Dict, first_update: int
                 ) -> Dict[str, float]:
    """prog/ref: {reward (L,E), nets {net: {leaf: (L,...)}}}; the
    reference also carries ``init`` (the same layout as ``nets``).
    ``first_update``: the first episode in which the networks update;
    the episodes before it bill the initial actor's actions only."""
    flat = lambda nets: {f"{n}.{k}": v for n, leaves in nets.items()
                         for k, v in leaves.items()}
    ch = change_gaps(flat(prog["nets"]), flat(ref["nets"]),
                     flat(ref["init"]))
    warm = slice(0, max(first_update, 1))
    return {
        "reward_gap": rel_gap(prog["reward"], ref["reward"]),
        "reward0_gap": rel_gap(np.asarray(prog["reward"])[:, warm],
                               np.asarray(ref["reward"])[:, warm]),
        "param_gap": ch["worst"],
        "param_median_gap": ch["median"],
        "param_elem_gap": ch["element"],
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Every number that ``limits`` names at or under its limit; one that
    is missing or not finite fails.  Returns the verdict and the compared
    numbers beside their limits."""
    table = {}
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name)
        passed = value is not None and np.isfinite(value) and value <= limit
        ok = ok and bool(passed)
        table[name] = {"value": value, "limit": limit}
    return ok, table
