"""A whole sweep: score every variant, reduce, and extract the answers.

``solve`` returns what a sweep answers -- per-app best fits (the first
variant of least aggregate), the 2-D front over (area, suite-mean
aggregate) and the 3-D front over (suite-mean aggregate, area, power) --
together with the intermediate arrays the comparison reads.
"""

from __future__ import annotations

import numpy as np

from . import congruence, pareto


def scan(P: dict, M: dict, beta, eps: float, clamp: bool, threads: int = 8):
    """Suite-mean aggregate per variant ``(V,)``, and per app the least
    aggregate and the first variant that attains it, ``(A,)`` each."""
    a = len(beta)

    def reduce(lo, out):
        agg = out["aggregate"]
        idx = np.argmin(agg, axis=1)
        return agg.mean(axis=0), agg[np.arange(a), idx], idx + lo

    parts = congruence.blocked(P, M, beta, eps, clamp, reduce,
                               threads=threads)
    mean = np.concatenate([p[0] for p in parts])
    mins = np.stack([p[1] for p in parts])          # (blocks, A)
    idxs = np.stack([p[2] for p in parts])
    first = np.argmin(mins, axis=0)                 # first block at the min
    cols = np.arange(a)
    return mean, mins[first, cols], idxs[first, cols]


def rows(P: dict, M: dict, indices, beta, eps: float, clamp: bool,
         outputs=congruence.OUTPUTS) -> dict:
    """Outputs ``(A, len(indices))`` at the given variant indices."""
    idx = np.asarray(indices, dtype=np.int64)
    sub = {k: v[idx] for k, v in M.items()}
    return congruence.congruence(P, sub, beta, eps, clamp, outputs)


def solve(P: dict, M: dict, beta, eps: float, clamp: bool, cost: dict,
          threads: int = 8) -> dict:
    """Best fits and fronts of the population ``M`` (all in ``M``'s dtype)."""
    mean, mins, best = scan(P, M, beta, eps, clamp, threads)
    area = pareto.area(cost, M)
    power = pareto.power(cost, M)
    return {
        "mean": mean,
        "min": mins,
        "best_fit": best,
        "area": area,
        "power": power,
        "front2": pareto.front_2d(area, mean),
        "front3": pareto.front_3d(mean, area, power),
    }
