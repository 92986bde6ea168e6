"""Cost model and Pareto fronts.

Area and power are relative to a reference chip, over four provisioned
rates ``r`` (``ici_bw_total`` is ``ici_bw * ici_links``):

    area  = sum_r w_r * rate_r / ref_r / sum_r w_r
    power = static + sum_r p_r * (rate_r / ref_r) ** e_r / sum_r p_r

A point is on a front when no other point is at least as good on every
axis and better on one; all axes are minimized.
"""

from __future__ import annotations

import numpy as np

COST_RATES = ("peak_flops", "hbm_bw", "ici_bw_total", "inter_pod_bw")


def _rate(M: dict, name: str):
    if name == "ici_bw_total":
        return M["ici_bw"] * M["ici_links"]
    return M[name]


def area(cost: dict, M: dict):
    ref = cost["reference"]
    w = cost["area_weights"]
    total = sum(w[r] for r in COST_RATES)
    return sum(w[r] * (_rate(M, r) / _rate(ref, r)) for r in COST_RATES) / total


def power(cost: dict, M: dict):
    ref = cost["reference"]
    w, e = cost["power_weights"], cost["power_exponents"]
    total = sum(w[r] for r in COST_RATES)
    dyn = sum(w[r] * (_rate(M, r) / _rate(ref, r)) ** e[r]
              for r in COST_RATES) / total
    return cost["static_power"] + dyn


def front_2d(area_, agg) -> np.ndarray:
    """Indices on the (area, aggregate) front, by increasing area."""
    order = np.lexsort((agg, area_))
    a = np.asarray(agg)[order]
    best_before = np.minimum.accumulate(np.concatenate(([np.inf], a[:-1])))
    return order[a < best_before]


def front_3d(agg, area_, power_, chunk: int = 4096) -> np.ndarray:
    """Indices on the (aggregate, area, power) front, by increasing area.

    Points are taken in (area, power, aggregate) order, so any point that
    dominates another comes before it; each chunk is first screened against
    the front found so far, and the few points left are settled one by
    one.
    """
    agg, area_, power_ = (np.asarray(x, dtype=np.float64)
                          for x in (agg, area_, power_))
    order = np.lexsort((agg, power_, area_))
    front = []
    fa = np.empty(0)
    fp = np.empty(0)
    fg = np.empty(0)
    for lo in range(0, order.size, chunk):
        idx = order[lo:lo + chunk]
        a, p, g = area_[idx], power_[idx], agg[idx]
        if fa.size:
            le = ((fa[:, None] <= a) & (fp[:, None] <= p) & (fg[:, None] <= g))
            lt = ((fa[:, None] < a) | (fp[:, None] < p) | (fg[:, None] < g))
            keep = ~np.any(le & lt, axis=0)
            idx, a, p, g = idx[keep], a[keep], p[keep], g[keep]
        for i, ai, pi, gi in zip(idx, a, p, g):
            dominated = np.any((fa <= ai) & (fp <= pi) & (fg <= gi)
                               & ((fa < ai) | (fp < pi) | (fg < gi)))
            if not dominated:
                front.append(int(i))
                fa, fp, fg = (np.append(fa, ai), np.append(fp, pi),
                              np.append(fg, gi))
    return np.array(front, dtype=np.int64)
