"""The numbers that decide ``correct``: what the timed path answered,
measured against the float64 reference.

A sweep answers with a population (machine rows at some variant indices),
the kernel's outputs on those rows, per-app best fits and the 2-D and 3-D
fronts.  Three numbers judge it:

* ``pop_err``: the largest relative difference of a reported machine
  parameter from the reference population's.
* ``score_err``: the largest difference of a kernel output from the
  reference's.  Times (beta, gamma, the alphas) count relative to their
  size.  A score ``1 - (alpha - beta) / (gamma - beta)`` and the aggregate
  count as their error times ``|gamma - beta| / gamma``: Eq. 1 divides by
  ``gamma - beta``, so a rounding of the inputs by ``d`` moves a score by
  ``d * gamma / |gamma - beta|``, without bound where ``gamma`` meets
  ``beta`` and the clipped score jumps from 0 to 1.  Weighted so, the
  error reads as the relative error of the inputs that would explain it.
* ``choice_gap``: how far the reported choices are from the reference's,
  in units of the aggregate.  A best fit reads the reference aggregate of
  the chosen variant above the reference's minimum.  A front member the
  reference drops reads by how much its best dominator beats it; a
  reference front member the answer drops reads by how much its nearest
  would-be dominator falls short of it.  Near-ties read near 0.

A co-design solve answers with designs, their objectives and the
accepted objective of each descent step.  ``objective_err`` is the
``rel_err`` of each reported objective against the reference objective of
the reported design, ``descent_err`` that of the first steps' objectives
against the reference descent's, and ``optimum_gap`` the ``median_rel_err``
of the final objectives against the reference descent's after as many
steps.  It reads the median design because a design still descending a
narrow valley at the last step ends where the rounding of its gradient
put it, while the others have converged.
"""

from __future__ import annotations

import numpy as np

TIMES = ("gamma", "alpha_compute", "alpha_memory", "alpha_interconnect")

#: Gap recorded for a dropped front member that nothing could dominate.
NO_DOMINATOR = 1.0e3


def _f64(x):
    return np.asarray(x, dtype=np.float64)


def _rel(got, ref):
    """Elementwise relative difference, or None for an answer of the wrong
    shape or not finite."""
    got, ref = _f64(got), _f64(ref)
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        return None
    with np.errstate(divide="ignore", invalid="ignore"):
        err = np.abs(got - ref) / np.abs(ref)
    return np.where(ref == 0, np.abs(got), err)


def rel_err(got, ref) -> float:
    """Largest relative difference; an answer of the wrong shape, or not
    finite, reads infinite."""
    err = _rel(got, ref)
    if err is None:
        return float("inf")
    return float(err.max()) if err.size else 0.0


def median_rel_err(got, ref) -> float:
    """Relative difference of the median element, read like ``rel_err``."""
    err = _rel(got, ref)
    return float("inf") if err is None else float(np.median(err))


def pop_err(got: dict, ref: dict) -> float:
    return max(rel_err(got[k], ref[k]) for k in ref)


def score_err(got: dict, ref: dict, beta_got, beta_ref) -> float:
    """``got``/``ref``: output name -> (A, n) arrays on the same rows."""
    worst = rel_err(beta_got, beta_ref)
    gamma = _f64(ref["gamma"])
    beta = _f64(beta_ref)[:, None]
    with np.errstate(divide="ignore", invalid="ignore"):
        weight = np.where(gamma != 0, np.abs(gamma - beta) / np.abs(gamma),
                          1.0)
    for name in got:
        g, r = _f64(got[name]), _f64(ref[name])
        if name in TIMES:
            worst = max(worst, rel_err(g, r))
        else:
            if g.shape != r.shape or not np.all(np.isfinite(g)):
                return float("inf")
            err = np.abs(g - r) * weight
            worst = max(worst, float(err.max()) if err.size else 0.0)
    return worst


def best_fit_gap(chosen_agg, ref_min) -> float:
    """``chosen_agg[a]``: reference aggregate of app ``a``'s chosen variant."""
    return float(np.max(_f64(chosen_agg) - _f64(ref_min), initial=0.0))


def front_gap(got, ref, agg, axes) -> float:
    """Fronts ``got`` and ``ref`` as variant indices into the reference's
    suite-mean ``agg`` and the cost ``axes`` (area, or area and power)."""
    agg = _f64(agg)
    axes = [_f64(a) for a in axes]
    worst = 0.0
    got_set, ref_set = set(int(i) for i in got), set(int(i) for i in ref)
    for i in got_set ^ ref_set:
        below = np.ones(agg.shape, dtype=bool)
        for a in axes:
            below &= a <= a[i]
        below[i] = False
        if i in got_set:      # reported, but the reference dominates it
            best = agg[below].min(initial=np.inf)
            gap = max(0.0, agg[i] - best) if np.isfinite(best) else 0.0
        else:                 # dropped, though nothing dominates it
            best = agg[below].min(initial=np.inf)
            gap = (max(0.0, best - agg[i]) if np.isfinite(best)
                   else NO_DOMINATOR)
        worst = max(worst, float(gap))
    return worst
