"""The scalarized co-design objective and its backtracking descent.

For machine ``v`` with log-rates ``theta[v]`` (peak_flops, hbm_bw, ici_bw,
inter_pod_bw; link count and delay scales held at the seed's values):

    J(v) = mean over apps of the unclamped Eq. 1 aggregate
           + w_area * area(v) + w_power * power(v)

The descent starts from the seed designs, clipped to ``log(seed) +-
log(span)``, and repeats: take the gradient, step by ``lr`` per variant,
clip, and accept the candidate only where it lowers ``J``; the step grows
by 1.2 on acceptance and halves otherwise.  The gradient is taken by the
complex step (``Im J(theta + i h e_j) / h``), which is exact to rounding
and needs no second implementation of the derivative.
"""

from __future__ import annotations

import numpy as np

from . import congruence, pareto

THETA_FIELDS = ("peak_flops", "hbm_bw", "ici_bw", "inter_pod_bw")

_COMPLEX = {np.dtype(np.float64): np.complex128,
            np.dtype(np.float32): np.complex64}


def _machines(theta, fixed: dict) -> dict:
    M = dict(fixed)
    for j, name in enumerate(THETA_FIELDS):
        M[name] = np.exp(theta[:, j])
    return M


def objective(P: dict, theta, fixed: dict, beta, req: dict, eps: float,
              cost: dict):
    """``(V,)`` objective at log-rates ``theta`` (real or complex)."""
    M = _machines(theta, fixed)
    agg = congruence.congruence(P, M, beta, eps, clamp=False,
                                outputs=("aggregate",))["aggregate"]
    return (agg.mean(axis=0) + req["w_area"] * pareto.area(cost, M)
            + req["w_power"] * pareto.power(cost, M))


def gradient(P, theta, fixed, beta, req, eps, cost):
    """Per-variant gradient of ``objective`` by the complex step."""
    ctype = _COMPLEX[theta.dtype]
    h = 1e-30 if ctype is np.complex128 else 1e-20
    Pc = {k: v.astype(ctype) for k, v in P.items()}
    fixed_c = {k: v.astype(ctype) for k, v in fixed.items()}
    beta_c = beta.astype(ctype)
    grad = np.empty_like(theta)
    for j in range(theta.shape[1]):
        probe = theta.astype(ctype)
        probe[:, j] += 1j * h
        grad[:, j] = objective(Pc, probe, fixed_c, beta_c, req, eps,
                               cost).imag / h
    return grad


def descend(P: dict, seeds: dict, beta, req: dict, eps: float, cost: dict,
            dtype=np.float64):
    """Run the descent from machine columns ``seeds``; returns the final
    ``theta`` (V, 4), the final objective (V,) and the accepted objective
    of the seed and of every step (steps + 1, V)."""
    P = {k: v.astype(dtype) for k, v in P.items()}
    fixed = {k: np.asarray(v, dtype=dtype) for k, v in seeds.items()}
    beta = np.asarray(beta, dtype=dtype)
    cost = cast_cost(cost, dtype)
    theta0 = np.log(np.stack([fixed[f] for f in THETA_FIELDS], axis=1))
    width = np.log(dtype(req["span"]))
    lo, hi = theta0 - width, theta0 + width
    theta = np.clip(theta0, lo, hi)
    f = objective(P, theta, fixed, beta, req, eps, cost)
    lr = np.full(theta.shape[0], req["lr"], dtype=dtype)
    history = [f]
    for _ in range(int(req["steps"])):
        g = gradient(P, theta, fixed, beta, req, eps, cost)
        cand = np.clip(theta - lr[:, None] * g, lo, hi)
        f_new = objective(P, cand, fixed, beta, req, eps, cost)
        ok = f_new < f
        theta = np.where(ok[:, None], cand, theta)
        f = np.where(ok, f_new, f)
        lr = np.where(ok, lr * dtype(1.2), lr * dtype(0.5))
        history.append(f)
    return theta, f, np.stack(history)


def cast_cost(cost: dict, dtype) -> dict:
    out = dict(cost)
    out["reference"] = {k: np.asarray(v, dtype=dtype)
                        for k, v in cost["reference"].items()}
    return out
