"""Eq. 1 congruence scores over apps x machines, with the default target.

Per app ``a`` and machine ``v`` the serial step time is the sum of three
roofline terms, each scaled by the machine's delay scale for it:

    compute      = flops / peak_flops
    memory       = hbm_bytes / hbm_bw        (bytes_accessed if hbm_bytes is 0)
    interconnect = (collective - pod) / (ici_bw * ici_links)
                   + pod / inter_pod_bw      (0 where pod is 0)

``gamma`` is that time; ``alpha_k`` is the time with term ``k`` idealized
(its scale replaced by ``eps``).  Eq. 1 scores each subsystem,

    score_k = 1 - (alpha_k - beta) / (gamma - beta)     (0 where gamma == beta)

clipped to [0, 1] when ``clamp``, and the aggregate is their L2 norm.  The
default target ``beta`` of an app is its ideal compute time (model FLOPs at
the nominal chip's peak over its devices), floored at half the nominal
chip's ``gamma``, or 5% of that ``gamma`` when the model FLOPs are unknown.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

PROFILE_FIELDS = ("flops", "mem_bytes", "collective_bytes",
                  "pod_collective_bytes", "model_flops", "num_devices")

#: The outputs of one pass, in the order the fused kernel writes them.
OUTPUTS = ("gamma", "alpha_compute", "alpha_memory", "alpha_interconnect",
           "lbcs", "hrcs", "ics", "aggregate")


def profile_columns(profiles, dtype=np.float64) -> dict:
    """Raw per-app columns from ``WorkloadProfile``-like objects."""
    def col(get):
        return np.array([float(get(p)) for p in profiles], dtype=dtype)

    return {
        "flops": col(lambda p: p.flops),
        "mem_bytes": col(lambda p: p.hbm_bytes if p.hbm_bytes > 0
                         else p.bytes_accessed),
        "collective_bytes": col(lambda p: sum(p.collective_bytes.values())),
        "pod_collective_bytes": col(lambda p: p.pod_collective_bytes),
        "model_flops": col(lambda p: p.model_flops),
        "num_devices": col(lambda p: p.num_devices),
    }


def _terms(P: dict, M: dict):
    """Raw (unscaled) compute, memory and interconnect times, (A, V)."""
    flops, mem = P["flops"][:, None], P["mem_bytes"][:, None]
    coll = P["collective_bytes"][:, None]
    pod = P["pod_collective_bytes"][:, None]
    compute = flops / M["peak_flops"][None, :]
    memory = mem / M["hbm_bw"][None, :]
    links = (M["ici_bw"] * M["ici_links"])[None, :]
    with np.errstate(divide="ignore", invalid="ignore"):
        pod_time = np.where(pod != 0, pod / M["inter_pod_bw"][None, :],
                            np.zeros_like(compute))
    interconnect = (coll - pod) / links + pod_time
    return compute, memory, interconnect


def eq1(alpha, gamma, beta):
    denom = gamma - beta
    zero = denom.real == 0
    safe = np.where(zero, np.ones_like(denom), denom)
    return np.where(zero, np.zeros_like(denom), 1 - (alpha - beta) / safe)


def default_beta(P: dict, nominal: dict, dtype=np.float64):
    """Per-app target against the nominal machine (a dict of rates)."""
    M = {k: np.array([float(nominal.get(k, 1.0))], dtype=dtype)
         for k in ("peak_flops", "hbm_bw", "ici_bw", "ici_links",
                   "inter_pod_bw")}
    c, m, i = _terms(P, M)
    gamma = (c + m + i)[:, 0]
    valid = (P["model_flops"] > 0) & (P["num_devices"] > 0)
    with np.errstate(divide="ignore", invalid="ignore"):
        ideal = P["model_flops"] / (P["num_devices"] * M["peak_flops"][0])
    half = gamma * dtype(0.5)
    return np.where(valid, np.minimum(ideal, half), gamma * dtype(0.05))


def congruence(P: dict, M: dict, beta, eps: float, clamp: bool,
               outputs=OUTPUTS) -> dict:
    """The requested ``outputs`` of one pass, each ``(A, V)``."""
    raw = _terms(P, M)
    scales = (M["scale_compute"], M["scale_memory"], M["scale_interconnect"])
    scaled = [s[None, :] * r for s, r in zip(scales, raw)]
    gamma = scaled[0] + scaled[1] + scaled[2]
    b = beta[:, None]
    out = {"gamma": gamma}
    names = (("alpha_compute", "lbcs"), ("alpha_memory", "hrcs"),
             ("alpha_interconnect", "ics"))
    squares = 0
    for k, (alpha_name, score_name) in enumerate(names):
        terms = list(scaled)
        terms[k] = eps * raw[k]
        alpha = terms[0] + terms[1] + terms[2]
        score = eq1(alpha, gamma, b)
        if clamp:
            score = np.clip(score, 0, 1)
        out[alpha_name], out[score_name] = alpha, score
        squares = squares + score * score
    out["aggregate"] = np.sqrt(squares)
    return {k: out[k] for k in outputs}


def block_size(apps: int) -> int:
    """Variants per block: about a million cells, so each of a pass's few
    dozen temporaries stays near 8 MB."""
    return max(128, (1 << 20) // max(apps, 1))


def blocked(P: dict, M: dict, beta, eps: float, clamp: bool, reduce,
            threads: int = 8) -> list:
    """``reduce(lo, outputs)`` of every variant block ``[lo, lo + block)``,
    in block order; blocks are scored on ``threads`` threads, since numpy
    releases the interpreter lock inside its loops."""
    v = len(next(iter(M.values())))
    block = block_size(len(beta))

    def one(lo):
        sub = {k: a[lo:lo + block] for k, a in M.items()}
        return reduce(lo, congruence(P, sub, beta, eps, clamp))

    with ThreadPoolExecutor(max_workers=threads) as pool:
        return list(pool.map(one, range(0, v, block)))
