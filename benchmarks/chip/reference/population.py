"""Machine populations: a rotated Halton sequence over the design space.

Row ``i`` of a population of seed ``s`` has one coordinate per swept
parameter ``j``: the radical inverse of ``i + 1`` in the ``j``-th prime,
shifted by a Cranley-Patterson rotation drawn as
``numpy.random.default_rng(s).random(d)`` and taken modulo 1.  A unit
coordinate ``u`` maps onto ``[lo, hi]`` geometrically (``lo * (hi/lo)**u``)
or, for an integer parameter, to ``floor(lo + (hi - lo + 1) * u)``.
Parameters the space does not sweep stay at the nominal machine's value.
"""

from __future__ import annotations

import numpy as np

PRIMES = (2, 3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37)

#: Every machine column, in the order the cells report them.
MACHINE_FIELDS = ("peak_flops", "hbm_bw", "ici_bw", "ici_links",
                  "inter_pod_bw", "scale_compute", "scale_memory",
                  "scale_interconnect")

RATES = ("peak_flops", "hbm_bw", "ici_bw", "inter_pod_bw")


def design_space(space: dict) -> list:
    """The swept dimensions of a configuration's ``space`` block, in order:
    ``(name, lo, hi, integer)``.  Rates span ``span`` times below and above
    the nominal chip; the link count runs from 1 to ``max_links``."""
    nominal, span = space["nominal"], float(space["span"])
    dims = []
    for name in ("peak_flops", "hbm_bw", "ici_bw", "ici_links",
                 "inter_pod_bw"):
        if name == "ici_links":
            dims.append((name, 1.0, float(space["max_links"]), True))
        else:
            rate = float(nominal[name])
            dims.append((name, rate / span, rate * span, False))
    return dims


def radical_inverse(index: np.ndarray, base: int) -> np.ndarray:
    """Van der Corput radical inverse of each non-negative ``index``."""
    n = np.array(index, dtype=np.int64)
    out = np.zeros(n.shape, dtype=np.float64)
    weight = 1.0 / base
    while n.any():
        n, digit = np.divmod(n, base)
        out += weight * digit
        weight /= base
    return out


def unit_rows(indices, dims: int, seed: int) -> np.ndarray:
    """``(len(indices), dims)`` rotated Halton points in [0, 1)."""
    idx = np.asarray(indices, dtype=np.int64)
    shift = np.random.default_rng(seed).random(dims)
    out = np.empty((idx.size, dims), dtype=np.float64)
    for j in range(dims):
        out[:, j] = (radical_inverse(idx + 1, PRIMES[j]) + shift[j]) % 1.0
    return out


def population(space: dict, indices, seed: int, dtype=np.float64) -> dict:
    """Machine columns of rows ``indices`` of the seed's population."""
    dims = design_space(space)
    u = unit_rows(indices, len(dims), seed).astype(dtype)
    one = np.ones(u.shape[0], dtype=dtype)
    cols = {}
    for j, (name, lo, hi, integer) in enumerate(dims):
        lo_, hi_ = dtype(lo), dtype(hi)
        if integer:
            cols[name] = np.clip(np.floor(lo_ + (hi_ - lo_ + one) * u[:, j]),
                                 lo_, hi_)
        else:
            cols[name] = lo_ * (hi_ / lo_) ** u[:, j]
    for name in MACHINE_FIELDS:
        if name not in cols:
            value = space["nominal"].get(name, 1.0)
            cols[name] = np.full(u.shape[0], value, dtype=dtype)
    return cols


def machine(values: dict, dtype=np.float64) -> dict:
    """Columns of the named machines ``values`` (a list of rate dicts)."""
    return {name: np.array([float(v.get(name, 1.0)) for v in values],
                           dtype=dtype)
            for name in MACHINE_FIELDS}
