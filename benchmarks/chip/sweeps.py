"""What a sweep answers, and how an answer is judged against the reference.

Both sweep entries (``entries/shard_sweep.py``, ``entries/run_sweep.py``)
reduce the program's result to a ``SweepAnswer``; ``judge`` runs the
float64 reference over the same population once and reads the three
numbers of ``compare`` for every answer; ``control_answer`` is the
reference itself, one precision lower, in the program's place.
"""

from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional

import numpy as np

import compare
import suite
from reference import congruence, population, sweep

THREADS = min(8, os.cpu_count() or 1)


@dataclasses.dataclass
class SweepAnswer:
    best_fit: np.ndarray              # (A,) variant index per app
    front2: np.ndarray                # variant indices
    front3: np.ndarray
    indices: Optional[np.ndarray] = None   # rows reported below; None: all
    machines: Optional[dict] = None        # machine columns on those rows
    beta: Optional[np.ndarray] = None
    outputs: Optional[dict] = None         # output name -> (A, rows)


def outputs_of(res) -> dict:
    """A ``SweepResult``'s output rows under the reference's names."""
    return {"gamma": res.gamma,
            "alpha_compute": res.alphas["compute"],
            "alpha_memory": res.alphas["memory"],
            "alpha_interconnect": res.alphas["interconnect"],
            "lbcs": res.scores["LBCS"], "hrcs": res.scores["HRCS"],
            "ics": res.scores["ICS"], "aggregate": res.aggregate}


def choice_gap(ans: SweepAnswer, sol: dict, chosen_agg) -> float:
    return max(compare.best_fit_gap(chosen_agg, sol["min"]),
               compare.front_gap(ans.front2, sol["front2"], sol["mean"],
                                 [sol["area"]]),
               compare.front_gap(ans.front3, sol["front3"], sol["mean"],
                                 [sol["area"], sol["power"]]))


def _score_err(ans: SweepAnswer, P, M, beta, eps, clamp) -> float:
    """``compare.score_err`` of the reported rows, in column blocks."""
    idx = (np.arange(len(M["peak_flops"])) if ans.indices is None
           else np.asarray(ans.indices))
    block = congruence.block_size(len(beta))

    def one(lo):
        cols = idx[lo:lo + block]
        full = sweep.rows(P, M, cols, beta, eps, clamp)
        got = {k: v[:, lo:lo + block] for k, v in ans.outputs.items()}
        ref = {k: full[k] for k in got}
        ref["gamma"] = full["gamma"]
        return compare.score_err(got, ref, ans.beta, beta)

    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        return max(pool.map(one, range(0, idx.size, block)))


def judge(answers: List[SweepAnswer], profiles, config: dict, seed: int,
          variants: int, clamp: bool) -> List[dict]:
    eps = float(config["eps"])
    P = congruence.profile_columns(profiles)
    beta = congruence.default_beta(P, config["space"]["nominal"])
    M = population.population(config["space"], np.arange(variants), seed)
    sol = sweep.solve(P, M, beta, eps, clamp, suite.reference_cost(config),
                      THREADS)
    apps = np.arange(len(beta))
    readings = []
    for ans in answers:
        r = {}
        if ans.machines is not None:
            idx = (slice(None) if ans.indices is None
                   else np.asarray(ans.indices))
            r["pop_err"] = compare.pop_err(
                ans.machines, {k: M[k][idx] for k in ans.machines})
        if ans.outputs is not None:
            r["score_err"] = _score_err(ans, P, M, beta, eps, clamp)
        chosen = np.unique(ans.best_fit)
        agg = sweep.rows(P, M, chosen, beta, eps, clamp,
                         ("aggregate",))["aggregate"]
        pos = np.searchsorted(chosen, ans.best_fit)
        r["choice_gap"] = choice_gap(ans, sol, agg[apps, pos])
        readings.append(r)
    return readings


def control_answer(profiles, config: dict, seed: int, variants: int,
                   clamp: bool, dtype, full: bool) -> SweepAnswer:
    """The reference computed in ``dtype`` answering in the program's
    place: best fits, fronts and, on the rows it reports (all of them when
    ``full``, else the union of its choices), the population and
    outputs."""
    eps = float(config["eps"])
    P = congruence.profile_columns(profiles, dtype)
    beta = congruence.default_beta(P, config["space"]["nominal"], dtype)
    M = population.population(config["space"], np.arange(variants), seed,
                              dtype)
    cost = suite.reference_cost(config)
    sol = sweep.solve(P, M, beta, eps, clamp, cost, THREADS)
    if full:
        idx = np.arange(variants)
    else:
        idx = np.unique(np.concatenate([sol["best_fit"], sol["front2"],
                                        sol["front3"]]))
    return SweepAnswer(
        best_fit=sol["best_fit"], front2=sol["front2"], front3=sol["front3"],
        indices=idx, machines={k: M[k][idx] for k in M}, beta=beta,
        outputs=sweep.rows(P, M, idx, beta, eps, clamp))
