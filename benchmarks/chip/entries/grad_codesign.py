"""Entry: one ``grad_codesign`` solve per operation.

The solve descends the scalarized objective from the traffic's seed
designs on the x64 jax backend.  Every solve answers with its final
designs, their objectives and the accepted objective of every step.  The
final objectives are compared with the reference objective of the same
designs (``objective_err``) and, for the median design, with the optimum
the reference descent reaches in as many steps (``optimum_gap``); the
first ``COMPARED_STEPS`` steps with the reference descent's
(``descent_err``).  The steps between
are not compared one by one: near convergence a candidate's objective
ties the incumbent's to rounding, an acceptance can go either way, and
the two descents then reach the optimum by slightly different paths.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import compare
import suite
from reference import codesign, congruence, population

THETA = codesign.THETA_FIELDS

#: The precision one step below the descent's float64.
CONTROL_DTYPE = "float32"

#: Descent steps whose accepted objectives are compared, after the seed's.
COMPARED_STEPS = 3


@dataclasses.dataclass
class SolveAnswer:
    objective: np.ndarray     # (V,) reported final objective
    theta: np.ndarray         # (V, 4) log of the reported final rates
    first_steps: np.ndarray   # (COMPARED_STEPS + 1, V) accepted objectives


class Cell:
    def __init__(self, run):
        from repro.core.sweep import MachineBatch

        self.run = run
        self.req = run.traffic
        self.profiles = suite.profiles(run.config, run.seed)
        self.cost = suite.cost_model(run.config)
        self.seeds = MachineBatch.from_models(
            [suite.machine_model(s, s["name"]) for s in self.req["seeds"]])
        self.answers = []

    def _solve(self):
        from repro.core.codesign import grad_codesign

        r = self.req
        return grad_codesign(self.profiles, self.seeds, steps=int(r["steps"]),
                             lr=float(r["lr"]), span=float(r["span"]),
                             eps=float(self.run.config["eps"]),
                             cost_model=self.cost, w_area=float(r["w_area"]),
                             w_power=float(r["w_power"]))

    def warm_up(self) -> None:
        self._solve()

    def op(self) -> None:
        res = self._solve()
        theta = np.log(np.array([[p[f] for f in THETA]
                                 for p in res.final_params]))
        self.answers.append(SolveAnswer(
            np.asarray(res.objective_final), theta,
            np.asarray(res.trajectory[:COMPARED_STEPS + 1])))

    def counts(self, ops: int) -> dict:
        return {"solves": float(ops),
                "descent_steps": float(ops) * int(self.req["steps"])}

    def kernel_work(self, ops: int):
        return None

    def _inputs(self, dtype=np.float64):
        P = congruence.profile_columns(self.profiles, dtype)
        seeds = population.machine(self.req["seeds"], dtype)
        beta = congruence.default_beta(P, self.req["seeds"][0], dtype)
        return P, seeds, beta

    def judge(self):
        P, seeds, beta = self._inputs()
        eps = float(self.run.config["eps"])
        cost = suite.reference_cost(self.run.config)
        _, optimum, ref_steps = codesign.descend(P, seeds, beta, self.req,
                                                 eps, cost)
        ref_steps = ref_steps[:COMPARED_STEPS + 1]
        readings = []
        for ans in self.answers:
            at = codesign.objective(P, ans.theta, seeds, beta, self.req, eps,
                                    codesign.cast_cost(cost, np.float64))
            readings.append({
                "objective_err": compare.rel_err(ans.objective, at),
                "optimum_gap": compare.median_rel_err(ans.objective,
                                                      optimum),
                "descent_err": compare.rel_err(ans.first_steps, ref_steps)})
        return readings

    def control(self, dtype):
        P, seeds, beta = self._inputs(dtype)
        theta, final, steps = codesign.descend(
            P, seeds, beta, self.req, float(self.run.config["eps"]),
            suite.reference_cost(self.run.config), dtype)
        return [SolveAnswer(final, theta, steps[:COMPARED_STEPS + 1])]
