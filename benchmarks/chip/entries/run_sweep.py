"""Entry: one materialized ``run_sweep`` per operation.

The operation returns the full score tensor, all eight output rows per
(app, variant) cell, then per-app best fits and both fronts.  Every
operation's choices are compared with the reference; the full tensor and
population of one operation, drawn from the seed, are compared cell by
cell (keeping every operation's tensor would not fit the host).
"""

from __future__ import annotations

import numpy as np

import roofline
import suite
import sweeps
from reference.population import MACHINE_FIELDS

#: The precision one step below the kernel's float32.
CONTROL_DTYPE = "bfloat16"


class Cell:
    def __init__(self, run):
        self.run = run
        self.variants = int(run.traffic["variants"])
        self.clamp = bool(run.traffic["clamp"])
        self.profiles = suite.profiles(run.config, run.seed)
        self.space = suite.param_space(run.config)
        self.cost = suite.cost_model(run.config)
        self.nominal = suite.nominal_model(run.config)
        self.answers = []
        self._rng = np.random.default_rng([run.seed, 1])
        self._kept = None

    def _sweep(self):
        from repro.core.sweep import run_sweep

        res = run_sweep(self.profiles, space=self.space, n=self.variants,
                        seed=self.run.seed,
                        backend=self.run.traffic["backend"],
                        clamp=self.clamp)
        return (res, res.best_fit_indices(),
                res.pareto_front(reference=self.nominal),
                res.pareto_front_3d(cost_model=self.cost))

    def warm_up(self) -> None:
        self._sweep()

    def op(self) -> None:
        res, best, front2, front3 = self._sweep()
        ans = sweeps.SweepAnswer(
            best_fit=np.asarray(best, dtype=np.int64),
            front2=np.asarray(front2, dtype=np.int64),
            front3=np.asarray(front3, dtype=np.int64))
        self.answers.append(ans)
        # one operation's full result, drawn from the seed (reservoir)
        if self._rng.random() * len(self.answers) < 1.0:
            if self._kept is not None:
                self._kept.machines = self._kept.beta = None
                self._kept.outputs = None
            ans.machines = {k: np.asarray(getattr(res.machines, k))
                            for k in MACHINE_FIELDS}
            ans.beta = np.asarray(res.beta)
            ans.outputs = sweeps.outputs_of(res)
            self._kept = ans

    def counts(self, ops: int) -> dict:
        return {"cells": float(ops) * len(self.profiles) * self.variants}

    def kernel_work(self, ops: int) -> tuple:
        """Every operation's check demands all eight outputs."""
        f, b = roofline.full_pass(len(self.profiles), self.variants)
        return ops * f, ops * b


    def judge(self):
        return sweeps.judge(self.answers, self.profiles, self.run.config,
                            self.run.seed, self.variants, self.clamp)

    def control(self, dtype):
        return [sweeps.control_answer(self.profiles, self.run.config,
                                      self.run.seed, self.variants,
                                      self.clamp, dtype, full=True)]
