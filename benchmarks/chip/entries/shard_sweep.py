"""Entry: one streamed ``shard_sweep`` per operation.

The operation is what an architect runs for a large design space: the
population streamed shard by shard from the seed, scored on the fused
kernel (under ``shard_map`` on the traffic's mesh), reduced on the device,
pre-filtered on the host, the survivors re-scored, then per-app best fits
and both fronts.  Every operation answers the same question, so each is
compared with one reference solve.
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
        self.mesh = None
        self.answers = []

    def _sweep(self):
        from repro.core.sweep import shard_sweep

        res = shard_sweep(self.profiles, space=self.space, n=self.variants,
                          seed=self.run.seed, stream=True,
                          backend=self.run.traffic["backend"],
                          mesh=self.mesh, clamp=self.clamp,
                          cost_model=self.cost)
        best = [res.best_fit(app) for app in res.apps]
        return res, best, res.pareto_front(), res.pareto_front_3d()

    def warm_up(self) -> None:
        from repro.launch.mesh import make_variant_mesh

        self.mesh = make_variant_mesh(int(self.run.traffic["devices"]))
        self._sweep()

    def op(self) -> None:
        res, best, front2, front3 = self._sweep()
        cand = np.asarray(res.candidate_indices)
        names = res.result.machines.names
        where = {n: int(cand[j]) for j, n in enumerate(names)}
        machines = res.result.machines
        self.shards = res.num_shards
        self.answers.append(sweeps.SweepAnswer(
            best_fit=np.array([where[n] for n in best], dtype=np.int64),
            front2=cand[np.asarray(front2, dtype=np.int64)],
            front3=cand[np.asarray(front3, dtype=np.int64)],
            indices=cand,
            machines={k: np.asarray(getattr(machines, k))
                      for k in MACHINE_FIELDS},
            beta=np.asarray(res.result.beta),
            outputs=sweeps.outputs_of(res.result)))

    def counts(self, ops: int) -> dict:
        return {"cells": float(ops) * len(self.profiles) * self.variants}

    def kernel_work(self, ops: int) -> tuple:
        """Operations and bytes the checks demand of the window's kernel
        calls: per shard the suite means and per-app minima, then all
        outputs of the survivors."""
        a = len(self.profiles)
        flops = nbytes = 0.0
        shard = -(-self.variants // self.shards)
        for ans in self.answers[:ops]:
            f, b = roofline.stats_pass(a, shard)
            flops += self.shards * f
            nbytes += self.shards * b
            f, b = roofline.full_pass(a, len(ans.indices))
            flops, nbytes = flops + f, nbytes + b
        return flops, nbytes

    def judge(self):
        return sweeps.judge(self.answers, self.profiles, self.run.config,
                            self.run.seed, self.variants, self.clamp)

    def control(self, dtype):
        return [sweeps.control_answer(self.profiles, self.run.config,
                                      self.run.seed, self.variants,
                                      self.clamp, dtype, full=False)]

