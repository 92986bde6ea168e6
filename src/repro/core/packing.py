"""Multi-tenant packing: A apps across M machine instances (ROADMAP item).

The task-partitioning-and-floorplanning scenario from PAPERS.md asked the
natural question after single-machine co-design: given a FLEET of ``M``
machine instances and ``A`` applications, which apps should live on which
machine, and what should each machine look like, under per-subsystem
envelopes and a TOTAL silicon budget shared by the whole fleet?

``pack_codesign`` answers by alternation, reusing the group-axis
machinery of ``joint_codesign`` with the roles transposed -- there, each
app GROUP picks one sharding variant per machine; here, each APP picks
one machine instance:

  * assignment step -- the ``(A, M)`` aggregate-congruence matrix under
    the current fleet hardens to a one-hot argmin per app (or relaxes to
    an annealed softmax in ``mode="softmax"``);
  * descent step -- all ``M`` machines descend JOINTLY as one flattened
    ``(1, M*D)`` log-rate vector through the shared
    ``backtracking_descent``, so the fleet-total budget couples them
    while the assignment weights decouple the fit terms.

The retraction composes the per-machine operators of
``repro.core.constrained`` (span-clip box ∩ per-subsystem envelope, per
instance) with a FLEET budget projection: one scalar downward log-shift
applied to every machine, bisected so the summed area/power meets the
total budget -- monotone in the shift, so the bisection is exact to f64
resolution, and rate decreases preserve envelope feasibility.

A ``budgets`` schedule traces the fleet-level frontier J*(total budget)
by warm-started continuation exactly like ``frontier_codesign`` (budget
enters the retraction as a traced scalar; one compile serves the whole
schedule; monotone propagation carries tighter-budget incumbents to
looser budgets).  ``PackingResult`` implements the uniform
``markdown(top_k)`` / ``to_json(top_k)`` protocol, so packing requests
serve through ``repro.serving`` unchanged.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Mapping, Optional, Sequence

import numpy as np

from repro.core import kernels_xp as K
from repro.core.codesign import (
    OPT_FIELDS,
    _compiled,
    _jax_x64,
    _suite_aggregate,
    _suite_objective,
    backtracking_descent,
    descent_suite,
    machine_arrays_from_theta,
    params_of_theta,
)
from repro.core.constrained import (
    FEASIBLE_RTOL,
    PROJECT_ITERS,
    _bisect,
    _budget_args,
    budget_feasible,
    project_to_budgets,
    validate_area_envelope,
)
from repro.core.costmodel import DEFAULT_COST_MODEL, CostModel
from repro.core.sweep import MachineBatch, _as_machine_batch

#: Packing assignment modes (mirrors ``joint_codesign``).
PACK_MODES = ("alternate", "softmax")

_PACK_DEFAULTS = dict(
    mode="alternate", steps=60, lr=0.1, span=16.0, beta=None,
    timing_model="serial", cost_model=DEFAULT_COST_MODEL,
    w_area=0.1, w_power=0.05, area_budget=None, power_budget=None,
    area_envelope=None, budgets=None, num_machines=4,
)


# --------------------------------------------------------------------------- #
# Assignment weights and the fleet objective
# --------------------------------------------------------------------------- #


def _pack_weights(agg: np.ndarray) -> np.ndarray:
    """``(A, M)`` one-hot-per-app weights: app ``a`` puts ``1/A`` on its
    argmin machine, so summing ``w * agg`` over both axes is the mean
    assigned aggregate (the transpose of ``joint``'s ``_hard_weights``)."""
    a, _ = agg.shape
    w = np.zeros_like(agg)
    w[np.arange(a), np.argmin(agg, axis=1)] = 1.0 / a
    return w


def _soft_weights(agg: np.ndarray, temp: float) -> np.ndarray:
    """Annealed-softmax assignment: rows sum to ``1/A``; hardens to
    ``_pack_weights`` as ``temp -> 0``."""
    a, _ = agg.shape
    z = -(agg - agg.min(axis=1, keepdims=True)) / max(temp, 1e-9)
    e = np.exp(z)
    return e / e.sum(axis=1, keepdims=True) / a


def fleet_objective(
    profiles,
    machines,
    *,
    beta=None,
    beta_ref: int = 0,
    timing_model: str = "serial",
    eps: float = K.IDEAL_EPS,
    cost_model: CostModel = DEFAULT_COST_MODEL,
    w_area: float = 0.1,
    w_power: float = 0.05,
) -> float:
    """Best-assignment fleet J for ANY fleet (NumPy reference, scalar).

    Every app is assigned to its argmin machine; silicon terms sum over
    the whole fleet -- the exact objective ``pack_codesign`` descends, so
    this is the yardstick for comparing a packed fleet against, e.g., M
    copies of the best single-machine design (the acceptance pin in
    tests/test_packing.py).
    """
    s = descent_suite(profiles, machines, beta=beta, beta_ref=beta_ref)
    m = s.fixed
    agg = np.asarray(K.congruence_kernel(np, s.p, m, s.beta, timing_model,
                                         eps, clamp=False).aggregate)
    fit = float(agg.min(axis=1).mean())
    return (fit + w_area * float(np.sum(cost_model.area(m)))
            + w_power * float(np.sum(cost_model.power(m))))


# --------------------------------------------------------------------------- #
# Fleet-total budget projection (one scalar shift across all machines)
# --------------------------------------------------------------------------- #


def _fleet_shift(xp, th, lo, fixed, cost_model: CostModel, area_budget,
                 power_budget, iters: int = PROJECT_ITERS):
    """Retract an ``(M, D)`` fleet onto the TOTAL-budget sublevel set.

    One scalar downward log-shift ``t`` (a uniform multiplicative rescale
    of every rate on every machine), floored at the box's lower edge,
    bisected to the smallest ``t >= 0`` with ``sum(area) <= area_budget``
    (and ``sum(power) <= power_budget`` when set).  Every summed quantity
    is strictly increasing in every rate, so feasibility is monotone in
    ``t`` and the bisection is exact to f64 resolution; shifting DOWN
    also preserves any per-machine envelope feasibility established
    before the call.  ``area_budget`` may be a traced scalar -- the
    frontier continuation compiles this once for its whole schedule.
    """

    def at(t):
        return xp.maximum(th - t, lo)

    def ok(t):
        m = machine_arrays_from_theta(xp, at(t), fixed)
        good = xp.asarray(True)
        if area_budget is not None:
            good = good & (xp.sum(cost_model.area(m)) <= area_budget)
        if power_budget is not None:
            good = good & (xp.sum(cost_model.power(m)) <= power_budget)
        return good

    zero = xp.zeros(())
    t_hi = _bisect(xp, ok, xp.max(th - lo), iters)
    return at(xp.where(ok(zero), zero, t_hi))


def _fleet_retraction(xp, th_flat, lo, hi, fixed, area_budget,
                      power_budget, area_envelope, *, cost_model: CostModel):
    """Retract the flattened ``(1, M*D)`` fleet: each machine onto its box
    ∩ envelope first (a clip with no envelope), then the fleet-total shift
    when a total budget is set -- which only lowers rates, keeping the
    per-machine feasibility just won.  Every array and budget is an
    argument."""
    th, _ = project_to_budgets(xp, th_flat.reshape(lo.shape), lo, hi, fixed,
                               cost_model, None, None,
                               area_envelope=area_envelope)
    if area_budget is not None or power_budget is not None:
        th = _fleet_shift(xp, th, lo, fixed, cost_model, area_budget,
                          power_budget)
    return th.reshape(1, -1)


def _fleet_objective(xp, th_flat, p, fixed, beta, weights, **settings):
    """The fleet's J as one ``(1,)`` row for the shared descent: summing
    the per-machine terms folds the assignment-weighted fit (rows of
    ``weights`` sum to 1/A) and the fleet silicon into one scalar."""
    theta = th_flat.reshape(fixed.peak_flops.shape[0], -1)
    return xp.sum(_suite_objective(xp, theta, p, fixed, beta, weights,
                                   **settings))[None]


def _fleet_feasible(m: K.MachineArrays, cost_model: CostModel,
                    area_budget: Optional[float],
                    power_budget: Optional[float],
                    area_envelope: Optional[Mapping[str, float]],
                    rtol: float = FEASIBLE_RTOL) -> bool:
    """Fleet-total budgets + every machine's envelope, to relative rtol."""
    ok = True
    if area_budget is not None:
        ok &= float(np.sum(cost_model.area(m))) <= area_budget * (1.0 + rtol)
    if power_budget is not None:
        ok &= float(np.sum(cost_model.power(m))) <= power_budget * (1.0 + rtol)
    if area_envelope:
        ok &= bool(np.all(budget_feasible(
            np, m, cost_model, None, None, rtol=rtol,
            area_envelope=area_envelope)))
    return bool(ok)


# --------------------------------------------------------------------------- #
# Result
# --------------------------------------------------------------------------- #


@dataclasses.dataclass
class PackingResult:
    """Outcome of one multi-tenant packing run.

    ``assignment[a]`` is the machine index app ``a`` landed on;
    ``trajectory`` is the accepted-objective history (monotone
    non-increasing in ``mode="alternate"`` -- descent steps only accept
    improvements and argmin re-assignment only lowers the fit term).
    When a ``budgets`` schedule was traced, the ``frontier_*`` arrays
    hold J*(total budget) ascending by budget and the main fields
    describe the TIGHTEST budget's fleet.

    Implements the uniform result protocol (``markdown(top_k)`` /
    ``to_json(top_k)``), so the serving front door renders it unchanged.
    """

    app_names: List[str]
    machine_names: List[str]
    assignment: np.ndarray            # (A,) machine index per app
    machines: MachineBatch            # the final fleet (M rows)
    seed_params: List[Dict[str, float]]
    final_params: List[Dict[str, float]]
    objective_seed: float
    objective_final: float
    trajectory: np.ndarray            # accepted fleet-J history
    per_app_aggregate: np.ndarray     # (A,) aggregate at the assigned machine
    area_total: float
    power_total: float
    feasible: Optional[bool]          # None when unconstrained
    mode: str = "alternate"
    steps: int = 0
    rounds: int = 0
    w_area: float = 0.1
    w_power: float = 0.05
    area_budget: Optional[float] = None      # fleet TOTAL
    power_budget: Optional[float] = None     # fleet TOTAL
    area_envelope: Optional[Dict[str, float]] = None
    budgets: Optional[np.ndarray] = None          # frontier schedule (asc)
    frontier_objective: Optional[np.ndarray] = None
    frontier_area: Optional[np.ndarray] = None
    frontier_feasible: Optional[np.ndarray] = None

    # ------------------------------ lookups --------------------------- #

    @property
    def improvement(self) -> float:
        return self.objective_seed - self.objective_final

    def apps_on(self, machine: int) -> List[str]:
        """App names assigned to machine index ``machine``."""
        return [a for a, mi in zip(self.app_names, self.assignment)
                if int(mi) == machine]

    # ------------------------------ reports --------------------------- #

    def markdown(self, top_k: Optional[int] = None) -> str:
        """Fleet table + assignment summary (``top_k`` caps listed app
        names per machine and frontier rows; None means the default 10,
        per the uniform result protocol)."""
        top_k = 10 if top_k is None else top_k
        m = self.machines
        lines = [
            f"packing: {len(self.app_names)} apps across "
            f"{len(m)} machines (pack-{self.mode}, {self.steps} steps, "
            f"{self.rounds} rounds)",
            f"objective: {self.objective_seed:.4f} -> "
            f"{self.objective_final:.4f} "
            f"(improvement {self.improvement:.4f})",
            f"fleet: area={self.area_total:.3f}"
            + (f" (budget {self.area_budget:.3f})"
               if self.area_budget is not None else "")
            + f" power={self.power_total:.3f}"
            + (f" (budget {self.power_budget:.3f})"
               if self.power_budget is not None else "")
            + ("" if self.feasible is None
               else f" feasible={bool(self.feasible)}"),
            "",
            "| machine | apps | mean agg | area | peak_flops | hbm_bw "
            "| ici_bw x links | inter_pod_bw |",
            "|---" * 8 + "|",
        ]
        for i in range(len(m)):
            rows = np.nonzero(self.assignment == i)[0]
            mean_agg = (float(self.per_app_aggregate[rows].mean())
                        if len(rows) else float("nan"))
            area_i = float(DEFAULT_COST_MODEL.area(m.take([i]))[0])
            lines.append(
                f"| {m.names[i]} | {len(rows)} | {mean_agg:.4f} "
                f"| {area_i:.3f} | {m.peak_flops[i]:.3e} "
                f"| {m.hbm_bw[i]:.3e} "
                f"| {m.ici_bw[i]:.3e} x {int(m.ici_links[i])} "
                f"| {m.inter_pod_bw[i]:.3e} |")
        lines.append("")
        for i in range(len(m)):
            apps = self.apps_on(i)
            shown = ", ".join(apps[:top_k])
            more = f" (+{len(apps) - top_k} more)" if len(apps) > top_k else ""
            lines.append(f"- {m.names[i]}: {shown or '(idle)'}{more}")
        if self.budgets is not None:
            lines += ["", f"fleet frontier J*(total budget) "
                          f"({len(self.budgets)} budgets, ascending):", ""]
            for j, b in enumerate(self.budgets[:top_k]):
                feas = bool(self.frontier_feasible[j])
                lines.append(
                    f"- budget {float(b):.3f}: "
                    f"J*={float(self.frontier_objective[j]):.4f} "
                    f"area={float(self.frontier_area[j]):.3f} "
                    f"{'feasible' if feas else 'INFEASIBLE'}")
        return "\n".join(lines)

    def to_json(self, top_k: Optional[int] = None) -> dict:
        top_k = 10 if top_k is None else top_k
        out = {
            "num_apps": len(self.app_names),
            "num_machines": len(self.machines),
            "mode": f"pack-{self.mode}",
            "steps": self.steps,
            "rounds": self.rounds,
            "objective_seed": self.objective_seed,
            "objective_final": self.objective_final,
            "improvement": self.improvement,
            "area_total": self.area_total,
            "power_total": self.power_total,
            "feasible": (None if self.feasible is None
                         else bool(self.feasible)),
            "area_budget": self.area_budget,
            "power_budget": self.power_budget,
            "area_envelope": (dict(self.area_envelope)
                              if self.area_envelope else None),
            "assignment": {app: self.machines.names[int(mi)]
                           for app, mi in zip(self.app_names,
                                              self.assignment)},
            "machines": [
                {"machine": self.machines.names[i],
                 "num_apps": int(np.sum(self.assignment == i)),
                 "apps": self.apps_on(i)[:top_k],
                 "params": self.final_params[i]}
                for i in range(len(self.machines))],
            "trajectory": [float(v) for v in self.trajectory],
        }
        if self.budgets is not None:
            out["frontier"] = [
                {"budget": float(b),
                 "objective": float(self.frontier_objective[j]),
                 "area_total": float(self.frontier_area[j]),
                 "feasible": bool(self.frontier_feasible[j])}
                for j, b in enumerate(self.budgets)]
        return out


# --------------------------------------------------------------------------- #
# The packing descent
# --------------------------------------------------------------------------- #


def pack_codesign(
    profiles,
    machines,
    *,
    num_machines: Optional[int] = None,
    mode: Optional[str] = None,
    rounds: int = 4,
    steps: Optional[int] = None,
    lr: Optional[float] = None,
    span: Optional[float] = None,
    beta=None,
    beta_ref: int = 0,
    timing_model: Optional[str] = None,
    eps: float = K.IDEAL_EPS,
    cost_model: Optional[CostModel] = None,
    w_area: Optional[float] = None,
    w_power: Optional[float] = None,
    area_budget: Optional[float] = None,
    power_budget: Optional[float] = None,
    area_envelope: Optional[Mapping[str, float]] = None,
    budgets: Optional[Sequence[float]] = None,
    temp0: float = 1.0,
    temp_min: float = 0.05,
    spec=None,
) -> PackingResult:
    """Assign ``A`` apps across ``num_machines`` instances by alternation.

    ``profiles`` accepts everything suite strings are accepted as
    elsewhere (a ``gen:<count>`` generated suite, a zoo suite, a profile
    list or a ``ProfileBatch``).  ``machines`` seeds the fleet: its rows
    are cycled up to ``num_machines`` instances, each descending its own
    log-rates.  ``area_budget`` / ``power_budget`` bound the fleet TOTAL
    (not each instance); ``area_envelope`` caps each instance
    per-subsystem, exactly as in ``constrained_codesign``.

    ``mode="alternate"`` hardens the assignment to each app's argmin
    machine between descent rounds (the round boundary is monotone:
    re-assignment can only lower the objective).  ``mode="softmax"``
    anneals a soft assignment from ``temp0`` down to ``temp_min`` and
    hardens at the end; an incumbent under the HARD assignment is tracked
    throughout, so the reported result never regresses past the seed.

    A ``budgets`` schedule traces J*(total budget) by warm-started
    continuation (ascending, validated like ``frontier_codesign``); the
    result's main fields then describe the tightest budget's fleet.

    >>> from repro.core import VARIANTS, pack_codesign
    >>> res = pack_codesign("gen:6", VARIANTS, num_machines=2,
    ...                     rounds=2, steps=4)
    >>> len(res.machines), len(res.assignment)
    (2, 6)
    >>> bool(res.objective_final <= res.objective_seed + 1e-12)
    True
    """
    from repro.core.frontier import _validate_budget_schedule
    from repro.core.spec import resolve_spec

    r = resolve_spec(spec, _PACK_DEFAULTS, dict(
        mode=mode, steps=steps, lr=lr, span=span, beta=beta,
        timing_model=timing_model, cost_model=cost_model, w_area=w_area,
        w_power=w_power, area_budget=area_budget, power_budget=power_budget,
        area_envelope=area_envelope, budgets=budgets,
        num_machines=num_machines))
    mode, steps, lr, span, beta = (r["mode"], r["steps"], r["lr"], r["span"],
                                   r["beta"])
    timing_model, cost_model = r["timing_model"], r["cost_model"]
    w_area, w_power = r["w_area"], r["w_power"]
    area_budget, power_budget = r["area_budget"], r["power_budget"]
    envelope = validate_area_envelope(r["area_envelope"])
    budgets, num_machines = r["budgets"], int(r["num_machines"])

    if mode not in PACK_MODES:
        raise ValueError(f"unknown packing mode {mode!r}; have {PACK_MODES}")
    if num_machines < 1:
        raise ValueError(f"num_machines must be >= 1, got {num_machines}")
    for name, b in (("area_budget", area_budget),
                    ("power_budget", power_budget)):
        if b is not None and not b > 0.0:
            raise ValueError(f"{name} must be positive, got {b!r}")
    schedule = (None if budgets is None
                else [float(b) for b in _validate_budget_schedule(budgets)])
    if schedule is not None and area_budget is not None:
        raise ValueError("pass either area_budget (one fleet budget) or "
                         "budgets (a frontier schedule), not both")

    jax, jnp, x64 = _jax_x64()
    seeds = _as_machine_batch(machines)
    if len(seeds) == 0:
        raise ValueError("pack_codesign needs at least one seed machine")
    # Beta derives from the seeds; the fleet's instances cycle the seeds'
    # rows of the suite.
    s = descent_suite(profiles, seeds, beta=beta, beta_ref=beta_ref,
                      span=span)
    s = s.take(np.arange(num_machines) % len(s.machines))
    fleet_names = [f"pack{i}-{n}" for i, n in enumerate(s.machines.names)]
    n_rates = s.theta0.shape[1]
    n_apps, n_mach = len(s.profiles), num_machines
    # The fleet-total budget couples every machine, so the whole fleet
    # descends as ONE (1, M*D) row: scalar objective, global acceptance.
    theta0_flat = s.theta0.reshape(1, -1)
    swept_budget = schedule is not None
    constrained = area_budget is not None or power_budget is not None
    settings = dict(timing_model=timing_model, eps=eps,
                    cost_model=cost_model, w_area=w_area, w_power=w_power)
    objective = functools.partial(_fleet_objective, jnp, **settings)
    retract = functools.partial(_fleet_retraction, jnp,
                                cost_model=cost_model)

    def assign(th_flat, temp=None):
        """Hard (or, at ``temp``, softmax) assignment weights at a fleet."""
        agg = np.asarray(_suite_aggregate(
            jnp, th_flat.reshape(n_mach, n_rates), *s.args,
            timing_model=timing_model, eps=eps))
        return _pack_weights(agg) if temp is None else _soft_weights(agg, temp)

    steps_round = max(1, steps // max(rounds + 1, 1))

    def solve(theta_start, w_start, lr_start, area_b):
        """One full alternation (rounds + polish) at a fixed budget.

        Returns ``(theta, w_hard, f_final, history, lr)`` with the
        incumbent guarantee: ``f_final`` never exceeds the seed J.
        """
        rargs = (s.lo, s.hi, s.fixed) + _budget_args(area_b, power_budget,
                                                     envelope)
        theta = _compiled(retract)(theta_start, *rargs)
        w_hard = assign(theta) if w_start is None else w_start
        f_seed = np.asarray(objective(theta, *s.args, w_hard))
        history: List[np.ndarray] = [f_seed]
        best_theta, best_f = theta, jnp.asarray(f_seed)
        lr_v = lr_start
        temps = np.geomspace(temp0, max(temp_min, 1e-6), max(rounds, 1))
        for ri in range(rounds):
            w_round = (w_hard if mode == "alternate"
                       else assign(theta, float(temps[ri])))
            theta, _, hist, _, lr_v = backtracking_descent(
                jax, jnp, theta, objective, steps_round, lr_v,
                retract=retract, obj_args=(*s.args, w_round),
                retract_args=rargs)
            if mode == "alternate":
                history.extend(hist[1:])
            w_hard = assign(theta)
            f_bound = np.asarray(objective(theta, *s.args, w_hard))
            history.append(f_bound)
            better = jnp.asarray(f_bound) < best_f
            best_theta = jnp.where(better[:, None], theta, best_theta)
            best_f = jnp.minimum(jnp.asarray(f_bound), best_f)
        # Polish from the incumbent under its hard assignment.
        theta = best_theta
        w_hard = assign(theta)
        theta, _, hist, _, lr_v = backtracking_descent(
            jax, jnp, theta, objective, steps_round, lr_v,
            retract=retract, obj_args=(*s.args, w_hard),
            retract_args=rargs)
        history.extend(hist[1:])
        w_hard = assign(theta)
        f_final = np.asarray(objective(theta, *s.args, w_hard))
        history.append(f_final)
        return theta, w_hard, f_final, history, lr_v

    with x64():
        if schedule is None:
            theta, w_hard, f_final, history, _ = solve(
                theta0_flat, None, lr, area_budget)
            theta_np = np.asarray(theta)
            obj_seed = float(history[0][0])
            obj_final = float(f_final[0])
            frontier = None
        else:
            # Loosest -> tightest continuation: the budget is a traced
            # scalar, so every schedule point reuses one compiled descent.
            solved: Dict[float, dict] = {}
            theta_w, w_w, lr_w = theta0_flat, None, lr
            obj_seed = None
            for b in sorted(schedule, reverse=True):
                theta_w, w_w, f_b, hist_b, lr_w = solve(
                    theta_w, w_w, lr_w, b)
                if obj_seed is None:
                    obj_seed = float(hist_b[0][0])
                th_b = np.asarray(theta_w)
                m_b = machine_arrays_from_theta(
                    np, th_b.reshape(n_mach, n_rates), s.fixed)
                solved[b] = dict(
                    theta=th_b, w=w_w, obj=float(f_b[0]), history=hist_b,
                    area=float(np.sum(cost_model.area(m_b))),
                    feasible=_fleet_feasible(m_b, cost_model, b,
                                             power_budget, envelope))
            # Monotone propagation tightest -> loosest: a fleet feasible
            # at a tighter total budget is feasible at every looser one,
            # so J*(budget) is non-increasing as the budget loosens.
            best = None
            for b in sorted(schedule):
                if (best is not None and best["feasible"]
                        and best["obj"] < solved[b]["obj"]):
                    solved[b] = dict(best, feasible=True)
                if solved[b]["feasible"] and (best is None
                                              or not best["feasible"]
                                              or solved[b]["obj"]
                                              <= best["obj"]):
                    best = solved[b]
            tightest = min(schedule)
            theta_np = solved[tightest]["theta"]
            obj_final = solved[tightest]["obj"]
            history = solved[tightest]["history"]
            frontier = dict(
                budgets=np.asarray(sorted(schedule)),
                objective=np.asarray([solved[b]["obj"]
                                      for b in sorted(schedule)]),
                area=np.asarray([solved[b]["area"]
                                 for b in sorted(schedule)]),
                feasible=np.asarray([solved[b]["feasible"]
                                     for b in sorted(schedule)]))
            area_budget = tightest

    theta_rows = theta_np.reshape(n_mach, n_rates)
    final_m = machine_arrays_from_theta(np, theta_rows, s.fixed)
    agg_final = _suite_aggregate(np, theta_rows, *s.args,
                                 timing_model=timing_model, eps=eps)
    assignment = np.argmin(agg_final, axis=1)
    per_app = agg_final[np.arange(n_apps), assignment]
    area_total = float(np.sum(cost_model.area(final_m)))
    power_total = float(np.sum(cost_model.power(final_m)))
    feasible = (_fleet_feasible(final_m, cost_model, area_budget,
                                power_budget, envelope)
                if (constrained or swept_budget or envelope) else None)
    final_params = [params_of_theta(theta_rows[i], s.fixed, i)
                    for i in range(n_mach)]
    final_machines = MachineBatch(
        names=list(fleet_names),
        **{f: np.array([row[f] for row in final_params])
           for f in OPT_FIELDS},
        ici_links=s.fixed.ici_links, scale_compute=s.fixed.scale_compute,
        scale_memory=s.fixed.scale_memory,
        scale_interconnect=s.fixed.scale_interconnect)

    res = PackingResult(
        app_names=list(s.profiles.names),
        machine_names=list(fleet_names),
        assignment=assignment,
        machines=final_machines,
        seed_params=[params_of_theta(s.theta0[i], s.fixed, i)
                     for i in range(n_mach)],
        final_params=final_params,
        objective_seed=obj_seed,
        objective_final=obj_final,
        trajectory=np.concatenate([np.atleast_1d(h) for h in history]),
        per_app_aggregate=per_app,
        area_total=area_total,
        power_total=power_total,
        feasible=feasible,
        mode=mode,
        steps=steps,
        rounds=rounds,
        w_area=w_area,
        w_power=w_power,
        area_budget=(float(area_budget) if area_budget is not None else None),
        power_budget=(float(power_budget)
                      if power_budget is not None else None),
        area_envelope=envelope,
    )
    if frontier is not None:
        res.budgets = frontier["budgets"]
        res.frontier_objective = frontier["objective"]
        res.frontier_area = frontier["area"]
        res.frontier_feasible = frontier["feasible"]
    return res

