"""Gradient-based machine co-design: ``jax.grad`` through the shared kernels.

The sweep engine answers "which of these sampled designs fits best?"; this
module answers the continuous version -- "in which direction should the
design move?" -- by differentiating a scalarized multi-objective

    J(m) = mean-over-apps aggregate congruence
           + w_area * CostModel.area(m) + w_power * CostModel.power(m)

with respect to the *log* of the provisioned rates (``peak_flops``,
``hbm_bw``, ``ici_bw``, ``inter_pod_bw``).  Descent is on log-rates, NOT
raw rates: log-parameterization keeps the rates positive and makes one
step a multiplicative change, matching how hardware design points actually
move (2x the MXUs, 1.5x the HBM stacks).  The ``span`` clip bounds the
feasible box in that same log space -- each rate is confined to
``[seed/span, seed*span]``, i.e. ``log(rate)`` to ``log(seed) +- log(span)``
-- so every operator downstream (the backtracking retraction here, the
budget projection in ``repro.core.constrained``) composes in one
coordinate system.

This is only possible because the timing/Eq. 1 math lives in ONE traceable
place (``repro.core.kernels_xp``): the JAX backend evaluates the identical
kernel the NumPy sweep runs, so the gradient descends the surface the sweep
scores.  ``ici_links`` (integer) and the per-subsystem degradation
``scale_*`` factors are held fixed at their seed values here; the
constrained subsystem (``repro.core.constrained``) relaxes ``ici_links``
continuously and rounds with repair.

The objective uses unclamped Eq. 1 scores: clamping to [0, 1] zeroes the
gradient wherever a score saturates, which is exactly where a dominated
subsystem most needs a push.  Descent uses per-variant backtracking (halve
the step on failure, grow it on success), so every accepted update strictly
decreases that variant's objective -- the acceptance property
``tests/test_codesign.py`` pins.

Entry points:
  scalarized_objective -- evaluate J per variant (NumPy in, NumPy out)
  grad_codesign        -- descend J from a MachineBatch seed; returns a
                          ``CodesignResult`` with per-variant trajectories
                          and the optimized ``MachineModel`` designs.

Every co-design mode -- this one, the budgeted, joint, frontier, packing
and implicit modes in ``repro.core.constrained``, ``frontier``,
``packing`` and ``implicit`` -- builds its problem with ``descent_suite``,
descends ``_suite_objective`` (or a module-level function over it) and
runs the one compiled loop, ``backtracking_descent``, drawing on the
module-level cache of its static setting; ``docs/codesign.md`` is the
worked optimization guide.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import TYPE_CHECKING, Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.core import kernels_xp as K
from repro.core.costmodel import DEFAULT_COST_MODEL, CostModel
from repro.core.machine import MachineModel
from repro.core.spans import span, spanned

if TYPE_CHECKING:
    from repro.core.sweep import MachineBatch, ProfileBatch

#: The machine constants the gradient may move, in theta column order.
#: ``repro.core.constrained`` appends a 5th column, ``log(ici_links)``,
#: when the integer relaxation is enabled.
OPT_FIELDS = ("peak_flops", "hbm_bw", "ici_bw", "inter_pod_bw")


def machine_arrays_from_theta(xp, theta, fixed: K.MachineArrays) -> K.MachineArrays:
    """Rebuild ``MachineArrays`` with rates ``exp(theta)``, rest from seed.

    ``theta`` has one column per ``OPT_FIELDS`` entry; a 5th column, when
    present, carries ``log(ici_links)`` (the continuous relaxation used by
    ``repro.core.constrained``), otherwise links stay at the seed value.
    """
    links = (xp.exp(theta[:, 4]) if theta.shape[1] == len(OPT_FIELDS) + 1
             else fixed.ici_links)
    return K.MachineArrays(
        peak_flops=xp.exp(theta[:, 0]),
        hbm_bw=xp.exp(theta[:, 1]),
        ici_bw=xp.exp(theta[:, 2]),
        ici_links=links,
        inter_pod_bw=xp.exp(theta[:, 3]),
        scale_compute=fixed.scale_compute,
        scale_memory=fixed.scale_memory,
        scale_interconnect=fixed.scale_interconnect,
    )


def _objective_terms(xp, p: K.ProfileArrays, m: K.MachineArrays, beta,
                     timing_model: str, eps: float, cost_model: CostModel,
                     w_area: float, w_power: float, app_weights=None):
    """Per-variant (V,) scalarized objective -- the traceable core.

    ``app_weights`` (``(A, V)``, each column summing to 1 -- every workload
    group contributes weight ``1/n_groups`` spread over its members)
    replaces the plain mean over apps; the joint machine+variant descent
    uses it to select (hard) or mix (softmax) sharding variants of the
    same application.
    """
    out = K.congruence_kernel(xp, p, m, beta, timing_model, eps, clamp=False)
    if app_weights is None:
        fit = xp.mean(out.aggregate, axis=0)
    else:
        fit = xp.sum(app_weights * out.aggregate, axis=0)
    return fit + w_area * cost_model.area(m) + w_power * cost_model.power(m)


def theta_box(machines, span: float, optimize_links: bool = False,
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Seed log-rates and the span clip's feasible box, as ``(V, D)`` arrays.

    Returns ``(theta0, lo, hi)`` with one column per ``OPT_FIELDS`` entry
    plus, when ``optimize_links`` is set, a trailing ``log(ici_links)``
    column floored at ``log(1)`` (a pod link count cannot drop below one).
    """
    from repro.core.sweep import _as_machine_batch
    mb = _as_machine_batch(machines)
    cols = [np.asarray(getattr(mb, f), dtype=np.float64) for f in OPT_FIELDS]
    if optimize_links:
        cols.append(np.asarray(mb.ici_links, dtype=np.float64))
    theta0 = np.log(np.stack(cols, axis=1))
    lo, hi = theta0 - np.log(span), theta0 + np.log(span)
    if optimize_links:
        lo[:, -1] = np.maximum(lo[:, -1], 0.0)
        theta0[:, -1] = np.maximum(theta0[:, -1], lo[:, -1])
    return theta0, lo, hi


def backtracking_descent(
    jax, jnp, theta0, obj_fn: Callable, steps: int, lr: float,
    retract: Callable, aux_fn: Optional[Callable] = None,
    obj_args: Tuple = (), retract_args: Tuple = (),
    cache: Optional[Dict[tuple, Callable]] = None,
) -> Tuple[object, object, List[np.ndarray], List[np.ndarray], object]:
    """Per-variant backtracking line search on ``obj_fn`` (shared by every
    co-design mode).

    ``retract`` maps a raw gradient candidate back onto the feasible set
    (the span-clip box for unconstrained descent, the budget projection of
    ``repro.core.constrained`` for projected-gradient mode); it is applied
    AFTER the gradient step, so accepted iterates are always feasible.
    ``aux_fn(theta, *retract_args) -> (V,)`` optionally records a
    per-step diagnostic of the set the retraction enforces (the
    constraint-violation trace).  ``lr`` may be a scalar or a ``(V,)``
    per-variant array -- multi-round callers (the joint/Lagrangian outer
    loops) pass the previous round's adapted rates back in so restarts do
    not re-pay the warm-up.

    The whole descent -- the seed's retraction and objective, then
    ``steps`` iterations of gradient, retraction, objective and the
    accept/``lr`` update -- is ONE compiled program (a ``lax.scan``), and
    the host fetches the stacked histories once, after it: no step
    dispatches or syncs on its own.  ``obj_args`` are extra TRACED
    positional arguments forwarded to ``obj_fn(theta, *obj_args)``;
    round-varying state (Lagrange multipliers, selection weights, softmax
    temperature, the suite itself) belongs there, not in a fresh closure
    per call.  ``retract_args`` do the same for
    ``retract(theta, *retract_args)`` -- the budget-continuation frontier
    (``repro.core.frontier``) passes the active budget as a traced scalar
    so ONE compiled loop serves the whole budget sweep.  The loop compiles
    once per ``steps`` and aux/no-aux into ``cache``, by default the
    module-level cache of the functions' static setting (``_static_key``):
    given module-level functions, or partials of them that bind only
    static settings, a later solve on a new suite of the same shapes
    traces nothing.  Returns the final ``theta``, final per-variant
    objective, the accepted-objective history (seed included, ``steps +
    1`` rows of ``(V,)``), the aux history (as many rows, empty without
    ``aux_fn``) and the adapted per-variant ``lr``.
    """
    if cache is None:
        cache = _SOLVE_CACHES.setdefault(
            _static_key(obj_fn, retract, aux_fn), {})
    key = (steps, aux_fn is not None)
    if key not in cache:
        cache[key] = jax.jit(_descent_loop(
            jax, jnp, obj_fn, retract, aux_fn, steps))
    # One (V,) shape for every ``lr``, so a scalar and a carried-over
    # per-variant rate share the compiled loop.
    lr_v = np.broadcast_to(np.asarray(lr, dtype=theta0.dtype),
                           (theta0.shape[0],))
    with span("descent.step"):
        theta, f_cur, hist, aux, lr_v = cache[key](
            theta0, lr_v, obj_args, retract_args)
        with span("descent.sync"):
            hist, aux = jax.device_get((hist, aux))
    return theta, f_cur, list(hist), [] if aux is None else list(aux), lr_v


def _descent_loop(jax, jnp, obj_fn, retract, aux_fn, steps: int) -> Callable:
    """The traceable body of ``backtracking_descent``: a backtracking
    step ``scan``ned ``steps`` times, emitting each step's accepted
    objective (and ``aux_fn``) for the histories."""
    grad = jax.grad(lambda th, *a: jnp.sum(obj_fn(th, *a)))

    def loop(theta0, lr_v, obj_args, retract_args):
        def record(theta, f_cur):
            if aux_fn is None:
                return f_cur
            return f_cur, aux_fn(theta, *retract_args)

        def step(carry, _):
            theta, f_cur, lr_v = carry
            g = grad(theta, *obj_args)
            cand = retract(theta - lr_v[:, None] * g, *retract_args)
            f_new = obj_fn(cand, *obj_args)
            ok = f_new < f_cur
            theta = jnp.where(ok[:, None], cand, theta)
            f_cur = jnp.where(ok, f_new, f_cur)
            lr_v = jnp.where(ok, lr_v * 1.2, lr_v * 0.5)
            return (theta, f_cur, lr_v), record(theta, f_cur)

        theta = retract(theta0, *retract_args)
        f_cur = obj_fn(theta, *obj_args)
        first = record(theta, f_cur)
        (theta, f_cur, lr_v), rest = jax.lax.scan(
            step, (theta, f_cur, lr_v), None, length=steps)
        hist = jax.tree.map(lambda a, b: jnp.concatenate([a[None], b]),
                            first, rest)
        f_hist, aux_hist = (hist, None) if aux_fn is None else hist
        return theta, f_cur, f_hist, aux_hist, lr_v

    return loop


@dataclasses.dataclass
class CodesignResult:
    """Outcome of one gradient co-design run (all arrays per-variant).

    Every mode (unconstrained, projected, Lagrangian, joint) returns this
    one type; the feasibility fields are populated whenever a budget was in
    force and ``feasibility_report()`` renders them.  Doctest (fields are
    plain NumPy; no descent needed to exercise the accessors):

    >>> import numpy as np
    >>> r = CodesignResult(
    ...     names=["a", "b"], objective_seed=np.array([2.0, 3.0]),
    ...     objective_final=np.array([1.0, 2.5]),
    ...     seed_params=[{}, {}], final_params=[{}, {}],
    ...     trajectory=np.array([[2.0, 3.0], [1.0, 2.5]]), steps=1,
    ...     w_area=0.1, w_power=0.05)
    >>> r.best
    0
    >>> r.improvement.tolist()
    [1.0, 0.5]
    """

    names: List[str]
    objective_seed: np.ndarray       # (V,) J at the seed designs
    objective_final: np.ndarray      # (V,) J after descent
    seed_params: List[Dict[str, float]]
    final_params: List[Dict[str, float]]
    trajectory: np.ndarray           # (steps+1, V) accepted J per step
    steps: int
    w_area: float
    w_power: float
    # ---- co-design mode + feasibility report (PR 4) ------------------- #
    mode: str = "unconstrained"      # unconstrained|projected|lagrangian|joint-*
    suffix: str = "+grad"            # appended to optimized variant names
    area_budget: Optional[float] = None
    power_budget: Optional[float] = None
    #: Per-subsystem area envelopes (PR 5): rate field -> budget on
    #: ``CostModel.subsystem_area`` -- one extra constraint per entry.
    area_envelope: Optional[Dict[str, float]] = None
    area_final: Optional[np.ndarray] = None      # (V,) CostModel.area
    power_final: Optional[np.ndarray] = None     # (V,) CostModel.power
    feasible: Optional[np.ndarray] = None        # (V,) bool, None = no budget
    violation_trace: Optional[np.ndarray] = None  # (T, V) relative violation
    selection_names: Optional[List[List[str]]] = None  # joint: (V,)(G,) picks
    #: Augmented-Lagrangian shadow-price estimates (PR 10): ``(V, C)``
    #: multipliers against the ABSOLUTE budgets, one column per
    #: ``constraint_names`` entry (cross-checkable against the implicit
    #: sensitivities in ``repro.core.implicit``).  Lagrangian mode only.
    multipliers: Optional[np.ndarray] = None
    constraint_names: Optional[Tuple[str, ...]] = None

    @property
    def improvement(self) -> np.ndarray:
        """Per-variant objective decrease (positive = better)."""
        return self.objective_seed - self.objective_final

    @property
    def best(self) -> int:
        """Index of the best FEASIBLE variant (best overall if no budget)."""
        if self.feasible is not None and bool(np.any(self.feasible)):
            obj = np.where(self.feasible, self.objective_final, np.inf)
            return int(np.argmin(obj))
        return int(np.argmin(self.objective_final))

    def best_model(self) -> MachineModel:
        return self.models()[self.best]

    def models(self) -> List[MachineModel]:
        out = []
        for name, params in zip(self.names, self.final_params):
            out.append(MachineModel(
                name=f"{name}{self.suffix}",
                peak_flops=params["peak_flops"],
                hbm_bw=params["hbm_bw"],
                ici_bw=params["ici_bw"],
                ici_links=int(round(params["ici_links"])),
                inter_pod_bw=params["inter_pod_bw"],
                scale={"compute": params["scale_compute"],
                       "memory": params["scale_memory"],
                       "interconnect": params["scale_interconnect"]},
            ))
        return out

    def feasibility_report(self) -> dict:
        """Budgets, final (area, power) and per-variant feasibility.

        ``max_violation`` is the worst relative constraint violation seen
        along the descent (0.0 everywhere for projected mode, damped toward
        0 for Lagrangian -- the trace itself is in ``violation_trace``).
        """
        if (self.area_budget is None and self.power_budget is None
                and not self.area_envelope):
            return {"constrained": False, "mode": self.mode}
        rep = {
            "constrained": True,
            "mode": self.mode,
            "area_budget": self.area_budget,
            "power_budget": self.power_budget,
            "all_feasible": bool(np.all(self.feasible)),
            "variants": [
                {"name": f"{n}{self.suffix}",
                 "area": float(self.area_final[i]),
                 "power": float(self.power_final[i]),
                 "feasible": bool(self.feasible[i])}
                for i, n in enumerate(self.names)],
        }
        if self.area_envelope:
            rep["area_envelope"] = dict(self.area_envelope)
        if self.violation_trace is not None and len(self.violation_trace):
            rep["max_violation"] = float(np.max(self.violation_trace))
            rep["final_violation"] = float(np.max(self.violation_trace[-1]))
        if self.multipliers is not None:
            rep["shadow_prices"] = {
                c: [float(x) for x in self.multipliers[:, j]]
                for j, c in enumerate(self.constraint_names)}
        return rep

    def _variant_order(self, top_k: Optional[int]) -> List[int]:
        """Variant indices to report: all, or the ``top_k`` best by final
        objective (feasible variants first, matching ``best``'s tie-break;
        original seed order preserved within the kept set)."""
        if top_k is None:
            return list(range(len(self.names)))
        obj = np.asarray(self.objective_final, dtype=float)
        if self.feasible is not None:
            obj = np.where(np.asarray(self.feasible, bool), obj, np.inf)
        keep = sorted(range(len(self.names)),
                      key=lambda i: (float(obj[i]), i))[:top_k]
        return sorted(keep)

    def to_json(self, top_k: Optional[int] = None) -> dict:
        order = self._variant_order(top_k)
        blob = {
            "steps": self.steps,
            "mode": self.mode,
            "w_area": self.w_area,
            "w_power": self.w_power,
            "best_variant": f"{self.names[self.best]}{self.suffix}",
            "variants": [
                {"name": f"{self.names[i]}{self.suffix}",
                 "objective_seed": float(self.objective_seed[i]),
                 "objective_final": float(self.objective_final[i]),
                 "seed_params": self.seed_params[i],
                 "final_params": self.final_params[i]}
                for i in order],
        }
        if (self.area_budget is not None or self.power_budget is not None
                or self.area_envelope):
            blob["feasibility"] = self.feasibility_report()
        if self.selection_names is not None:
            blob["selection"] = {
                f"{self.names[i]}{self.suffix}": self.selection_names[i]
                for i in order}
        return blob

    def markdown(self, top_k: Optional[int] = None) -> str:
        """GitHub-flavoured summary table (the uniform result protocol:
        every sweep/co-design result renders via ``markdown``/``to_json``
        so the serving front door needs exactly one renderer)."""
        order = self._variant_order(top_k)
        has_budget = self.feasible is not None
        head = "| variant | J seed | J final | improvement |"
        rule = "|---|---|---|---|"
        if has_budget:
            head += " area | power | feasible |"
            rule += "---|---|---|"
        lines = [head, rule]
        for i in order:
            star = " *" if i == self.best else ""
            row = (f"| {self.names[i]}{self.suffix}{star} "
                   f"| {float(self.objective_seed[i]):.4f} "
                   f"| {float(self.objective_final[i]):.4f} "
                   f"| {float(self.improvement[i]):+.4f} |")
            if has_budget:
                row += (f" {float(self.area_final[i]):.3f} "
                        f"| {float(self.power_final[i]):.3f} "
                        f"| {'yes' if bool(self.feasible[i]) else 'NO'} |")
            lines.append(row)
        lines.append("")
        lines.append(f"mode: {self.mode}; steps: {self.steps}; "
                     f"best: {self.names[self.best]}{self.suffix}")
        return "\n".join(lines)


def params_of_theta(theta_row: np.ndarray, fixed_np: K.MachineArrays,
                    i: int) -> Dict[str, float]:
    """One variant's full parameter dict from a log-rate row + seed arrays."""
    d = {f: float(np.exp(theta_row[j])) for j, f in enumerate(OPT_FIELDS)}
    d["ici_links"] = (float(np.exp(theta_row[len(OPT_FIELDS)]))
                      if len(theta_row) == len(OPT_FIELDS) + 1
                      else float(fixed_np.ici_links[i]))
    d["scale_compute"] = float(fixed_np.scale_compute[i])
    d["scale_memory"] = float(fixed_np.scale_memory[i])
    d["scale_interconnect"] = float(fixed_np.scale_interconnect[i])
    return d


def scalarized_objective(
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
) -> np.ndarray:
    """Evaluate J for every variant (NumPy reference; shape ``(V,)``).

    Uses the same default-beta convention as ``batched_congruence``: when
    ``beta`` is None the per-app target derives from variant ``beta_ref``.
    """
    s = descent_suite(profiles, machines, beta=beta, beta_ref=beta_ref)
    with np.errstate(divide="ignore", invalid="ignore"):
        return _objective_terms(np, s.p, s.fixed, s.beta, timing_model, eps,
                                cost_model, w_area, w_power)


# --------------------------------------------------------------------------- #
# The descent seam: one suite, one objective, one cache per static setting
# --------------------------------------------------------------------------- #


def _jax_x64():
    """``(jax, jnp, x64)``: the modules every descent mode traces with and
    ``x64()``, the float64 scope it runs in -- the one reach into the jax
    backend outside ``kernels_xp``."""
    backend = K.get_backend("jax")
    return backend._jax, backend._jnp, backend._x64


@dataclasses.dataclass(frozen=True)
class DescentSuite:
    """What a co-design mode descends, as ``descent_suite`` packs it.

    ``args`` -- ``(p, fixed, beta)`` in float64 host arrays -- is the suite
    every compiled loop takes as traced arguments (its call moves them to
    the device); ``theta0``/``lo``/``hi`` are the seeds' log-rates and the
    span box; ``profiles``/``machines`` the packed batches that result
    assembly reads.
    """

    profiles: "ProfileBatch"
    machines: "MachineBatch"
    p: K.ProfileArrays
    fixed: K.MachineArrays
    beta: np.ndarray
    theta0: np.ndarray
    lo: np.ndarray
    hi: np.ndarray

    @property
    def args(self) -> Tuple[K.ProfileArrays, K.MachineArrays, np.ndarray]:
        return self.p, self.fixed, self.beta

    def take(self, rows) -> "DescentSuite":
        """The suite over machine rows ``rows`` (a fleet's instances, a
        frontier's winners), with the same apps and beta."""
        rows = np.asarray(rows, dtype=np.int64)
        return dataclasses.replace(
            self, machines=self.machines.take(rows),
            fixed=K.MachineArrays(*(f[rows] for f in self.fixed)),
            theta0=self.theta0[rows], lo=self.lo[rows], hi=self.hi[rows])


def descent_suite(profiles, machines, *, beta=None, beta_ref: int = 0,
                  span: float = 16.0, optimize_links: bool = False,
                  ) -> DescentSuite:
    """Pack ``profiles`` x ``machines`` into the suite every mode descends.

    ``beta`` is a scalar or ``(A,)`` target, or None for the per-app
    default derived from variant ``beta_ref`` -- frozen during descent:
    the paper's beta is a user target, not a design variable.  The box is
    ``theta_box``'s.
    """
    from repro.core.sweep import _as_machine_batch, _as_profile_batch

    pb, mb = _as_profile_batch(profiles), _as_machine_batch(machines)
    host = K.get_backend("numpy")
    if beta is None:
        beta = host.default_beta(pb.arrays(), mb.select(beta_ref).arrays())
    theta0, lo, hi = theta_box(mb, span, optimize_links=optimize_links)
    return DescentSuite(
        profiles=pb, machines=mb, p=host.profile_arrays(pb.arrays()),
        fixed=host.machine_arrays(mb.arrays()),
        beta=np.broadcast_to(np.asarray(beta, dtype=np.float64),
                             (len(pb),)).copy(),
        theta0=theta0, lo=lo, hi=hi)


def _suite_objective(xp, theta, p: K.ProfileArrays, fixed: K.MachineArrays,
                     beta, app_weights=None, *, timing_model: str,
                     eps: float, cost_model: CostModel, w_area: float,
                     w_power: float):
    """J per variant at log-rates ``theta`` over the suite: the objective
    every mode descends.  ``app_weights`` (traced, ``(A, V)``) replaces the
    mean over apps -- the joint and packing modes' selection."""
    m = machine_arrays_from_theta(xp, theta, fixed)
    return _objective_terms(xp, p, m, beta, timing_model, eps, cost_model,
                            w_area, w_power, app_weights=app_weights)


def _suite_aggregate(xp, theta, p: K.ProfileArrays, fixed: K.MachineArrays,
                     beta, *, timing_model: str, eps: float):
    """The ``(A, V)`` aggregate congruence at ``theta``: what the joint and
    packing modes select by."""
    m = machine_arrays_from_theta(xp, theta, fixed)
    return K.congruence_kernel(xp, p, m, beta, timing_model, eps,
                               clamp=False).aggregate


#: Compiled programs of every mode, one dict per setting that is static in
#: them (``_static_key``): the descent loops of ``backtracking_descent`` and
#: the ``_compiled`` functions.  The suite, box, budgets and round state
#: are traced arguments, so a later solve of any mode on a suite of the
#: same shapes reuses them.  It grows by one entry per static setting (and
#: per closure a caller passes, which keys by identity).
_SOLVE_CACHES: Dict[tuple, dict] = {}


def _static_key(*fns) -> tuple:
    """The cache key of the programs over ``fns``: module-level functions
    (or None), or partials of them binding only static settings (the
    array namespace, timing model, eps, cost model, weights, projection
    method).  ``_objective_terms`` joins it (a replaced kernel compiles
    programs of its own); a CostModel keys by its repr, as it holds
    dicts."""
    key = [_objective_terms]
    for fn in fns:
        if isinstance(fn, functools.partial):
            key += [fn.func, fn.args, tuple(sorted(
                (k, repr(v) if isinstance(v, CostModel) else v)
                for k, v in fn.keywords.items()))]
        else:
            key.append(fn)
    return tuple(key)


def _compiled(fn, grad: bool = False) -> Callable:
    """``jax.jit(fn)`` -- or, with ``grad``, of the gradient of
    ``sum(fn(theta, *args))`` in ``theta`` -- kept per static setting, for
    what a mode evaluates outside its loop (a seed's or a final
    projection, a sensitivity report's gradient)."""
    cache = _SOLVE_CACHES.setdefault(_static_key(fn), {})
    if grad not in cache:
        jax, jnp, _ = _jax_x64()
        cache[grad] = jax.jit(
            jax.grad(lambda th, *a: jnp.sum(fn(th, *a))) if grad else fn)
    return cache[grad]


@spanned("codesign")
def grad_codesign(
    profiles,
    machines,
    *,
    steps: int = 100,
    lr: float = 0.1,
    span: float = 16.0,
    beta=None,
    beta_ref: int = 0,
    timing_model: str = "serial",
    eps: float = K.IDEAL_EPS,
    cost_model: CostModel = DEFAULT_COST_MODEL,
    w_area: float = 0.1,
    w_power: float = 0.05,
) -> CodesignResult:
    """Descend J from a seed population by ``jax.grad`` on log-rates.

    ``machines`` is the seed -- typically the named variants
    (``MachineBatch.from_models(VARIANTS)``); every seed design descends
    independently (the objective sums per-variant terms, so the gradient
    does not couple them).  ``beta`` follows the sweep convention (per-app
    default from variant ``beta_ref``, frozen during descent -- the paper's
    beta is a user target, not a design variable).

    Descent runs on the LOG of each rate; ``span`` clips ``log(rate)`` to
    ``[log(seed) - log(span), log(seed) + log(span)]`` -- i.e. the rate to
    ``[seed/span, seed*span]`` -- keeping designs inside a plausible
    process envelope.  That clip box is exactly the feasible box the
    constrained modes (``repro.core.constrained``) intersect with the
    area/power budget set, and the combined clip+projection operator there
    is order-invariant with this clip (pinned in tests/test_constrained.py).
    ``lr`` is the initial per-variant step on log-rates, adapted by
    backtracking (x1.2 on success, x0.5 on failure), so the accepted
    objective sequence is monotone non-increasing per variant.

    Example (descend the three named seeds for a few steps):

    >>> from repro.core import VARIANTS, WorkloadProfile, grad_codesign
    >>> from repro.core.sweep import MachineBatch
    >>> apps = [WorkloadProfile(name="app0", flops=2e14, hbm_bytes=1.5e11,
    ...                         collective_bytes={"all-reduce": 2e10},
    ...                         num_devices=256, model_flops=5e16)]
    >>> cd = grad_codesign(apps, MachineBatch.from_models(VARIANTS), steps=3)
    >>> cd.names
    ['baseline', 'denser', 'densest']
    >>> bool((cd.improvement >= 0).all())     # backtracking never regresses
    True
    >>> cd.best_model().peak_flops > 0
    True
    >>> cd.mode
    'unconstrained'
    """
    jax, jnp, x64 = _jax_x64()
    s = descent_suite(profiles, machines, beta=beta, beta_ref=beta_ref,
                      span=span)
    objective = functools.partial(
        _suite_objective, jnp, timing_model=timing_model, eps=eps,
        cost_model=cost_model, w_area=w_area, w_power=w_power)
    with x64():
        theta, f_cur, history, _, _ = backtracking_descent(
            jax, jnp, s.theta0, objective, steps, lr, retract=jnp.clip,
            obj_args=s.args, retract_args=(s.lo, s.hi))
        theta_np, f_final = jax.device_get((theta, f_cur))

    return _codesign_result(s, theta_np, history, f_final, steps, cost_model,
                            w_area, w_power)


def _codesign_result(s: DescentSuite, theta_np, history, f_final, steps,
                     cost_model: CostModel, w_area, w_power,
                     violation_trace=None, feasible=None,
                     **fields) -> CodesignResult:
    """The ``CodesignResult`` of a descent over ``s`` that ended at
    ``theta_np`` (``fields``: the mode, suffix, budgets and reports)."""
    final_m = machine_arrays_from_theta(np, theta_np, s.fixed)
    rows = range(len(s.machines))
    return CodesignResult(
        names=list(s.machines.names),
        objective_seed=np.asarray(history[0]),
        objective_final=np.asarray(f_final),
        seed_params=[params_of_theta(s.theta0[i], s.fixed, i) for i in rows],
        final_params=[params_of_theta(theta_np[i], s.fixed, i) for i in rows],
        trajectory=np.stack(history, axis=0),
        steps=steps,
        w_area=w_area,
        w_power=w_power,
        area_final=np.asarray(cost_model.area(final_m)),
        power_final=np.asarray(cost_model.power(final_m)),
        feasible=None if feasible is None else np.asarray(feasible, bool),
        violation_trace=(None if violation_trace is None
                         else np.stack(violation_trace, axis=0)),
        **fields,
    )
