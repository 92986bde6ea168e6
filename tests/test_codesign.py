"""Gradient co-design: jax.grad through the shared kernels must strictly
improve the scalarized (congruence, area, power) objective from the named
variant seeds on the synthetic profile suite (the ISSUE acceptance gate)."""

import functools

import numpy as np
import pytest

from repro.core import VARIANTS
from repro.core.codesign import (
    CodesignResult,
    OPT_FIELDS,
    grad_codesign,
    scalarized_objective,
)
from repro.core.costmodel import CostModel
from repro.core.sweep import MachineBatch
from test_sweep import random_profiles


def synthetic_suite():
    """The benchmark harness's synthetic trio (compute / memory / collective
    bound) -- the 'synthetic profile suite' the acceptance criterion names."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import common
    return common.synthetic_profiles()


@pytest.fixture(scope="module")
def result():
    return grad_codesign(synthetic_suite(),
                         MachineBatch.from_models(VARIANTS), steps=60)


def test_grad_strictly_improves_named_seeds(result):
    """Every named-variant seed must end with a strictly lower objective."""
    assert list(result.names) == [m.name for m in VARIANTS]
    assert np.all(result.objective_final < result.objective_seed)
    assert np.all(result.improvement > 0)


def test_trajectory_is_monotone_non_increasing(result):
    """Backtracking line search: accepted objective never goes up."""
    diffs = np.diff(result.trajectory, axis=0)
    assert np.all(diffs <= 1e-12)


def test_final_objective_matches_numpy_reference(result):
    """The jax-descended objective must re-evaluate identically (to 1e-6)
    on the NumPy reference kernels -- same math, one kernel layer."""
    models = result.models()
    # freeze beta to the seed convention: derived from the seed baseline
    from repro.core.sweep import default_beta_batched
    beta = default_beta_batched(
        synthetic_suite(), MachineBatch.from_models(VARIANTS))
    ref = scalarized_objective(synthetic_suite(),
                               MachineBatch.from_models(models), beta=beta)
    np.testing.assert_allclose(ref, result.objective_final, rtol=1e-6)


def test_optimized_models_are_well_formed(result):
    models = result.models()
    assert [m.name for m in models] == [f"{v.name}+grad" for v in VARIANTS]
    for m, seed in zip(models, VARIANTS):
        assert m.peak_flops > 0 and m.hbm_bw > 0
        assert m.ici_links == seed.ici_links  # held fixed
        for s, v in m.scale.items():
            assert v == seed.scale.get(s, 1.0)  # scales held fixed too
        # span clip: rates stay within the process envelope (relative
        # slack: exp(log(x)) round-trips to ~1e-13 of the boundary)
        for f in OPT_FIELDS:
            assert getattr(seed, f) / 16.0 * (1 - 1e-9) <= getattr(m, f) \
                <= getattr(seed, f) * 16.0 * (1 + 1e-9)


def test_to_json_serializable(result):
    import json
    blob = result.to_json()
    json.dumps(blob)
    assert blob["best_variant"].endswith("+grad")
    assert len(blob["variants"]) == len(VARIANTS)


def test_objective_gradient_matches_finite_differences():
    """The jax gradient every descent in this repo follows must match
    central finite differences of the NumPy reference objective (shared
    ``conftest.gradcheck`` harness -- the same one that pins the
    implicit budget sensitivities in tests/test_implicit.py)."""
    from conftest import gradcheck

    from repro.core.codesign import (
        _objective_terms,
        descent_suite,
        machine_arrays_from_theta,
    )
    from repro.core.costmodel import DEFAULT_COST_MODEL
    from repro.core.kernels_xp import IDEAL_EPS, get_backend

    s = descent_suite(synthetic_suite(), MachineBatch.from_models(VARIANTS))
    pb, fixed_np, beta_np, theta0 = s.profiles, s.fixed, s.beta, s.theta0
    backend = get_backend("jax")
    jax, jnp = backend._jax, backend._jnp

    with backend._x64():
        p_arrays = backend.profile_arrays(pb.arrays())
        fixed = backend.machine_arrays(fixed_np)
        beta_j = backend.asarray(beta_np)

        def obj_jax(flat):
            th = jnp.reshape(backend.asarray(flat), theta0.shape)
            m = machine_arrays_from_theta(jnp, th, fixed)
            return jnp.sum(_objective_terms(
                jnp, p_arrays, m, beta_j, "serial", IDEAL_EPS,
                DEFAULT_COST_MODEL, 0.1, 0.05))

        grad = np.asarray(jax.grad(obj_jax)(
            backend.asarray(theta0.ravel())))

    def obj_np(flat):
        th = flat.reshape(theta0.shape)
        m = machine_arrays_from_theta(np, th, fixed_np)
        with np.errstate(divide="ignore", invalid="ignore"):
            return float(np.sum(_objective_terms(
                np, pb.arrays(), m, beta_np, "serial", IDEAL_EPS,
                DEFAULT_COST_MODEL, 0.1, 0.05)))

    worst = gradcheck(obj_np, theta0.ravel(), grad, rtol=1e-4, h=1e-5)
    assert worst <= 1e-4


def test_grad_respects_cost_model_weights():
    """Cranking the area weight must pull the optimized designs smaller."""
    profiles = random_profiles(3, seed=51)
    seeds = MachineBatch.from_models(VARIANTS)
    cheap = grad_codesign(profiles, seeds, steps=40, w_area=0.0,
                          w_power=0.0)
    lean = grad_codesign(profiles, seeds, steps=40, w_area=2.0,
                         w_power=1.0)
    cm = CostModel()
    area_cheap = np.mean([cm.area(m) for m in cheap.models()])
    area_lean = np.mean([cm.area(m) for m in lean.models()])
    assert area_lean < area_cheap


def test_scalarized_objective_shape_and_beta_forms():
    profiles = random_profiles(4, seed=53)
    machines = MachineBatch.from_models(VARIANTS)
    j = scalarized_objective(profiles, machines)
    assert j.shape == (len(VARIANTS),)
    j0 = scalarized_objective(profiles, machines, beta=0.0)
    assert j0.shape == (len(VARIANTS),)
    assert np.all(np.isfinite(j)) and np.all(np.isfinite(j0))


def test_codesign_result_best(result):
    assert isinstance(result, CodesignResult)
    assert result.best == int(np.argmin(result.objective_final))
    assert result.best_model().name == f"{result.names[result.best]}+grad"


def test_grad_codesign_reports_final_silicon(result):
    """The feasibility-report fields are populated even unconstrained:
    final area/power under the run's cost model, no budget, no trace."""
    from repro.core.costmodel import DEFAULT_COST_MODEL

    models = result.models()
    np.testing.assert_allclose(
        result.area_final, [DEFAULT_COST_MODEL.area(m) for m in models],
        rtol=1e-9)
    np.testing.assert_allclose(
        result.power_final, [DEFAULT_COST_MODEL.power(m) for m in models],
        rtol=1e-9)
    assert result.mode == "unconstrained"
    assert result.feasible is None and result.violation_trace is None
    assert result.feasibility_report() == {
        "constrained": False, "mode": "unconstrained"}


# --------------------------------------------------------------------------- #
# The compiled descent against the step-by-step loop it replaced
# --------------------------------------------------------------------------- #


def eager_descent(jax, jnp, theta0, obj_fn, steps, lr, retract, aux_fn=None,
                  obj_args=(), retract_args=()):
    """The descent as one host loop: three jitted calls, the ``where``
    updates and one sync a step -- the reference for the compiled loop."""
    obj_j = jax.jit(obj_fn)
    grad_j = jax.jit(jax.grad(lambda th, *a: jnp.sum(obj_fn(th, *a))))
    retract_j = jax.jit(retract)
    aux_j = jax.jit(aux_fn) if aux_fn is not None else None
    theta = retract_j(theta0, *retract_args)
    f_cur = obj_j(theta, *obj_args)
    lr_v = jnp.broadcast_to(jnp.asarray(lr, dtype=theta.dtype),
                            (theta.shape[0],))
    history = [np.asarray(f_cur)]
    aux = ([] if aux_j is None
           else [np.asarray(aux_j(theta, *retract_args))])
    for _ in range(steps):
        g = grad_j(theta, *obj_args)
        cand = retract_j(theta - lr_v[:, None] * g, *retract_args)
        f_new = obj_j(cand, *obj_args)
        ok = f_new < f_cur
        theta = jnp.where(ok[:, None], cand, theta)
        f_cur = jnp.where(ok, f_new, f_cur)
        lr_v = jnp.where(ok, lr_v * 1.2, lr_v * 0.5)
        history.append(np.asarray(f_cur))
        if aux_j is not None:
            aux.append(np.asarray(aux_j(theta, *retract_args)))
    return theta, f_cur, history, aux, lr_v


def descent_problem(mode):
    """The inputs ``grad_codesign`` descends (``"clip"``: the span box, a
    scalar ``lr``), or a projected budget descent (``"projected"``: the
    budget a traced retraction argument, the violation under it as
    ``aux_fn``, a ``(V,)`` ``lr``)."""
    from repro.core.codesign import (
        _suite_objective,
        descent_suite,
        machine_arrays_from_theta,
    )
    from repro.core.constrained import budget_violation, project_to_budgets
    from repro.core.costmodel import DEFAULT_COST_MODEL
    from repro.core.kernels_xp import IDEAL_EPS, get_backend

    backend = get_backend("jax")
    jax, jnp = backend._jax, backend._jnp
    s = descent_suite(random_profiles(5, seed=61),
                      MachineBatch.from_models(VARIANTS))
    theta0, lo, hi = s.theta0, s.lo, s.hi
    with backend._x64():
        fixed = backend.machine_arrays(s.fixed)
        lo_j, hi_j = backend.asarray(lo), backend.asarray(hi)
        suite = (backend.profile_arrays(s.p), fixed,
                 backend.asarray(s.beta))
        objective = functools.partial(
            _suite_objective, jnp, timing_model="serial", eps=IDEAL_EPS,
            cost_model=DEFAULT_COST_MODEL, w_area=0.1, w_power=0.05)
        if mode == "clip":
            return dict(theta0=backend.asarray(theta0), obj_fn=objective,
                        lr=0.1, retract=jnp.clip, obj_args=suite,
                        retract_args=(lo_j, hi_j))

        def project(theta, budget):
            out, _ = project_to_budgets(jnp, theta, lo_j, hi_j, fixed,
                                        DEFAULT_COST_MODEL, budget, None)
            return out

        def violation(theta, budget):
            m = machine_arrays_from_theta(jnp, theta, fixed)
            return budget_violation(jnp, m, DEFAULT_COST_MODEL, budget, None)

        return dict(theta0=backend.asarray(theta0), obj_fn=objective,
                    lr=backend.asarray(np.linspace(0.05, 0.2, len(lo))),
                    retract=project, aux_fn=violation, obj_args=suite,
                    retract_args=(backend.asarray(1.5),))


@pytest.mark.parametrize("mode,steps", [("clip", 40), ("projected", 40),
                                        ("clip", 0), ("projected", 0)])
def test_compiled_descent_matches_the_step_loop(mode, steps):
    """The compiled descent accepts the same steps as the step-by-step
    loop over the first 10, and its histories agree to 1e-12 relative;
    ``steps=0`` returns the seed's row alone."""
    from repro.core.codesign import backtracking_descent
    from repro.core.kernels_xp import get_backend

    backend = get_backend("jax")
    jax, jnp = backend._jax, backend._jnp
    problem = descent_problem(mode)
    with backend._x64():
        theta0 = problem.pop("theta0")
        obj_fn = problem.pop("obj_fn")
        got = backtracking_descent(jax, jnp, theta0, obj_fn, steps, **problem)
        want = eager_descent(jax, jnp, theta0, obj_fn, steps, **problem)
    v = theta0.shape[0]
    hist, want_hist = np.stack(got[2]), np.stack(want[2])
    assert hist.shape == (steps + 1, v)
    aux_rows = steps + 1 if mode == "projected" else 0
    assert len(got[3]) == len(want[3]) == aux_rows
    if aux_rows:
        assert np.stack(got[3]).shape == (steps + 1, v)
        np.testing.assert_allclose(np.stack(got[3]), np.stack(want[3]),
                                   rtol=1e-12, atol=0)
    np.testing.assert_array_equal(np.diff(hist[:11], axis=0) < 0,
                                  np.diff(want_hist[:11], axis=0) < 0)
    np.testing.assert_allclose(hist, want_hist, rtol=1e-12, atol=0)
    for a, b in ((got[0], want[0]), (got[1], want[1]), (got[4], want[4])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-12)
    if steps:
        assert np.any(np.diff(hist, axis=0) < 0)   # the descent did move


def test_grad_codesign_matches_the_step_loop():
    """``grad_codesign``'s trajectory is the step-by-step loop's on the
    objective it descended before the suite became an argument: a closure
    over the suite and the clip box."""
    from repro.core.codesign import (
        _objective_terms,
        descent_suite,
        machine_arrays_from_theta,
    )
    from repro.core.costmodel import DEFAULT_COST_MODEL
    from repro.core.kernels_xp import IDEAL_EPS, get_backend

    profiles = random_profiles(5, seed=67)
    seeds = MachineBatch.from_models(VARIANTS)
    res = grad_codesign(profiles, seeds, steps=30)
    backend = get_backend("jax")
    jax, jnp = backend._jax, backend._jnp
    s = descent_suite(profiles, seeds)
    with backend._x64():
        p_arrays = backend.profile_arrays(s.p)
        fixed = backend.machine_arrays(s.fixed)
        beta_j = backend.asarray(s.beta)
        lo_j, hi_j = backend.asarray(s.lo), backend.asarray(s.hi)

        def per_variant(theta):
            m = machine_arrays_from_theta(jnp, theta, fixed)
            return _objective_terms(jnp, p_arrays, m, beta_j, "serial",
                                    IDEAL_EPS, DEFAULT_COST_MODEL, 0.1, 0.05)

        _, f_cur, history, _, _ = eager_descent(
            jax, jnp, backend.asarray(s.theta0), per_variant, 30, 0.1,
            retract=lambda th: jnp.clip(th, lo_j, hi_j))
    want = np.stack(history)
    np.testing.assert_array_equal(np.diff(res.trajectory[:11], axis=0) < 0,
                                  np.diff(want[:11], axis=0) < 0)
    np.testing.assert_allclose(res.trajectory, want, rtol=1e-12, atol=0)
    np.testing.assert_allclose(res.objective_final, np.asarray(f_cur),
                               rtol=1e-12)


def _solve_in_mode(mode, suite, seeds):
    """One small solve of co-design ``mode`` on ``suite``, and the
    trajectory it reports."""
    from repro.core.constrained import constrained_codesign, joint_codesign
    from repro.core.frontier import frontier_codesign
    from repro.core.packing import pack_codesign

    if mode == "grad":
        return grad_codesign(suite, seeds, steps=8).trajectory
    if mode in ("projected", "lagrangian"):
        return constrained_codesign(
            suite, seeds, area_budget=0.8, power_budget=1.2, mode=mode,
            steps=8, outer_iters=2).trajectory
    if mode == "joint":
        groups = [suite[i:i + 2] for i in range(0, len(suite), 2)]
        return joint_codesign(groups, seeds, rounds=2, steps=6).trajectory
    if mode == "frontier":
        return frontier_codesign(suite, seeds, [0.6, 1.2], steps=4,
                                 refine_steps=2).per_seed_objective
    return pack_codesign(suite, seeds, num_machines=2, rounds=2, steps=4,
                         area_budget=1.5).trajectory


@pytest.mark.parametrize("mode", ["grad", "projected", "lagrangian", "joint",
                                  "frontier", "pack"])
def test_second_solve_on_a_new_suite_traces_nothing(mode):
    """The suite is an argument of every mode's compiled programs, not a
    constant in them: a solve on a new generated suite of the same size
    reuses the first solve's programs and traces nothing."""
    from repro.core import spans
    from repro.core.model_zoo import resolve_suite

    seeds = MachineBatch.from_models(VARIANTS)
    first, second = (list(resolve_suite(f"gen:16:seed={s}:mode=halton"))
                     for s in (5, 6))
    a = _solve_in_mode(mode, first, seeds)
    before = spans.counters()["retrace"]
    b = _solve_in_mode(mode, second, seeds)
    assert spans.counters()["retrace"] == before
    assert not np.allclose(a, b)


# --------------------------------------------------------------------------- #
# Joint (machine, sharding-variant) descent vs machine-only: the ISSUE
# acceptance gate on the 10 default profiles
# --------------------------------------------------------------------------- #


def default_profile_groups():
    """The 10 default profiles (benchmarks.common.scaling_profiles) each
    with three synthetic sharding layouts: member 0 is the default; the
    others trade collective traffic against memory traffic the way
    tp/zero1/fsdp layouts do."""
    import dataclasses as _dc
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import common
    groups = []
    for p in common.scaling_profiles(10):
        group = [p]
        for k, (coll_f, mem_f) in enumerate(((0.4, 1.25), (2.2, 0.8)), 1):
            q = _dc.replace(
                p, name=f"{p.name}/v{k}",
                hbm_bytes=p.hbm_bytes * mem_f,
                bytes_accessed=p.bytes_accessed * mem_f,
                collective_bytes={"all-reduce":
                                  p.total_collective_bytes * coll_f},
            )
            group.append(q)
        groups.append(group)
    return groups


def test_joint_beats_machine_only_on_default_profiles():
    """Joint (machine, sharding-variant) descent must match or beat
    machine-only descent on the per-profile scalarized objective for at
    least 8 of the 10 default profiles (ISSUE 4 acceptance criterion).

    Machine-only descends with every app pinned to its default sharding
    (member 0); joint may re-select per (app, machine variant).  Both are
    scored at their own best final machine: per-profile objective =
    aggregate congruence of the (chosen) member + the shared silicon
    terms.
    """
    from repro.core.constrained import joint_codesign
    from repro.core.costmodel import DEFAULT_COST_MODEL
    from repro.core.sweep import batched_congruence, default_beta_batched

    groups = default_profile_groups()
    seeds = MachineBatch.from_models(VARIANTS)
    defaults = [g[0] for g in groups]
    beta = default_beta_batched(defaults, seeds)

    machine_only = grad_codesign(defaults, seeds, steps=40, beta=beta)
    joint = joint_codesign(groups, seeds, rounds=3, steps=40, beta=beta)

    def per_profile_objective(model, chosen):
        res = batched_congruence(chosen, MachineBatch.from_models([model]),
                                 beta=beta, clamp=False)
        cm = DEFAULT_COST_MODEL
        silicon = 0.1 * cm.area(model) + 0.05 * cm.power(model)
        return res.aggregate[:, 0] + silicon

    mo_best = machine_only.best_model()
    j_best = joint.best_model()
    picks = joint.selection_names[joint.best]
    by_name = {p.name: p for g in groups for p in g}
    j_chosen = [by_name[n] for n in picks]

    mo_obj = per_profile_objective(mo_best, defaults)
    j_obj = per_profile_objective(j_best, j_chosen)
    wins = int(np.sum(j_obj <= mo_obj + 1e-9))
    assert wins >= 8, (
        f"joint beat machine-only on only {wins}/10 profiles "
        f"(joint={j_obj}, machine_only={mo_obj})")
    # The totals must agree with what each run reported for its best seed.
    np.testing.assert_allclose(np.mean(j_obj), joint.objective_final[
        joint.best], rtol=1e-6)
    np.testing.assert_allclose(np.mean(mo_obj), machine_only.objective_final[
        machine_only.best], rtol=1e-6)
