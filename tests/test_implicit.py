"""Implicit differentiation through the co-design optimum (ISSUE 10).

The acceptance gates, each pinned by the shared finite-difference
harness (``conftest.gradcheck`` + ``repro.core.implicit.polish_theta``
as the warm-started re-solver):

  * the implicit ``dJ*/d(budget)`` matches central finite differences to
    rtol 1e-3 on every named seed machine AND every feasible
    ``FrontierResult`` point;
  * KKT structure holds under random budget schedules (multipliers
    nonnegative, ~zero for inactive constraints -- complementary
    slackness -- and ``dJ*/db <= 0``, i.e. J* monotone in the budget);
  * the implicit multipliers agree with the augmented-Lagrangian
    estimate wherever that path converges to the same optimum;
  * the implicit custom-VJP's traced graph does NOT grow with solver
    ``steps`` (the unrolled baseline's does -- that is the point);
  * ``bilevel_codesign`` strictly improves on the uniform 50/50 budget
    split on the default profile suite.
"""

import argparse
import os
import sys
import types

import numpy as np
import pytest

from conftest import gradcheck, hypothesis_shim

given, settings, st = hypothesis_shim(seed=0x1CC7, trials=4)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks import common  # noqa: E402

from repro.core import VARIANTS, VARIANTS_BY_NAME  # noqa: E402
from repro.core.codesign import OPT_FIELDS  # noqa: E402
from repro.core.constrained import (  # noqa: E402
    constrained_codesign,
    constraint_labels,
)
from repro.core.frontier import frontier_codesign  # noqa: E402
from repro.core.implicit import (  # noqa: E402
    BilevelResult,
    SensitivityReport,
    bilevel_codesign,
    implicit_jstar_fn,
    implicit_sensitivities,
    polish_theta,
    sensitivities_of,
    unrolled_jstar_fn,
)
from repro.core.kernels_xp import get_backend  # noqa: E402
from repro.core.sweep import MachineBatch  # noqa: E402

PROFILES = common.synthetic_profiles()
SEEDS = MachineBatch.from_models(VARIANTS)
B_AREA = 0.18  # binds on every named seed for the synthetic suite


def _theta_of(params_list):
    return np.log(np.array(
        [[p[f] for f in OPT_FIELDS] for p in params_list]))


@pytest.fixture(scope="module")
def res_proj():
    return constrained_codesign(PROFILES, SEEDS, steps=200,
                                area_budget=B_AREA, mode="projected")


@pytest.fixture(scope="module")
def rep(res_proj):
    # The IFT formulas hold AT the optimum; polish the 200-step descent
    # point to stationarity before reading multipliers off it.
    return sensitivities_of(res_proj, PROFILES, polish_steps=100)


@pytest.fixture(scope="module")
def fr():
    # 0.05 sits below the span-box area floor (~0.0625 for the smallest
    # named seed) -- the infeasible-row NaN contract needs a floor row.
    return frontier_codesign(PROFILES, SEEDS, [0.05, 0.15, 0.25, 0.5],
                             steps=80, refine_steps=30)


# --------------------------------------------------------------------------- #
# The tentpole gate: implicit dJ*/db == central FD (named seeds + frontier)
# --------------------------------------------------------------------------- #


def test_implicit_matches_fd_on_every_named_seed(res_proj, rep):
    """dJ*/d(area budget) from the linearized KKT system must match a
    warm-started central-difference re-solve to rtol 1e-3 PER SEED.

    The seeds descend independently, so summing per-variant objectives
    at per-variant budgets turns the (V,) check into one scalar
    gradcheck: coordinate v of the FD gradient is variant v's dJ*/db.
    """
    theta_star = _theta_of(res_proj.final_params)

    def jstar_sum(budgets):
        _, f = polish_theta(PROFILES, SEEDS, theta_star,
                            area_budget=budgets, steps=120, lr=0.05)
        return float(np.sum(f))

    assert list(rep.constraint_names) == ["area"]
    worst = gradcheck(jstar_sum, np.full(len(VARIANTS), B_AREA),
                      rep.dJ_dbudget[:, 0], rtol=1e-3, atol=1e-7,
                      h=1e-3, log_space=True)
    assert worst <= 1e-3
    # Shadow prices are the negated sensitivities and the budget binds.
    np.testing.assert_allclose(rep.multipliers[:, 0],
                               -rep.dJ_dbudget[:, 0], rtol=0, atol=0)
    assert np.all(rep.multipliers[:, 0] > 0) and np.all(rep.active[:, 0])


def test_implicit_matches_fd_on_every_feasible_frontier_point(fr):
    """Every feasible frontier row's attached dJ*/d(area budget) must
    survive the same FD harness -- including the propagated flat-segment
    rows, whose slack area constraint prices at exactly zero."""
    rows = [i for i in range(len(fr))
            if fr.feasible[i] and np.isfinite(fr.dJ_dbudget[i])]
    assert len(rows) >= 2  # the binding knee AND the flat tail
    row_seeds = MachineBatch.from_models(
        [VARIANTS_BY_NAME[fr.best_names[i]] for i in rows])
    theta = _theta_of([fr.best_params[i] for i in rows])

    def jstar_sum(budgets):
        _, f = polish_theta(PROFILES, row_seeds, theta,
                            area_budget=budgets, steps=100, lr=0.05)
        return float(np.sum(f))

    worst = gradcheck(jstar_sum, fr.budgets[rows], fr.dJ_dbudget[rows],
                      rtol=1e-3, atol=1e-6, h=1e-3, log_space=True)
    assert worst <= 1e-3
    # The flat tail exists and prices at zero (slack => lambda == 0).
    assert np.any(fr.dJ_dbudget[rows] == 0.0)
    assert np.any(fr.dJ_dbudget[rows] < 0.0)


def test_infeasible_frontier_rows_carry_nan_sensitivities(fr):
    bad = ~fr.feasible
    assert bad.any()
    assert np.all(np.isnan(fr.dJ_dbudget[bad]))


# --------------------------------------------------------------------------- #
# Cross-check: augmented-Lagrangian multipliers vs the implicit ones
# --------------------------------------------------------------------------- #


def test_lagrangian_multipliers_agree_where_al_converges(res_proj, rep):
    """The AL path maintains running multiplier estimates; wherever its
    descent reaches the same optimum as the projected path, those
    estimates must agree with the implicit shadow prices (same KKT
    point, two independent derivations)."""
    res_al = constrained_codesign(PROFILES, SEEDS, steps=200,
                                  area_budget=B_AREA, mode="lagrangian")
    assert res_al.constraint_names == ("area",)
    lam_al = res_al.multipliers[:, 0]
    assert np.all(lam_al >= 0.0)
    # Condition on actual convergence: the objective is flat near the
    # optimum, so only variants whose AL descent lands on the SAME point
    # (objective equal to 1e-6) carry converged multiplier estimates --
    # the others stall nearby with a stale running lambda.
    same = np.isclose(res_al.objective_final, res_proj.objective_final,
                      rtol=1e-6)
    assert same.any(), "AL never matched the projected optimum"
    np.testing.assert_allclose(lam_al[same], rep.multipliers[same, 0],
                               rtol=0.1)


# --------------------------------------------------------------------------- #
# KKT property suite under random budget schedules
# --------------------------------------------------------------------------- #


@settings(max_examples=4, deadline=None)
@given(area=st.floats(0.16, 0.5), power=st.floats(0.2, 0.6))
def test_kkt_structure_for_random_budgets(area, power):
    """For any budget schedule: multipliers nonnegative, zero on slack
    constraints (complementary slackness), and dJ*/db nonpositive."""
    rep = implicit_sensitivities(PROFILES, SEEDS, area_budget=area,
                                 power_budget=power, polish_steps=60)
    assert list(rep.constraint_names) == constraint_labels(area, power)
    assert np.all(rep.multipliers >= 0.0)
    assert np.all(rep.multipliers[~rep.active] == 0.0)
    assert np.all(rep.dJ_dbudget <= 0.0)
    np.testing.assert_allclose(rep.dJ_dbudget, -rep.multipliers)
    for i in range(len(rep.names)):
        best = rep.best_relaxation(i)
        if best is not None:
            j = list(rep.constraint_names).index(best)
            assert rep.multipliers[i, j] == rep.multipliers[i].max()


@settings(max_examples=3, deadline=None)
@given(budget=st.floats(0.16, 0.35), widen=st.floats(0.05, 0.3))
def test_jstar_monotone_nonincreasing_in_budget(budget, widen):
    """Relaxing the budget can only help: J*(b) >= J*(b + widen) per
    seed (the global sign condition behind dJ*/db <= 0)."""
    theta0 = np.log(np.stack([[getattr(m, f) for f in OPT_FIELDS]
                              for m in VARIANTS]))
    _, tight = polish_theta(PROFILES, SEEDS, theta0,
                            area_budget=np.full(3, budget), steps=80)
    _, loose = polish_theta(PROFILES, SEEDS, theta0,
                            area_budget=np.full(3, budget + widen),
                            steps=80)
    assert np.all(tight >= loose - 1e-9)


def test_sensitivities_need_a_constraint():
    with pytest.raises(ValueError, match="at least one"):
        implicit_sensitivities(PROFILES, SEEDS)


def test_envelope_prices_route_to_named_subsystem():
    """A binding per-subsystem envelope gets its own named column; slack
    scalar budgets price at ~0 next to it."""
    rep = implicit_sensitivities(PROFILES, SEEDS, area_budget=0.2,
                                 power_budget=0.28,
                                 area_envelope={"hbm_bw": 0.25},
                                 polish_steps=80)
    assert list(rep.constraint_names) == ["area", "power", "hbm_bw"]
    j = rep.constraint_names.index("hbm_bw")
    assert np.any(rep.multipliers[:, j] > 0.0)
    md = rep.markdown()
    assert "hbm_bw" in md and "relax first" in md


# --------------------------------------------------------------------------- #
# The custom-VJP jstar map: gradient correctness + structure regression
# --------------------------------------------------------------------------- #


def test_custom_vjp_budget_gradient_matches_fd():
    """jax.grad through implicit_jstar_fn == central FD of its own value
    path (the gradient jax sees is the envelope-theorem cotangent)."""
    backend = get_backend("jax")
    jax, jnp = backend._jax, backend._jnp
    f = implicit_jstar_fn(PROFILES, SEEDS, steps=60)
    with backend._x64():
        b = jnp.asarray([B_AREA, 0.30], dtype=jnp.float64)
        grad = np.asarray(jax.jit(
            jax.grad(lambda bb: jnp.min(f(bb))))(b))
        v = jax.jit(lambda bb: jnp.min(f(bb)))

        def value(bvec):
            with backend._x64():
                return float(v(jnp.asarray(bvec, dtype=jnp.float64)))

    worst = gradcheck(value, np.array([B_AREA, 0.30]), grad,
                      rtol=1e-3, atol=1e-8, log_space=True)
    assert worst <= 1e-3
    assert grad[0] < 0.0  # area binds on the synthetic suite


def test_implicit_graph_size_is_steps_independent():
    """The memory/structure regression: the implicit map's traced graph
    must be IDENTICAL at steps=10 and steps=200 (one ``lax.scan`` body of
    the shared descent loop + one ridge solve), while the unrolled
    baseline's grows linearly."""
    backend = get_backend("jax")
    jax, jnp = backend._jax, backend._jnp

    def count_eqns(jaxpr):
        n = 0
        for eq in jaxpr.eqns:
            n += 1
            for v in eq.params.values():
                if hasattr(v, "jaxpr"):
                    n += count_eqns(v.jaxpr)
                elif hasattr(v, "eqns"):
                    n += count_eqns(v)
        return n

    def size_of(fn):
        with backend._x64():
            b = jnp.asarray([B_AREA, 0.30], dtype=jnp.float64)
            return count_eqns(jax.make_jaxpr(
                lambda bb: jnp.min(fn(bb)))(b).jaxpr)

    imp10 = size_of(implicit_jstar_fn(PROFILES, SEEDS, steps=10))
    imp200 = size_of(implicit_jstar_fn(PROFILES, SEEDS, steps=200))
    assert imp10 == imp200
    unr10 = size_of(unrolled_jstar_fn(PROFILES, SEEDS, steps=10))
    unr30 = size_of(unrolled_jstar_fn(PROFILES, SEEDS, steps=30))
    assert unr30 > 1.5 * unr10  # grows with steps
    assert unr30 > 2 * imp200   # the graph the implicit VJP avoids


def test_jstar_forward_is_the_shared_descent():
    """The implicit map's forward value is ``backtracking_descent``'s: the
    same objective, projection, seed, ``lr`` and step count give the same
    J* per variant to 1e-12 relative."""
    import functools

    from repro.core.codesign import (
        _suite_objective,
        backtracking_descent,
        descent_suite,
    )
    from repro.core.constrained import project_to_budgets
    from repro.core.costmodel import DEFAULT_COST_MODEL
    from repro.core.kernels_xp import IDEAL_EPS

    backend = get_backend("jax")
    jax, jnp = backend._jax, backend._jnp
    steps, lr, budgets = 40, 0.1, (B_AREA, 0.30)
    jstar = implicit_jstar_fn(PROFILES, SEEDS, steps=steps, lr=lr)
    s = descent_suite(PROFILES, SEEDS)
    objective = functools.partial(
        _suite_objective, jnp, timing_model="serial", eps=IDEAL_EPS,
        cost_model=DEFAULT_COST_MODEL, w_area=0.1, w_power=0.05)

    def project(theta):
        return project_to_budgets(jnp, theta, s.lo, s.hi, s.fixed,
                                  DEFAULT_COST_MODEL, *budgets,
                                  method="euclidean")[0]

    with backend._x64():
        value = np.asarray(jstar(jnp.asarray(budgets, dtype=jnp.float64)))
        _, f_cur, history, _, _ = backtracking_descent(
            jax, jnp, s.theta0, objective, steps, lr, retract=project,
            obj_args=s.args)
    assert np.any(np.stack(history)[-1] < np.stack(history)[0])
    np.testing.assert_allclose(value, np.asarray(f_cur), rtol=1e-12, atol=0)


# --------------------------------------------------------------------------- #
# Result surfacing: frontier columns, CodesignResult shadow prices
# --------------------------------------------------------------------------- #


def test_frontier_markdown_and_json_carry_sensitivities(fr):
    md = fr.markdown()
    assert "dJ*/db" in md and "shadow price" in md
    blob = fr.to_json()
    assert blob["sensitivity_constraints"][0] == "area"
    feas = [p for p in blob["points"] if p["feasible"]]
    assert all("dJ_dbudget" in p and "shadow_prices" in p for p in feas)
    infeas = [p for p in blob["points"] if not p["feasible"]]
    assert all("dJ_dbudget" not in p for p in infeas)
    import json
    json.dumps(blob)


def test_frontier_sensitivities_opt_out():
    fr2 = frontier_codesign(PROFILES, SEEDS, [0.25], steps=20,
                            sensitivities=False)
    assert fr2.dJ_dbudget is None
    assert "dJ*/db" not in fr2.markdown()


def test_lagrangian_result_reports_shadow_prices():
    res = constrained_codesign(PROFILES, SEEDS, steps=60,
                               area_budget=0.2, power_budget=0.3,
                               mode="lagrangian")
    rep = res.feasibility_report()
    assert set(rep["shadow_prices"]) == {"area", "power"}
    assert res.multipliers.shape == (len(VARIANTS), 2)
    assert np.all(res.multipliers >= 0.0)


def test_sensitivity_report_json_and_markdown(rep):
    import json
    blob = rep.to_json(top_k=2)
    json.dumps(blob)
    assert len(blob["variants"]) == 2
    v0 = blob["variants"][0]
    assert v0["shadow_prices"]["area"] == -v0["dJ_dbudget"]["area"]
    assert "| variant |" in rep.markdown()


def test_sensitivities_of_rejects_joint_results(res_proj):
    fake = types.SimpleNamespace(mode="joint-alternation")
    with pytest.raises(ValueError, match="joint"):
        sensitivities_of(fake, PROFILES)


# --------------------------------------------------------------------------- #
# Bilevel budget descent: the outer consumer of the implicit gradient
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def bl():
    return bilevel_codesign(common.scaling_profiles(10), SEEDS,
                            total_budget=0.35, steps=40, outer_steps=4)


def test_bilevel_beats_uniform_split_on_default_suite(bl):
    """The acceptance pin: on the 10 default profiles the learned split
    strictly improves the scalarized objective over the fixed 50/50
    split of the same total budget."""
    assert isinstance(bl, BilevelResult)
    assert bl.improvement_over_uniform > 1e-3
    assert bl.split_final != 0.5
    assert np.all(np.diff(bl.objective_trajectory) <= 1e-12)
    assert abs(bl.area_budget + bl.power_budget - 0.35) < 1e-12
    assert bool(bl.inner.feasible[bl.inner.best])
    assert isinstance(bl.sensitivity, SensitivityReport)


def test_bilevel_result_protocol(bl):
    import json
    blob = bl.to_json()
    json.dumps(blob)
    assert blob["improvement_over_uniform"] > 0
    md = bl.markdown()
    assert "split" in md and "uniform" in md


def test_bilevel_validates_inputs():
    with pytest.raises(ValueError, match="total_budget"):
        bilevel_codesign(PROFILES, SEEDS)
    with pytest.raises(ValueError, match="split0"):
        bilevel_codesign(PROFILES, SEEDS, total_budget=0.4, split0=1.5)


def test_bilevel_through_spec_funnel():
    from repro.core.spec import CodesignSpec
    spec = CodesignSpec(total_budget=0.4, split0=0.5, outer_steps=2,
                        steps=15, lr=0.1)
    spec.validate()
    bl = bilevel_codesign(PROFILES, SEEDS, spec=spec)
    assert bl.total_budget == 0.4
    assert bl.outer_steps == 2
    rt = CodesignSpec.from_json(spec.to_json())
    assert rt.total_budget == spec.total_budget


# --------------------------------------------------------------------------- #
# CLI surface: --sensitivities / --bilevel parse-time validation
# --------------------------------------------------------------------------- #


class _Boom(argparse.ArgumentParser):
    def error(self, message):
        raise RuntimeError(message)


def _args_of(**kw):
    base = dict(grad=0, area_budget=None, power_budget=None,
                constraint_mode=None, opt_links=False, joint=False,
                budget_sweep=None, area_envelope=None, pack=0,
                pack_gen=0, sensitivities=False, bilevel=None)
    base.update(kw)
    return types.SimpleNamespace(**base)


@pytest.mark.parametrize("kw,frag", [
    (dict(grad=5, bilevel=-1.0), "positive"),
    (dict(bilevel=0.4), "requires --grad"),
    (dict(grad=5, bilevel=0.4, area_budget=0.2), "derives"),
    (dict(grad=5, bilevel=0.4, joint=True), "own co-design mode"),
    (dict(grad=5, bilevel=0.4, pack=2), "own co-design mode"),
    (dict(sensitivities=True), "requires --grad"),
    (dict(grad=5, sensitivities=True), "needs a constraint"),
    (dict(grad=5, sensitivities=True, joint=True, area_budget=0.2),
     "joint"),
])
def test_cli_rejects_inconsistent_flags(kw, frag):
    from repro.launch.hillclimb import validate_codesign_args
    with pytest.raises(RuntimeError, match=frag):
        validate_codesign_args(_Boom(), _args_of(**kw))


@pytest.mark.parametrize("kw", [
    dict(grad=5, bilevel=0.4),
    dict(grad=5, bilevel=0.4, sensitivities=True),
    dict(grad=5, bilevel=0.4, area_envelope={"hbm_bw": 0.5}),
    dict(grad=5, sensitivities=True, area_budget=0.2),
    dict(grad=5, sensitivities=True, budget_sweep=[0.1, 0.2]),
])
def test_cli_accepts_consistent_flags(kw):
    from repro.launch.hillclimb import validate_codesign_args
    validate_codesign_args(_Boom(), _args_of(**kw))
