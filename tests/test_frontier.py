"""Feasibility-frontier subsystem: the ISSUE acceptance gates.

The load-bearing properties:
  * random budget schedules => J*(budget) is monotone non-increasing over
    the feasible points and every feasible frontier point satisfies its
    area budget to 1e-9 (hypothesis-driven end-to-end);
  * warm-started continuation and cold restarts trace the same monotone,
    feasible frontier shape;
  * a single-key area envelope budgets exactly what a scalar area budget
    under the single-key CostModel restriction budgets (projection-level
    AND end-to-end);
  * the sweep -> frontier bridge and the hillclimb --budget-sweep /
    --area-envelope parse-time validation.
"""

import functools

import numpy as np
import pytest

from conftest import hypothesis_shim

# Few fallback trials -- each trial here is a full jax descent.
given, settings, st = hypothesis_shim(seed=0xF407, trials=6)

from repro.core import VARIANTS, frontier_codesign
from repro.core.codesign import theta_box
from repro.core.constrained import (
    FEASIBLE_RTOL,
    constrained_codesign,
    project_to_budgets,
)
from repro.core.costmodel import DEFAULT_COST_MODEL, CostModel
from repro.core.frontier import FrontierResult, _validate_budget_schedule
from repro.core.sweep import MachineBatch, run_sweep
from test_sweep import random_profiles

SEEDS = MachineBatch.from_models(VARIANTS)
FIXED = SEEDS.arrays()
THETA0, LO, HI = theta_box(SEEDS, span=16.0)

#: Tiny descent configs: the properties under test are structural
#: (monotonicity, feasibility), not convergence quality.
FAST = dict(steps=3, refine_steps=1)


@pytest.fixture(scope="module")
def suite():
    return random_profiles(2, seed=61)


def _assert_frontier_contract(fr):
    """The ISSUE acceptance gate, shared by every end-to-end test."""
    feas = fr.feasible
    # Feasible points satisfy their budgets to 1e-9 ...
    assert np.all(fr.area[feas] <= fr.budgets[feas] * (1.0 + FEASIBLE_RTOL))
    # ... and J* is monotone non-increasing in the budget across them.
    assert np.all(np.diff(fr.objective[feas]) <= 1e-12)
    # Budgets are reported ascending and deduplicated.
    assert np.all(np.diff(fr.budgets) > 0)


# --------------------------------------------------------------------------- #
# The frontier property (hypothesis: random schedules => monotone + feasible)
# --------------------------------------------------------------------------- #


@functools.lru_cache(maxsize=None)
def _schedule_suite():
    return random_profiles(2, seed=61)


@settings(max_examples=6, deadline=None)
@given(lo=st.floats(0.05, 0.5), span=st.floats(0.5, 3.0))
def test_frontier_monotone_and_feasible_for_random_schedules(lo, span):
    """For ANY budget schedule (attainable or not), every feasible
    frontier point is area-feasible to 1e-9 and J* never increases with
    the budget -- the tentpole's acceptance gate."""
    budgets = [lo, lo + 0.5 * span, lo + span]
    fr = frontier_codesign(_schedule_suite(), SEEDS, budgets, **FAST)
    _assert_frontier_contract(fr)
    assert fr.per_seed_objective.shape == (3, len(SEEDS))


def test_frontier_named_seeds_monotone_feasible_and_warm_matches_cold(suite):
    """On the named seeds: both continuation and cold restarts honour the
    contract, and an unattainable tightest budget is flagged rather than
    silently reported feasible."""
    budgets = [0.03, 0.2, 0.6, 1.5]          # 0.03 < the span-box floor
    warm = frontier_codesign(suite, SEEDS, budgets, steps=6, refine_steps=2)
    cold = frontier_codesign(suite, SEEDS, budgets, steps=6, refine_steps=2,
                             warm_start=False)
    for fr in (warm, cold):
        _assert_frontier_contract(fr)
        assert not fr.feasible[0]            # floor area > 0.03, flagged
        assert np.all(fr.feasible[1:])
    assert warm.warm_start and not cold.warm_start
    # Same seeds, same schedule: the two traces agree on which budgets are
    # attainable and on the frontier's weak ordering.
    np.testing.assert_array_equal(warm.feasible, cold.feasible)


def test_frontier_respects_fixed_power_budget_and_envelope(suite):
    """power_budget and area_envelope are held FIXED across the sweep;
    every feasible point satisfies them on top of its area budget."""
    env = {"hbm_bw": 0.5}
    fr = frontier_codesign(suite, SEEDS, [0.3, 0.8], power_budget=1.0,
                           area_envelope=env, **FAST)
    _assert_frontier_contract(fr)
    for i in np.nonzero(fr.feasible)[0]:
        m = fr.best_model(int(i))
        assert DEFAULT_COST_MODEL.power(m) <= 1.0 * (1.0 + FEASIBLE_RTOL)
        assert (DEFAULT_COST_MODEL.subsystem_area(m, "hbm_bw")
                <= 0.5 * (1.0 + FEASIBLE_RTOL))
    assert fr.area_envelope == env and fr.power_budget == 1.0
    assert "area_envelope" in fr.to_json()


def test_frontier_validates_inputs(suite):
    with pytest.raises(ValueError, match="at least one budget"):
        frontier_codesign(suite, SEEDS, [], **FAST)
    with pytest.raises(ValueError, match="must be positive"):
        frontier_codesign(suite, SEEDS, [1.0, -0.5], **FAST)
    with pytest.raises(ValueError, match="iterable of numbers"):
        _validate_budget_schedule(0.5)
    with pytest.raises(ValueError, match="power_budget must be positive"):
        frontier_codesign(suite, SEEDS, [1.0], power_budget=0.0, **FAST)
    with pytest.raises(ValueError, match="unknown area_envelope field"):
        frontier_codesign(suite, SEEDS, [1.0], area_envelope={"lutram": 1},
                          **FAST)


def test_budget_schedule_normalization():
    assert _validate_budget_schedule([2.0, 0.5, 2.0, 1.0]) == [0.5, 1.0, 2.0]


# --------------------------------------------------------------------------- #
# FrontierResult accessors (best_at / knee / reports)
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def traced(suite):
    return frontier_codesign(suite, SEEDS, [0.25, 0.5, 1.0, 2.0],
                             steps=6, refine_steps=2)


def test_best_at_returns_affordable_machine(traced):
    m = traced.best_at(0.7)
    # best_at picks the largest traced budget <= 0.7; nested feasible sets
    # make that machine affordable at 0.7 too.
    assert DEFAULT_COST_MODEL.area(m) <= 0.7 * (1.0 + FEASIBLE_RTOL)
    assert "+frontier@" in m.name
    with pytest.raises(ValueError, match="no feasible frontier point"):
        traced.best_at(1e-6)


def test_knee_is_a_traced_feasible_budget(traced):
    knee = traced.knee()
    feas_budgets = traced.budgets[traced.feasible]
    assert knee in feas_budgets.tolist()


def test_reports_render(traced):
    md = traced.markdown()
    assert "| area budget |" in md and "J*" in md
    blob = traced.to_json()
    assert len(blob["points"]) == len(traced)
    assert blob["budgets"] == sorted(blob["budgets"])
    # Every point's machine params round-trip into MachineModel.
    for i in range(len(traced)):
        assert traced.best_model(i).peak_flops > 0


def test_knee_flat_frontier_returns_tightest_feasible():
    """A flat frontier means extra budget buys nothing: the knee is the
    tightest feasible budget (the 'how much fabric do I need' answer)."""
    r = FrontierResult(
        budgets=np.array([0.5, 1.0, 2.0]),
        objective=np.array([1.0, 1.0, 1.0]),
        best_names=["a"] * 3, best_params=[{}] * 3,
        area=np.array([0.4, 0.4, 0.4]), power=np.array([0.5] * 3),
        feasible=np.array([True] * 3),
        per_seed_objective=np.ones((3, 1)), seed_names=["a"],
        steps=1, refine_steps=1, warm_start=True)
    assert r.knee() == 0.5


# --------------------------------------------------------------------------- #
# Envelope-vs-scalar-budget consistency (the single-key pin)
# --------------------------------------------------------------------------- #


def test_single_key_envelope_matches_scalar_budget_projection():
    """Projection level: a one-entry envelope on a field is the SAME
    constraint set as a scalar area budget under the single-key CostModel
    restriction, and the Euclidean operator maps both to the same point
    (the shift operator would rescale every rate for the scalar form --
    exactly the asymmetry the true projection removes)."""
    rng = np.random.default_rng(3)
    theta = THETA0 + rng.uniform(-4, 4, size=THETA0.shape)
    for field, b in (("peak_flops", 0.9), ("hbm_bw", 1.4),
                     ("ici_bw_total", 0.6)):
        single = CostModel(area_weights={field: 1.0})
        p_scalar, f_scalar = project_to_budgets(
            np, theta, LO, HI, FIXED, single, b, method="euclidean")
        p_env, f_env = project_to_budgets(
            np, theta, LO, HI, FIXED, DEFAULT_COST_MODEL, None,
            area_envelope={field: b}, method="euclidean")
        np.testing.assert_allclose(p_scalar, p_env, atol=1e-6)
        np.testing.assert_array_equal(f_scalar, f_env)


def test_single_key_envelope_matches_scalar_budget_end_to_end(suite):
    """End-to-end: with the SAME single-key cost model (so the scalarized
    objectives coincide), descending under the envelope form and under
    the scalar form lands on the same machines."""
    single = CostModel(area_weights={"hbm_bw": 1.0})
    kw = dict(steps=6, projection="euclidean", cost_model=single)
    scalar = constrained_codesign(suite, SEEDS, area_budget=0.8, **kw)
    env = constrained_codesign(suite, SEEDS,
                               area_envelope={"hbm_bw": 0.8}, **kw)
    np.testing.assert_allclose(scalar.objective_final, env.objective_final,
                               rtol=1e-5)
    for ps, pe in zip(scalar.final_params, env.final_params):
        for key in ps:
            np.testing.assert_allclose(ps[key], pe[key], rtol=1e-4)
    assert np.all(scalar.feasible) and np.all(env.feasible)


# --------------------------------------------------------------------------- #
# Sweep -> frontier bridge
# --------------------------------------------------------------------------- #


def test_sweep_frontier_bridge(suite):
    """run_sweep(...).frontier(...) warm-starts the continuation from the
    sweep's seed_codesign survivors over the same profile suite."""
    res = run_sweep(suite, n=64, seed=9, include_named=VARIANTS)
    fr = res.frontier([0.4, 1.0], k=3, **FAST)
    _assert_frontier_contract(fr)
    assert set(fr.seed_names) == set(res.seed_codesign(k=3).names)


# --------------------------------------------------------------------------- #
# CLI parse-time validation (hillclimb --budget-sweep / --area-envelope)
# --------------------------------------------------------------------------- #


def test_hillclimb_validates_frontier_args_at_parse_time():
    import argparse

    from repro.launch.hillclimb import (
        parse_area_envelope,
        parse_budget_sweep,
        validate_codesign_args,
    )

    class Boom(Exception):
        pass

    class P(argparse.ArgumentParser):
        def error(self, message):
            raise Boom(message)

    p = P()
    assert parse_budget_sweep(p, None) is None
    assert parse_budget_sweep(p, "0.5:1.5:3") == [0.5, 1.0, 1.5]
    for bad in ("nope", "1:2", "0:1:4", "2:1:4", "0.5:1.5:1", "a:b:3"):
        with pytest.raises(Boom):
            parse_budget_sweep(p, bad)
    assert parse_area_envelope(p, None) is None
    assert parse_area_envelope(p, "peak_flops=1.5, hbm_bw=0.8") == \
        {"peak_flops": 1.5, "hbm_bw": 0.8}
    for bad in ("peak_flops", "peak_flops=x", "sram=1.0", "hbm_bw=0"):
        with pytest.raises(Boom):
            parse_area_envelope(p, bad)

    def args_of(**kw):
        base = dict(grad=0, area_budget=None, power_budget=None,
                    constraint_mode=None, opt_links=False, joint=False,
                    budget_sweep=None, area_envelope=None)
        base.update(kw)
        return argparse.Namespace(**base)

    validate_codesign_args(p, args_of(grad=5, budget_sweep="0.5:1.5:3"))
    validate_codesign_args(p, args_of(grad=5, area_envelope="hbm_bw=0.8"))
    with pytest.raises(Boom, match="require --grad"):
        validate_codesign_args(p, args_of(budget_sweep="0.5:1.5:3"))
    with pytest.raises(Boom, match="require --grad"):
        validate_codesign_args(p, args_of(area_envelope="hbm_bw=0.8"))
    with pytest.raises(Boom, match="IS the area-budget axis"):
        validate_codesign_args(p, args_of(grad=5, budget_sweep="0.5:1.5:3",
                                          area_budget=1.0))
    with pytest.raises(Boom, match="projected continuation"):
        validate_codesign_args(p, args_of(grad=5, budget_sweep="0.5:1.5:3",
                                          opt_links=True))
    with pytest.raises(Boom, match="projected continuation"):
        validate_codesign_args(p, args_of(grad=5, budget_sweep="0.5:1.5:3",
                                          constraint_mode="lagrangian"))
    with pytest.raises(Boom, match="does not support --area-envelope"):
        validate_codesign_args(p, args_of(grad=5, joint=True,
                                          area_envelope="hbm_bw=0.8"))
